"""The main-path Pallas kernels compile for a TPU v5e at published widths.

Nothing runs: each test compiles one kernel for a described (not attached)
v5e chip and checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  This catches blocks that break the (8, 128) tiling
rule and kernels that overrun VMEM, which interpret mode never sees.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  Keep these tests in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import paged_attention as pk
from repro.kernels.moe.dispatch import round_up
from repro.kernels.moe.grouped_gemm import grouped_matmul_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_text(one_chip):
    """Compile ``fn`` at the given shapes on the described chip with the
    persistent cache off (such entries could not be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield run
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (B, H, KV, S, hd after padding to 128, window): qwen2-moe-a2.7b at seq
# 2048 (MHA, hd 128) and at the fine-tune cell's 1 x 8192 (64 x 64 tiles per
# head, dead causal steps clamped to the last live kv block); h2o-danube-1.8b
# at seq 4096 (GQA 4, hd 80 -> 128, sliding window 4096)
FLASH = {"qwen2-moe": (4, 16, 16, 2048, 128, None),
         "qwen2-moe-8k": (1, 16, 16, 8192, 128, None),
         "h2o-danube": (2, 32, 8, 4096, 128, 4096)}


@pytest.mark.parametrize("model", sorted(FLASH))
def test_flash_forward_compiles(compile_text, model):
    B, H, KV, S, hd, window = FLASH[model]
    text = compile_text(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, scale=0.1,
            interpret=False, return_lse=True),
        ((B, H, S, hd), BF16), ((B, KV, S, hd), BF16), ((B, KV, S, hd), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("model", sorted(FLASH))
def test_flash_backward_compiles(compile_text, model):
    B, H, KV, S, hd, window = FLASH[model]
    text = compile_text(
        lambda q, k, v, lse, delta, do: fa.flash_attention_bwd(
            q, k, v, lse, delta, do, causal=True, window=window, scale=0.1,
            interpret=False),
        ((B, H, S, hd), BF16), ((B, KV, S, hd), BF16), ((B, KV, S, hd), BF16),
        ((B, H, S), F32), ((B, H, S), F32), ((B, H, S, hd), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("proj", ["gate_up", "down"])
def test_grouped_gemm_compiles(compile_text, proj):
    """qwen2-moe-a2.7b expert GEMMs: 4 x 2048 tokens, top-4 of 60 experts,
    d_model 2048, expert width 1408, 128-row tiles."""
    E, d, f, block_m = 60, 2048, 1408, 128
    m_pad = round_up(4 * 2048 * 4 + E * (block_m - 1), block_m)
    K, N = (d, f) if proj == "gate_up" else (f, d)
    text = compile_text(
        lambda a, b, te: grouped_matmul_pallas(a, b, te, block_m=block_m,
                                               interpret=False),
        ((m_pad, K), BF16), ((E, K, N), BF16), ((m_pad // block_m,), I32))
    assert "tpu_custom_call" in text


def test_paged_decode_compiles(compile_text):
    """h2o-danube-1.8b decode: 8 slots, 32 heads over 8 kv heads, hd 80,
    16-token pages, a 704-token context, window 4096."""
    B, H, KV, hd, page, ctx = 8, 32, 8, 80, 16, 704
    n_pages = ctx // page
    P = B * n_pages
    text = compile_text(
        lambda q, k, v, pos, pt, t: pk.paged_attention_pallas(
            q, k, v, pos, pt, t, kv_len=ctx, window=4096, interpret=False),
        ((B, H, hd), BF16), ((P, page, KV, hd), BF16),
        ((P, page, KV, hd), BF16), ((P, page), I32), ((B, n_pages), I32),
        ((B,), I32))
    assert "tpu_custom_call" in text


# one small call per named kernel, (8, 128)-tiled; mamba_ssd and rwkv6_scan
# do not lower for the TPU yet (their bodies use cumsum) and are checked in
# interpret mode by tests/test_scopes.py
NAMED = {
    "flash_fwd": (lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=0.1, interpret=False, return_lse=True),
        [((1, 2, 256, 128), BF16)] * 3),
    "flash_dq": (lambda q, k, v, lse, delta, do: fa.flash_attention_bwd(
        q, k, v, lse, delta, do, causal=True, scale=0.1, interpret=False),
        [((1, 2, 256, 128), BF16)] * 3 + [((1, 2, 256), F32)] * 2
        + [((1, 2, 256, 128), BF16)]),
    "gmm": (lambda a, b, te: grouped_matmul_pallas(a, b, te, block_m=128,
                                                   interpret=False),
            [((256, 128), BF16), ((2, 128, 256), BF16), ((2,), I32)]),
    "paged_decode": (lambda q, k, v, pos, pt, t: pk.paged_attention_pallas(
        q, k, v, pos, pt, t, kv_len=64, window=None, interpret=False),
        [((2, 4, 128), BF16), ((8, 16, 2, 128), BF16),
         ((8, 16, 2, 128), BF16), ((8, 16), I32), ((2, 4), I32),
         ((2,), I32)]),
    "rmsnorm": (lambda x, s: rmsnorm_kernel(x, s, interpret=False),
                [((256, 128), BF16), ((128,), BF16)]),
}
NAMED["flash_dkv"] = NAMED["flash_dq"]     # the backward runs both kernels


def rmsnorm_kernel(x, s, *, interpret):
    from repro.kernels.rmsnorm import rmsnorm
    return rmsnorm(x, s, interpret=interpret)


@pytest.mark.parametrize("name", sorted(NAMED))
def test_kernel_custom_call_carries_its_name(compile_text, name):
    fn, shapes = NAMED[name]
    calls = [line for line in compile_text(fn, *shapes).splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert any(line.lstrip().startswith((f"%{name}.", f"ROOT %{name}."))
               and f'{name}/pallas_call"' in line for line in calls), calls
