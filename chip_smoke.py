#!/usr/bin/env python3
"""Smoke test of the main paths on one TPU chip, at published widths.

    python chip_smoke.py                # phases A-C on one chip
    python chip_smoke.py --four-chips   # EP=4 vs one device, on a 4-chip host

Phases drive the same code as ``repro.launch.train`` and
``repro.launch.serve`` (``repro.train.driver.train`` and ``ServingEngine``)
with random weights from a fixed seed:

  A  qwen2-moe-a2.7b fine-tune: all 60 experts (top-4, 4 shared), the full
     vocabulary, depth cut to fit; flash fwd/bwd kernels and grouped-GEMM
     dispatch; fused LoMo, seq 2048, batch 4.
  B  h2o-danube-1.8b fine-tune: window 4096, depth cut to fit, default
     attention path; fused AdamW, seq 4096, batch 2.
  C  h2o-danube-1.8b serving, all 24 layers: paged engine, 8 slots, 16
     requests (128-token shared prefix + 64..512 tokens), 64 new tokens each
     at temperature 0, after a warmup that mirrors the workload.
  K  the Pallas kernels against their ``impl="jax"`` paths at the phase A
     layer shapes (flash fwd+bwd, grouped GEMM fwd+bwd) and the phase C
     decode shapes (paged attention).

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero before
that line.  The script refuses to run anywhere but a TPU: CPU tests drive the
phase functions at reduced size instead (tests/test_chip_smoke.py).
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: relative L2 error ||pallas - jax|| / ||jax|| allowed per kernel output.
#: Both implementations accumulate in f32 from bf16 operands; what differs
#: is summation order and where results round to bf16 (the paged jax path
#: rounds probabilities to bf16 before the PV product, as dense decode
#: does; the kernel keeps them in f32).  bf16 carries 8 mantissa bits
#: (relative spacing 2**-8 = 3.9e-3), so a few such roundings bound the
#: error near 1e-2; a tiling or masking bug gives errors of order 1.
KERNEL_REL_TOL = 2e-2

#: EP-vs-one-device limits (four-chip check).  The FSDP-sharded router and
#: projections reduce in another order than one device does, so bf16 sums
#: differ by a few ulps, and a token whose top-4 experts tie that closely
#: may route differently; each such flip moves one token's share of an
#: expert's gradient.  A sharding or exchange bug gives errors of order 1.
#: The per-leaf limit applies to leaves that carry at least
#: ``EP_LEAF_FLOOR`` of the tree's gradient norm: below it a leaf is
#: rounding noise (the key bias's exact gradient is zero, since softmax
#: ignores a shift shared by all keys of a query), and the whole-tree
#: limit still bounds it.
EP_LOSS_REL_TOL = 1e-2
EP_TREE_REL_TOL = 1e-2
EP_GRAD_REL_TOL = 5e-2
EP_LEAF_FLOOR = 1e-3

#: smoke learning rates at published widths: large enough that 4 steps
#: from random weights beat the batch-to-batch spread of the loss, small
#: enough not to diverge.  LoMo is SGD under a global-norm clip of 1, so a
#: step moves the weights by at most ``LOMO_LR`` in L2.  AdamW moves every
#: weight by about its lr per step; at 1e-3 the first h2o-danube step
#: doubled the loss on a v5e.
LOMO_LR = 1.0
ADAMW_LR = 1e-4


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def _device_stats() -> dict:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use",
                                      "bytes_limit")}


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _abstract_batch(batch: int, seq: int) -> dict:
    import jax
    import jax.numpy as jnp
    return {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32)}


# ------------------------------------------------------------------ phases

def phase_train(cfg, optimizer, *, batch: int, seq: int, steps: int,
                require_kernels: bool) -> dict:
    """Fused fine-tune through ``repro.train.driver.train`` (stage 2 only,
    so one step program compiles).  The same step is compiled ahead of the
    run to read its HLO and memory analysis; the driver's own compile, read
    back from its telemetry, shows whether it found that in the persistent
    cache."""
    import os

    import jax
    import numpy as np
    from repro.core import schedule
    from repro.obs.sink import read_events
    from repro.data.pipeline import DataConfig
    from repro.models.model import Model
    from repro.train.driver import RunConfig, train
    from repro.train.trainer import make_train_step

    model = Model(cfg)
    step = jax.jit(make_train_step(model, optimizer,
                                   mask_fn=schedule.stage2_mask, fused=True),
                   donate_argnums=(0, 1))
    aparams = model.abstract_params()
    t0 = time.perf_counter()
    compiled = step.lower(aparams, jax.eval_shape(optimizer.init, aparams),
                          _abstract_batch(batch, seq)).compile()
    compile_s = time.perf_counter() - t0
    custom_call = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    del step, compiled

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                    global_batch=batch)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        rc = RunConfig(total_steps=steps, stage1_steps=0,
                       ckpt_every=steps + 1, ckpt_dir=ckpt_dir,
                       log_every=steps, fused_optimizer=True)
        tel_path = os.path.join(ckpt_dir, "run.jsonl")
        params, opt_state, losses = train(model, optimizer, dc, rc,
                                          log_fn=lambda *_: None,
                                          telemetry=tel_path)
        events = read_events(tel_path)
    del params, opt_state
    window = [e for e in events if e["kind"] == "train_window"][-1]

    failed = []
    if len(losses) != steps or not all(np.isfinite(losses)):
        failed.append("losses_finite")
    elif not losses[-1] < losses[0]:
        failed.append("loss_falls")
    if require_kernels and not custom_call:
        failed.append("tpu_custom_call")
    return {
        "config": cfg.name, "num_layers": cfg.num_layers,
        "params": model.num_params(), "optimizer": type(optimizer).__name__,
        "batch": batch, "seq": seq, "losses": losses,
        "compile_s": compile_s,
        "driver_compile_s": sum(e["dur_s"] for e in events
                                if e["kind"] == "compile"),
        "tpu_custom_call": custom_call,
        "estimator_bytes": window.get("mem_predicted_bytes"),
        "step_bytes": {"argument": mem.argument_size_in_bytes,
                       "output": mem.output_size_in_bytes,
                       "temp": mem.temp_size_in_bytes,
                       "alias": mem.alias_size_in_bytes},
        **_device_stats(), "failed": failed,
    }


def phase_serve(cfg, *, slots: int, requests: int, shared_prefix: int,
                min_prompt: int, max_prompt: int, gen: int,
                page_size: int = 16) -> dict:
    """Paged serving through ``ServingEngine`` with the launcher's
    warmup-then-serve sequence (``repro.launch.serve.serve_requests``)."""
    import jax
    import numpy as np
    from repro.launch.serve import make_prompts, serve_requests
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine

    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, slots=slots,
                        buf_len=shared_prefix + max_prompt + gen,
                        paged=True, page_size=page_size)
    prompts = make_prompts(np.random.default_rng(0), requests,
                           lo_len=min_prompt, hi_len=max_prompt,
                           shared_prefix=shared_prefix,
                           vocab_size=cfg.vocab_size)
    t0 = time.perf_counter()
    done, serve_s, recompiles = serve_requests(
        eng, prompts, gen=gen, vocab_size=cfg.vocab_size)
    total_s = time.perf_counter() - t0
    lengths = [len(done[u].generated) for u in range(requests) if u in done]
    ok_requests = sum(n == gen for n in lengths)

    failed = []
    if ok_requests != requests:
        failed.append("requests_complete")
    if recompiles != 0:
        failed.append("no_recompiles_after_warmup")
    return {
        "config": cfg.name, "num_layers": cfg.num_layers,
        "params": model.num_params(), "slots": slots,
        "requests": requests, "requests_with_all_tokens": ok_requests,
        "new_tokens_each": gen, "prompt_lens": [len(p) for p in prompts],
        "recompiles_post_warmup": recompiles,
        "jit_cache": eng.jit_cache_sizes(),
        "warmup_and_serve_s": total_s, "serve_s": serve_s,
        **_device_stats(), "failed": failed,
    }


def phase_kernels(moe_cfg, dense_cfg, *, batch: int, seq: int, slots: int,
                  kv_len: int, page_size: int = 16) -> dict:
    """Pallas vs ``impl="jax"``: flash fwd+bwd and grouped GEMM fwd+bwd at
    ``moe_cfg``'s layer shapes (batch x seq tokens), paged decode attention
    at ``dense_cfg``'s serving shapes (``slots`` x ``kv_len`` context)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.kernels import paged_attention as pk
    from repro.kernels.moe import dispatch as dsp
    from repro.kernels.moe.ops import grouped_matmul

    dt = jnp.dtype(moe_cfg.dtype)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    rnd = lambda shape, d=dt: jax.random.normal(next(keys), shape, d)
    errs = {}

    # flash attention: causal, the MoE config's heads, forward + vjp
    H, KV, hd = moe_cfg.num_heads, moe_cfg.num_kv_heads, moe_cfg.head_dim
    q, k, v = rnd((batch, H, seq, hd)), rnd((batch, KV, seq, hd)), \
        rnd((batch, KV, seq, hd))
    ct = rnd((batch, H, seq, hd))

    def flash(impl, q, k, v, ct):
        f = lambda q_, k_, v_: kops.flash_attention_trainable(
            q_, k_, v_, True, None, moe_cfg.logit_softcap,
            moe_cfg.flash_block_q, moe_cfg.flash_block_k, impl)
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(ct)

    got, want = (jax.jit(flash, static_argnums=0)(impl, q, k, v, ct)
                 for impl in ("pallas", "jax"))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        errs[f"flash_{name}"] = _rel_err(a, b)

    # grouped GEMM: top-k routed rows of the MoE layer, gate projection
    d, f, E = moe_cfg.d_model, moe_cfg.d_ff_expert, moe_cfg.num_experts
    T, top_k, block_m = batch * seq, moe_cfg.top_k, 128
    idx = jax.random.randint(next(keys), (T, top_k), 0, E)
    plan = dsp.make_plan(idx, E, block_m)
    lhs = dsp.permute(rnd((T, d)), plan)
    rhs = rnd((E, d, f)) * d ** -0.5
    gct = rnd((plan.m_pad, f))

    def gmm(impl, lhs, rhs, tile_expert, ct):
        out, vjp = jax.vjp(lambda a, b: grouped_matmul(
            a, b, tile_expert, block_m, impl), lhs, rhs)
        return (out,) + vjp(ct)

    got, want = (jax.jit(gmm, static_argnums=0)(
        impl, lhs, rhs, plan.tile_expert, gct) for impl in ("pallas", "jax"))
    for name, a, b in zip(("out", "dlhs", "drhs"), got, want):
        errs[f"gmm_{name}"] = _rel_err(a, b)

    # paged decode attention at the served shapes, pages shuffled
    H, KV, hd = dense_cfg.num_heads, dense_cfg.num_kv_heads, \
        dense_cfg.head_dim
    n_pages = -(-kv_len // page_size)
    P = slots * n_pages
    ddt = jnp.dtype(dense_cfg.dtype)
    qd = rnd((slots, H, hd), ddt)
    kp, vp = rnd((P, page_size, KV, hd), ddt), rnd((P, page_size, KV, hd),
                                                    ddt)
    t = jax.random.randint(next(keys), (slots,), 1, kv_len)
    pt = jax.random.permutation(next(keys), P).reshape(
        slots, n_pages).astype(jnp.int32)
    logical = jnp.arange(n_pages)[:, None] * page_size + jnp.arange(page_size)
    pos = jnp.zeros((P, page_size), jnp.int32).at[pt].set(
        jnp.where(logical[None] <= t[:, None, None], logical[None], -1))
    paged = jax.jit(functools.partial(
        pk.paged_attention, kv_len=kv_len, window=dense_cfg.sliding_window),
        static_argnames="impl")
    errs["paged_out"] = _rel_err(
        *(paged(qd, kp, vp, pos, pt, t, impl=impl)
          for impl in ("pallas", "jax")))

    failed = [k for k, e in errs.items() if not e <= KERNEL_REL_TOL]
    return {"rel_err": errs, "tolerance": KERNEL_REL_TOL,
            "flash_shape": [batch, moe_cfg.num_heads, seq, moe_cfg.head_dim],
            "gmm_shape": [plan.m_pad, d, f, E],
            "paged_shape": [slots, H, hd, kv_len, page_size],
            "failed": failed}


def phase_ep_parity(cfg, *, ep: int, batch: int, seq: int) -> dict:
    """Loss and gradient of one batch on one device, then with experts
    sharded ``ep`` ways (and everything else FSDP-sharded) over a mesh of
    all devices: same weights, same batch."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.core import settings
    from repro.data.pipeline import DataConfig, packed_batches
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import Model

    one = Model(cfg.replace(expert_parallel=0))
    params = one.init(jax.random.PRNGKey(0))
    data = next(packed_batches(DataConfig(vocab_size=cfg.vocab_size,
                                          seq_len=seq, global_batch=batch)))
    grad_fn = lambda m: jax.jit(jax.value_and_grad(
        lambda p, b: m.loss(p, b)))
    loss1, g1 = grad_fn(one)(params, data)
    loss1, g1 = float(loss1), jax.device_get(g1)

    n_dev = len(jax.devices())
    mesh = make_debug_mesh(data=n_dev // ep, expert=ep)
    settings.set_ep_mesh(mesh)
    try:
        sharded = Model(cfg.replace(expert_parallel=ep))
        params = jax.device_put(params, shd.param_shardings(
            sharded.logical_axes(), sharded.abstract_params(), mesh))
        data = jax.device_put(data, NamedSharding(
            mesh, shd.batch_pspec(mesh, batch, 2)))
        with jax.set_mesh(mesh):
            loss_ep, g_ep = grad_fn(sharded)(params, data)
        loss_ep, g_ep = float(loss_ep), jax.device_get(g_ep)
    finally:
        settings.set_ep_mesh(None)

    f32 = lambda x: np.asarray(x, np.float32)
    names = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_leaves_with_path(g1)]
    want = [f32(x) for x in jax.tree_util.tree_leaves(g1)]
    got = [f32(x) for x in jax.tree_util.tree_leaves(g_ep)]
    sq = lambda xs: sum(float(np.sum(np.square(x))) for x in xs)
    norm1, norm_ep = np.sqrt(sq(want)), np.sqrt(sq(got))
    tree_err = np.sqrt(sq([a - b for a, b in zip(got, want)])) / norm1
    checked, noise = {}, {}
    for name, a, b in zip(names, got, want):
        big = np.linalg.norm(b) >= EP_LEAF_FLOOR * norm1
        (checked if big else noise)[name] = _rel_err(a, b)
    worst = max(checked, key=checked.get)
    loss_rel = abs(loss_ep - loss1) / max(abs(loss1), 1e-30)
    failed = []
    if not (np.isfinite(loss1) and np.isfinite(loss_ep)):
        failed.append("losses_finite")
    if not loss_rel <= EP_LOSS_REL_TOL:
        failed.append("loss_parity")
    if not tree_err <= EP_TREE_REL_TOL:
        failed.append("grad_tree_parity")
    if not checked[worst] <= EP_GRAD_REL_TOL:
        failed.append("grad_leaf_parity")
    return {"config": cfg.name, "num_layers": cfg.num_layers, "ep": ep,
            "devices": n_dev, "batch": batch, "seq": seq,
            "loss_one_device": loss1, "loss_ep": loss_ep,
            "loss_rel_err": loss_rel, "grad_norm_one_device": float(norm1),
            "grad_norm_ep": float(norm_ep), "grad_tree_rel_err": tree_err,
            "worst_leaf": worst, "worst_leaf_rel_err": checked[worst],
            "leaves_checked": len(checked),
            "noise_leaves": {k: noise[k] for k in sorted(noise)},
            "tolerance": {"loss": EP_LOSS_REL_TOL, "tree": EP_TREE_REL_TOL,
                          "leaf": EP_GRAD_REL_TOL,
                          "leaf_floor": EP_LEAF_FLOOR},
            "failed": failed}


# -------------------------------------------------------------- configs

def moe_config(num_layers: int = 2):
    """qwen2-moe-a2.7b at published widths, depth cut to ``num_layers``.
    Two layers is what one 16 GB chip holds with fused LoMo: every bf16
    weight keeps an f32 master copy (6 bytes per parameter), and one layer
    has 0.61 B parameters besides the 0.62 B of embedding and head."""
    from repro.configs.base import get_config
    return get_config("qwen2-moe-a2.7b").replace(
        num_layers=num_layers, use_flash_kernel=True, moe_backend="grouped")


def dense_config(num_layers: int = 24):
    """h2o-danube-1.8b at published widths.  Fine-tuning at seq 4096 on
    the default attention path holds 7 GB of per-chunk scores, probabilities
    and masks while one layer's backward recomputes its attention, whatever
    the depth; with fused AdamW (10 bytes per parameter) the v5e compiler
    fits 5 layers and refuses 6, so phase B takes 4 for margin."""
    from repro.configs.base import get_config
    return get_config("h2o-danube-1.8b").replace(num_layers=num_layers)


# ----------------------------------------------------------------- main

def _run_phase(name: str, fn, *args, **kw) -> bool:
    gc.collect()            # the previous phase's arrays leave the device
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kw)
    except Exception as e:  # reported as a failed phase, then exit != 0
        traceback.print_exc()
        out = {"error": f"{type(e).__name__}: {e}"[:2000],
               "failed": ["raised"]}
    _emit({"phase": name, "ok": not out["failed"],
           "wall_s": time.perf_counter() - t0, **out})
    return not out["failed"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP=4 vs one-device parity check "
                         "(needs a four-chip host)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if args.four_chips and n_dev < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {n_dev}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim.adamw import AdamW
    from repro.optim.lomo import LoMo
    enable_compile_cache()

    if args.four_chips:
        ok = _run_phase("EP4", phase_ep_parity, moe_config(), ep=4,
                        batch=4, seq=2048)
    else:
        ok = _run_phase("A", phase_train, moe_config(), LoMo(lr=LOMO_LR),
                        batch=4, seq=2048, steps=4, require_kernels=True)
        ok &= _run_phase("B", phase_train, dense_config(num_layers=4),
                         AdamW(lr=ADAMW_LR), batch=2, seq=4096, steps=4,
                         require_kernels=False)
        ok &= _run_phase("C", phase_serve, dense_config(), slots=8,
                         requests=16, shared_prefix=128, min_prompt=64,
                         max_prompt=512, gen=64)
        ok &= _run_phase("K", phase_kernels, moe_config(), dense_config(),
                         batch=4, seq=2048, slots=8, kv_len=128 + 512 + 64)
    if not ok:
        return 1
    _emit({"ok": True, "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": n_dev}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
