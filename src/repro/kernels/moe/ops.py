"""Public grouped-MoE ops: backend selection + custom_vjp + the full
dropless expert FFN (dispatch -> grouped GEMMs -> combine).

``grouped_matmul`` is the differentiable entry point.  Its backward is a
``custom_vjp`` that re-permutes cotangents instead of storing any dispatch
structure (DESIGN.md §7 residual layout):

  * d_lhs is itself a grouped GEMM against the transposed expert weights
    (same kernel, rhs axes swapped) — the cotangent rows are already in
    expert-contiguous order;
  * d_rhs is a per-tile contraction segment-summed into expert slots
    (the "tgmm"); pure-JAX today, a second Pallas kernel when profiles
    demand it.

Residuals are exactly (lhs, rhs, tile_expert): the sorted activations, the
weights autodiff keeps anyway, and one int32 per tile.  Compare the einsum
path, whose backward keeps the (G, t, E, C) dispatch AND combine tensors.

``grouped_expert_ffn`` composes cleanly with ``core/reversible.py``: the
reversible stack re-runs a block's forward under ``jax.vjp`` during its
backward sweep, so the per-block residency is one sorted activation buffer
per GEMM — never a dispatch tensor.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe import dispatch as dsp
from repro.kernels.moe.grouped_gemm import grouped_matmul_pallas
from repro.kernels.moe.ref import grouped_matmul_ref
from repro.obs import scopes

IMPLS = ("pallas", "jax")


def default_impl() -> str:
    """Pallas (compiled) on TPU; the pure-JAX tiled reference elsewhere —
    interpret-mode Pallas is for parity tests, not the hot path."""
    return "pallas" if jax.default_backend() == "tpu" else "jax"


def default_block_m() -> int:
    """MXU-height tiles on TPU; small tiles off-TPU so the per-expert
    padding (E * (block_m - 1) rows worst case) stays negligible in tests."""
    return 128 if jax.default_backend() == "tpu" else 16


def _run(lhs, rhs, tile_expert, block_m: int, impl: str):
    assert impl in IMPLS, impl
    if impl == "pallas":
        return grouped_matmul_pallas(lhs, rhs, tile_expert, block_m=block_m,
                                     interpret=jax.default_backend() != "tpu")
    return grouped_matmul_ref(lhs, rhs, tile_expert, block_m=block_m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, tile_expert, block_m: int, impl: str):
    """out[m] = lhs[m] @ rhs[tile_expert[m // block_m]].

    lhs: (m_pad, K) expert-contiguous rows, rhs: (E, K, N),
    tile_expert: (m_pad/block_m,) int32.  Differentiable in lhs and rhs.
    """
    return _run(lhs, rhs, tile_expert, block_m, impl)


def _gmm_fwd(lhs, rhs, tile_expert, block_m, impl):
    return _run(lhs, rhs, tile_expert, block_m, impl), (lhs, rhs, tile_expert)


def _gmm_bwd(block_m, impl, res, ct):
    lhs, rhs, tile_expert = res
    n_tiles = lhs.shape[0] // block_m
    ct = ct.astype(lhs.dtype)
    d_lhs = _run(ct, rhs.transpose(0, 2, 1), tile_expert, block_m, impl)
    per_tile = jnp.einsum(
        "tmk,tmn->tkn",
        lhs.reshape(n_tiles, block_m, lhs.shape[1]),
        ct.reshape(n_tiles, block_m, ct.shape[1]),
        preferred_element_type=jnp.float32)
    d_rhs = jnp.zeros(rhs.shape, jnp.float32).at[tile_expert].add(
        per_tile).astype(rhs.dtype)
    d_te = np.zeros(tile_expert.shape, jax.dtypes.float0)
    return d_lhs, d_rhs, d_te


grouped_matmul.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_expert_ffn(x, expert_idx, gates, w_gate, w_up, w_down, *,
                       block_m: Optional[int] = None,
                       impl: Optional[str] = None):
    """Dropless SwiGLU expert FFN over sorted tokens.

    x: (T, d); expert_idx/gates: (T, k); w_gate/w_up: (E, d, f);
    w_down: (E, f, d).  Returns (T, d) = sum_k gate * expert_k(x).
    """
    block_m = block_m or default_block_m()
    impl = impl or default_impl()
    num_tokens = x.shape[0]
    with jax.named_scope(scopes.MOE_DISPATCH):
        plan = dsp.make_plan(expert_idx, w_gate.shape[0], block_m)
        xs = dsp.permute(x, plan)
    with jax.named_scope(scopes.MOE_EXPERTS):
        g = grouped_matmul(xs, w_gate, plan.tile_expert, block_m, impl)
        u = grouped_matmul(xs, w_up, plan.tile_expert, block_m, impl)
        h = jax.nn.silu(g) * u
        ys = grouped_matmul(h, w_down, plan.tile_expert, block_m, impl)
    with jax.named_scope(scopes.MOE_COMBINE):
        return dsp.combine(ys, gates, plan, num_tokens)
