"""Pallas TPU ragged grouped GEMM: out[m] = lhs[m] @ rhs[expert_of(m)].

Grid: (m_pad / block_m, N / block_n).  The tokens were permuted into
expert-contiguous rows with each expert's run padded to a multiple of
``block_m`` (repro.kernels.moe.dispatch), so every lhs row-tile belongs to
exactly one expert.  The per-tile expert id is a scalar-prefetched int32
vector consumed by the rhs BlockSpec index map — the weight block for tile
``i`` streams straight from HBM without any gather materialisation.

The contraction axis K is kept whole per tile (one MXU pass per (BM, BN)
output block); at d_model <= 8k and block_m = 128 the (BM, K) + (K, BN)
working set stays well inside VMEM.  Padding rows are zero and compute
zeros — they are never read back by the combine scatter.

``interpret`` is a required keyword: ``repro.kernels.moe.ops`` decides it
from the backend (compiled on TPU, the Pallas interpreter in CPU tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pick_block_n(n: int, prefer: int = 512) -> int:
    """Largest MXU-friendly divisor of N (N itself when nothing divides)."""
    for cand in (prefer, 256, 128):
        if n % cand == 0:
            return cand
    return n


def _kernel(e_ref, lhs_ref, rhs_ref, out_ref):
    del e_ref  # consumed by the index maps
    out_ref[...] = jnp.dot(lhs_ref[...], rhs_ref[0],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def grouped_matmul_pallas(lhs, rhs, tile_expert, *, block_m: int,
                          block_n: int = 0, interpret: bool):
    """lhs: (m_pad, K), rhs: (E, K, N), tile_expert: (m_pad/block_m,) int32.

    Returns (m_pad, N) in lhs.dtype (f32 MXU accumulation).
    """
    m_pad, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, (K, K2)
    assert m_pad % block_m == 0, (m_pad, block_m)
    bn = block_n or pick_block_n(N)
    assert N % bn == 0, (N, bn)
    n_tiles, nn = m_pad // block_m, N // bn
    assert tile_expert.shape == (n_tiles,), (tile_expert.shape, n_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, nn),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j, e_ref: (i, 0)),
            pl.BlockSpec((1, K, bn), lambda i, j, e_ref: (e_ref[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, e_ref: (i, j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, N), lhs.dtype),
        interpret=interpret,
        name="gmm",
    )(tile_expert.astype(jnp.int32), lhs, rhs)
