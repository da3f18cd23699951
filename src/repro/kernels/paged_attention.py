"""Paged gather-attention for the serving decode step (DESIGN.md §15).

The paged serving engine stores KV state in a pool of fixed-size physical
pages shared by every slot; a per-slot page table maps logical context
positions to pool pages.  Decode attention therefore has to read K/V
*through* the page table.  Two implementations behind one wrapper, the same
convention as the grouped-MoE and flash kernels (DESIGN.md §7/§8):

* ``impl="pallas"`` — a TPU kernel over ``PrefetchScalarGridSpec``: the page
  table and per-slot positions ride as scalar-prefetch operands, so the
  BlockSpec index map resolves each grid step's physical page *before* the
  body runs and the pool tiles are DMA'd straight from HBM into VMEM —
  no (B, C, KV, hd) gathered copy is ever materialised.  Flash-style
  running-max/sum accumulation over the page axis.

* ``impl="jax"`` — gather the mapped pages into a contiguous per-slot
  buffer and run the exact dense decode-attention einsum over it.  This is
  the CPU/GPU path and the parity oracle; it reproduces
  ``models.common._attend_cache`` bit-for-bit, which is what the engine's
  paged-vs-dense equivalence gate leans on.

``paged_attention`` picks the implementation from the backend and, for the
kernel, whether it runs compiled (TPU) or under the Pallas interpreter (CPU
parity tests, never the hot path); ``paged_attention_pallas`` takes
``interpret`` as a required keyword.

Shapes (decode: one query position per slot):

  q          (B, H, hd)
  k/v pool   (P, page, KV, hd)     P physical pages of ``page`` positions
  pos pool   (P, page) int32       stored absolute positions, -1 = invalid
  page_table (B, n_pages) int32    physical page per logical page, -1 = unmapped
  t          (B,) int32            current query position per slot
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ------------------------------------------------------------- jax reference

def gather_pages(k_pool, v_pool, pos_pool, page_table, kv_len: int):
    """Materialise each slot's logical KV buffer from the pool.

    Returns (k, v, pos) shaped ((B, kv_len, KV, hd) x2, (B, kv_len));
    unmapped pages surface as pos = -1 (their K/V rows are arbitrary and
    must be masked by the caller — exactly how the dense cache treats
    never-written entries)."""
    page = k_pool.shape[1]
    pt = page_table[:, : pl.cdiv(kv_len, page)]
    safe = jnp.clip(pt, 0, k_pool.shape[0] - 1)
    k = k_pool[safe].reshape(pt.shape[0], -1, *k_pool.shape[2:])[:, :kv_len]
    v = v_pool[safe].reshape(pt.shape[0], -1, *v_pool.shape[2:])[:, :kv_len]
    pos = jnp.where(pt[:, :, None] >= 0, pos_pool[safe], -1)
    pos = pos.reshape(pt.shape[0], -1)[:, :kv_len]
    return k, v, pos


def paged_attention_jax(q, k_pool, v_pool, pos_pool, page_table, t, *,
                        kv_len: int, window=None, softcap=None):
    """Reference paged decode attention: gather + dense masked softmax.

    The einsum/mask/softmax sequence mirrors ``models.common._attend_cache``
    on a dense cache exactly (same ops, same dtypes, same shapes after the
    gather), so on matching inputs the result is bit-identical to the dense
    decode path.  ``window`` may be a traced scalar (local/global layers).
    """
    B, H, hd = q.shape
    k, v, pos = gather_pages(k_pool, v_pool, pos_pool, page_table, kv_len)
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    pk = pos[:, None, None, None, :]                       # (B,1,1,1,C)
    pq = t[:, None, None, None, None]                      # (B,1,1,1,1)
    mask = (pk >= 0) & (pk <= pq)
    if window is not None:
        mask = mask & ((pq - pk) < window)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v).reshape(B, 1, H * hd)
    return out[:, 0]


# ------------------------------------------------------------ pallas kernel

def _paged_kernel(pt_ref, t_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page, kv_len, n_pages,
                  window, softcap, scale):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (H, hd)
    k = k_ref[0].astype(jnp.float32)                       # (page, KV, hd)
    v = v_ref[0].astype(jnp.float32)
    pos = pos_ref[0]                                       # (1, page)
    H, hd = q.shape
    KV = k.shape[1]
    G = H // KV

    qg = q.reshape(KV, G, hd)
    # (KV, G, page): batch over kv heads, contract head_dim
    s = jax.lax.dot_general(
        qg, k, dimension_numbers=(((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    s = s.reshape(H, page)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    tq = t_ref[b]
    mapped = pt_ref[b, j] >= 0
    off = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = (pos >= 0) & (pos <= tq) & (j * page + off < kv_len) & mapped
    if window is not None:
        valid &= (tq - pos) < window
    s = jnp.where(valid, s, NEG_INF)                       # (H, page)

    m_prev = m_ref[:]                                      # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[:] = m_new
    pv = jax.lax.dot_general(
        p.reshape(KV, G, page), v,
        dimension_numbers=(((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32).reshape(H, hd)
    acc_ref[:] = acc_ref[:] * alpha + pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:], 1e-37)
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_len", "window", "softcap",
                                             "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, pos_pool, page_table, t, *,
                           kv_len: int, window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: bool):
    """Pallas paged decode attention.  Grid (B, n_pages); the page table is a
    scalar-prefetch operand so each step's K/V/pos blocks are fetched from
    the physical page ``page_table[b, j]`` (clipped for unmapped entries,
    which the in-kernel validity mask then zeroes out)."""
    B, H, hd = q.shape
    P, page, KV, _ = k_pool.shape
    n_pages = page_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, pt, tt: (b, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, j, pt, tt:
                         (jnp.clip(pt[b, j], 0, P - 1), 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, j, pt, tt:
                         (jnp.clip(pt[b, j], 0, P - 1), 0, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda b, j, pt, tt:
                         (jnp.clip(pt[b, j], 0, P - 1), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, pt, tt: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    kern = functools.partial(
        _paged_kernel, page=page, kv_len=kv_len, n_pages=n_pages,
        window=window, softcap=softcap, scale=hd ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(page_table, t, q, k_pool, v_pool, pos_pool.reshape(P, 1, page))
    return out.reshape(B, H * hd)


# ------------------------------------------------------------------ wrapper

PAGED_IMPLS = ("pallas", "jax")


def _impl(impl: Optional[str]) -> str:
    if impl is not None:
        assert impl in PAGED_IMPLS, impl
        return impl
    return "pallas" if _on_tpu() else "jax"


def paged_attention(q, k_pool, v_pool, pos_pool, page_table, t, *,
                    kv_len: int, window=None, softcap=None,
                    impl: Optional[str] = None):
    """Paged decode attention; returns (B, H*hd).

    ``impl``: None (pallas on TPU, gather-jax elsewhere) | "pallas" | "jax".
    Traced ``window`` values (local/global layer schedules) force the jax
    path — the kernel needs a static window to bake the mask."""
    if _impl(impl) == "jax" or not isinstance(window, (int, type(None))):
        return paged_attention_jax(q, k_pool, v_pool, pos_pool, page_table,
                                   t, kv_len=kv_len, window=window,
                                   softcap=softcap)
    return paged_attention_pallas(q, k_pool, v_pool, pos_pool, page_table,
                                  t, kv_len=kv_len, window=window,
                                  softcap=softcap, interpret=not _on_tpu())
