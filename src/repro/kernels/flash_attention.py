"""Pallas TPU flash attention: blocked online-softmax with GQA, causal /
sliding-window masking and logit soft-capping (gemma2) — forward AND backward.

Forward grid: (B * H, Sq/BQ, Skv/BK).  The kv axis is innermost (sequential on
TPU), so the running max / denominator / accumulator live in f32 VMEM scratch
and persist across kv steps of one q block.  MXU work: q @ k^T and p @ v per
(BQ, BK) tile; the ops wrapper pads head_dim to a multiple of 128.  With
``return_lse`` the kernel also emits the per-row logsumexp (m + log l), the
O(S) residual the backward kernels recompute probability tiles from.  Tiles
fully masked by causal/window (dead) are skipped with ``pl.when``, and the
k/v index maps clamp a dead step to the q block's live kv range, so it keeps
the block the previous step holds and the pipeline issues no copy for it.
Causal calls on square tiles with an even number of q blocks fold the grid
to (B * H, Sq/BQ/2, Sq/BQ + 1), pairing q block a with q block Sq/BQ-1-a, so
the grid holds no dead step at all (``_fwd_walk``).

Backward (DESIGN.md §8) splits into two passes over the same recomputed
p tiles — p = exp(s - lse) needs no second online softmax:

  * dq pass, grid (B*H, Sq/BQ, Skv/BK), kv innermost: dq accumulates in a
    (BQ, hd) f32 scratch across kv tiles of one q block.
  * dk/dv pass, grid (B*KV, Skv/BK, G*Sq/BQ), (group, q) innermost: dk and dv
    accumulate in (BK, hd) f32 scratch across all q tiles of every q head in
    the kv group — the GQA head-group reduction happens in-kernel, so the
    kernel never materialises per-q-head dk/dv.

Both passes take the precomputed delta = rowsum(dO * O) (the softmax-jacobian
row term), apply softcap's tanh chain rule where enabled, and skip dead tiles
via ``pl.when`` on the grid indices, as the forward does.

``flash_attention_fwd_jax`` / ``flash_attention_bwd_jax`` are the pure-JAX
tiled fallbacks (the off-TPU production path, same pattern as the grouped-GEMM
MoE kernels): identical math, ``lax.map`` over q tiles (forward, dq) and k
tiles (dk/dv), so no (Sq, Skv) tensor is ever materialised there either.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask_positions(pos_q, pos_k, causal: bool, window: Optional[int]):
    """Validity predicate on broadcastable position grids — the single
    source of the causal/window semantics for the forward, backward AND
    pure-JAX fallback paths (the backward recomputes p from lse, so they
    must never diverge)."""
    mask = jnp.ones(jnp.broadcast_shapes(pos_q.shape, pos_k.shape), jnp.bool_)
    if causal:
        mask &= pos_q >= pos_k
    if window is not None:
        mask &= (pos_q - pos_k) < window
    return mask


def _tile_mask(iq, jk, *, causal: bool, window: Optional[int],
               bq: int, bk: int):
    """(bq, bk) validity mask of tile (iq, jk) for the Pallas kernels."""
    pos_q = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    pos_k = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return _mask_positions(pos_q, pos_k, causal, window)


def _tile_live(iq, jk, *, causal: bool, window: Optional[int],
               bq: int, bk: int):
    """False iff tile (iq, jk) is fully masked (dead) under causal/window."""
    live = jnp.bool_(True)
    if causal:                          # max q pos >= min k pos
        live &= iq * bq + (bq - 1) >= jk * bk
    if window is not None:              # min (q - k) < window
        live &= iq * bq - (jk * bk + bk - 1) < window
    return live


def _live_kv_block(i, j, *, causal: bool, window: Optional[int],
                   bq: int, bk: int, nk: int):
    """kv block index for grid step (i, j) of the unfolded forward: j clamped
    to the live range of q block i, so a dead step names the block the
    previous step already holds and the pipeline issues no copy for it."""
    if window is not None:              # first live: holds i*bq - window + 1
        lo = jnp.maximum(i * bq - window + 1, 0) // bk
        j = jnp.maximum(j, jnp.minimum(lo, nk - 1))
    if causal:                          # last live: holds i*bq + bq - 1
        j = jnp.minimum(j, (i * bq + bq - 1) // bk)
    return j


def _fwd_walk(*, causal: bool, window: Optional[int], bq: int, bk: int,
              nq: int, nk: int):
    """The forward's grid over (q block, kv block) and ``step(a, b)`` ->
    (q block, kv tile, kv block to fetch, first step of the row, last step
    of the row) for grid step (a, b).

    Causal attention on square tiles with an even number of q blocks is
    walked folded: grid (nq/2, nq+1) visits row a for b <= a and then row
    nq-1-a, so each pair of rows holds exactly its nq+1 live tiles and no
    step is dead (a dead step still costs its grid-step overhead).  Every
    other call walks (nq, nk), skips dead tiles and clamps their kv block.
    Both walk each row's kv tiles in ascending order, so o and lse agree to
    the bit."""
    if causal and window is None and bq == bk and nq == nk and nq % 2 == 0:
        def step(a, b):
            lower = b <= a
            jk = jnp.where(lower, b, b - a - 1)
            return (jnp.where(lower, a, nq - 1 - a), jk, jk,
                    (b == 0) | (b == a + 1), (b == a) | (b == nq))
        return (nq // 2, nq + 1), step

    def step(a, b):
        kv = _live_kv_block(a, b, causal=causal, window=window, bq=bq, bk=bk,
                            nk=nk)
        return a, b, kv, b == 0, b == nk - 1
    return (nq, nk), step


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], bq: int, bk: int, step):
    iq, jk, _, first, last = step(pl.program_id(1), pl.program_id(2))

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_live(iq, jk, causal=causal, window=window, bq=bq, bk=bk))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        mask = _tile_mask(iq, jk, causal=causal, window=window, bq=bq, bk=bk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                              # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(last)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool,
                    return_lse: bool = False):
    """q: (B,H,Sq,hd), k/v: (B,KV,Skv,hd) -> (B,H,Sq,hd).  GQA via H % KV == 0.

    ``scale`` defaults to hd**-0.5 (pass the pre-padding value when padding).
    ``return_lse`` additionally returns the per-row logsumexp (B,H,Sq) f32 —
    the backward-pass residual.  The kernel carries it as (B*H, Sq, 1) so its
    (bq, 1) blocks meet the TPU (8, 128) block rule (a trailing dim equal to
    the array's passes)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk

    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * KV, Skv, hd)
    vr = v.reshape(B * KV, Skv, hd)

    grid, step = _fwd_walk(causal=causal, window=window, bq=bq, bk=bk,
                           nq=nq, nk=nk)
    kern = functools.partial(
        _kernel, scale=scale if scale is not None else hd ** -0.5,
        causal=causal, window=window, softcap=softcap, bq=bq, bk=bk,
        step=step)

    def q_rows(bh, a, b):
        return bh, step(a, b)[0], 0

    def kv_rows(bh, a, b):
        return bh // G, step(a, b)[2], 0

    out, lse = pl.pallas_call(
        kern,
        grid=(B * H, *grid),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_rows),
            pl.BlockSpec((1, bk, hd), kv_rows),
            pl.BlockSpec((1, bk, hd), kv_rows),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, hd), q_rows),
            pl.BlockSpec((1, bq, 1), q_rows),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out = out.reshape(B, H, Sq, hd)
    if return_lse:
        return out, lse.reshape(B, H, Sq)
    return out


# ================================================================== backward

def _p_ds_tiles(q, k, v, do, lse, delta, iq, jk, *, scale, causal, window,
                softcap, bq, bk):
    """Shared backward tile math: probabilities p = exp(s - lse) and the
    pre-scale score cotangent ds (softcap chain rule applied)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        t = jnp.tanh(s / softcap)
        s = t * softcap
    mask = _tile_mask(iq, jk, causal=causal, window=window, bq=bq, bk=bk)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                                       # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if softcap is not None:
        ds = ds * (1.0 - t * t)          # d tanh(x/c)*c = (1 - tanh^2)
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale: float, causal: bool,
                   window: Optional[int], softcap: Optional[float],
                   bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_live(iq, jk, causal=causal, window=window, bq=bq, bk=bk))
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                # (bq, 1)
        delta = delta_ref[0]
        _, ds = _p_ds_tiles(q, k, v, do, lse, delta, iq, jk, scale=scale,
                            causal=causal, window=window, softcap=softcap,
                            bq=bq, bk=bk)
        acc_scr[...] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(jk == nk - 1)
    def _finish():
        dq_ref[0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, window: Optional[int],
                    softcap: Optional[float], bq: int, bk: int,
                    nq: int, ng: int):
    jk = pl.program_id(1)
    t = pl.program_id(2)                # t = g * nq + iq (q heads outer)
    iq = jax.lax.rem(t, nq)

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_live(iq, jk, causal=causal, window=window, bq=bq, bk=bk))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                             # (bq, 1)
        delta = delta_ref[0, 0]
        p, ds = _p_ds_tiles(q, k, v, do, lse, delta, iq, jk, scale=scale,
                            causal=causal, window=window, softcap=softcap,
                            bq=bq, bk=bk)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(t == ng * nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, lse, delta, do, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """Pallas flash-attention backward from O(S) residuals.

    q/do: (B,H,Sq,hd), k/v: (B,KV,Skv,hd), lse/delta: (B,H,Sq) f32 with
    delta = rowsum(dO * O).  Returns (dq, dk, dv) — dk/dv group-reduced to
    (B,KV,Skv,hd).  lse/delta ride as (..., Sq, 1) columns, like the forward's
    lse output."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    scale = scale if scale is not None else hd ** -0.5

    qr = q.reshape(B * H, Sq, hd)
    kr = k.reshape(B * KV, Skv, hd)
    vr = v.reshape(B * KV, Skv, hd)
    dor = do.reshape(B * H, Sq, hd)
    lser = lse.reshape(B * H, Sq, 1).astype(jnp.float32)
    deltar = delta.reshape(B * H, Sq, 1).astype(jnp.float32)

    dq_kern = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nk=nk)
    dq = pl.pallas_call(
        dq_kern,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, i, j: (bh // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, i, j: (bh // G, j, 0)),
            pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(qr, kr, vr, dor, lser, deltar)

    # group-major layouts so the dk/dv grid walks (g, iq) innermost
    qg = qr.reshape(B * KV, G, Sq, hd)
    dog = dor.reshape(B * KV, G, Sq, hd)
    lseg = lser.reshape(B * KV, G, Sq, 1)
    deltag = deltar.reshape(B * KV, G, Sq, 1)

    dkv_kern = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, nq=nq, ng=G)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(B * KV, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, j, t: (b, t // nq, t % nq, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, j, t: (b, t // nq, t % nq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, j, t: (b, t // nq, t % nq, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, j, t: (b, t // nq, t % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, hd), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KV, Skv, hd), k.dtype),
            jax.ShapeDtypeStruct((B * KV, Skv, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(qg, kr, vr, dog, lseg, deltag)

    return (dq.reshape(B, H, Sq, hd),
            dk.reshape(B, KV, Skv, hd),
            dv.reshape(B, KV, Skv, hd))


# ====================================================== pure-JAX tiled fallback

def _mask_tile(pos_q, pos_k, causal: bool, window: Optional[int]):
    return _mask_positions(pos_q[:, None], pos_k[None, :], causal, window)


def flash_attention_fwd_jax(q, k, v, *, causal: bool = True,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_q: int = 128):
    """Tiled pure-JAX forward emitting (out, lse) — the off-TPU production
    path.  ``lax.map`` over q tiles: peak transient is (B,H,bq,Skv), never
    (Sq, Skv)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, Sq)
    assert Sq % bq == 0
    nq = Sq // bq
    scale = scale if scale is not None else hd ** -0.5

    qg = q.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    pos_k = jnp.arange(Skv)

    def tile(args):
        qt, pos_qt = args                        # (B,KV,G,bq,hd), (bq,)
        s = jnp.einsum("bkgqh,bksh->bkgqs", qt, kf) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        mask = _mask_tile(pos_qt, pos_k, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        o = jnp.einsum("bkgqs,bksh->bkgqh", p, vf) / l[..., None]
        return o, m + jnp.log(l)

    qt = qg.reshape(B, KV, G, nq, bq, hd).transpose(3, 0, 1, 2, 4, 5)
    pos_q = jnp.arange(Sq).reshape(nq, bq)
    o, lse = jax.lax.map(tile, (qt, pos_q))
    o = o.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, Sq, hd).astype(q.dtype)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, H, Sq)
    return o, lse


def flash_attention_bwd_jax(q, k, v, lse, delta, do, *, causal: bool = True,
                            window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            scale: Optional[float] = None,
                            block_q: int = 128, block_k: int = 128):
    """Tiled pure-JAX backward from (q, k, v, lse, delta) — same math as the
    Pallas kernels, ``lax.map`` over q tiles (dq) and k tiles (dk/dv)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    nq, nk = Sq // bq, Skv // bk
    scale = scale if scale is not None else hd ** -0.5

    qg = q.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    dog = do.reshape(B, KV, G, Sq, hd).astype(jnp.float32)
    lseg = lse.reshape(B, KV, G, Sq).astype(jnp.float32)
    deltag = delta.reshape(B, KV, G, Sq).astype(jnp.float32)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    pos_q_all, pos_k_all = jnp.arange(Sq), jnp.arange(Skv)

    def p_ds(qt, kt, vt, dot, lset, deltat, mask):
        s = jnp.einsum("bkgqh,bksh->bkgqs", qt, kt) * scale
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            s = t * softcap
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lset[..., None])
        dp = jnp.einsum("bkgqh,bksh->bkgqs", dot, vt)
        ds = p * (dp - deltat[..., None])
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        return p, ds

    def dq_tile(args):
        qt, dot, lset, deltat, pos_qt = args
        mask = _mask_tile(pos_qt, pos_k_all, causal, window)
        _, ds = p_ds(qt, kf, vf, dot, lset, deltat, mask)
        return jnp.einsum("bkgqs,bksh->bkgqh", ds, kf) * scale

    def per_q_tiles(a):                          # (..., Sq, rest) -> tile-major
        return a.reshape(*a.shape[:3], nq, bq, *a.shape[4:]).transpose(
            3, 0, 1, 2, 4, *range(5, a.ndim + 1))

    dq = jax.lax.map(dq_tile, (
        per_q_tiles(qg), per_q_tiles(dog), per_q_tiles(lseg),
        per_q_tiles(deltag), pos_q_all.reshape(nq, bq)))
    dq = dq.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, Sq, hd).astype(q.dtype)

    def dkv_tile(args):
        kt, vt, pos_kt = args                    # (B,KV,bk,hd), (bk,)
        mask = _mask_tile(pos_q_all, pos_kt, causal, window)
        p, ds = p_ds(qg, kt, vt, dog, lseg, deltag, mask)
        dv_t = jnp.einsum("bkgqs,bkgqh->bksh", p, dog)
        dk_t = jnp.einsum("bkgqs,bkgqh->bksh", ds, qg) * scale
        return dk_t, dv_t

    kt = kf.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    vt = vf.reshape(B, KV, nk, bk, hd).transpose(2, 0, 1, 3, 4)
    dk, dv = jax.lax.map(dkv_tile, (kt, vt, pos_k_all.reshape(nk, bk)))
    dk = dk.transpose(1, 2, 0, 3, 4).reshape(B, KV, Skv, hd).astype(k.dtype)
    dv = dv.transpose(1, 2, 0, 3, 4).reshape(B, KV, Skv, hd).astype(v.dtype)
    return dq, dk, dv
