"""Pallas TPU RWKV6 wkv kernel: chunked data-dependent-decay linear recurrence.

TPU adaptation of the CUDA wkv6 kernel (DESIGN.md §2): grid (B*H, S/T) with
the per-head (C x C) state resident in f32 VMEM scratch across the sequential
chunk axis.  Within a chunk of T tokens:

  o[t]  = sum_{s<t} (sum_c r[t,c] k[s,c] exp(lw[t-1,c] - lw[s,c])) v[s]
          + (r[t] . (u*k[t])) v[t]                      (bonus, diagonal)
          + (r[t] * exp(lw[t-1])) @ S0                  (carry-in,  MXU)
  S_end = exp(lw[T-1]) * S0 + sum_s (k[s] * exp(lw[T-1]-lw[s]))^T v[s]  (MXU)

All exponent arguments are <= 0 (decays in (0,1)), so the chunked form is
numerically safe at any chunk length.  The (T,T,C) decay tensor is the
VPU-bound part — per-channel decay has no pure-matmul form; chunking keeps it
in VMEM (T=64, C=64 -> 1 MB f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *, T: int, C: int):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)          # (T, C)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)        # per-step log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # (1, C) bonus

    clw = jnp.cumsum(lw, axis=0)              # (T, C) inclusive
    clw_prev = clw - lw                       # exclusive: lw[t-1] cumulative

    # intra-chunk: D[t,s,c] = exp(clw_prev[t,c] - clw[s,c]), s < t.
    # mask INSIDE the exp (s >= t differences are positive and can overflow)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    D = jnp.exp(jnp.where(tri[:, :, None],
                          clw_prev[:, None, :] - clw[None, :, :], -1e30))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * D, axis=-1)
    bonus = jnp.sum(r * u * k, axis=-1)       # (T,)
    A = A + jnp.diag(bonus)
    o = jax.lax.dot(A, v, preferred_element_type=jnp.float32)

    # carry-in from previous chunks (MXU)
    o = o + jax.lax.dot(r * jnp.exp(clw_prev), s_scr[...],
                        preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)

    # state update (MXU)
    endw = clw[T - 1:T, :]                    # (1, C)
    kd = k * jnp.exp(endw - clw)              # (T, C)
    s_scr[...] = (jnp.exp(endw).T * s_scr[...] +
                  jax.lax.dot(kd.T, v, preferred_element_type=jnp.float32))


def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64, interpret: bool):
    """r,k,v: (B,H,S,C); w: decay in (0,1) (B,H,S,C); u: (H,C).
    Returns out (B,H,S,C).  S must be a multiple of ``chunk``."""
    B, H, S, C = r.shape
    T = min(chunk, S)
    assert S % T == 0
    nc = S // T
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))

    rr = r.reshape(B * H, S, C)
    kk = k.reshape(B * H, S, C)
    vv = v.reshape(B * H, S, C)
    ll = lw.reshape(B * H, S, C)
    uu = jnp.broadcast_to(u[None], (B, H, C)).reshape(B * H, 1, C)

    out = pl.pallas_call(
        functools.partial(_kernel, T=T, C=C),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, T, C), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, T, C), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, T, C), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, T, C), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, C), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, C), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, C), r.dtype),
        scratch_shapes=[pltpu.VMEM((C, C), jnp.float32)],
        interpret=interpret,
        name="rwkv6_scan",
    )(rr, kk, vv, ll, uu)
    return out.reshape(B, H, S, C)
