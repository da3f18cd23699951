"""Pallas TPU Mamba2 SSD kernel: chunked state-space scan with the (N x P)
state resident in f32 VMEM scratch across the sequential chunk axis.

Scalar-per-head decay makes everything matmul-shaped (unlike RWKV6's
per-channel decay): within a chunk of T tokens,

  scores[t,s] = (C_t . B_s) * exp(la_t - la_s) * dt_s,  s <= t   (MXU + VPU)
  y_intra     = scores @ x                                        (MXU)
  y_inter[t]  = exp(la_t) * (C_t @ state)                         (MXU)
  state'      = exp(la_T) * state + (B * exp(la_T - la_s) * dt)^T @ x

All exponent arguments <= 0 (decays in (0,1)] => numerically safe.
Grid: (B*H, S/T); B/C are shared across heads (n_groups=1) and indexed by
bh // H.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, o_ref, s_scr, *, T: int):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0].astype(jnp.float32)          # (T, P)
    b = b_ref[0].astype(jnp.float32)          # (T, N)
    c = c_ref[0].astype(jnp.float32)          # (T, N)
    dt = dt_ref[0].astype(jnp.float32)        # (T, 1)
    la = jnp.cumsum(la_ref[0].astype(jnp.float32), axis=0)   # (T, 1) cumulative

    # intra-chunk; mask BEFORE exp: la_t - la_s > 0 for s > t can overflow
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (T, T)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    decay = jnp.exp(jnp.where(tri, la - la.T, -1e30))
    scores = cb * decay * dt.T
    y = jax.lax.dot(scores, x, preferred_element_type=jnp.float32)

    # inter-chunk carry
    y = y + jnp.exp(la) * jax.lax.dot(c, s_scr[...],
                                      preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)

    # state update
    end = la[T - 1:T, :]                       # (1,1)
    bd = b * jnp.exp(end - la) * dt            # (T, N)
    s_scr[...] = (jnp.exp(end) * s_scr[...] +
                  jax.lax.dot(bd.T, x, preferred_element_type=jnp.float32))


def mamba_ssd(x, B_t, C_t, dt, log_a, *, chunk: int = 128,
              interpret: bool):
    """x: (B,H,S,P); B_t/C_t: (B,S,N); dt/log_a: (B,H,S).  Returns y like x."""
    Bb, H, S, P = x.shape
    N = B_t.shape[-1]
    T = min(chunk, S)
    assert S % T == 0
    nc = S // T

    xr = x.reshape(Bb * H, S, P)
    dtr = dt.reshape(Bb * H, S, 1)
    lar = log_a.reshape(Bb * H, S, 1)

    out = pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=(Bb * H, nc),
        in_specs=[
            pl.BlockSpec((1, T, P), lambda bh, c_: (bh, c_, 0)),
            pl.BlockSpec((1, T, N), lambda bh, c_: (bh // H, c_, 0)),
            pl.BlockSpec((1, T, N), lambda bh, c_: (bh // H, c_, 0)),
            pl.BlockSpec((1, T, 1), lambda bh, c_: (bh, c_, 0)),
            pl.BlockSpec((1, T, 1), lambda bh, c_: (bh, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, P), lambda bh, c_: (bh, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
        name="mamba_ssd",
    )(xr, B_t, C_t, dtr, lar)
    return out.reshape(Bb, H, S, P)
