"""Jit'd public wrappers around the Pallas kernels: dtype/shape plumbing,
head-dim padding to MXU-friendly multiples of 128, and interpret-mode
selection.  These selectors are the only place ``interpret`` is decided: the
kernels run compiled on a TPU backend and under the Pallas interpreter
everywhere else.  The raw ``pallas_call`` wrappers take it as a required
keyword.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_ssd as _ms
from repro.kernels import rmsnorm as _rn
from repro.kernels import rwkv6_scan as _rw


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_last(x, mult: int):
    d = x.shape[-1]
    pad = (-d) % mult
    if pad == 0:
        return x, d
    cfgpad = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfgpad), d


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    """q: (B,H,Sq,hd), k/v: (B,KV,Skv,hd).  Pads hd to a multiple of 128
    (zero-padding is exact: scores and outputs are unchanged; softmax scale
    keeps the original hd)."""
    hd = q.shape[-1]
    qp, _ = _pad_last(q, 128)
    kp, _ = _pad_last(k, 128)
    vp, _ = _pad_last(v, 128)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              softcap=softcap, scale=hd ** -0.5,
                              block_q=block_q, block_k=block_k,
                              interpret=not _on_tpu())
    return out[..., :hd]


#: flash-attention train-path implementations: compiled Pallas kernels on
#: TPU, the tiled pure-JAX fallback elsewhere (interpret-mode Pallas is for
#: parity tests, not the hot path — same convention as repro.kernels.moe).
FLASH_IMPLS = ("pallas", "jax")


def _flash_impl(impl: Optional[str]) -> str:
    if impl is not None:
        assert impl in FLASH_IMPLS, impl
        return impl
    return "pallas" if _on_tpu() else "jax"


def _fat_fwd_lse(q, k, v, causal, window, softcap, block_q, block_k, impl):
    """Forward emitting (out, lse) under the selected implementation."""
    hd = q.shape[-1]
    scale = hd ** -0.5
    if _flash_impl(impl) == "jax":
        return _fa.flash_attention_fwd_jax(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, block_q=block_q)
    qp, _ = _pad_last(q, 128)
    kp, _ = _pad_last(k, 128)
    vp, _ = _pad_last(v, 128)
    out, lse = _fa.flash_attention(
        qp, kp, vp, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k,
        interpret=not _on_tpu(), return_lse=True)
    return out[..., :hd], lse


def _batch_mesh():
    """The mesh a sharded train step runs under: the one entered with
    ``jax.set_mesh``, else the expert-parallel mesh (``repro.core.settings``);
    None on one device."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        from repro.core import settings
        mesh = settings.EP_MESH
    return mesh if mesh is not None and mesh.size > 1 else None


def flash_attention_trainable(q, k, v, causal=True, window=None, softcap=None,
                              block_q=128, block_k=128, impl=None):
    """Differentiable flash attention: flash forward AND flash backward.

    Residuals are (q, k, v, o, lse) — O(S) per head; the backward recomputes
    probability tiles from them (dq pass + dk/dv pass with in-kernel GQA
    reduction) instead of re-running a dense O(S^2) reference vjp.  ``impl``:
    None (pallas on TPU, tiled jax elsewhere) | "pallas" | "jax".

    Under a multi-device mesh the Pallas kernels run per batch shard inside
    a ``shard_map`` over the mesh's data axes: GSPMD cannot partition a
    Mosaic kernel itself."""
    args = (causal, window, softcap, block_q, block_k, impl)
    mesh = _batch_mesh()
    if mesh is None or _flash_impl(impl) == "jax":
        return _flash_vjp(q, k, v, *args)
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import data_axes
    axes = data_axes(mesh)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    spec = P(axes) if axes and q.shape[0] % n == 0 else P()
    return jax.shard_map(lambda q_, k_, v_: _flash_vjp(q_, k_, v_, *args),
                         mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                         check_vma=False)(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_vjp(q, k, v, causal, window, softcap, block_q, block_k, impl):
    out, _ = _fat_fwd_lse(q, k, v, causal, window, softcap,
                          block_q, block_k, impl)
    return out


def _fat_fwd(q, k, v, causal, window, softcap, block_q, block_k, impl):
    out, lse = _fat_fwd_lse(q, k, v, causal, window, softcap,
                            block_q, block_k, impl)
    return out, (q, k, v, out, lse)


def _fat_bwd(causal, window, softcap, block_q, block_k, impl, res, ct):
    q, k, v, o, lse = res
    hd = q.shape[-1]
    scale = hd ** -0.5
    # delta = rowsum(dO * O): the softmax-jacobian row term, O(S*hd) work
    delta = jnp.sum(ct.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if _flash_impl(impl) == "jax":
        dq, dk, dv = _fa.flash_attention_bwd_jax(
            q, k, v, lse, delta, ct, causal=causal, window=window,
            softcap=softcap, scale=scale, block_q=block_q, block_k=block_k)
    else:
        qp, _ = _pad_last(q, 128)
        kp, _ = _pad_last(k, 128)
        vp, _ = _pad_last(v, 128)
        dop, _ = _pad_last(ct, 128)
        dq, dk, dv = _fa.flash_attention_bwd(
            qp, kp, vp, lse, delta, dop, causal=causal, window=window,
            softcap=softcap, scale=scale, block_q=block_q, block_k=block_k,
            interpret=not _on_tpu())
        dq, dk, dv = dq[..., :hd], dk[..., :hd], dv[..., :hd]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_vjp.defvjp(_fat_fwd, _fat_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, eps: float = 1e-6, block_rows: int = 128):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64):
    return _rw.rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def mamba_ssd(x, B_t, C_t, dt, log_a, *, chunk: int = 128):
    return _ms.mamba_ssd(x, B_t, C_t, dt, log_a, chunk=chunk,
                         interpret=not _on_tpu())


@jax.custom_vjp
def mamba_ssd_trainable(x, B_t, C_t, dt, log_a):
    """Differentiable wrapper: Pallas SSD kernel forward, oracle backward."""
    return mamba_ssd(x, B_t, C_t, dt, log_a)


def _ms_fwd(x, B_t, C_t, dt, log_a):
    return mamba_ssd(x, B_t, C_t, dt, log_a), (x, B_t, C_t, dt, log_a)


def _ms_bwd(res, ct):
    from repro.kernels import ref
    _, vjp = jax.vjp(lambda *a: ref.mamba_ssd_ref(*a)[0], *res)
    return vjp(ct)


mamba_ssd_trainable.defvjp(_ms_fwd, _ms_bwd)


@jax.custom_vjp
def rwkv6_scan_trainable(r, k, v, w, u):
    """Differentiable wrapper: Pallas wkv kernel forward, oracle backward."""
    return rwkv6_scan(r, k, v, w, u)


def _rwkv_fwd(r, k, v, w, u):
    return rwkv6_scan(r, k, v, w, u), (r, k, v, w, u)


def _rwkv_bwd(res, ct):
    from repro.kernels import ref
    r, k, v, w, u = res
    _, vjp = jax.vjp(lambda *a: ref.rwkv6_ref(*a)[0], r, k, v, w, u)
    return vjp(ct)


rwkv6_scan_trainable.defvjp(_rwkv_fwd, _rwkv_bwd)
