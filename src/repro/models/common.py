"""Shared model components: norms, RoPE, GQA attention (SWA / softcap / cross),
SwiGLU MLP.  All pure functions over explicit param dicts built from ParamSpec.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.spec import ParamSpec
from repro.obs import scopes


# ---------------------------------------------------------------- norms

def rms_norm(x, scale, eps: float = 1e-6):
    with jax.named_scope(scopes.NORM):
        dtype = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
        return out.astype(dtype)


def embed_lookup(table, tokens):
    """Rows of ``table`` at ``tokens``; the backward sums each row in f32.

    A frequent token's gradient row sums thousands of contributions, and a
    bf16 scatter-add drops most of them (4096 equal contributions sum to a
    tenth of their total; its order, e.g. one device vs FSDP shards, moves a
    row by tens of percent).  The backward sorts the contributions by token,
    sums each token's run in f32 (a (tokens, d) buffer), and writes every
    row once, so no (vocab, d) f32 buffer is needed."""
    return _take_rows(table, tokens, table.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows(table, tokens, n_rows):
    return jnp.take(table, tokens, axis=0)


def _take_rows_fwd(table, tokens, n_rows):
    return jnp.take(table, tokens, axis=0), tokens


def _take_rows_bwd(n_rows, tokens, ct):
    flat = tokens.reshape(-1)
    n = flat.shape[0]
    order = jnp.argsort(flat)
    rows = flat[order]
    # one segment per distinct token, in sorted order
    seg = jnp.cumsum(jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), (rows[1:] != rows[:-1]).astype(jnp.int32)]))
    sums = jax.ops.segment_sum(
        ct.reshape(n, -1)[order].astype(jnp.float32), seg, num_segments=n,
        indices_are_sorted=True)
    # unused segments add exact zeros to row 0
    seg_row = jnp.zeros((n,), flat.dtype).at[seg].set(rows)
    g = jnp.zeros((n_rows, ct.shape[-1]), ct.dtype).at[seg_row].add(
        sums.astype(ct.dtype))
    return g, np.zeros(tokens.shape, jax.dtypes.float0)


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def norm_spec(dim: int) -> ParamSpec:
    # zero-init: rms_norm uses (1 + scale) so this is identity-scale at init
    return ParamSpec((dim,), (None,), init="zeros")


def softcap(x, cap: Optional[float]):
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------- RoPE

def rope(x, positions, theta: float):
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def attn_specs(cfg: ModelConfig, use_rope: bool = True) -> dict:
    d, q_dim, kv_dim = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": ParamSpec((d, q_dim), ("embed", "heads")),
        "wk": ParamSpec((d, kv_dim), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kv_dim), ("embed", "kv_heads")),
        "wo": ParamSpec((q_dim, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((q_dim,), ("heads",), init="zeros")
        p["bk"] = ParamSpec((kv_dim,), ("kv_heads",), init="zeros")
        p["bv"] = ParamSpec((kv_dim,), ("kv_heads",), init="zeros")
    return p


def _proj(x, w, b=None):
    y = jnp.einsum("bsd,dk->bsk", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def attention(p, cfg: ModelConfig, xq, xkv, *,
              positions_q, positions_k,
              causal: bool = True,
              window: Optional[int] = None,
              use_rope: bool = True,
              cache: Optional[dict] = None,
              cache_index=None):
    """GQA attention.  xq: (B,Sq,d), xkv: (B,Skv,d).

    If ``cache`` is given (decode), the new k/v are written at ``cache_index``
    and attention runs over the whole cache; returns (out, new_cache).
    """
    B, Sq, _ = xq.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = _proj(xq, p["wq"], p.get("bq")).reshape(B, Sq, H, hd)
    k = _proj(xkv, p["wk"], p.get("bk")).reshape(B, xkv.shape[1], KV, hd)
    v = _proj(xkv, p["wv"], p.get("bv")).reshape(B, xkv.shape[1], KV, hd)

    if use_rope:
        q = rope(q, positions_q, cfg.rope_theta)
        k = rope(k, positions_k, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        k = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                         (0, cache_index, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                         (0, cache_index, 0, 0))
        new_cache = {"k": k, "v": v}
        positions_k = jnp.broadcast_to(jnp.arange(k.shape[1], dtype=jnp.int32)[None],
                                       (B, k.shape[1]))

    # Flash-attention backend (train path only: no cache, static window,
    # contiguous 0..S positions — which is what the train/prefill callers
    # pass).  Fully differentiable: flash_attention_trainable pairs the flash
    # forward with the flash backward kernels (residuals q,k,v,o,lse — no
    # O(S^2) recompute), so jax.vjp inside the reversible stack stays O(S).
    if (cfg.use_flash_kernel and cache is None
            and isinstance(window, (int, type(None)))):
        from repro.kernels import ops as kops
        bq = min(cfg.flash_block_q, Sq)
        bk = min(cfg.flash_block_k, k.shape[1])
        if Sq % bq == 0 and k.shape[1] % bk == 0:
            q4 = q.transpose(0, 2, 1, 3)
            k4 = k.transpose(0, 2, 1, 3)
            v4 = v.transpose(0, 2, 1, 3)
            out = kops.flash_attention_trainable(
                q4, k4, v4, causal, window, cfg.logit_softcap,
                cfg.flash_block_q, cfg.flash_block_k)
            out = out.transpose(0, 2, 1, 3).reshape(B, Sq, H * hd)
            out = jnp.einsum("bsk,kd->bsd", out, p["wo"])
            return out

    # GQA: fold q heads into kv groups
    G = H // KV
    scale = hd ** -0.5

    def attend(q_blk, pos_q_blk):
        qg = q_blk.reshape(B, q_blk.shape[1], KV, G, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        scores = softcap(scores, cfg.logit_softcap)
        pq = pos_q_blk[:, None, None, :, None]        # (B,1,1,q,1)
        pk = positions_k[:, None, None, None, :]      # (B,1,1,1,Skv)
        mask = jnp.ones_like(scores, dtype=bool)
        if causal:
            mask &= pq >= pk
        if window is not None:
            mask &= (pq - pk) < window
        if cache is not None:
            mask &= pk <= cache_index + Sq - 1 + 0 * pq
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskh->bqkgh", probs, v).reshape(
            B, q_blk.shape[1], H * hd)

    qc = cfg.attn_q_chunk
    if cache is None and qc and Sq > qc and Sq % qc == 0:
        # q-block chunking: never materialise the full Sq x Skv score matrix
        nq = Sq // qc
        qr = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        pr = positions_q.reshape(B, nq, qc).transpose(1, 0, 2)
        out = jax.lax.map(lambda ab: attend(*ab), (qr, pr))
        out = out.transpose(1, 0, 2, 3).reshape(B, Sq, H * hd)
    else:
        out = attend(q, positions_q)
    out = jnp.einsum("bsk,kd->bsd", out, p["wo"])
    return (out, new_cache) if cache is not None else out


# ------------------------------------------------------- decode-path attention

def init_kv_cache(cfg: ModelConfig, batch: int, buf_len: int, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, buf_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, buf_len, cfg.num_kv_heads, cfg.head_dim), dtype),
        "pos": jnp.full((buf_len,), -1, jnp.int32),
    }


def cross_kv(p, cfg: ModelConfig, feats):
    """Precompute cross-attention K/V from encoder/image features (no rope)."""
    B, Se, _ = feats.shape
    k = _proj(feats, p["wk"], p.get("bk")).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    v = _proj(feats, p["wv"], p.get("bv")).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    return {"k": k, "v": v}


def _attend_cache(q, k, v, mask, cfg: ModelConfig):
    """q: (B,Sq,H,hd), k/v: (B,C,KV,hd), mask: (B,1,1,Sq,C) or broadcastable."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * hd ** -0.5
    scores = softcap(scores, cfg.logit_softcap)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v).reshape(B, Sq, H * hd)


def attention_decode(p, cfg: ModelConfig, xq, xkv, cache, t, *,
                     window=None, rolling: bool = False, use_rope: bool = True,
                     length=None):
    """Self-attention with a KV buffer.  Writes xkv's K/V at position t
    (rolling buffers write at t % buf_len, Sq must be 1), attends over the
    whole buffer with validity/causal/window masking by stored positions.

    ``length`` (optional traced scalar): the real token count when a
    longer-than-buffer prefill is right-padded to Sq > real length.  The
    long-prefill path then keeps the last ``min(length, C)`` REAL positions
    in the rolling buffer instead of the last C entries of the padded
    stream — without it every pad token would displace one real window
    entry, which is why bucketed (padded) windowed prefill used to require
    exact lengths and a compile per prompt length.
    """
    B, Sq, _ = xq.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    C = cache["k"].shape[1]

    pos_q = t + jnp.arange(Sq, dtype=jnp.int32)[None, :]          # (1,Sq)
    q = _proj(xq, p["wq"], p.get("bq")).reshape(B, Sq, H, hd)
    k = _proj(xkv, p["wk"], p.get("bk")).reshape(B, Sq, KV, hd)
    v = _proj(xkv, p["wv"], p.get("bv")).reshape(B, Sq, KV, hd)
    if use_rope:
        q = rope(q, jnp.broadcast_to(pos_q, (B, Sq)), cfg.rope_theta)
        k = rope(k, jnp.broadcast_to(pos_q, (B, Sq)), cfg.rope_theta)

    if Sq > C:
        # prefill longer than a rolling window buffer: attend train-style over
        # the full prompt (window mask), keep only the last C keys in cache.
        pk_full = pos_q[0]                                   # (Sq,)

        def att_block(q_blk, pq_blk):
            mask = (pq_blk[None, None, None, :, None]
                    >= pk_full[None, None, None, None, :])
            if window is not None:
                mask = mask & ((pq_blk[None, None, None, :, None]
                                - pk_full[None, None, None, None, :]) < window)
            return _attend_cache(q_blk, k, v, mask, cfg)

        qc = cfg.attn_q_chunk
        if qc and Sq > qc and Sq % qc == 0:
            nq = Sq // qc
            qr = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
            pr = pos_q[0].reshape(nq, qc)
            out = jax.lax.map(lambda ab: att_block(*ab), (qr, pr))
            out = out.transpose(1, 0, 2, 3).reshape(B, Sq, H * hd)
        else:
            out = att_block(q, pos_q[0])
        out = jnp.einsum("bsk,kd->bsd", out, p["wo"])
        if length is None:
            shift = (Sq - C) % C   # place pos p at slot p % C (static ints)
            ck = jnp.roll(k[:, -C:].astype(cache["k"].dtype), shift, axis=1)
            cv = jnp.roll(v[:, -C:].astype(cache["v"].dtype), shift, axis=1)
            cpos = jnp.roll(pos_q[0, -C:], shift)
        else:
            # right-padded stream: keep the last min(length, C) REAL tokens,
            # each at slot (token index) % C.  Pad queries attend to junk but
            # their outputs are discarded by the caller; pad keys sit beyond
            # every real query so the causal mask already excludes them.
            start = jnp.maximum(length - C, 0)
            j = jnp.arange(C, dtype=jnp.int32)
            idx = start + jnp.mod(j - start, C)     # token index held by slot j
            valid = idx < length
            ck = jnp.take(k, idx, axis=1).astype(cache["k"].dtype)
            cv = jnp.take(v, idx, axis=1).astype(cache["v"].dtype)
            cpos = jnp.where(valid, jnp.take(pos_q[0], idx), -1)
        return out, {"k": ck, "v": cv, "pos": cpos}

    slot = jax.lax.rem(t, C) if rolling else t
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
    cpos = jax.lax.dynamic_update_slice(cache["pos"], pos_q[0], (slot,))

    def att_cached(q_blk, pq_blk):
        pk = cpos[None, None, None, None, :]                      # (1,1,1,1,C)
        pq = pq_blk[None, None, None, :, None]
        mask = (pk >= 0) & (pk <= pq)
        if window is not None:
            mask = mask & ((pq - pk) < window)
        return _attend_cache(q_blk, ck, cv, mask, cfg)

    qc = cfg.attn_q_chunk
    if qc and Sq > qc and Sq % qc == 0:      # chunked prefill into the buffer
        nq = Sq // qc
        qr = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        pr = pos_q[0].reshape(nq, qc)
        out = jax.lax.map(lambda ab: att_cached(*ab), (qr, pr))
        out = out.transpose(1, 0, 2, 3).reshape(B, Sq, H * hd)
    else:
        out = att_cached(q, pos_q[0])
    out = jnp.einsum("bsk,kd->bsd", out, p["wo"])
    return out, {"k": ck, "v": cv, "pos": cpos}


def attention_decode_paged(p, cfg: ModelConfig, xq, xkv, pool, page_table, t,
                           *, write_mask, window=None, rolling: bool = False,
                           kv_len: Optional[int] = None, use_rope: bool = True,
                           impl: Optional[str] = None):
    """Paged decode self-attention (DESIGN.md §15): one layer's KV state
    lives in a pool of physical pages shared across slots; a per-slot page
    table maps logical positions to pages.

    xq/xkv: (B, 1, d) — decode only (prefill runs on the dense path and is
    scattered into pages by the engine).  t: (B,) per-slot positions.
    pool: {"k"/"v": (P, page, KV, hd), "pos": (P, page)}.
    page_table: (B, n_pages) int32, -1 = unmapped.
    write_mask: (B,) bool — rows NOT selected write nothing (their pages may
    have been freed and remapped to another request; the dense engine can
    tolerate garbage writes into inactive slots, the pool cannot).

    The new K/V is scattered at logical slot ``t`` (``t % C`` when rolling)
    through the page table; attention then reads every mapped page.  The
    off-TPU implementation gathers the pages and reuses the dense decode
    einsum verbatim, so paged and dense decode are bit-identical — the
    equivalence gate in tests/test_serving.py leans on this.
    """
    from repro.kernels import paged_attention as pk

    B = xq.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    P, page = pool["pos"].shape
    n_pages = page_table.shape[1]
    C = kv_len if kv_len is not None else n_pages * page

    pos_q = t[:, None]                                          # (B, 1)
    q = _proj(xq, p["wq"], p.get("bq")).reshape(B, 1, H, hd)
    k = _proj(xkv, p["wk"], p.get("bk")).reshape(B, 1, KV, hd)
    v = _proj(xkv, p["wv"], p.get("bv")).reshape(B, 1, KV, hd)
    if use_rope:
        q = rope(q, pos_q, cfg.rope_theta)
        k = rope(k, pos_q, cfg.rope_theta)

    slot = jax.lax.rem(t, C) if rolling else t
    page_idx = jnp.clip(slot // page, 0, n_pages - 1)
    off = slot % page
    phys = page_table[jnp.arange(B), page_idx]                  # (B,)
    # masked rows scatter to index P == out-of-bounds -> dropped
    phys = jnp.where(write_mask & (phys >= 0) & (slot < C), phys, P)
    nk = pool["k"].at[phys, off].set(k[:, 0].astype(pool["k"].dtype),
                                     mode="drop")
    nv = pool["v"].at[phys, off].set(v[:, 0].astype(pool["v"].dtype),
                                     mode="drop")
    npos = pool["pos"].at[phys, off].set(pos_q[:, 0], mode="drop")
    new_pool = {"k": nk, "v": nv, "pos": npos}

    out = pk.paged_attention(q[:, 0], nk, nv, npos, page_table, t,
                             kv_len=C, window=window,
                             softcap=cfg.logit_softcap, impl=impl)
    out = jnp.einsum("bsk,kd->bsd", out[:, None], p["wo"])
    return out, new_pool


def cross_attention_decode(p, cfg: ModelConfig, xq, kv_cache):
    """Cross-attention over precomputed (fully valid) K/V."""
    B, Sq, _ = xq.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = _proj(xq, p["wq"], p.get("bq")).reshape(B, Sq, H, hd)
    mask = jnp.ones((1, 1, 1, Sq, kv_cache["k"].shape[1]), bool)
    out = _attend_cache(q, kv_cache["k"], kv_cache["v"], mask, cfg)
    return jnp.einsum("bsk,kd->bsd", out, p["wo"])


# ---------------------------------------------------------------- LM head

def lm_head_logits(h, w, cap: Optional[float] = None):
    """Vocabulary logits from final hidden states.  ``h``: (..., d) — any
    leading shape (the serving engine feeds (slots, d) single positions so the
    fused decode+sample step never materialises per-position logits it will
    not read)."""
    logits = jnp.einsum("...d,dv->...v", h, w)
    return softcap(logits, cap)


# ---------------------------------------------------------------- MLP

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((d, ff), ("embed", "mlp")),
        "w_up": ParamSpec((d, ff), ("embed", "mlp")),
        "w_down": ParamSpec((ff, d), ("mlp", "embed")),
    }


def mlp(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])
