"""Composable model definition: every assigned architecture as a stack of
reversible units over split hidden streams (RevFFN), plus the standard
(non-reversible) residual path used by the SFT baselines.

A model is one or more ``StackDef``s.  Each StackDef scans ``n`` identical
*units*; a unit is a chain of reversible couplings (self-attention, MoE/MLP,
Mamba2, RWKV6, cross-attention...) built from ``repro.core.reversible``
primitives.  Heterogeneous archs (gemma2 local/global, zamba2 hybrid,
llama-3.2-vision cross-attn period) group their repeating pattern into one
unit so the scanned param tree stays homogeneous.

MoE aux (load-balancing) loss is intentionally omitted: RevFFN freezes the
routers in both training stages (paper §3.3), making the aux term a constant.

MoE expert execution follows ``cfg.moe_backend``: the dense one-hot dispatch
einsum ("einsum") or the sort-based dropless grouped-GEMM path ("grouped",
repro.kernels.moe / DESIGN.md §7) — both the reversible coupling ``_moe_G``
and the standard baseline block read it through ``moe_lib.moe_apply``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import adapters as ad
from repro.core.reversible import (chain, coupling, grouped_mixed_policy_stack,
                                   grouped_reversible_stack, make_coupled,
                                   merge_streams, mixed_policy_stack,
                                   read_unit, reversible_stack, split_streams)
from repro.models import common, moe as moe_lib, spec, ssm as ssm_lib
from repro.models.common import (attention, attention_decode, attn_specs,
                                 cross_attention_decode, cross_kv,
                                 embed_lookup, init_kv_cache, lm_head_logits,
                                 mlp, mlp_specs, norm_spec, rms_norm, softcap)
from repro.models.spec import ParamSpec
from repro.obs import scopes

BIG_WINDOW = 1 << 30


@dataclasses.dataclass
class StackDef:
    name: str
    n: int
    unit_specs: Any
    fwd: Callable                       # (lp, sh, ctx, i, x1, x2) -> (y1, y2)
    inv: Optional[Callable]             # inverse bijection (None => standard path)
    decode: Optional[Callable] = None   # (lp, sh, ctx, i, x1, x2, cache) -> ((y1,y2), cache)
    cache_init: Optional[Callable] = None  # (lp, cfg, B, buf, dtype, extras) -> unit cache
    role: str = "main"                  # "main" | "encoder"
    std_fwd: Optional[Callable] = None  # standard residual path on full-width h
    half_inv: Optional[Callable] = None  # exact x2 = y2 - G(y1) (semi-reversible)
    moe_tap: Optional[Callable] = None  # (lp, sh, ctx, i, x1, x2) ->
    #   (router params, (T, d) routing input) — the audit layer re-runs the
    #   router through this to compute per-expert stats (obs/audit, §12)
    layout: Optional[spec.GroupLayout] = None  # layer-group tie map: when
    #   set, the stack's params are {"base", "delta", "per"} (DESIGN.md §14)
    #   and every walk reads units through the group indirection
    decode_paged: Optional[Callable] = None  # (lp, sh, ctx, i, x1, x2,
    #   pool_unit, page_table, write_mask) -> ((y1, y2), pool_unit) — decode
    #   step against the paged KV pool (DESIGN.md §15); None => family has
    #   no paged layout (recurrent state etc.)
    pool_init: Optional[Callable] = None  # (n_pages, page_size, dtype) ->
    #   one unit's page-pool leaves (stacked over units by init_kv_pool)


# ===================================================================== helpers

def _act_constrain(x):
    """Sequence-parallel activation constraint (settings.ACT_SPEC)."""
    from repro.core import settings
    if settings.ACT_SPEC is not None and x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, settings.ACT_SPEC)
    return x


def _up(p, x):
    return ad.up(p, _act_constrain(x))


def _down(p, x):
    return _act_constrain(ad.down(p, x))


def _fold_attn(ad_p, attn_p):
    """Fold P_up/P_down into the attention projections (exact: the adapters
    are linear and adjacent to the pretrained matmuls).  The fused weights
    contract directly from the d/2 stream: W'q = P_up @ Wq, W'o = Wo @ P_down.
    Biases are unaffected (they add after the projection)."""
    pu, pd = ad_p["p_up"], ad_p["p_down"]
    eff = {"wq": pu @ attn_p["wq"], "wk": pu @ attn_p["wk"],
           "wv": pu @ attn_p["wv"], "wo": attn_p["wo"] @ pd}
    for b in ("bq", "bk", "bv"):
        if b in attn_p:
            eff[b] = attn_p[b]
    return eff


def _attn_F(cfg: ModelConfig, window_fn, causal=True):
    """Paper Eq. 1: cross-branch attention residual (Q from x1, K/V from x2)."""
    def F(p, sh, ctx, i, x1, x2):
        n1 = rms_norm(x1, p["norm1"], cfg.norm_eps)
        n2 = rms_norm(x2, p["norm2"], cfg.norm_eps)
        win = window_fn(i) if window_fn else None
        with jax.named_scope(scopes.ATTN):
            if cfg.fold_adapters:
                eff = _fold_attn(p["attn_ad"], p["attn"])
                return attention(eff, cfg, _act_constrain(n1),
                                 _act_constrain(n2),
                                 positions_q=ctx["positions"],
                                 positions_k=ctx["positions"],
                                 causal=causal, window=win)
            q_in = _up(p["attn_ad"], n1)
            kv_in = _up(p["attn_ad"], n2)
            att = attention(p["attn"], cfg, q_in, kv_in,
                            positions_q=ctx["positions"],
                            positions_k=ctx["positions"],
                            causal=causal, window=win)
            return _down(p["attn_ad"], att)
    return F


def _mlp_G(cfg: ModelConfig):
    """Paper Eq. 2: FFN driven by the updated left stream."""
    def G(p, sh, ctx, i, y1, _y2=None):
        h = rms_norm(y1, p["norm_mlp"], cfg.norm_eps)
        with jax.named_scope(scopes.MLP):
            if cfg.fold_adapters:
                pu, pd = p["mlp_ad"]["p_up"], p["mlp_ad"]["p_down"]
                eff = {"w_gate": pu @ p["mlp"]["w_gate"],
                       "w_up": pu @ p["mlp"]["w_up"],
                       "w_down": p["mlp"]["w_down"] @ pd}
                return mlp(eff, _act_constrain(h))
            return _down(p["mlp_ad"], mlp(p["mlp"], _up(p["mlp_ad"], h)))
    return G


def _moe_G(cfg: ModelConfig):
    def G(p, sh, ctx, i, y1, _y2=None):
        h = rms_norm(y1, p["norm_mlp"], cfg.norm_eps)
        if cfg.fold_adapters:
            pu, pd = p["mlp_ad"]["p_up"], p["mlp_ad"]["p_down"]
            m = p["moe"]
            eff = {"router": pu @ m["router"],
                   "w_gate": jnp.einsum("hd,edf->ehf", pu, m["w_gate"]),
                   "w_up": jnp.einsum("hd,edf->ehf", pu, m["w_up"]),
                   "w_down": jnp.einsum("efd,dh->efh", m["w_down"], pd)}
            if "shared" in m:
                sh_ = m["shared"]
                eff["shared"] = {"w_gate": pu @ sh_["w_gate"],
                                 "w_up": pu @ sh_["w_up"],
                                 "w_down": sh_["w_down"] @ pd,
                                 "gate": pu @ sh_["gate"]}
            y, _aux = moe_lib.moe_apply(eff, cfg, _act_constrain(h))
            return y
        h = _up(p["mlp_ad"], h)
        y, _aux = moe_lib.moe_apply(p["moe"], cfg, h)
        return _down(p["mlp_ad"], y)
    return G


def _dense_sub_specs(cfg: ModelConfig, use_moe: bool = False) -> dict:
    half = cfg.stream_dim
    sp = {
        "norm1": norm_spec(half),
        "norm2": norm_spec(half),
        "attn_ad": ad.adapter_specs(cfg.d_model),
        "attn": attn_specs(cfg),
        "norm_mlp": norm_spec(half),
        "mlp_ad": ad.adapter_specs(cfg.d_model),
    }
    if use_moe:
        sp["moe"] = moe_lib.moe_specs(cfg)
    else:
        sp["mlp"] = mlp_specs(cfg)
    return sp


def _window_fn(cfg: ModelConfig):
    if cfg.local_global:
        return lambda i: jnp.where(i % 2 == 0, cfg.local_window, BIG_WINDOW)
    if cfg.sliding_window:
        return lambda i: cfg.sliding_window
    return None


# ------------------------------------------------- standard (baseline) blocks

def _std_block(cfg: ModelConfig, use_moe: bool):
    window_fn = _window_fn(cfg)

    def fwd(p, sh, ctx, i, h):
        a_in = rms_norm(h, p["norm1"], cfg.norm_eps)
        with jax.named_scope(scopes.ATTN):
            att = attention(p["attn"], cfg, a_in, a_in,
                            positions_q=ctx["positions"],
                            positions_k=ctx["positions"], causal=True,
                            window=window_fn(i) if window_fn else None)
        h = h + att
        m_in = rms_norm(h, p["norm_mlp"], cfg.norm_eps)
        if use_moe:
            y, _ = moe_lib.moe_apply(p["moe"], cfg, m_in)
        else:
            with jax.named_scope(scopes.MLP):
                y = mlp(p["mlp"], m_in)
        return h + y
    return fwd


def _std_specs(cfg: ModelConfig, use_moe: bool) -> dict:
    sp = {"norm1": norm_spec(cfg.d_model), "norm_mlp": norm_spec(cfg.d_model),
          "attn": attn_specs(cfg)}
    if use_moe:
        sp["moe"] = moe_lib.moe_specs(cfg)
    else:
        sp["mlp"] = mlp_specs(cfg)
    return sp


# ===================================================================== builders

def build_dense(cfg: ModelConfig, use_moe: bool = False):
    window_fn = _window_fn(cfg)
    F = _attn_F(cfg, window_fn)
    G = _moe_G(cfg) if use_moe else _mlp_G(cfg)
    fwd, inv = make_coupled(F, G, mode=cfg.coupling, fp_iters=cfg.inverse_fp_iters)
    rolling = cfg.sliding_window is not None

    def decode(lp, sh, ctx, i, x1, x2, cu):
        q_in = _up(lp["attn_ad"], rms_norm(x1, lp["norm1"], cfg.norm_eps))
        kv_in = _up(lp["attn_ad"], rms_norm(x2, lp["norm2"], cfg.norm_eps))
        att, nkv = attention_decode(lp["attn"], cfg, q_in, kv_in, cu["kv"],
                                    ctx["t"], window=window_fn(i) if window_fn else None,
                                    rolling=rolling, length=ctx.get("seq_len"))
        y1 = x1 + _down(lp["attn_ad"], att)
        y2 = x2 + G(lp, sh, ctx, i, y1)
        return (y1, y2), {"kv": nkv}

    def cache_init(lp, B, buf, dtype, extras):
        return {"kv": init_kv_cache(cfg, B, buf, dtype)}

    def decode_paged(lp, sh, ctx, i, x1, x2, pu, pt, wmask):
        q_in = _up(lp["attn_ad"], rms_norm(x1, lp["norm1"], cfg.norm_eps))
        kv_in = _up(lp["attn_ad"], rms_norm(x2, lp["norm2"], cfg.norm_eps))
        att, npu = common.attention_decode_paged(
            lp["attn"], cfg, q_in, kv_in, pu["kv"], pt, ctx["t"],
            write_mask=wmask, window=window_fn(i) if window_fn else None,
            rolling=rolling, kv_len=ctx["kv_len"])
        y1 = x1 + _down(lp["attn_ad"], att)
        y2 = x2 + G(lp, sh, ctx, i, y1)
        return (y1, y2), {"kv": npu}

    def pool_init(n_pages, page_size, dtype):
        KV, hd = cfg.num_kv_heads, cfg.head_dim
        return {"kv": {
            "k": jnp.zeros((n_pages, page_size, KV, hd), dtype),
            "v": jnp.zeros((n_pages, page_size, KV, hd), dtype),
            "pos": jnp.full((n_pages, page_size), -1, jnp.int32)}}

    def half_inv(lp, sh, ctx, i, x1, y1, y2):
        return y2 - G(lp, sh, ctx, i, y1)

    moe_tap = None
    if use_moe:
        def moe_tap(lp, sh, ctx, i, x1, x2):
            # G's input is the post-F stream: replicate the coupling up to
            # the router so audited routing sees exactly what training sees
            y1 = x1 + F(lp, sh, ctx, i, x1, x2)
            h = rms_norm(y1, lp["norm_mlp"], cfg.norm_eps)
            if cfg.fold_adapters:
                router = lp["mlp_ad"]["p_up"] @ lp["moe"]["router"]
            else:
                h = _up(lp["mlp_ad"], h)
                router = lp["moe"]["router"]
            B, S, d = h.shape
            return {"router": router}, h.reshape(B * S, d)

    return [StackDef("layers", cfg.num_layers, _dense_sub_specs(cfg, use_moe),
                     fwd, inv, decode, cache_init,
                     std_fwd=_std_block(cfg, use_moe), half_inv=half_inv,
                     moe_tap=moe_tap, decode_paged=decode_paged,
                     pool_init=pool_init)], {}


def build_moe(cfg: ModelConfig):
    return build_dense(cfg, use_moe=True)


def build_rwkv(cfg: ModelConfig):
    d = cfg.d_model

    def F(p, sh, ctx, i, x1, x2):           # token mix reads stream 2 only
        h = _up(p["attn_ad"], rms_norm(x2, p["norm2"], cfg.norm_eps))
        return _down(p["attn_ad"], ssm_lib.rwkv_time_apply(p["time"], cfg, h))

    def G(p, sh, ctx, i, y1, _=None):       # channel mix driven by stream 1
        h = _up(p["mlp_ad"], rms_norm(y1, p["norm_mlp"], cfg.norm_eps))
        return _down(p["mlp_ad"], ssm_lib.rwkv_channel_apply(p["chan"], cfg, h))

    fwd, inv = make_coupled(F, G, mode="standard")
    sp = {
        "norm2": norm_spec(cfg.stream_dim),
        "attn_ad": ad.adapter_specs(d),
        "time": ssm_lib.rwkv_time_specs(cfg),
        "norm_mlp": norm_spec(cfg.stream_dim),
        "mlp_ad": ad.adapter_specs(d),
        "chan": ssm_lib.rwkv_channel_specs(cfg),
    }
    H, hd = ssm_lib.rwkv_dims_for(d, cfg)

    def decode(lp, sh, ctx, i, x1, x2, cu):
        h = _up(lp["attn_ad"], rms_norm(x2, lp["norm2"], cfg.norm_eps))
        out, ns, nxt = ssm_lib.rwkv_time_apply(lp["time"], cfg, h, state=cu["s"],
                                               last_x=cu["xt"], return_state=True)
        y1 = x1 + _down(lp["attn_ad"], out)
        hc = _up(lp["mlp_ad"], rms_norm(y1, lp["norm_mlp"], cfg.norm_eps))
        out2, nxc = ssm_lib.rwkv_channel_apply(lp["chan"], cfg, hc, last_x=cu["xc"],
                                               return_state=True)
        y2 = x2 + _down(lp["mlp_ad"], out2)
        return (y1, y2), {"s": ns, "xt": nxt, "xc": nxc}

    def cache_init(lp, B, buf, dtype, extras):
        return {"s": jnp.zeros((B, H, hd, hd), jnp.float32),
                "xt": jnp.zeros((B, d), dtype), "xc": jnp.zeros((B, d), dtype)}

    def std_fwd(p, sh, ctx, i, h):
        h = h + ssm_lib.rwkv_time_apply(p["time"], cfg,
                                        rms_norm(h, p["norm1"], cfg.norm_eps))
        h = h + ssm_lib.rwkv_channel_apply(p["chan"], cfg,
                                           rms_norm(h, p["norm_mlp"], cfg.norm_eps))
        return h

    def half_inv(lp, sh, ctx, i, x1, y1, y2):
        return y2 - G(lp, sh, ctx, i, y1)

    return [StackDef("layers", cfg.num_layers, sp, fwd, inv, decode, cache_init,
                     std_fwd=std_fwd, half_inv=half_inv)], {}


def build_zamba(cfg: ModelConfig):
    """Mamba2 backbone; a SHARED attention+MLP block every ``attn_period``
    layers, expressed as a single LAYER GROUP (G=1): the attn/MLP keys live
    in the unit tree's ``base`` with one canonical slice that every unit
    reads, so gradient accumulation across applications is the grouped
    walks' ordinary base scatter-add (DESIGN.md §14) — no bespoke
    shared-tree path.  Unit = attn_period mamba couplings (alternating
    target stream, per-layer under ``per``) + the shared attn/MLP
    couplings."""
    d, half = cfg.d_model, cfg.stream_dim
    k = cfg.attn_period
    n_units, tail = cfg.num_layers // k, cfg.num_layers % k

    msub = {"norm": norm_spec(half), "ad": ad.adapter_specs(d),
            "mamba": ssm_lib.mamba_specs(cfg)}

    def mamba_delta(sub_p, src):
        h = rms_norm(src, sub_p["norm"], cfg.norm_eps)
        if cfg.fold_adapters:
            # exact: every input-side mamba op is a matmul; conv/gating act
            # in d_inner space which is untouched by the fold
            pu, pd = sub_p["ad"]["p_up"], sub_p["ad"]["p_down"]
            m = sub_p["mamba"]
            eff = dict(m)
            for k_ in ("w_x", "w_z", "w_B", "w_C", "w_dt"):
                eff[k_] = pu @ m[k_]
            eff["w_out"] = m["w_out"] @ pd
            return ssm_lib.mamba_apply(eff, cfg, _act_constrain(h))
        return _down(sub_p["ad"],
                     ssm_lib.mamba_apply(sub_p["mamba"], cfg,
                                         _up(sub_p["ad"], h)))

    def attn_F(p, sh, ctx, i, x1, x2):
        n1 = rms_norm(x1, p["norm1"], cfg.norm_eps)
        n2 = rms_norm(x2, p["norm2"], cfg.norm_eps)
        with jax.named_scope(scopes.ATTN):
            if cfg.fold_adapters:
                eff = _fold_attn(p["attn_ad"], p["attn"])
                return attention(eff, cfg, _act_constrain(n1),
                                 _act_constrain(n2),
                                 positions_q=ctx["positions"],
                                 positions_k=ctx["positions"])
            att = attention(p["attn"], cfg, _up(p["attn_ad"], n1),
                            _up(p["attn_ad"], n2),
                            positions_q=ctx["positions"],
                            positions_k=ctx["positions"])
            return _down(p["attn_ad"], att)

    mlp_G = _mlp_G(cfg)

    def unit_fwd(lp, sh, ctx, i, x1, x2):
        for j in range(k):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            if j % 2 == 0:
                x1 = x1 + mamba_delta(sub, x2)
            else:
                x2 = x2 + mamba_delta(sub, x1)
        f, _ = chain(coupling(attn_F, 1, cfg.inverse_fp_iters), coupling(mlp_G, 2, 1))
        return f(lp, sh, ctx, i, x1, x2)

    def unit_inv(lp, sh, ctx, i, y1, y2):
        _, g = chain(coupling(attn_F, 1, cfg.inverse_fp_iters), coupling(mlp_G, 2, 1))
        y1, y2 = g(lp, sh, ctx, i, y1, y2)
        for j in reversed(range(k)):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            if j % 2 == 0:
                y1 = y1 - mamba_delta(sub, y2)
            else:
                y2 = y2 - mamba_delta(sub, y1)
        return y1, y2

    d_inner, nh, P = ssm_lib.mamba_dims(cfg)
    N, K = cfg.ssm_state, cfg.ssm_conv

    def mamba_delta_decode(sub_p, src, st):
        h = _up(sub_p["ad"], rms_norm(src, sub_p["norm"], cfg.norm_eps))
        out, ns, ntail = ssm_lib.mamba_apply(sub_p["mamba"], cfg, h,
                                             state=st["h"], conv_tail=st["conv"],
                                             return_state=True)
        return _down(sub_p["ad"], out), {"h": ns, "conv": ntail}

    def unit_decode(lp, sh, ctx, i, x1, x2, cu):
        nstates = []
        for j in range(k):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            st = jax.tree_util.tree_map(lambda a: a[j], cu["m"])
            src = x2 if j % 2 == 0 else x1
            delta, nst = mamba_delta_decode(sub, src, st)
            if j % 2 == 0:
                x1 = x1 + delta
            else:
                x2 = x2 + delta
            nstates.append(nst)
        q_in = _up(lp["attn_ad"], rms_norm(x1, lp["norm1"], cfg.norm_eps))
        kv_in = _up(lp["attn_ad"], rms_norm(x2, lp["norm2"], cfg.norm_eps))
        att, nkv = attention_decode(lp["attn"], cfg, q_in, kv_in, cu["kv"], ctx["t"])
        y1 = x1 + _down(lp["attn_ad"], att)
        y2 = x2 + mlp_G(lp, sh, ctx, i, y1)
        nm = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *nstates)
        return (y1, y2), {"m": nm, "kv": nkv}

    def cache_init(lp, B, buf, dtype, extras):
        one = {"h": jnp.zeros((B, nh, N, P), jnp.float32),
               "conv": jnp.zeros((B, K - 1, d_inner), dtype)}
        return {"m": jax.tree_util.tree_map(
                    lambda a: jnp.stack([a] * k), one),
                "kv": init_kv_cache(cfg, B, buf, dtype)}

    unit_specs = {
        "inner": spec.stack(k, msub),
        "norm1": norm_spec(half), "norm2": norm_spec(half),
        "attn_ad": ad.adapter_specs(d), "attn": attn_specs(cfg),
        "norm_mlp": norm_spec(half), "mlp_ad": ad.adapter_specs(d),
        "mlp": mlp_specs(cfg),
    }
    # the attn_period shared block IS a layer group: one base slice (G=1)
    # every unit reads; the mamba inners stay per-layer
    layout = spec.GroupLayout(n_units, 1, (0,) * n_units,
                              ("norm1", "norm2", "attn_ad", "attn",
                               "norm_mlp", "mlp_ad", "mlp"), 0)
    stacks = [StackDef("units", n_units, unit_specs, unit_fwd, unit_inv,
                       unit_decode, cache_init, layout=layout)]

    if tail:
        # trailing mamba layers (no shared-attn application); update stream 1
        def t_fwd(lp, sh, ctx, i, x1, x2):
            return x1 + mamba_delta(lp, x2), x2

        def t_inv(lp, sh, ctx, i, y1, y2):
            return y1 - mamba_delta(lp, y2), y2

        def t_decode(lp, sh, ctx, i, x1, x2, cu):
            delta, nst = mamba_delta_decode(lp, x2, cu["m"])
            return (x1 + delta, x2), {"m": nst}

        def t_cache(lp, B, buf, dtype, extras):
            return {"m": {"h": jnp.zeros((B, nh, N, P), jnp.float32),
                          "conv": jnp.zeros((B, K - 1, d_inner), dtype)}}

        stacks.append(StackDef("tail", tail, msub, t_fwd, t_inv, t_decode, t_cache))
    return stacks, {}


def build_encdec(cfg: ModelConfig):
    """Whisper-style: reversible encoder (non-causal) + reversible decoder
    (self-attn, cross-attn to encoder output, MLP)."""
    d, half = cfg.d_model, cfg.stream_dim

    # ---- encoder
    encF = _attn_F(cfg, None, causal=False)
    encG = _mlp_G(cfg)
    enc_fwd, enc_inv = make_coupled(encF, encG, mode=cfg.coupling,
                                    fp_iters=cfg.inverse_fp_iters)
    enc_specs = _dense_sub_specs(cfg)

    # ---- decoder: chain of self-attn (->s1), cross-attn (->s2), MLP (->s1)
    selfF = _attn_F(cfg, None, causal=True)

    def crossF(p, sh, ctx, i, y1, x2):      # target 2; reads y1 + encoder output
        n = rms_norm(y1, p["norm_cross"], cfg.norm_eps)
        enc = sh["enc"]
        with jax.named_scope(scopes.ATTN):
            att = attention(p["cross"], cfg, _up(p["cross_ad"], n), enc,
                            positions_q=ctx["positions"],
                            positions_k=jnp.broadcast_to(
                                jnp.arange(enc.shape[1],
                                           dtype=jnp.int32)[None],
                                enc.shape[:2]),
                            causal=False, use_rope=False)
            return _down(p["cross_ad"], att)

    def mlpF(p, sh, ctx, i, x1, y2):        # target 1; reads y2
        h = rms_norm(y2, p["norm_mlp"], cfg.norm_eps)
        with jax.named_scope(scopes.MLP):
            return _down(p["mlp_ad"], mlp(p["mlp"], _up(p["mlp_ad"], h)))

    dec_fwd, dec_inv = chain(coupling(selfF, 1, cfg.inverse_fp_iters),
                             coupling(crossF, 2, 1),
                             coupling(mlpF, 1, 1))
    dec_specs = {
        "norm1": norm_spec(half), "norm2": norm_spec(half),
        "attn_ad": ad.adapter_specs(d), "attn": attn_specs(cfg),
        "norm_cross": norm_spec(half), "cross_ad": ad.adapter_specs(d),
        "cross": attn_specs(cfg),
        "norm_mlp": norm_spec(half), "mlp_ad": ad.adapter_specs(d),
        "mlp": mlp_specs(cfg),
    }

    def dec_decode(lp, sh, ctx, i, x1, x2, cu):
        q_in = _up(lp["attn_ad"], rms_norm(x1, lp["norm1"], cfg.norm_eps))
        kv_in = _up(lp["attn_ad"], rms_norm(x2, lp["norm2"], cfg.norm_eps))
        att, nkv = attention_decode(lp["attn"], cfg, q_in, kv_in, cu["kv"], ctx["t"])
        y1 = x1 + _down(lp["attn_ad"], att)
        qc = _up(lp["cross_ad"], rms_norm(y1, lp["norm_cross"], cfg.norm_eps))
        catt = cross_attention_decode(lp["cross"], cfg, qc, cu["cross"])
        y2 = x2 + _down(lp["cross_ad"], catt)
        h = _up(lp["mlp_ad"], rms_norm(y2, lp["norm_mlp"], cfg.norm_eps))
        z1 = y1 + _down(lp["mlp_ad"], mlp(lp["mlp"], h))
        return (z1, y2), {"kv": nkv, "cross": cu["cross"]}

    def dec_cache(lp, B, buf, dtype, extras):
        enc_out = extras["enc_out"]         # (B, Se, d) — encoder already run
        return {"kv": init_kv_cache(cfg, B, buf, dtype),
                "cross": cross_kv(lp["cross"], cfg, enc_out)}

    return [
        StackDef("encoder", cfg.num_encoder_layers, enc_specs, enc_fwd, enc_inv,
                 role="encoder"),
        StackDef("decoder", cfg.num_layers, dec_specs, dec_fwd, dec_inv,
                 dec_decode, dec_cache),
    ], {}


def build_vlm(cfg: ModelConfig):
    """Text backbone with a gated image cross-attention coupling heading every
    ``cross_attn_period``-layer unit (llama-3.2-vision style)."""
    d, half = cfg.d_model, cfg.stream_dim
    k = cfg.cross_attn_period
    assert cfg.num_layers % k == 0
    n_units = cfg.num_layers // k

    selfF = _attn_F(cfg, None, causal=True)
    G = _mlp_G(cfg)
    inner_fwd, inner_inv = make_coupled(selfF, G, mode=cfg.coupling,
                                        fp_iters=cfg.inverse_fp_iters)

    def crossF(p, sh, ctx, i, x1, x2):      # target 1; reads x2 + image feats
        n = rms_norm(x2, p["norm_cross"], cfg.norm_eps)
        img = sh["img"]
        with jax.named_scope(scopes.ATTN):
            att = attention(p["cross"], cfg, _up(p["cross_ad"], n), img,
                            positions_q=ctx["positions"],
                            positions_k=jnp.broadcast_to(
                                jnp.arange(img.shape[1],
                                           dtype=jnp.int32)[None],
                                img.shape[:2]),
                            causal=False, use_rope=False)
            return (jnp.tanh(p["cross_gate"]).astype(att.dtype)
                    * _down(p["cross_ad"], att))

    cross_fwd, cross_inv = coupling(crossF, 1, 1)

    inner_specs = _dense_sub_specs(cfg)
    unit_specs = {
        "norm_cross": norm_spec(half), "cross_ad": ad.adapter_specs(d),
        "cross": attn_specs(cfg), "cross_gate": ParamSpec((1,), (None,), init="zeros"),
        "inner": spec.stack(k, inner_specs),
    }

    def unit_fwd(lp, sh, ctx, i, x1, x2):
        x1, x2 = cross_fwd(lp, sh, ctx, i, x1, x2)
        for j in range(k):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            x1, x2 = inner_fwd(sub, sh, ctx, i, x1, x2)
        return x1, x2

    def unit_inv(lp, sh, ctx, i, y1, y2):
        for j in reversed(range(k)):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            y1, y2 = inner_inv(sub, sh, ctx, i, y1, y2)
        return cross_inv(lp, sh, ctx, i, y1, y2)

    def unit_decode(lp, sh, ctx, i, x1, x2, cu):
        qc = _up(lp["cross_ad"], rms_norm(x2, lp["norm_cross"], cfg.norm_eps))
        catt = cross_attention_decode(lp["cross"], cfg, qc, cu["cross"])
        x1 = x1 + jnp.tanh(lp["cross_gate"]).astype(catt.dtype) * _down(lp["cross_ad"], catt)
        nkvs = []
        for j in range(k):
            sub = jax.tree_util.tree_map(lambda a: a[j], lp["inner"])
            kvj = jax.tree_util.tree_map(lambda a: a[j], cu["kv"])
            q_in = _up(sub["attn_ad"], rms_norm(x1, sub["norm1"], cfg.norm_eps))
            kv_in = _up(sub["attn_ad"], rms_norm(x2, sub["norm2"], cfg.norm_eps))
            att, nkv = attention_decode(sub["attn"], cfg, q_in, kv_in, kvj, ctx["t"])
            x1 = x1 + _down(sub["attn_ad"], att)
            x2 = x2 + G(sub, sh, ctx, i, x1)
            nkvs.append(nkv)
        nkv = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *nkvs)
        return (x1, x2), {"cross": cu["cross"], "kv": nkv}

    def cache_init(lp, B, buf, dtype, extras):
        img = extras["img"]
        one = init_kv_cache(cfg, B, buf, dtype)
        return {"cross": cross_kv(lp["cross"], cfg, img),
                "kv": jax.tree_util.tree_map(lambda a: jnp.stack([a] * k), one)}

    return [StackDef("units", n_units, unit_specs, unit_fwd, unit_inv,
                     unit_decode, cache_init)], {}


_BUILDERS = {
    "dense": build_dense,
    "moe": build_moe,
    "ssm": build_rwkv,
    "hybrid": build_zamba,
    "encdec": build_encdec,
    "vlm": build_vlm,
}


# ===================================================================== model

class Model:
    def __init__(self, cfg: ModelConfig):
        assert cfg.moe_backend in moe_lib.MOE_BACKENDS, (
            f"unknown moe_backend {cfg.moe_backend!r}; "
            f"known: {moe_lib.MOE_BACKENDS}")
        if cfg.expert_parallel > 0:
            # fail at construction, not deep inside a trace: EP needs MoE
            # layers and an expert axis every device can own a slice of
            if cfg.num_experts == 0:
                raise ValueError(
                    f"{cfg.name}: expert_parallel={cfg.expert_parallel} "
                    f"requires an MoE config (num_experts > 0)")
            from repro.kernels.moe.ep import validate_ep
            validate_ep(moe_lib.padded_experts(cfg.num_experts),
                        num_tokens=0,       # token count checked per call
                        ep=cfg.expert_parallel,
                        num_experts_raw=cfg.num_experts)
        self.cfg = cfg
        self.stacks, self.shared_specs = _BUILDERS[cfg.family](cfg)
        if cfg.num_layer_groups:
            if not cfg.reversible:
                raise ValueError(
                    f"{cfg.name}: num_layer_groups="
                    f"{cfg.num_layer_groups} requires reversible=True — "
                    f"the grouped walks live in the reversible stack "
                    f"machinery (set reversible or drop --layer-groups)")
            if cfg.family == "hybrid":
                raise ValueError(
                    f"{cfg.name}: the zamba2 hybrid family already shares "
                    f"its attn/MLP block as a built-in layer group (one "
                    f"group per attn_period window); num_layer_groups is "
                    f"not composable with it — use a dense/moe/ssm/vlm "
                    f"config for --layer-groups")
            for s in self.stacks:
                # grouping covers the main stacks; an encdec encoder keeps
                # its flat layout (plans and fused walks cover mains only)
                if s.role != "main" or s.layout is not None:
                    continue
                s.layout = spec.contiguous_layout(
                    s.n, cfg.num_layer_groups, tuple(s.unit_specs.keys()),
                    cfg.delta_rank)
        d = cfg.d_model
        self.top_specs = {
            "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"),
                               init="unit_normal"),
            "final_norm": norm_spec(d),
            "lm_head": ParamSpec((d, cfg.vocab_size), ("embed", "vocab")),
        }
        if cfg.family == "encdec":
            self.top_specs["enc_norm"] = norm_spec(d)

    # ------------------------------------------------------------- specs

    def param_specs(self):
        if self.cfg.reversible:
            tree = {s.name: (spec.grouped_stack(s.layout, s.unit_specs)
                             if s.layout is not None
                             else spec.stack(s.n, s.unit_specs))
                    for s in self.stacks}
        else:
            tree = {s.name: spec.stack(s.n, _std_specs(self.cfg, self.cfg.family == "moe"))
                    for s in self.stacks if s.role == "main"}
            if self.cfg.family == "ssm":
                tree = {s.name: spec.stack(s.n, {
                    "norm1": norm_spec(self.cfg.d_model),
                    "norm_mlp": norm_spec(self.cfg.d_model),
                    "time": ssm_lib.rwkv_time_specs(self.cfg),
                    "chan": ssm_lib.rwkv_channel_specs(self.cfg)})
                    for s in self.stacks}
        out = dict(self.top_specs)
        out["stacks"] = tree
        if self.shared_specs and self.cfg.reversible:
            out["shared"] = self.shared_specs
        return out

    def init(self, key):
        return spec.initialize(self.param_specs(), key, self.cfg.dtype)

    def abstract_params(self):
        return spec.abstract(self.param_specs(), self.cfg.dtype)

    def logical_axes(self):
        return spec.logical_axes(self.param_specs())

    def num_params(self) -> int:
        return spec.count_params(self.param_specs())

    # ------------------------------------------------------------- forward

    def _shared(self, params, extras):
        sh = dict(params.get("shared", {}))
        if extras:
            sh.update(extras)
        return sh

    # set by the launcher/dry-run to add activation sharding constraints
    batch_spec = None

    def _constrain(self, x):
        if self.batch_spec is not None:
            return jax.lax.with_sharding_constraint(
                x, self.batch_spec if x.ndim == 3 else self.batch_spec)
        return x

    def _std_mixed(self, s, stacked, shared, ctx, h, policies):
        """Mixed activation policies on the standard (non-reversible) path.
        "reversible" is not available here — the planner never emits it for
        ``reversible=False`` configs."""
        from repro.core.reversible import policy_segments
        assert "reversible" not in policies, \
            "reversible policy requires cfg.reversible=True"
        for start, end, pol in policy_segments(policies):
            seg_params = jax.tree_util.tree_map(lambda a: a[start:end], stacked)
            if pol == "offload":
                from repro.memory.offload import offload_std_block
                ob = offload_std_block(s.std_fwd)
                for j in range(end - start):
                    lp = jax.tree_util.tree_map(lambda a, j=j: a[j], seg_params)
                    h = ob(lp, shared, ctx, jnp.int32(start + j), h)
                continue
            body_fn = s.std_fwd if pol == "store" else jax.checkpoint(s.std_fwd)

            def scan_body(hh, inp, fn=body_fn, sh=shared):
                i, lp = inp
                return fn(lp, sh, ctx, i, hh), None
            idxs = start + jnp.arange(end - start, dtype=jnp.int32)
            h, _ = jax.lax.scan(scan_body, h, (idxs, seg_params))
        return h

    def audit_streams(self, params, tokens, extras=None):
        """The prefix of ``hidden`` up to the first main stack, for the
        layer auditor (repro.obs.audit): embedding split into the two
        reversible streams, the position ctx, and the shared tree (with the
        encoder already run for encdec — the audit walks main stacks only).
        Requires a reversible config; the auditor then drives each stack's
        fwd/inv per layer itself."""
        cfg = self.cfg
        assert cfg.reversible, "layer audit requires cfg.reversible=True"
        B, S = tokens.shape
        h = embed_lookup(params["embed"], tokens)
        h = self._constrain(h)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
        ctx = {"positions": positions}
        shared = self._shared(params, extras)
        if cfg.family == "encdec":
            enc = extras["enc_feats"]
            e1, e2 = split_streams(enc.astype(h.dtype))
            ectx = {"positions": jnp.broadcast_to(
                jnp.arange(enc.shape[1], dtype=jnp.int32)[None],
                enc.shape[:2])}
            enc_stack = next(s for s in self.stacks if s.role == "encoder")
            apply_e = reversible_stack(enc_stack.fwd, enc_stack.inv,
                                       enc_stack.n)
            e1, e2 = apply_e(params["stacks"][enc_stack.name], shared, ectx,
                             e1, e2)
            shared = dict(shared)
            shared["enc"] = rms_norm(merge_streams(e1, e2),
                                     params["enc_norm"], cfg.norm_eps)
        x1, x2 = split_streams(h)
        return x1, x2, ctx, shared

    def hidden(self, params, tokens, extras=None, save_memory=True):
        """Final-normed hidden states (B,S,d) — everything before the LM head.

        ``save_memory``: True (paper O(1) mode) / "half" / False (cached SFT
        baseline), or a per-layer policy list ("store" | "remat" |
        "reversible" | "offload", one per main-stack unit) as produced by
        ``repro.memory.planner`` — mixed policies per DESIGN.md §6.
        """
        cfg = self.cfg
        B, S = tokens.shape
        h = embed_lookup(params["embed"], tokens)
        h = self._constrain(h)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        ctx = {"positions": positions}
        shared = self._shared(params, extras)

        policy_list = (list(save_memory)
                       if isinstance(save_memory, (list, tuple)) else None)
        if policy_list is not None:
            n_main = sum(s.n for s in self.stacks if s.role == "main")
            assert len(policy_list) == n_main, (
                f"plan has {len(policy_list)} policies for {n_main} units")

        if cfg.family == "encdec":
            enc = extras["enc_feats"]
            e1, e2 = split_streams(enc.astype(h.dtype))
            ectx = {"positions": jnp.broadcast_to(
                jnp.arange(enc.shape[1], dtype=jnp.int32)[None], enc.shape[:2])}
            enc_stack = next(s for s in self.stacks if s.role == "encoder")
            # plans cover the main stacks only; the encoder keeps the default
            # O(1) reversible mode under a policy list
            enc_sm = True if policy_list is not None else save_memory
            apply_e = reversible_stack(enc_stack.fwd, enc_stack.inv, enc_stack.n,
                                       save_memory=enc_sm)
            e1, e2 = apply_e(params["stacks"][enc_stack.name], shared, ectx, e1, e2)
            enc_out = rms_norm(merge_streams(e1, e2), params["enc_norm"], cfg.norm_eps)
            shared = dict(shared)
            shared["enc"] = enc_out

        if cfg.reversible:
            x1, x2 = split_streams(h)
            for s in self.stacks:
                if s.role != "main":
                    continue
                if policy_list is not None:
                    seg, policy_list = policy_list[:s.n], policy_list[s.n:]
                    if s.layout is not None:
                        apply = grouped_mixed_policy_stack(s.fwd, s.inv,
                                                           s.layout, seg)
                    else:
                        apply = mixed_policy_stack(s.fwd, s.inv, seg,
                                                   half_inv=s.half_inv)
                elif s.layout is not None:
                    sm = save_memory
                    if sm == "half":
                        sm = True        # grouped stacks: full inversion only
                    apply = grouped_reversible_stack(s.fwd, s.inv, s.layout,
                                                     save_memory=sm)
                else:
                    sm = save_memory
                    if sm == "half" and s.half_inv is None:
                        sm = True                  # fall back to full inversion
                    apply = reversible_stack(s.fwd, s.inv, s.n, save_memory=sm,
                                             half_inv=s.half_inv)
                x1, x2 = apply(params["stacks"][s.name], shared, ctx, x1, x2)
            h = merge_streams(x1, x2)
        else:
            use_remat = cfg.remat_policy == "block"
            for s in self.stacks:
                if s.role != "main":
                    continue
                body_fn = s.std_fwd
                assert body_fn is not None, f"standard path unsupported for {cfg.family}"
                if policy_list is not None:
                    seg, policy_list = policy_list[:s.n], policy_list[s.n:]
                    h = self._std_mixed(s, params["stacks"][s.name], shared,
                                        ctx, h, seg)
                    continue
                if use_remat:
                    body_fn = jax.checkpoint(body_fn, static_argnums=())

                def scan_body(hh, inp, fn=body_fn, sh=shared):
                    i, lp = inp
                    return fn(lp, sh, ctx, i, hh), None
                idxs = jnp.arange(s.n, dtype=jnp.int32)
                h, _ = jax.lax.scan(scan_body, h, (idxs, params["stacks"][s.name]))

        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        return self._constrain(h)

    def forward(self, params, tokens, extras=None, save_memory=True):
        h = self.hidden(params, tokens, extras, save_memory)
        return self.lm_logits(params, h)

    def lm_logits(self, params, h):
        """LM-head logits from final-normed hidden states (any leading shape)."""
        return lm_head_logits(h, params["lm_head"], self.cfg.final_softcap)

    def _nll(self, params, h, tgt):
        """Per-position nll from final hidden states (chunk-sized)."""
        lg = self.lm_logits(params, h).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        return lse - gold

    def loss(self, params, batch, save_memory=True):
        """Next-token cross-entropy.  batch: tokens (B,S) [+ enc_feats/img].
        Sequence-chunked so the full (B,S,vocab) logits never materialise."""
        tokens = batch["tokens"]
        extras = {k: v for k, v in batch.items() if k in ("enc_feats", "img")}
        h = self.hidden(params, tokens, extras or None, save_memory)
        return self._token_loss(params, h, batch)

    def loss_from_streams(self, params, y1, y2, batch):
        """Tail of ``loss`` from the main stacks' output streams: final norm
        + LM head + token CE.  The fused train step (repro.train.fused)
        differentiates this piece separately from the per-layer walk, so it
        must match ``hidden``'s epilogue + ``loss``'s CE exactly."""
        h = rms_norm(merge_streams(y1, y2), params["final_norm"],
                     self.cfg.norm_eps)
        return self._token_loss(params, self._constrain(h), batch)

    def _token_loss(self, params, h, batch):
        """Masked, sequence-chunked CE from final-normed hidden states."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S, _ = h.shape
        tgt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)  # last pos dummy
        valid = jnp.ones((B, S), jnp.float32).at[:, -1].set(0.0)
        mask = batch.get("loss_mask")
        if mask is not None:
            valid = valid * jnp.concatenate(
                [mask[:, 1:], mask[:, :1]], axis=1).astype(jnp.float32)

        ck = cfg.loss_chunk
        if ck and S > ck and S % ck == 0:
            nc = S // ck
            hs = h.reshape(B, nc, ck, -1).transpose(1, 0, 2, 3)
            ts = tgt.reshape(B, nc, ck).transpose(1, 0, 2)
            # checkpoint the chunk body: without it autodiff stacks each
            # chunk's f32 logits as residuals — the full (B,S,vocab) the
            # chunking exists to avoid (estimator made this visible, §6)
            nll = jax.lax.map(
                jax.checkpoint(lambda ab: self._nll(params, ab[0], ab[1])),
                (hs, ts))
            nll = nll.transpose(1, 0, 2).reshape(B, S)
        else:
            nll = self._nll(params, h, tgt)
        return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    # ------------------------------------------------------------- decode

    def init_cache(self, params, batch_size: int, buf_len: int, extras=None):
        """Decode caches (stacked per unit).  ``extras``: enc_feats / img."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        ex = dict(extras or {})
        if cfg.family == "encdec":
            enc = ex["enc_feats"]
            # run the encoder once; its output feeds the decoder cross-attn caches
            shared = self._shared(params, None)
            e1, e2 = split_streams(enc.astype(dtype))
            ectx = {"positions": jnp.broadcast_to(
                jnp.arange(enc.shape[1], dtype=jnp.int32)[None], enc.shape[:2])}
            enc_stack = next(s for s in self.stacks if s.role == "encoder")
            apply_e = reversible_stack(enc_stack.fwd, enc_stack.inv, enc_stack.n)
            e1, e2 = apply_e(params["stacks"][enc_stack.name], shared, ectx, e1, e2)
            ex["enc_out"] = rms_norm(merge_streams(e1, e2), params["enc_norm"],
                                     cfg.norm_eps)
        caches = {"t": jnp.zeros((), jnp.int32)}
        for s in self.stacks:
            if s.role != "main":
                continue
            buf = buf_len
            if cfg.sliding_window:
                buf = min(buf_len, cfg.sliding_window)
            if s.layout is not None:
                gp = params["stacks"][s.name]
                caches[s.name] = jax.vmap(
                    lambda i, s=s, gp=gp: s.cache_init(
                        read_unit(s.layout, gp, i), batch_size, buf, dtype,
                        ex))(jnp.arange(s.n, dtype=jnp.int32))
            else:
                caches[s.name] = jax.vmap(
                    lambda lp, s=s: s.cache_init(lp, batch_size, buf, dtype,
                                                 ex))(
                    params["stacks"][s.name])
        return caches

    def decode_step_hidden(self, params, cache, token, *, seq_len=None):
        """Decode/prefill step up to the final norm — the hook the serving
        engine fuses sampling onto.  token: (B, Sq) — Sq=1 for decode, Sq=S
        for (non-rolling) prefill.  Returns (h (B, Sq, d), new_cache); callers
        that only need one position (batched bucketed prefill reads the last
        real position per row) gather from ``h`` and apply ``lm_logits`` there
        instead of materialising (B, Sq, V) logits.  ``seq_len`` (optional
        traced scalar): real token count of a right-padded prefill — lets the
        longer-than-window path keep the real tail instead of pad tokens."""
        cfg = self.cfg
        B, Sq = token.shape
        t = cache["t"]
        h = jnp.take(params["embed"], token, axis=0)
        ctx = {"t": t, "seq_len": seq_len,
               "positions": t + jnp.broadcast_to(
                   jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))}
        shared = self._shared(params, None)
        x1, x2 = split_streams(h)
        new_cache = {"t": t + Sq}
        for s in self.stacks:
            if s.role != "main":
                continue

            idxs = jnp.arange(s.n, dtype=jnp.int32)
            if s.layout is not None:
                gp = params["stacks"][s.name]

                def gbody(carry, inp, s=s, gp=gp):
                    i, cu = inp
                    lp = read_unit(s.layout, gp, i)
                    (a, b), ncu = s.decode(lp, shared, ctx, i, *carry, cu)
                    return (a, b), ncu
                (x1, x2), ncache = jax.lax.scan(
                    gbody, (x1, x2), (idxs, cache[s.name]))
            else:
                def body(carry, inp, s=s):
                    i, lp, cu = inp
                    (a, b), ncu = s.decode(lp, shared, ctx, i, *carry, cu)
                    return (a, b), ncu
                (x1, x2), ncache = jax.lax.scan(
                    body, (x1, x2),
                    (idxs, params["stacks"][s.name], cache[s.name]))
            new_cache[s.name] = ncache
        h = rms_norm(merge_streams(x1, x2), params["final_norm"], cfg.norm_eps)
        return h, new_cache

    def decode_step(self, params, cache, token):
        """token: (B, Sq) — Sq=1 for decode, Sq=S for (non-rolling) prefill.
        Returns (logits (B, Sq, V), new_cache)."""
        h, new_cache = self.decode_step_hidden(params, cache, token)
        return self.lm_logits(params, h), new_cache

    # ------------------------------------------------------- paged decode

    def paged_supported(self) -> bool:
        """True when every main stack has a paged decode path (attention-KV
        cache layouts only — recurrent/hybrid state has no page structure)."""
        main = [s for s in self.stacks if s.role == "main"]
        return bool(main) and all(s.decode_paged is not None
                                  and s.pool_init is not None for s in main)

    def init_kv_pool(self, n_pages: int, page_size: int):
        """Paged KV storage (DESIGN.md §15): per main stack, pool leaves with
        a leading layer axis — k/v (L, P, page, KV, hd) and stored positions
        (L, P, page).  Physical pages are shared across slots; per-slot page
        tables (engine-owned) map logical positions into the pool."""
        if not self.paged_supported():
            raise ValueError(
                f"config {self.cfg.name} (family {self.cfg.family}) has no "
                "paged KV layout — paged serving supports attention-KV "
                "families only")
        dtype = jnp.dtype(self.cfg.dtype)
        pools = {}
        for s in self.stacks:
            if s.role != "main":
                continue
            one = s.pool_init(n_pages, page_size, dtype)
            pools[s.name] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (s.n,) + a.shape).copy(), one)
        return pools

    def decode_step_hidden_paged(self, params, pools, page_tables, t, token,
                                 write_mask, *, kv_len: int):
        """One decode step against the paged KV pool.  token: (B, 1);
        t: (B,) per-slot positions (unlike the dense path, slots advance
        independently — no vmap over per-slot cache trees); page_tables:
        (B, n_pages); write_mask: (B,) — rows not selected must not write
        (their pages may belong to someone else now).  Returns
        (h (B, 1, d), new_pools)."""
        cfg = self.cfg
        B, Sq = token.shape
        assert Sq == 1, "paged decode is single-position"
        h = jnp.take(params["embed"], token, axis=0)
        ctx = {"t": t, "kv_len": kv_len, "positions": t[:, None]}
        shared = self._shared(params, None)
        x1, x2 = split_streams(h)
        new_pools = {}
        for s in self.stacks:
            if s.role != "main":
                continue
            idxs = jnp.arange(s.n, dtype=jnp.int32)
            if s.layout is not None:
                gp = params["stacks"][s.name]

                def gbody(carry, inp, s=s, gp=gp):
                    i, pu = inp
                    lp = read_unit(s.layout, gp, i)
                    (a, b), npu = s.decode_paged(lp, shared, ctx, i, *carry,
                                                 pu, page_tables, write_mask)
                    return (a, b), npu
                (x1, x2), npool = jax.lax.scan(
                    gbody, (x1, x2), (idxs, pools[s.name]))
            else:
                def body(carry, inp, s=s):
                    i, lp, pu = inp
                    (a, b), npu = s.decode_paged(lp, shared, ctx, i, *carry,
                                                 pu, page_tables, write_mask)
                    return (a, b), npu
                (x1, x2), npool = jax.lax.scan(
                    body, (x1, x2),
                    (idxs, params["stacks"][s.name], pools[s.name]))
            new_pools[s.name] = npool
        h = rms_norm(merge_streams(x1, x2), params["final_norm"], cfg.norm_eps)
        return h, new_pools
