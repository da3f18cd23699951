"""Mixture-of-Experts layer: top-k router + two interchangeable dispatch
backends + shared experts.

``cfg.moe_backend`` selects the expert-execution path (overridable per call):

* ``"einsum"`` — GShard-style grouped-capacity dispatch/combine einsums
  (DESIGN.md §2).  Tokens are split into groups of <= ``GROUP`` so the dense
  one-hot dispatch tensor is linear in total tokens (T * group * k * cf);
  token counts that do not divide the group size are zero-padded to the next
  multiple and the pad slots are masked out of routing, capacity and the aux
  loss.  Tokens beyond an expert's capacity are dropped.

* ``"grouped"`` — sort-based dropless dispatch (repro.kernels.moe,
  DESIGN.md §7): stable argsort by expert id, ragged grouped GEMMs (Pallas
  on TPU, pure-JAX tiled fallback elsewhere), gate-weighted combine.  No
  capacity, no drops, no dispatch tensor.

``cfg.expert_parallel > 0`` overrides the backend choice with the expert-
parallel dispatch path (repro.kernels.moe.ep, DESIGN.md §10): experts and
tokens shard over the mesh "expert" axis, a shard_map all-to-all routes
token rows to their expert's device, and each device runs the grouped GEMMs
over its local experts.  Numerically it is the grouped backend (same
permute/GEMM/f32-combine chain), distributed.  Requires the launcher/test
to install the mesh via ``repro.core.settings.set_ep_mesh``.

Experts are zero-padded to a multiple of 16 (EP_PAD) so the expert axis
divides the `model` mesh axis (padded experts are masked to -inf in the
router and receive no tokens).

Routers can be frozen (paper stage 2) via the schedule mask — the router
weight lives at key "router" in the layer param dict.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.spec import ParamSpec
from repro.obs import scopes

EP_PAD = 16
GROUP = 512

MOE_BACKENDS = ("einsum", "grouped")


def padded_experts(num_experts: int) -> int:
    if num_experts >= EP_PAD:
        return int(math.ceil(num_experts / EP_PAD) * EP_PAD)
    return num_experts


def moe_specs(cfg: ModelConfig) -> dict:
    d, e, ff = cfg.d_model, padded_experts(cfg.num_experts), cfg.d_ff_expert
    p = {
        "router": ParamSpec((d, e), ("embed", None), init="normal"),
        "w_gate": ParamSpec((e, d, ff), ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((e, d, ff), ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((e, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.num_shared_experts > 0:
        ffs = cfg.num_shared_experts * cfg.d_ff_expert
        p["shared"] = {
            "w_gate": ParamSpec((d, ffs), ("embed", "mlp")),
            "w_up": ParamSpec((d, ffs), ("embed", "mlp")),
            "w_down": ParamSpec((ffs, d), ("mlp", "embed")),
            "gate": ParamSpec((d, 1), ("embed", None), init="zeros"),
        }
    return p


def _capacity(tokens_per_group: int, num_experts: int, top_k: int,
              capacity_factor: float) -> int:
    c = int(math.ceil(tokens_per_group * top_k * capacity_factor / num_experts))
    return max(4, int(math.ceil(c / 4) * 4))


def _route(p, cfg: ModelConfig, xf):
    """xf: (T, d) -> probs (T, E) f32, gate_vals (T, k) f32, expert_idx (T, k)."""
    E, k = padded_experts(cfg.num_experts), cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if E > cfg.num_experts:  # mask padded experts
        pad_mask = jnp.arange(E) < cfg.num_experts
        logits = jnp.where(pad_mask[None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    return probs, gate_vals, expert_idx


def _pad_rows(a, pad: int):
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths)


def _einsum_dispatch(p, cfg: ModelConfig, xf, probs, gate_vals, expert_idx,
                     g_size: int):
    """Dense one-hot dispatch/combine einsums over token groups.

    Token counts not divisible by ``g_size`` are padded up; pad slots carry
    zero routing weight (no capacity consumed, no aux contribution).
    Returns (y (T, d), aux scalar f32).
    """
    T, d = xf.shape
    E, k = padded_experts(cfg.num_experts), cfg.top_k
    pad = (-T) % g_size
    G = (T + pad) // g_size

    with jax.named_scope(scopes.MOE_DISPATCH):
        xg = _pad_rows(xf, pad).reshape(G, g_size, d)
        probs_g = _pad_rows(probs, pad).reshape(G, g_size, E)
        gate_g = _pad_rows(gate_vals, pad).reshape(G, g_size, k)
        idx_g = _pad_rows(expert_idx, pad).reshape(G, g_size, k)
        valid = _pad_rows(jnp.ones((T,), jnp.float32), pad).reshape(G, g_size)

        # position-in-expert with top-k priority (k-major within token order)
        onehot = jax.nn.one_hot(idx_g, E, dtype=jnp.float32)  # (G, t, k, E)
        onehot = onehot * valid[..., None, None]             # pads route nowhere
        flat = onehot.transpose(0, 2, 1, 3).reshape(G, k * g_size, E)
        pos = jnp.cumsum(flat, axis=1) - flat                # (G, k*t, E)
        C = _capacity(g_size, E, k, cfg.capacity_factor)
        keep = (pos < C) * flat
        pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
        # back to token-major: (G, k, t, E, C) -> sum over k
        pos_oh = pos_oh.reshape(G, k, g_size, E, C)
        dispatch = jnp.sum(pos_oh, axis=1)                   # (G, t, E, C) 0/1
        expert_in = jnp.einsum("gtec,gtd->gecd", dispatch.astype(xf.dtype),
                               xg)

    with jax.named_scope(scopes.MOE_EXPERTS):
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                                   p["w_gate"])) * \
            jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
        expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])

    with jax.named_scope(scopes.MOE_COMBINE):
        gates_te = jnp.einsum(
            "gtke,gtk->gte",
            onehot * keep.reshape(G, k, g_size, E).transpose(0, 2, 1, 3),
            gate_g)
        combine = dispatch * gates_te[..., None]             # (G, t, E, C)
        y = jnp.einsum("gtec,gecd->gtd", combine.astype(xf.dtype), expert_out)
        y = y.reshape(G * g_size, d)[:T]

    # load-balancing aux loss (Switch): E * mean_g(sum_e frac_e * mean_prob_e),
    # masked so pad slots do not dilute the per-group statistics
    with jax.named_scope(scopes.MOE_ROUTER):
        n_valid = jnp.sum(valid, axis=1)                     # (G,) >= 1
        frac = jnp.sum(jnp.sum(onehot, axis=2), axis=1) / n_valid[:, None]
        mean_p = jnp.sum(probs_g * valid[..., None], axis=1) / n_valid[:, None]
        aux = cfg.num_experts * jnp.mean(jnp.sum(frac * mean_p, axis=-1))
    return y, aux.astype(jnp.float32)


def einsum_dropped_fraction(cfg: ModelConfig, expert_idx,
                            group: Optional[int] = None):
    """Fraction of (token, k) assignments the einsum backend's capacity
    path drops, replaying ``_einsum_dispatch``'s exact priority order
    (k-major within token order, per group, pads masked).  The dropless
    backends (grouped / ep) drop nothing by construction."""
    T, k = expert_idx.shape
    E = padded_experts(cfg.num_experts)
    g_size = min(group or GROUP, T)
    pad = (-T) % g_size
    G = (T + pad) // g_size
    idx_g = _pad_rows(expert_idx, pad).reshape(G, g_size, k)
    valid = _pad_rows(jnp.ones((T,), jnp.float32), pad).reshape(G, g_size)
    onehot = jax.nn.one_hot(idx_g, E, dtype=jnp.float32) * valid[..., None, None]
    flat = onehot.transpose(0, 2, 1, 3).reshape(G, k * g_size, E)
    pos = jnp.cumsum(flat, axis=1) - flat
    C = _capacity(g_size, E, k, cfg.capacity_factor)
    kept = jnp.sum((pos < C) * flat)
    total = jnp.sum(flat)
    return (1.0 - kept / jnp.maximum(total, 1.0)).astype(jnp.float32)


def routing_stats(cfg: ModelConfig, probs, expert_idx, *,
                  backend: Optional[str] = None,
                  group: Optional[int] = None) -> dict:
    """Per-layer routing telemetry from one routed batch (DESIGN.md §12).

    probs: (T, E) f32 router softmax, expert_idx: (T, k) — the ``_route``
    outputs.  Returns device scalars/arrays (no host sync here; callers
    pull values at audit/log windows):

      expert_load       (num_experts,) token-assignment counts per expert
      imbalance         max expert load / mean expert load (1.0 = uniform)
      entropy           mean per-token routing entropy in nats (0 = a
                        collapsed router that puts all mass on one expert)
      dropped_fraction  capacity-path drops ("einsum" backend; 0 for the
                        dropless grouped/ep paths)

    ``backend`` defaults to the config's active dispatch path (ep when
    expert_parallel > 0).
    """
    Er = cfg.num_experts
    E = padded_experts(Er)
    if backend is None:
        backend = "ep" if cfg.expert_parallel > 0 else cfg.moe_backend
    load = jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32),
                   axis=tuple(range(expert_idx.ndim)))[:Er]
    imbalance = jnp.max(load) * Er / jnp.maximum(jnp.sum(load), 1.0)
    p = probs[..., :Er]
    entropy = jnp.mean(-jnp.sum(p * jnp.log(p + 1e-9), axis=-1))
    if backend == "einsum":
        dropped = einsum_dropped_fraction(cfg, expert_idx, group)
    else:
        dropped = jnp.float32(0.0)
    return {"expert_load": load, "imbalance": imbalance,
            "entropy": entropy, "dropped_fraction": dropped}


def _switch_aux(cfg: ModelConfig, probs, expert_idx):
    """Global (ungrouped) Switch load-balancing statistic, shared by the
    grouped and expert-parallel dispatch paths."""
    E = padded_experts(cfg.num_experts)
    with jax.named_scope(scopes.MOE_ROUTER):
        frac = jnp.mean(jnp.sum(jax.nn.one_hot(expert_idx, E,
                                               dtype=jnp.float32),
                                axis=1), axis=0)             # (E,)
        aux = cfg.num_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return aux.astype(jnp.float32)


def _grouped_dispatch(p, cfg: ModelConfig, xf, probs, gate_vals, expert_idx):
    """Sort-based dropless dispatch (repro.kernels.moe).  No capacity: every
    (token, k) assignment executes.  Returns (y (T, d), aux scalar f32)."""
    from repro.kernels.moe import grouped_expert_ffn
    y = grouped_expert_ffn(xf, expert_idx, gate_vals.astype(xf.dtype),
                           p["w_gate"], p["w_up"], p["w_down"])
    return y, _switch_aux(cfg, probs, expert_idx)


def _ep_dispatch(p, cfg: ModelConfig, xf, probs, gate_vals, expert_idx):
    """Expert-parallel dispatch over the mesh "expert" axis (kernels/moe/ep,
    DESIGN.md §10).  Dropless like the grouped backend; the expert GEMMs run
    on the device owning each expert, fed by a shard_map all-to-all."""
    from repro.core import settings
    from repro.kernels.moe import ep as ep_lib
    mesh = settings.EP_MESH
    if mesh is None:
        raise ValueError(
            f"{cfg.name}: expert_parallel={cfg.expert_parallel} needs the "
            f"device mesh (with an 'expert' axis) installed via "
            f"repro.core.settings.set_ep_mesh(mesh) before tracing — the "
            f"launchers do this from --ep; tests build one with "
            f"make_debug_mesh(..., expert=N).")
    E = padded_experts(cfg.num_experts)
    ep_lib.validate_ep(E, xf.shape[0], cfg.expert_parallel,
                       num_experts_raw=cfg.num_experts)
    if ep_lib.EP_AXIS in mesh.axis_names \
            and mesh.shape[ep_lib.EP_AXIS] != cfg.expert_parallel:
        raise ValueError(
            f"{cfg.name}: expert_parallel={cfg.expert_parallel} does not "
            f"match the mesh '{ep_lib.EP_AXIS}' axis size "
            f"{mesh.shape[ep_lib.EP_AXIS]}")
    y = ep_lib.ep_expert_ffn(xf, expert_idx, gate_vals.astype(xf.dtype),
                             p["w_gate"], p["w_up"], p["w_down"], mesh)
    return y, _switch_aux(cfg, probs, expert_idx)


def moe_apply(p, cfg: ModelConfig, x, *, group: Optional[int] = None,
              backend: Optional[str] = None):
    """x: (B, S, d) -> (y, aux_loss).  GSPMD-shardable either way."""
    B, S, d = x.shape
    T = B * S
    backend = backend or cfg.moe_backend
    assert backend in MOE_BACKENDS, backend
    xf = x.reshape(T, d)

    with jax.named_scope(scopes.MOE_ROUTER):
        probs, gate_vals, expert_idx = _route(p, cfg, xf)
    if cfg.expert_parallel > 0:
        y, aux = _ep_dispatch(p, cfg, xf, probs, gate_vals, expert_idx)
    elif backend == "grouped":
        y, aux = _grouped_dispatch(p, cfg, xf, probs, gate_vals, expert_idx)
    else:
        g_size = min(group or GROUP, T)
        y, aux = _einsum_dispatch(p, cfg, xf, probs, gate_vals, expert_idx,
                                  g_size)
    y = y.reshape(B, S, d)

    if "shared" in p:
        sh = p["shared"]
        with jax.named_scope(scopes.MOE_SHARED):
            hs = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sh["w_gate"])) * \
                jnp.einsum("bsd,df->bsf", x, sh["w_up"])
            ys = jnp.einsum("bsf,fd->bsd", hs, sh["w_down"])
            sgate = jax.nn.sigmoid(jnp.einsum("bsd,do->bso", x, sh["gate"]))
            y = y + sgate.astype(y.dtype) * ys
    return y, aux


def moe_apply_oracle(p, cfg: ModelConfig, x):
    """Dense per-token oracle (computes every expert on every token).
    Used only in tests to validate the dispatch paths (the grouped backend
    matches it exactly; the einsum backend matches when capacity_factor is
    large enough that nothing drops)."""
    B, S, d = x.shape
    E, k = padded_experts(cfg.num_experts), cfg.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"].astype(jnp.float32))
    if E > cfg.num_experts:
        logits = jnp.where((jnp.arange(E) < cfg.num_experts)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)
    # all experts on all tokens
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"])) * \
        jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    out_all = jnp.einsum("bsef,efd->bsed", h, p["w_down"])      # (B,S,E,d)
    sel = jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
                  * gate_vals[..., None], axis=2)               # (B,S,E)
    y = jnp.einsum("bse,bsed->bsd", sel.astype(x.dtype), out_all)
    if "shared" in p:
        sh = p["shared"]
        hs = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
        y = y + jax.nn.sigmoid(x @ sh["gate"]).astype(y.dtype) * (hs @ sh["w_down"])
    return y
