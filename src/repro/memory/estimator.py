"""Static per-layer memory model for the activation-policy planner.

Everything here is computed WITHOUT allocating or running anything, so the
full-size configs are estimable on this CPU container:

  * parameter / optimizer-state bytes come from the declarative param specs
    (``Model.abstract_params`` + ``jax.eval_shape(opt.init, ...)``) — exact.
  * residual (activation) bytes come from evaluating ``jax.vjp`` of the model
    loss **under** ``jax.eval_shape``: the leaves of the returned vjp closure
    are exactly the arrays autodiff saves for backward, and eval_shape gives
    their ShapeDtypeStructs with zero FLOPs.  This is the same trace-level
    quantity ``benchmarks/table1_memory.py`` measures concretely.
  * per-layer-per-policy costs are derived by depth differencing: trace a
    1-unit and a 2-unit model under the policy and subtract (net of the
    stacked-parameter growth, which is known exactly from the specs).

The resulting ``MemoryEstimate`` is the planner's cost model; its totals are
cross-checkable against live ``jax.local_devices()[0].memory_stats()`` via
``device_memory_stats`` (TPU/GPU; the CPU backend reports nothing).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

GiB = 2**30

#: planner-facing policy names, cheapest-compute first (single source of
#: truth lives next to the mixed-policy stack implementation)
from repro.core.reversible import POLICIES  # noqa: E402


def array_bytes(tree) -> int:
    """Total bytes of a pytree of arrays or ShapeDtypeStructs."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return total


def abstract_batch(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStruct batch matching what ``Model.loss`` consumes."""
    out = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.family == "encdec":
        out["enc_feats"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_seq_len, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        out["img"] = jax.ShapeDtypeStruct(
            (batch, cfg.num_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    return out


def residual_bytes(model, batch: int, seq: int, save_memory=True) -> int:
    """Trace-level bytes autodiff saves for backward of ``model.loss`` —
    computed statically (eval_shape; nothing is allocated).  ``save_memory``
    takes the same values as ``Model.loss``: True / "half" / False / a
    per-layer policy list."""
    abatch = abstract_batch(model.cfg, batch, seq)

    def residuals(params, b):
        _, vjp_fn = jax.vjp(lambda p: model.loss(p, b, save_memory=save_memory),
                            params)
        return tuple(leaf for leaf in jax.tree_util.tree_leaves(vjp_fn)
                     if hasattr(leaf, "shape"))

    out = jax.eval_shape(residuals, model.abstract_params(), abatch)
    return array_bytes(out)


def optimizer_by_name(name: str, lr: float = 1e-5):
    from repro.optim.adamw import AdamW
    from repro.optim.galore import GaLore
    from repro.optim.lomo import LoMo
    return {"adamw": AdamW(lr=lr), "lomo": LoMo(lr=lr),
            "galore": GaLore(lr=lr)}[name]


def unit_layers_for(cfg: ModelConfig) -> int:
    """Model layers per plannable (scanned) unit."""
    if cfg.family == "hybrid" and cfg.attn_period:
        return cfg.attn_period
    if cfg.family == "vlm" and cfg.cross_attn_period:
        return cfg.cross_attn_period
    return 1


def n_plan_units(model) -> int:
    """Plannable units = total scanned length of the main stacks."""
    return sum(s.n for s in model.stacks if s.role == "main")


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    """Byte-level cost model for one (config, microbatch, seq, optimizer).

    ``fused`` marks the fused optimizer-in-backward step (repro.train.fused,
    DESIGN.md §13): ``grad_bytes`` then covers only the non-stack remainder
    plus one layer's slice of the largest main stack — per-layer cotangents
    die inside the backward walk.  The fused numbers model ``n_micro == 1``;
    grad accumulation adds one accumulator tree in the accumulation dtype."""
    arch: str
    family: str
    batch: int
    seq: int
    optimizer: str
    n_units: int
    unit_layers: int
    param_bytes: int
    grad_bytes: int
    opt_bytes: int
    # depth-independent residuals (embed/head/loss) are NOT policy-free:
    # e.g. the store path keeps final hidden states the reversible path
    # reconstructs — so they are tracked per policy.
    fixed_act_by_policy: Dict[str, int]
    unit_act_bytes: Dict[str, int]       # per-policy DEVICE bytes per unit
    unit_host_bytes: Dict[str, int]      # per-policy HOST bytes per unit
    fused: bool = False

    def fixed_act_for(self, policies: Sequence[str]) -> int:
        """Depth-free activation residuals of a mixed plan: the heaviest
        policy present dominates (its segment keeps those residuals)."""
        return max(self.fixed_act_by_policy[p] for p in set(policies))

    @property
    def fixed_act_bytes(self) -> int:
        return max(self.fixed_act_by_policy.values())

    def device_total(self, policies: Sequence[str]) -> int:
        assert len(policies) == self.n_units, (len(policies), self.n_units)
        return (self.param_bytes + self.grad_bytes + self.opt_bytes
                + self.fixed_act_for(policies)
                + sum(self.unit_act_bytes[p] for p in policies))

    def host_total(self, policies: Sequence[str]) -> int:
        return sum(self.unit_host_bytes[p] for p in policies)


def _model_for(cfg: ModelConfig, n_units: int):
    from repro.models.model import Model
    kw = dict(num_layers=n_units * unit_layers_for(cfg))
    if cfg.num_layer_groups:
        # keep the layer-group layout valid at probe depths 1/2 (groups
        # must divide the depth); the depth-differenced ACTIVATION costs
        # are layout-insensitive — params are netted out exactly via the
        # probes' own spec trees
        import math
        kw["num_layer_groups"] = math.gcd(kw["num_layers"],
                                          cfg.num_layer_groups)
    return Model(cfg.replace(**kw))


def estimate(cfg: ModelConfig, batch: int, seq: int,
             optimizer: str = "adamw",
             policies: Sequence[str] = POLICIES,
             fused: bool = False) -> MemoryEstimate:
    """Build the per-layer cost model for ``cfg`` at microbatch (batch, seq)."""
    from repro.models.model import Model

    model = Model(cfg)
    aparams = model.abstract_params()
    param_bytes = array_bytes(aparams)
    n_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(aparams))

    opt = optimizer_by_name(optimizer)
    opt_bytes = array_bytes(jax.eval_shape(opt.init, aparams))
    # LoMo's donated update reuses one param-sized buffer; AdamW/GaLore
    # cast the full gradient tree to f32 before the moment update.
    grad_bytes = param_bytes if optimizer == "lomo" else 4 * n_params
    if fused:
        # optimizer-in-backward: only the non-stack remainder (embed / norms
        # / LM head / shared) plus ONE layer's slice of the heaviest main
        # stack are ever live as gradients
        per_layer_n = per_layer_b = main_n = main_b = 0
        for s in model.stacks:
            if s.role != "main":
                continue
            st = aparams["stacks"][s.name]
            cnt = sum(l.size for l in jax.tree_util.tree_leaves(st))
            byt = array_bytes(st)
            main_n += cnt
            main_b += byt
            if s.layout is not None:
                # grouped stack (DESIGN.md §14): one layer's {delta, per}
                # slice is live per iteration, but the base cotangent
                # accumulator — grouped shape, already 1/sharing-factor of
                # a flat stacked grad — rides the whole backward walk
                base = st["base"]
                bn = sum(l.size
                         for l in jax.tree_util.tree_leaves(base))
                bb = array_bytes(base)
                ln = (cnt - bn) // s.layout.n_layers + bn
                lb = (byt - bb) // s.layout.n_layers + bb
            else:
                ln, lb = cnt // s.n, byt // s.n
            per_layer_n = max(per_layer_n, ln)
            per_layer_b = max(per_layer_b, lb)
        grad_bytes = ((param_bytes - main_b) + per_layer_b
                      if optimizer == "lomo"
                      else 4 * ((n_params - main_n) + per_layer_n))

    # host bytes for an offloaded unit: its input streams (x1 + x2 = d_model
    # per token) for each model layer in the unit.
    act_itemsize = jnp.dtype(cfg.dtype).itemsize
    k = unit_layers_for(cfg)
    host_unit = batch * seq * cfg.d_model * act_itemsize * k

    # the standard (non-reversible) path has no inverse to exploit
    policies = [p for p in policies if p != "reversible" or cfg.reversible]

    m1, m2 = _model_for(cfg, 1), _model_for(cfg, 2)
    p1, p2 = array_bytes(m1.abstract_params()), array_bytes(m2.abstract_params())

    if "store" not in policies:
        policies = tuple(policies) + ("store",)

    unit_act: Dict[str, int] = {}
    unit_host: Dict[str, int] = {}
    fixed_act: Dict[str, int] = {}
    for pol in policies:
        r1 = residual_bytes(m1, batch, seq, save_memory=[pol] * n_plan_units(m1))
        r2 = residual_bytes(m2, batch, seq, save_memory=[pol] * n_plan_units(m2))
        per_unit = max(r2 - r1 - (p2 - p1), 0)
        fixed_act[pol] = max(r1 - per_unit * n_plan_units(m1) - p1, 0)
        if pol == "offload":
            unit_host[pol] = min(host_unit, per_unit)
            per_unit -= unit_host[pol]
        else:
            unit_host[pol] = 0
        unit_act[pol] = per_unit

    return MemoryEstimate(
        arch=cfg.name, family=cfg.family, batch=batch, seq=seq,
        optimizer=optimizer, n_units=n_plan_units(model), unit_layers=k,
        param_bytes=param_bytes, grad_bytes=grad_bytes, opt_bytes=opt_bytes,
        fixed_act_by_policy=fixed_act, unit_act_bytes=unit_act,
        unit_host_bytes=unit_host, fused=fused)


def residual_attribution(est: MemoryEstimate, policies: Sequence[str]):
    """Per-unit backward-residual device bytes of a mixed plan, in layer
    order — the byte attribution the layer auditor stamps into its
    ``layer_audit`` events (DESIGN.md §12).  Just ``unit_act_bytes`` keyed
    by each unit's policy; the depth-free residuals are a plan-level
    property (``fixed_act_for``) and not attributed to any single layer."""
    assert len(policies) == est.n_units, (len(policies), est.n_units)
    return [est.unit_act_bytes[p] for p in policies]


def moe_dispatch_cost(cfg: ModelConfig, batch: int, seq: int,
                      backend: Optional[str] = None,
                      block_m: int = 128) -> dict:
    """Analytic per-MoE-layer cost of the token-routing machinery alone —
    dispatch/combine FLOPs, bytes moved, backward residual bytes, and the
    row count fed to the expert GEMMs.  Expert-GEMM FLOPs themselves are
    excluded (equal work per executed row on either backend).

    ``einsum``: the dense one-hot dispatch/combine tensors are
    (G, group, E, C) f32 — quadratic in the group size — and both are
    backward residuals; the expert GEMMs run over G*E*C capacity rows
    (empty slots included, dropped tokens excluded).

    ``grouped`` (repro.kernels.moe): dispatch is a permutation — zero MAC
    FLOPs, one gather + one scatter of the token rows each way, int32 index
    vectors as the only dispatch residuals; the expert GEMMs run over
    exactly T*k assignment rows plus per-expert tile padding.

    The full residual story of a *model* under either backend needs no
    special-casing here: ``residual_bytes`` traces ``Model.loss`` with the
    config's ``moe_backend`` and picks the difference up automatically —
    this helper exists for `benchmarks/moe_dispatch.py` and planner docs.
    """
    import math as _math

    from repro.models import moe as moe_lib

    backend = backend or cfg.moe_backend
    T = batch * seq
    E = moe_lib.padded_experts(cfg.num_experts)
    k, d = cfg.top_k, cfg.d_model
    itemsize = jnp.dtype(cfg.dtype).itemsize

    # Byte accounting counts the same boundary for both backends: the token
    # rows moved into and out of expert space, plus whatever dispatch
    # structure the contraction has to stream.
    if backend == "einsum":
        g = min(moe_lib.GROUP, T)
        G = _math.ceil(T / g)
        C = moe_lib._capacity(g, E, k, cfg.capacity_factor)
        disp_elems = G * g * E * C                    # one-hot dispatch tensor
        row_traffic = 2 * (T + G * E * C) * d * itemsize   # in + out einsums
        return {
            "backend": "einsum",
            "dispatch_flops": 4 * disp_elems * d,     # dispatch + combine einsums
            "dispatch_bytes": 2 * disp_elems * 4 + row_traffic,
            "residual_bytes": 2 * disp_elems * 4,     # both saved for backward
            "expert_rows": G * E * C,
        }

    assert backend == "grouped", backend
    M = T * k
    from repro.kernels.moe.dispatch import round_up
    m_pad = round_up(M + E * (block_m - 1), block_m)
    n_tiles = m_pad // block_m
    row_traffic = ((M + m_pad) + (m_pad + T)) * d * itemsize  # gather + scatter
    return {
        "backend": "grouped",
        "dispatch_flops": 0,                          # permutation only
        "dispatch_bytes": row_traffic + (3 * M + n_tiles) * 4,  # + int32 indices
        "residual_bytes": 2 * M * 4 + n_tiles * 4,    # int32 order/dest + tile map
        "expert_rows": m_pad,
    }


def ep_a2a_cost(cfg: ModelConfig, batch: int, seq: int,
                ep: Optional[int] = None, block_m: int = 128) -> dict:
    """Analytic per-MoE-layer all-to-all cost of expert-parallel dispatch
    (kernels/moe/ep, DESIGN.md §10), per device.

    ``payload`` counts the token rows a ragged exchange puts on the wire:
    each device sends its Tl*k assignment rows out and receives the results
    back, so payload bytes scale exactly ∝ 1/EP in the per-device token
    share.  ``expected_wire`` scales that by the uniform-routing off-device
    fraction (1 - 1/ep); ``buffer`` is what the dense-a2a emulation on this
    JAX moves instead (static worst-case per-peer capacity — see the module
    docstring of kernels/moe/ep for why).  ``local_gemm_rows`` is the padded
    row count each device's grouped GEMMs run over.  Figures are per data
    replica: when the token dim additionally shards over (pod, data), each
    device carries 1/data_shards of every quantity here.
    """
    from repro.kernels.moe.dispatch import round_up
    from repro.kernels.moe.ep import validate_ep
    from repro.models import moe as moe_lib

    ep = ep or cfg.expert_parallel or 1
    T = batch * seq
    E = moe_lib.padded_experts(cfg.num_experts)
    validate_ep(E, T, ep, num_experts_raw=cfg.num_experts)
    El, Tl = E // ep, T // ep
    k, d = cfg.top_k, cfg.d_model
    itemsize = jnp.dtype(cfg.dtype).itemsize
    M = Tl * k                                   # per-device assignment rows
    payload = 2 * M * d * itemsize               # rows out + results back
    off_frac = 1.0 - 1.0 / ep
    return {
        "ep": ep,
        "local_experts": El,
        "rows_per_device": M,
        "a2a_payload_bytes": payload,
        "a2a_expected_wire_bytes": int(payload * off_frac),
        "a2a_buffer_bytes": 2 * ep * M * d * itemsize,
        "local_gemm_rows": round_up(ep * M + El * (block_m - 1), block_m),
    }


def attention_backward_cost(cfg: ModelConfig, batch: int, seq: int,
                            causal: bool = True,
                            window: Optional[int] = None,
                            block_q: Optional[int] = None,
                            block_k: Optional[int] = None) -> dict:
    """Analytic per-attention-layer backward cost for the two backward
    strategies behind ``flash_attention_trainable`` (DESIGN.md §8):

      ``dense``: the reference-vjp backward — residuals are (q, k, v); the
      backward re-runs the dense reference under ``jax.vjp``, materialising
      the f32 (B, H, S, S) score AND probability tensors as transients.

      ``flash``: the flash backward kernels — residuals are (q, k, v, o,
      lse), O(S) per head; transients are the per-core VMEM tile working set
      (score/prob/cotangent tiles + row accumulators), independent of S.

    ``window`` defaults to ``cfg.sliding_window``.  FLOPs count MACs*2 of the
    S x S x hd contractions, scaled by the live-tile fraction for the flash
    path (dead tiles are skipped; the dense path computes everything).
    """
    itemsize = jnp.dtype(cfg.dtype).itemsize
    H, KV, hd, S = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, seq
    if window is None:
        window = cfg.sliding_window
    bq = min(block_q or cfg.flash_block_q, S)
    bk = min(block_k or cfg.flash_block_k, S)

    q_bytes = batch * H * S * hd * itemsize
    kv_bytes = batch * KV * S * hd * itemsize
    scores_f32 = batch * H * S * S * 4
    mm = 2 * batch * H * S * S * hd          # one full S x S x hd contraction

    # fraction of (bq, bk) tiles that survive dead-tile skipping
    live = 1.0
    if causal:
        live = min(0.5 + bk / (2 * S), 1.0)
    if window is not None:
        live = min(live, (window + bq + bk) / S, 1.0)

    dense = {
        "residual_bytes": q_bytes + 2 * kv_bytes,
        # recomputed scores + probs (both f32, both alive at once in the vjp)
        "transient_bytes": 2 * scores_f32,
        # fwd recompute (2 mm) + dv/dp/dq/dk backward contractions (4 mm)
        "flops": 6 * mm,
    }
    # VMEM tile working set: s/p/dp/ds f32 tiles, q/do/k/v row tiles, the
    # dq or dk+dv accumulators, lse/delta rows; x2 for pipeline buffering
    tile_bytes = (4 * bq * bk + 3 * bq * hd + 4 * bk * hd
                  + 2 * (bq + bk)) * 4 * 2
    flash = {
        "residual_bytes": 2 * q_bytes + 2 * kv_bytes + batch * H * S * 4,
        "transient_bytes": tile_bytes,
        # dq pass: s/dp/dq (3 mm); dkv pass: s/dv/dp/dk (4 mm); live only
        "flops": int(7 * mm * live),
    }
    return {"seq": S, "batch": batch, "block_q": bq, "block_k": bk,
            "live_tile_fraction": live, "dense": dense, "flash": flash}


def kv_page_cost(cfg: ModelConfig, page_size: int = 16,
                 seq: int = 4096) -> dict:
    """Serving paged-KV cost model (DESIGN.md §15): bytes per physical page
    across every KV-carrying layer, pages per sequence at the serving
    context length, and the dense-slot bytes the page pool replaces.

    The serving engine sizes its pool from this (``kv_budget_gb``), and the
    dryrun plan surfaces it next to the ``attn_bwd`` / ``moe_ep`` lines so
    the serve-time KV budget is decided from the same report as the train
    plan.  Per-token KV bytes = L * 2 (k+v) * KV_heads * head_dim *
    itemsize; each page also stores its int32 positions (validity /
    causal-mask source), which is what lets freed pages be remapped without
    a device-side reset pass.
    """
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if cfg.family == "hybrid" and cfg.attn_period:
        L = cfg.num_layers // cfg.attn_period    # shared attn block layers
    else:
        L = cfg.num_layers
    token_bytes = L * 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
    page_bytes = page_size * token_bytes + L * page_size * 4
    ctx = min(seq, cfg.sliding_window) if cfg.sliding_window else seq
    pages_per_seq = -(-ctx // page_size)
    dense_slot_bytes = ctx * token_bytes + L * ctx * 4
    return {
        "page_size": page_size,
        "kv_layers": L,
        "token_bytes": token_bytes,
        "page_bytes": page_bytes,
        "ctx_len": ctx,
        "pages_per_seq": pages_per_seq,
        "seq_bytes": pages_per_seq * page_bytes,
        "dense_slot_bytes": dense_slot_bytes,
        "pages_per_gib": int(GiB // page_bytes),
    }


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks: utilisation and roofline denominators."""
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    source: str


#: peaks keyed by ``jax.Device.device_kind``.  A device that is not here has
#: no peak: ``device_peaks`` raises instead of guessing one.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9,
        source="Google Cloud documentation, 'TPU v5e'"),
}


def device_peaks(device=None) -> DevicePeaks:
    """Peaks of ``device`` (default: device 0).  CPU runs report counts
    only, so a CPU device — like any kind missing from ``DEVICE_PEAKS`` —
    is an error."""
    device = device if device is not None else jax.devices()[0]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform}); known: "
            f"{sorted(DEVICE_PEAKS)}") from None


def device_memory_stats() -> Optional[dict]:
    """Live allocator stats of device 0 (None on backends without them, e.g.
    CPU) — the runtime cross-check for the static estimates."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001
        return None
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    return {key: stats[key] for key in keep if key in stats}
