"""Logical-axis -> mesh-axis sharding rules with divisibility fallback.

Parameters carry logical axis names (see repro.models.spec).  ``param_pspecs``
maps them onto the physical mesh:

  embed (d_model dims)          -> FSDP axes ("pod","data") — ZeRO-3 style
  heads / kv_heads / mlp / ...  -> "model" (tensor parallel)
  experts                       -> "expert" (explicit EP axis, DESIGN.md §10)
                                   when the mesh has one, else "model"
  vocab                         -> "model"
  layers / None                 -> replicated

A mesh axis is dropped for a given tensor dimension when (a) the mesh does
not have it (no "expert" axis without EP, no "model" axis on a pure-FSDP
mesh), (b) it is trivial (size 1 — sharding over it is replication, and
assigning it would shadow a later candidate that actually splits), (c) it
does not divide the dimension (e.g. whisper's vocab 51865, GQA
kv_heads < 16), or (d) it is already used by another dimension of the same
tensor (e.g. expert ffn dim when the expert dim already took "model").
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# candidate mesh-axis groups per logical axis, in priority order.
# "fsdp" expands to the mesh's data axes (("pod","data") or ("data",)).
RULES = {
    "embed": ("fsdp",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("expert", "model"),
    "expert_mlp": ("model", "fsdp"),
    "vocab": ("model",),
    "stream": ("model",),
    "embed_out": ("model",),
    "layers": (),
    "groups": (),   # lean layer-group dim (DESIGN.md §14): like "layers",
                    # never sharded — ZeRO-3/TP/EP apply to the inner dims
                    # of the deduplicated base leaves exactly as flat
    None: (),
}


# HSDP (perf iteration, EXPERIMENTS.md §Perf): shard parameters over "data"
# only and replicate across pods, so per-microbatch FSDP all-gathers stay on
# intra-pod ICI; the only cross-pod traffic is one gradient all-reduce per
# step (which GSPMD inserts because grads psum over the replicated axis).
HSDP: bool = False

# Serving rules (perf iteration, EXPERIMENTS.md §Perf): weights TP-only
# (replicated over the data axes) so decode never re-gathers parameters —
# they stay HBM-resident.  Only valid when params_bytes/TP fits per-device.
SERVE_TP_ONLY: bool = False


def fsdp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """ZeRO-3 parameter-sharding axes.  The "expert" axis (carved out of
    the data dimension, DESIGN.md §10) participates: its device groups are
    data replicas for everything outside the MoE dispatch, so excluding it
    would multiply every non-expert param shard (and batch compute) by EP.
    ``spec_for`` drops already-used axes from the expansion, so MoE expert
    weights — whose expert dim takes "expert" itself — still shard their
    embed dims over the remaining (pod, data)."""
    if SERVE_TP_ONLY:
        return ()
    axes = tuple(a for a in mesh.axis_names
                 if a in ("pod", "data", "expert"))
    if HSDP:
        axes = tuple(a for a in axes if a != "pod")
    return axes


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Batch sharding axes — always includes the pod axis (even under HSDP)
    and the "expert" axis when present (tokens re-shard onto it inside the
    MoE layer's shard_map; everywhere else it behaves as data parallelism)."""
    return tuple(a for a in mesh.axis_names
                 if a in ("pod", "data", "expert"))


def _expand(cand: str, mesh: Mesh):
    if cand == "fsdp":
        return fsdp_axes(mesh)
    return (cand,) if cand in mesh.axis_names else ()


def _axis_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def spec_for(shape: Sequence[int], logical: Sequence[Optional[str]],
             mesh: Mesh) -> P:
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        assigned = None
        for cand in RULES.get(name, ()):
            # drop axes another dim of this tensor already took (partial
            # fsdp expansions stay useful: expert weights shard embed dims
            # over (pod, data) after the expert dim consumed "expert")
            axes = tuple(a for a in _expand(cand, mesh) if a not in used)
            if not axes:
                continue
            if _axis_size(mesh, axes) == 1:
                # trivial axis (e.g. the size-1 "expert" axis of an EP=1
                # mesh): sharding over it is replication — skip so a later
                # candidate that actually splits can take the dim
                continue
            if dim % _axis_size(mesh, axes) != 0:
                continue
            assigned = axes if len(axes) > 1 else axes[0]
            used.update(axes)
            break
        out.append(assigned)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_pspecs(logical_tree, shape_tree, mesh: Mesh):
    """Tree of PartitionSpec from (logical-axes tree, ShapeDtypeStruct tree)."""
    return jax.tree_util.tree_map(
        lambda ax, sds: spec_for(sds.shape, ax, mesh),
        logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def param_shardings(logical_tree, shape_tree, mesh: Mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_pspecs(logical_tree, shape_tree, mesh))


# ------------------------------------------------------------ activations

def batch_pspec(mesh: Mesh, batch_size: int, ndim: int = 2,
                dim1: Optional[int] = None) -> P:
    """Shard the batch dim over FSDP axes when divisible, else fall back to
    sequence sharding (dim 1) for batch-1 long-context shapes (only when that
    dim is divisible too — a (1,1) decode token stays replicated)."""
    fa = data_axes(mesh)
    if not fa:
        # model-only TP mesh (e.g. SERVE_TP_ONLY serving pods): nothing to
        # shard the batch over — replicate instead of indexing an empty tuple
        return P()
    sz = _axis_size(mesh, fa)
    faxis = fa if len(fa) > 1 else fa[0]
    if batch_size % sz == 0:
        return P(faxis, *(None,) * (ndim - 1))
    if ndim >= 2 and dim1 is not None and dim1 % sz == 0 and dim1 >= sz:
        return P(None, faxis, *(None,) * (ndim - 2))
    return P()


def cache_pspecs(cache_shapes, mesh: Mesh, batch_size: int,
                 kv_heads: int = 0):
    """Decode-cache shardings: batch over the data axes if divisible, else the
    longest (sequence) dim; the kv-heads dim over "model" ONLY when it matches
    ``kv_heads`` exactly and divides — never head_dim or other vector dims
    (a mismatched cache sharding makes GSPMD replicate the whole buffer on
    every decode step: the "involuntary full rematerialization" trap)."""
    fa = data_axes(mesh)
    fsz = _axis_size(mesh, fa)
    # model-only TP mesh: no data axes to place the batch on — keep faxis
    # None and let the kv-heads / "model" fallback below do the sharding
    faxis = (fa if len(fa) > 1 else fa[0]) if fa else None
    msz = mesh.shape.get("model", 1)

    def one(sds):
        shape = sds.shape
        if not shape:
            return P()
        out = [None] * len(shape)
        if faxis is not None:
            used_f = False
            # stacked cache leaves: (n_units, B, seq, kv, hd) or (B, seq, ...)
            # etc.  find batch dim: first dim equal to batch_size after the
            # stack dim
            for i, d in enumerate(shape):
                if d == batch_size and batch_size % fsz == 0:
                    out[i] = faxis
                    used_f = True
                    break
            if not used_f:
                # shard the largest dim over the data axes (the sequence buffer)
                big = max(range(len(shape)), key=lambda i: shape[i])
                if shape[big] % fsz == 0 and shape[big] >= fsz * 8:
                    out[big] = faxis
        if kv_heads and msz > 1 and kv_heads % msz == 0:
            for i, d in enumerate(shape):
                if out[i] is None and d == kv_heads:
                    out[i] = "model"
                    break
        elif msz > 1:
            # kv heads don't divide the model axis (GQA kv < TP): shard the
            # sequence buffer over "model" instead so the cache still fits
            big = max(range(len(shape)), key=lambda i: shape[i])
            if (out[big] is None and shape[big] % msz == 0
                    and shape[big] >= msz * 8):
                out[big] = "model"
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    return jax.tree_util.tree_map(one, cache_shapes)
