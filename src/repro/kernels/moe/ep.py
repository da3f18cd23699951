"""Expert-parallel MoE dispatch over a dedicated mesh axis (DESIGN.md §10).

Experts shard over the mesh's ``"expert"`` axis (E/ep per device) and tokens
shard over the same axis for dispatch.  Inside a ``shard_map`` each device:

  1. flattens its local (token, k) assignments, computes each assignment's
     destination shard (``global_expert_id // local_experts``), and packs the
     token rows into an expert-shard-major send buffer with a sort-based
     plan — the same stable-argsort machinery as the single-device grouped
     path (repro.kernels.moe.dispatch), keyed by shard instead of expert;
  2. exchanges the buffers with ``jax.lax.all_to_all`` — a *ragged* exchange
     emulated over a static-capacity layout: per-peer send counts come from
     the pack plan, live rows sit at the front of each peer block, and the
     tail is zero padding (``lax.ragged_all_to_all`` could take the same
     counts/layout and shrink the wire bytes to them);
  3. runs the PR-2 grouped GEMMs over its LOCAL experts on the received
     rows (top_k=1 plan over local expert ids — padding rows hit expert 0
     with zero inputs and are never read back);
  4. reverses the all-to-all (the exchange is an involution: block ``s`` of
     the return buffer is exactly this device's block ``s`` processed) and
     gate-combines per token in f32, matching ``dispatch.combine``.

``ep_expert_ffn`` wraps the whole thing in a ``custom_vjp`` whose residuals
are ONLY the per-device inputs (tokens, routing, local expert weights): the
backward re-runs the shard_map forward under ``jax.vjp``, so the cotangent
all-to-alls are the forward exchanges reversed and nothing buffer-sized is
stored across the forward/backward gap.  This composes with the reversible
stack's recompute-in-backward exactly like the single-device grouped path:
per-block residency stays O(local tokens), never O(global tokens).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.moe import dispatch as dsp
from repro.kernels.moe.ops import default_block_m, default_impl, grouped_matmul

EP_AXIS = "expert"


def validate_ep(num_experts: int, num_tokens: int, ep: int,
                num_experts_raw: Optional[int] = None,
                token_shards: Optional[int] = None):
    """Actionable divisibility errors, raised at trace time (before any
    reshape/psum inside the shard_map can fail with a raw XLA error).
    ``token_shards`` is the total token-dim sharding (data axes × ep);
    defaults to ``ep`` when the caller has no mesh at hand."""
    raw = num_experts_raw if num_experts_raw is not None else num_experts
    if ep < 1:
        raise ValueError(f"expert_parallel={ep} must be >= 1")
    if num_experts % ep != 0:
        pad_note = (f" (num_experts={raw} padded to {num_experts})"
                    if num_experts != raw else "")
        raise ValueError(
            f"num_experts={raw}{pad_note} is not divisible by the expert-"
            f"parallel size ep={ep}: each device must own an equal slice of "
            f"the expert axis. Pick ep dividing {num_experts} or adjust "
            f"num_experts.")
    shards = token_shards or ep
    if num_tokens % shards != 0:
        note = (f" (ep={ep} x {shards // ep} data shards)"
                if shards != ep else f" ep={ep}")
        raise ValueError(
            f"token count {num_tokens} (batch*seq) is not divisible by the "
            f"token-dispatch sharding {shards}{note}: tokens shard over the "
            f"data axes and the '{EP_AXIS}' mesh axis for dispatch. Pad the "
            f"batch or pick a dividing ep.")


def _pack_plan(dest_shard, ep: int, cap: int):
    """Shard-major pack plan: ``slot[m]`` is assignment ``m``'s row in the
    (ep * cap) send buffer (destination-shard block, then arrival rank);
    ``counts[s]`` is the ragged send count for peer ``s``."""
    M = dest_shard.shape[0]
    order = jnp.argsort(dest_shard, stable=True).astype(jnp.int32)
    sorted_s = dest_shard[order]
    counts = jnp.zeros(ep, jnp.int32).at[dest_shard].add(1)
    zero = jnp.zeros((1,), jnp.int32)
    start = jnp.concatenate([zero, jnp.cumsum(counts)])[:ep]
    rank = jnp.arange(M, dtype=jnp.int32) - start[sorted_s]
    pos = sorted_s * cap + rank
    slot = jnp.zeros(M, jnp.int32).at[order].set(pos, unique_indices=True)
    return slot, counts


def _ep_ffn_shard(xs, expert_idx, gates, w_gate, w_up, w_down, *,
                  ep: int, axis: str, block_m: int, impl: str,
                  tp: Optional[str] = None):
    """Per-device body (runs under shard_map over ``axis``).

    xs: (Tl, d) local tokens; expert_idx/gates: (Tl, k) GLOBAL expert ids;
    w_gate/w_up: (El, d, f) local experts; w_down: (El, f, d).  With ``tp``
    the expert ffn dim f is additionally sharded over that mesh axis (the
    GEMMs see f/tp columns; the down-projection is a partial sum psum'd over
    ``tp``) so TP-sharded expert weights are never gathered at the shard_map
    boundary.  Returns (Tl, d).
    """
    Tl, d = xs.shape
    k = expert_idx.shape[1]
    El = w_gate.shape[0]
    M = Tl * k
    # per-peer capacity: Tl*k is the droplessness bound (every local
    # assignment routed to one peer).  The all_to_all moves the full
    # (ep, cap, d) layout; the counts below are what a ragged
    # exchange would put on the wire.
    cap = M
    flat_e = expert_idx.reshape(M).astype(jnp.int32)
    dshard = flat_e // El
    slot, _counts = _pack_plan(dshard, ep, cap)
    src = jnp.arange(M, dtype=jnp.int32) // k

    send = jnp.zeros((ep * cap, d), xs.dtype).at[slot].set(
        xs[src], unique_indices=True)
    # local expert id rides along; padding slots keep 0 and compute expert 0
    # on zero rows (zero output, never read back by the unpack gather)
    send_eid = jnp.zeros((ep * cap,), jnp.int32).at[slot].set(
        flat_e - dshard * El, unique_indices=True)

    recv = jax.lax.all_to_all(send.reshape(ep, cap, d), axis, 0, 0)
    recv_eid = jax.lax.all_to_all(send_eid.reshape(ep, cap), axis, 0, 0)

    rows = recv.reshape(ep * cap, d)
    plan = dsp.make_plan(recv_eid.reshape(ep * cap, 1), El, block_m)
    rows_p = dsp.permute(rows, plan)
    g = grouped_matmul(rows_p, w_gate, plan.tile_expert, block_m, impl)
    u = grouped_matmul(rows_p, w_up, plan.tile_expert, block_m, impl)
    h = jax.nn.silu(g) * u
    ys_p = grouped_matmul(h, w_down, plan.tile_expert, block_m, impl)
    if tp is not None:
        # f was sharded over ``tp``: each shard's down-projection is a
        # partial sum over its f/tp slice
        ys_p = jax.lax.psum(ys_p, tp)
    # un-permute to recv-row order (top_k=1 combine with unit gates)
    ys_rows = dsp.combine(ys_p, jnp.ones((ep * cap, 1), rows.dtype),
                          plan, ep * cap)

    # reverse exchange: my block s of ``ret`` is my send block s, processed
    ret = jax.lax.all_to_all(ys_rows.reshape(ep, cap, d), axis, 0, 0)
    contrib = ret.reshape(ep * cap, d)[slot]
    # f32 accumulation across the k contributions, rounded once — matching
    # dispatch.combine so EP output is bit-comparable to the grouped backend
    y = jnp.zeros((Tl, d), jnp.float32).at[src].add(
        contrib.astype(jnp.float32)
        * gates.reshape(M, 1).astype(jnp.float32))
    return y.astype(xs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ep_apply(smapped, x, expert_idx, gates, w_gate, w_up, w_down):
    return smapped(x, expert_idx, gates, w_gate, w_up, w_down)


def _ep_fwd(smapped, x, expert_idx, gates, w_gate, w_up, w_down):
    y = smapped(x, expert_idx, gates, w_gate, w_up, w_down)
    # residuals: the inputs only — O(local tokens) per device, no a2a buffer
    return y, (x, expert_idx, gates, w_gate, w_up, w_down)


def _ep_bwd(smapped, res, ct):
    x, expert_idx, gates, w_gate, w_up, w_down = res
    _, vjp = jax.vjp(
        lambda x_, g_, a, b, c: smapped(x_, expert_idx, g_, a, b, c),
        x, gates, w_gate, w_up, w_down)
    dx, dg, dwg, dwu, dwd = vjp(ct)
    d_idx = np.zeros(expert_idx.shape, jax.dtypes.float0)
    return dx, d_idx, dg, dwg, dwu, dwd


_ep_apply.defvjp(_ep_fwd, _ep_bwd)


def ep_expert_ffn(x, expert_idx, gates, w_gate, w_up, w_down, mesh: Mesh, *,
                  axis: str = EP_AXIS,
                  block_m: Optional[int] = None,
                  impl: Optional[str] = None):
    """Expert-parallel dropless SwiGLU expert FFN.

    x: (T, d); expert_idx/gates: (T, k); w_gate/w_up: (E, d, f);
    w_down: (E, f, d).  ``mesh`` must carry the ``axis`` axis; its size is
    the EP degree.  Returns (T, d) = sum_k gate * expert_k(x), numerically
    matching ``grouped_expert_ffn`` (same permute/GEMM/f32-combine chain).
    """
    if axis not in mesh.axis_names:
        raise ValueError(
            f"expert-parallel dispatch needs a '{axis}' mesh axis; mesh has "
            f"{mesh.axis_names}. Build it with make_debug_mesh(..., "
            f"expert=N) / make_production_mesh(..., expert=N).")
    ep = mesh.shape[axis]
    E, _d, f = w_gate.shape
    # tokens shard over the data axes TOO — only "expert" carries the
    # all-to-all, but leaving the data axes off the token spec would gather
    # the global batch and replicate every device's expert GEMMs data-ways
    tok_axes = tuple(a for a in mesh.axis_names
                     if a in ("pod", "data") or a == axis)
    shards = 1
    for a in tok_axes:
        shards *= mesh.shape[a]
    validate_ep(E, x.shape[0], ep, token_shards=shards)
    block_m = block_m or default_block_m()
    impl = impl or default_impl()

    # expert-ffn tensor parallelism: when the mesh has a "model" axis that
    # divides f, keep the weights' f dim sharded over it inside the region
    # (partial down-projections psum over it) instead of letting the
    # replicated in_spec all-gather TP-sharded expert weights every call
    tp = None
    if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
            and f % mesh.shape["model"] == 0:
        tp = "model"
    body = functools.partial(_ep_ffn_shard, ep=ep, axis=axis,
                             block_m=block_m, impl=impl, tp=tp)
    tok = P(tok_axes)
    w_in, w_out = P(axis, None, tp), P(axis, tp, None)
    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok, tok, tok, w_in, w_in, w_out),
        out_specs=tok, check_vma=False)
    return _ep_apply(smapped, x, expert_idx, gates, w_gate, w_up, w_down)


def ep_dispatch_stats(expert_idx, num_experts: int, ep: int,
                      d_model: int, itemsize: int) -> dict:
    """Measured per-device dispatch traffic of one routed batch (host-side
    diagnostic for benchmarks; not part of the jitted path).

    Replays the production ``_pack_plan`` on each token shard's slice of the
    real routing, so the per-peer send counts are exactly what the dispatch
    packs — a regression that drops or duplicates rows shows up here, not
    just in parity.  Returns per-device payload rows/bytes (what a ragged
    exchange puts on the wire, send + return), the measured off-device
    fraction, and the static buffer bytes the dense-a2a emulation moves
    instead.
    """
    idx = np.asarray(expert_idx)
    T, k = idx.shape
    validate_ep(num_experts, T, ep)
    El = num_experts // ep
    Tl = T // ep
    cap = Tl * k
    rows = off = 0
    send_counts = np.zeros((ep, ep), np.int64)   # [src shard, dest shard]
    for s in range(ep):
        flat = jnp.asarray(idx[s * Tl:(s + 1) * Tl].reshape(-1),
                           dtype=jnp.int32)
        _slot, counts = _pack_plan(flat // El, ep, cap)
        counts = np.asarray(counts)
        assert int(counts.sum()) == cap, (int(counts.sum()), cap)
        send_counts[s] = counts
        rows += int(counts.sum())
        off += int(counts.sum() - counts[s])
    rows_per_dev = rows // ep
    off_frac = off / rows if rows else 0.0
    payload = 2 * rows_per_dev * d_model * itemsize          # send + return
    return {
        "ep": ep,
        "rows_per_device": rows_per_dev,
        "payload_bytes_per_device": payload,
        "offdevice_fraction": off_frac,
        "wire_bytes_per_device": int(payload * off_frac),
        "buffer_bytes_per_device": 2 * ep * rows_per_dev * d_model * itemsize,
        # per-(source, dest) ragged send counts: the routing-telemetry path
        # (obs/audit) reports these so a skewed expert placement is visible
        # as a hot destination column, not just a worse aggregate fraction
        "send_counts": send_counts.tolist(),
    }
