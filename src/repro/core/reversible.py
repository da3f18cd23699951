"""RevFFN reversible blocks and the O(1)-activation-memory stack.

The paper's coupled update (Eqs. 1-2):

    Y1 = X1 + F(X1, X2)        F = cross-branch attention (Q from X1, K/V from X2)
    Y2 = X2 + G(Y1)            G = MLP or MoE

with inverse

    X2 = Y2 - G(Y1)
    X1 = Y1 - F(X1, X2)        (fixed point in X1; paper runs 1 iteration seeded at Y1)

``coupling="standard"`` is the RevNet form where F depends only on X2, making
the inverse exact in one step — used for attention-free token mixers
(RWKV6 / Mamba2, see DESIGN.md §4).

``reversible_stack`` wraps a scan over blocks in a ``jax.custom_vjp`` whose
residuals are ONLY (params, final outputs): the backward pass reconstructs each
block's input by inversion and re-runs one block at a time under ``jax.vjp``.
Peak activation memory is therefore O(one block), independent of depth — this
is the paper's memory claim, realised JAX-natively.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import scopes


def coupling(F: Callable, target: int, fp_iters: int = 1):
    """One reversible additive update of a single stream.

    F(params, shared, ctx, i, x1, x2) -> delta added to stream ``target``.

    ``fp_iters == 1`` asserts F does not depend on the target stream (exact
    inverse, RevNet "standard" coupling).  ``fp_iters > 1`` allows the paper's
    cross form where F reads the stream it updates (Q from X1 while updating
    X1): the inverse runs that many fixed-point iterations seeded at Y.
    """
    assert target in (1, 2)

    def fwd(p, sh, ctx, i, x1, x2):
        if target == 1:
            return x1 + F(p, sh, ctx, i, x1, x2), x2
        return x1, x2 + F(p, sh, ctx, i, x1, x2)

    def inv(p, sh, ctx, i, y1, y2):
        if target == 1:
            x1 = y1                                  # paper: seed at Y1
            for _ in range(max(fp_iters, 1)):
                x1 = y1 - F(p, sh, ctx, i, x1, y2)
            return x1, y2
        x2 = y2
        for _ in range(max(fp_iters, 1)):
            x2 = y2 - F(p, sh, ctx, i, y1, x2)
        return y1, x2

    return fwd, inv


def make_coupled(F: Callable, G: Callable, *, mode: str = "cross",
                 fp_iters: int = 3):
    """Paper Eqs. 1-2: Y1 = X1 + F(X1, X2); Y2 = X2 + G(Y1).

    mode="cross": F reads X1 (queries) -> fixed-point inverse (paper).
    mode="standard": F must ignore X1 -> exact inverse (RevNet form, used for
    attention-free mixers per DESIGN.md §4).
    """
    it = fp_iters if mode == "cross" else 1
    return chain(coupling(F, 1, it), coupling(G, 2, 1))


def chain(*pairs):
    """Compose bijections: fwd applies in order, inv in reverse order."""
    def fwd(p, sh, ctx, i, x1, x2):
        for f, _ in pairs:
            x1, x2 = f(p, sh, ctx, i, x1, x2)
        return x1, x2

    def inv(p, sh, ctx, i, y1, y2):
        for _, g in reversed(pairs):
            y1, y2 = g(p, sh, ctx, i, y1, y2)
        return y1, y2

    return fwd, inv


def _zeros_tangent(tree):
    """float0 zero-cotangents for nondiff (integer) pytrees."""
    def z(x):
        if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
            return jnp.zeros_like(x)
        return np.zeros(jnp.shape(x), jax.dtypes.float0)
    return jax.tree_util.tree_map(z, tree)


def zero_shared(shared):
    """Zero cotangent accumulator for a shared tree: zeros for inexact
    leaves, ``None`` placeholders for integer leaves (filled to float0 by
    ``shared_cotangent`` once accumulation is done)."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.result_type(x))
        if jnp.issubdtype(jnp.result_type(x), jnp.inexact) else None, shared)


def accumulate_shared(csh, dsh):
    """csh += dsh, skipping the ``None`` (integer-leaf) placeholders."""
    return jax.tree_util.tree_map(
        lambda a, b: a + b if a is not None else None, csh, dsh,
        is_leaf=lambda x: x is None)


def shared_cotangent(csh, shared):
    """Replace ``None`` placeholders with float0 zeros so the accumulated
    shared cotangent is a valid vjp input/output."""
    return jax.tree_util.tree_map(
        lambda z, s: z if z is not None
        else np.zeros(jnp.shape(s), jax.dtypes.float0),
        csh, shared, is_leaf=lambda x: x is None)


def layer_vjp(f: Callable, primals, cts):
    """Cotangents of ``f``'s inputs: the forward that ``jax.vjp`` linearises
    runs under ``rev.recompute``, the vjp applied to ``cts`` under
    ``rev.vjp`` (the per-layer step of every backward walk)."""
    with jax.named_scope(scopes.REV_RECOMPUTE):
        _, vjp = jax.vjp(f, *primals)
    with jax.named_scope(scopes.REV_VJP):
        return vjp(cts)


def reversible_stack(block_fwd: Callable, block_inv: Callable, n_layers: int,
                     save_memory=True, half_inv: Callable = None,
                     idx_offset: int = 0):
    """Return apply(stacked_params, shared, ctx, x1, x2) -> (y1, y2).

    ``stacked_params``: pytree with leading dim n_layers (scanned).
    ``shared``: differentiable tree shared across layers (e.g. encoder output,
    image embeddings, shared attention weights); cotangents accumulate.
    ``ctx``: non-differentiable tree (positions, indices).

    save_memory:
      True   — paper mode: O(1) activations, fixed-point inversion of the
               cross coupling during backward.
      "half" — beyond-paper semi-reversible mode (EXPERIMENTS.md §Perf):
               save stream-1 inputs per layer (d/2 activations).  Then layer
               k's output y1 equals layer k+1's saved x1, so the backward
               needs only the EXACT closed-form ``half_inv``
               (x2 = y2 - G(y1)) — no fixed point, no F re-evaluations,
               and gradients are exact regardless of inverse_fp_iters.
      False  — plain scan (XLA default AD, full caching): the SFT baseline.
    """
    from repro.core import settings
    idxs = idx_offset + jnp.arange(n_layers, dtype=jnp.int32)

    def plain(stacked, shared, ctx, x1, x2):
        def body(carry, inp):
            i, lp = inp
            return block_fwd(lp, shared, ctx, i, *carry), None
        (y1, y2), _ = jax.lax.scan(body, (x1, x2), (idxs, stacked),
                                   unroll=settings.SCAN_UNROLL)
        return y1, y2

    if save_memory is False:
        return plain

    if save_memory == "half":
        assert half_inv is not None, "half mode needs a half_inv callable"
        return _half_stack(block_fwd, half_inv, n_layers, plain, idxs)

    @jax.custom_vjp
    def apply(stacked, shared, ctx, x1, x2):
        with jax.named_scope(scopes.WALK_FORWARD):
            return plain(stacked, shared, ctx, x1, x2)

    def fwd_rule(stacked, shared, ctx, x1, x2):
        with jax.named_scope(scopes.WALK_FORWARD):
            y1, y2 = plain(stacked, shared, ctx, x1, x2)
        # residuals: params + OUTPUT only — no per-layer activations
        return (y1, y2), (stacked, shared, ctx, y1, y2)

    def bwd_rule(res, cts):
        stacked, shared, ctx, y1, y2 = res
        ct1, ct2 = cts

        def body(carry, inp):
            i, lp = inp
            cy1, cy2, c1, c2, csh = carry
            with jax.named_scope(scopes.REV_INVERT):
                x1, x2 = block_inv(lp, shared, ctx, i, cy1, cy2)
            x1 = jax.lax.stop_gradient(x1)
            x2 = jax.lax.stop_gradient(x2)
            dlp, dsh, d1, d2 = layer_vjp(
                lambda lp_, sh_, a, b: block_fwd(lp_, sh_, ctx, i, a, b),
                (lp, shared, x1, x2), (c1, c2))
            return (x1, x2, d1, d2, accumulate_shared(csh, dsh)), dlp

        init = (y1, y2, ct1, ct2, zero_shared(shared))
        from repro.core import settings as _s
        with jax.named_scope(scopes.WALK_UPDATE):
            (_, _, d1, d2, dsh), dstacked = jax.lax.scan(
                body, init, (idxs, stacked), reverse=True,
                unroll=_s.SCAN_UNROLL)
        return (dstacked, shared_cotangent(dsh, shared),
                _zeros_tangent(ctx), d1, d2)

    apply.defvjp(fwd_rule, bwd_rule)
    return apply


def _half_stack(block_fwd, half_inv, n_layers, plain, idxs):
    """Semi-reversible stack: residuals = stream-1 inputs per layer only."""

    @jax.custom_vjp
    def apply(stacked, shared, ctx, x1, x2):
        with jax.named_scope(scopes.WALK_FORWARD):
            return plain(stacked, shared, ctx, x1, x2)

    def fwd_rule(stacked, shared, ctx, x1, x2):
        from repro.core import settings

        def body(carry, inp):
            i, lp = inp
            a, b = carry
            return block_fwd(lp, shared, ctx, i, a, b), a   # save x1 input
        with jax.named_scope(scopes.WALK_FORWARD):
            (y1, y2), x1_stack = jax.lax.scan(body, (x1, x2),
                                              (idxs, stacked),
                                              unroll=settings.SCAN_UNROLL)
        return (y1, y2), (stacked, shared, ctx, x1_stack, y1, y2)

    def bwd_rule(res, cts):
        from repro.core import settings
        stacked, shared, ctx, x1_stack, y1_fin, y2_fin = res
        ct1, ct2 = cts
        # y1 of layer k == x1 input of layer k+1 (saved); last layer: y1_fin
        y1_stack = jnp.concatenate([x1_stack[1:], y1_fin[None]], axis=0)

        def body(carry, inp):
            i, lp, x1_k, y1_k = inp
            y2_k, c1, c2, csh = carry
            with jax.named_scope(scopes.REV_INVERT):
                x2_k = jax.lax.stop_gradient(
                    half_inv(lp, shared, ctx, i, x1_k, y1_k, y2_k))
            dlp, dsh, d1, d2 = layer_vjp(
                lambda lp_, sh_, a, b: block_fwd(lp_, sh_, ctx, i, a, b),
                (lp, shared, x1_k, x2_k), (c1, c2))
            return (x2_k, d1, d2, accumulate_shared(csh, dsh)), dlp

        init = (y2_fin, ct1, ct2, zero_shared(shared))
        with jax.named_scope(scopes.WALK_UPDATE):
            (_, d1, d2, dsh), dstacked = jax.lax.scan(
                body, init, (idxs, stacked, x1_stack, y1_stack),
                reverse=True, unroll=settings.SCAN_UNROLL)
        return (dstacked, shared_cotangent(dsh, shared),
                _zeros_tangent(ctx), d1, d2)

    apply.defvjp(fwd_rule, bwd_rule)
    return apply


# ------------------------------------------------------ mixed-policy stacks

POLICIES = ("store", "remat", "reversible", "offload")


def policy_segments(policies):
    """Group a per-layer policy list into contiguous (start, end, policy) runs."""
    segs = []
    for i, p in enumerate(policies):
        assert p in POLICIES, f"unknown activation policy {p!r}"
        if segs and segs[-1][2] == p:
            segs[-1] = (segs[-1][0], i + 1, p)
        else:
            segs.append((i, i + 1, p))
    return segs


def mixed_policy_stack(block_fwd: Callable, block_inv: Callable, policies,
                       half_inv: Callable = None):
    """Per-layer activation-policy stack (memory-planner output; DESIGN.md §6).

    ``policies``: one of ``POLICIES`` per layer.  Contiguous runs of the same
    policy become one segment:

      store       — plain scan, XLA default AD caches every intermediate.
      remat       — scan with a ``jax.checkpoint``-ed body: only each layer's
                    input streams persist; the rest recomputes in backward.
      reversible  — the O(1)-activation custom_vjp (requires ``block_inv``).
      offload     — per-layer ``jax.custom_vjp`` that parks the input streams
                    in host memory and restores them for backward
                    (repro.memory.offload).

    Same signature as ``reversible_stack``'s apply:
    (stacked_params, shared, ctx, x1, x2) -> (y1, y2).
    """
    from repro.core import settings
    n_layers = len(policies)
    segs = policy_segments(policies)
    if any(p == "reversible" for p in policies):
        assert block_inv is not None, "reversible policy needs block_inv"

    def apply(stacked, shared, ctx, x1, x2):
        from repro.memory.offload import offload_block
        for start, end, pol in segs:
            seg_params = jax.tree_util.tree_map(lambda a: a[start:end], stacked)
            n = end - start
            if pol == "reversible":
                f = reversible_stack(block_fwd, block_inv, n, save_memory=True,
                                     half_inv=half_inv, idx_offset=start)
                x1, x2 = f(seg_params, shared, ctx, x1, x2)
            elif pol in ("store", "remat"):
                body_fn = block_fwd
                if pol == "remat":
                    body_fn = jax.checkpoint(block_fwd)
                idxs = start + jnp.arange(n, dtype=jnp.int32)

                def body(carry, inp, fn=body_fn):
                    i, lp = inp
                    return fn(lp, shared, ctx, i, *carry), None
                (x1, x2), _ = jax.lax.scan(body, (x1, x2), (idxs, seg_params),
                                           unroll=settings.SCAN_UNROLL)
            else:                                       # offload
                ob = offload_block(block_fwd)
                for j in range(n):
                    lp = jax.tree_util.tree_map(lambda a, j=j: a[j], seg_params)
                    x1, x2 = ob(lp, shared, ctx,
                                jnp.int32(start + j), x1, x2)
        return x1, x2

    return apply


# ------------------------------------------------- fused optimizer walks
#
# The fused train step (repro.train.fused, DESIGN.md §13) does NOT go
# through custom_vjp: it drives the same per-layer inversion + vjp walk the
# bwd_rules above run, but hands each layer's parameter cotangent to a
# ``consume`` callback the moment it exists — the optimizer update (or a
# grad-norm probe, or a grad-accumulation add) happens inside the scan and
# the cotangent dies with the scan iteration.  No full gradient tree is
# ever live.  The walks mirror ``mixed_policy_stack``'s segments:
#
#   reversible       — no saves; backward reconstructs inputs by inversion.
#   store / remat    — forward saves each layer's input streams; backward
#                      recomputes the layer under jax.vjp from them (store
#                      degrades to remat here: per-layer recompute is what
#                      lets the grad die per layer, and it is never worse
#                      in memory than XLA's default caching).
#   offload          — like store, but the saved streams park in host
#                      memory (repro.memory.offload) until backward.


def fused_stack_forward(block_fwd: Callable, policies, idx_offset: int = 0):
    """Gradient-free forward walk.  Returns
    ``run(stacked, shared, ctx, x1, x2) -> ((y1, y2), saves)`` where
    ``saves`` has one entry per policy segment: ``None`` for reversible
    segments, the stacked per-layer input streams otherwise."""
    from repro.core import settings
    from repro.memory.offload import to_host
    segs = policy_segments(policies)

    def run(stacked, shared, ctx, x1, x2):
        saves = []
        for start, end, pol in segs:
            n = end - start
            seg_params = jax.tree_util.tree_map(
                lambda a: a[start:end], stacked)
            idxs = idx_offset + start + jnp.arange(n, dtype=jnp.int32)
            if pol == "reversible":
                def body(carry, inp):
                    i, lp = inp
                    return block_fwd(lp, shared, ctx, i, *carry), None
                (x1, x2), _ = jax.lax.scan(body, (x1, x2),
                                           (idxs, seg_params),
                                           unroll=settings.SCAN_UNROLL)
                saves.append(None)
            else:
                def body(carry, inp):
                    i, lp = inp
                    a, b = carry
                    return block_fwd(lp, shared, ctx, i, a, b), (a, b)
                (x1, x2), ins = jax.lax.scan(body, (x1, x2),
                                             (idxs, seg_params),
                                             unroll=settings.SCAN_UNROLL)
                saves.append(to_host(ins) if pol == "offload" else ins)
        return (x1, x2), saves

    return run


def read_layer(stacked, j):
    """Layer ``j``'s slice of a stacked tree (traced index OK; ``None``
    leaves pass through)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
        stacked)


def write_layer(stacked, update, j):
    """Write ``update`` into layer ``j`` of a stacked tree.  Inside a scan
    body this lowers to an in-place dynamic-update-slice on the carried
    buffer — the reason the fused walk carries the stacked trees instead of
    emitting new ones as scan ys (which would double-buffer old + new)."""
    return jax.tree_util.tree_map(
        lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, j, 0),
        stacked, update)


def fused_stack_backward(block_fwd: Callable, block_inv: Callable, policies,
                         consume: Callable, idx_offset: int = 0):
    """Reverse walk with a per-layer cotangent consumer that updates the
    stacked params/extras IN PLACE.

    ``consume(i, lp, dlp, ex) -> (new_lp, new_ex, stat)``: called once per
    layer inside the scan with the layer index, the layer's param slice,
    its parameter cotangent, and the layer's slice of ``extras`` (a stacked
    tree with leading dim n_layers — optimizer state, grad accumulators...
    — or ``None``).  ``new_lp``/``new_ex`` are replacement slices written
    back at layer ``i`` (``None`` = leave unchanged); ``stat`` is a scalar
    summed across layers (grad squared-norm accumulation).

    The stacked trees ride the scan CARRY and each layer's result lands via
    ``write_layer`` — with buffer donation XLA keeps the whole update in the
    parameters' own buffers, so no old+new double buffer and no gradient
    tree are ever live (the fused optimizer's memory claim).  A layer's
    slice is read before it is written and no other layer reads it, so the
    in-place ordering is safe.

    Returns ``run(stacked, extras, saves, shared, ctx, y1, y2, ct1, ct2)
    -> ((stacked, extras, stat), (x1, x2), (d1, d2), csh)`` where (x1, x2)
    are the reconstructed stack inputs, (d1, d2) their cotangents and
    ``csh`` the accumulated shared cotangent (``None`` placeholders on
    integer leaves; finalize with ``shared_cotangent``)."""
    from repro.core import settings
    from repro.memory.offload import to_device
    segs = policy_segments(policies)

    def run(stacked, extras, saves, shared, ctx, y1, y2, ct1, ct2):
        assert len(saves) == len(segs), \
            f"saves/segment mismatch: {len(saves)} vs {len(segs)}"
        csh = zero_shared(shared)
        c1, c2 = ct1, ct2
        stat = jnp.zeros((), jnp.float32)

        def consume_write(i, lp, dlp, st, ex, st_stat, csh_, dsh):
            new_lp, new_ex, s = consume(i, lp, dlp, ex)
            if new_lp is not None:
                st = write_layer(st, new_lp, i - idx_offset)
            return st, new_ex, st_stat + s, accumulate_shared(csh_, dsh)

        for k in range(len(segs) - 1, -1, -1):
            start, end, pol = segs[k]
            n = end - start
            idxs = idx_offset + start + jnp.arange(n, dtype=jnp.int32)
            if pol == "reversible":
                def body(carry, i):
                    cy1, cy2, cc1, cc2, st, ext, st_stat, csh_ = carry
                    lp = read_layer(st, i - idx_offset)
                    ex = None if ext is None else read_layer(ext,
                                                             i - idx_offset)
                    with jax.named_scope(scopes.REV_INVERT):
                        x1, x2 = block_inv(lp, shared, ctx, i, cy1, cy2)
                    x1 = jax.lax.stop_gradient(x1)
                    x2 = jax.lax.stop_gradient(x2)
                    dlp, dsh, d1, d2 = layer_vjp(
                        lambda lp_, sh_, a, b:
                        block_fwd(lp_, sh_, ctx, i, a, b),
                        (lp, shared, x1, x2), (cc1, cc2))
                    st, new_ex, st_stat, csh_ = consume_write(
                        i, lp, dlp, st, ex, st_stat, csh_, dsh)
                    if new_ex is not None:
                        ext = write_layer(ext, new_ex, i - idx_offset)
                    return (x1, x2, d1, d2, st, ext, st_stat, csh_), None
                (y1, y2, c1, c2, stacked, extras, stat, csh), _ = \
                    jax.lax.scan(
                        body, (y1, y2, c1, c2, stacked, extras, stat, csh),
                        idxs, reverse=True, unroll=settings.SCAN_UNROLL)
            else:
                ins = saves[k]
                assert ins is not None, f"segment {k} ({pol}) has no saves"
                if pol == "offload":
                    ins = to_device(ins)
                x1s, x2s = ins

                def body(carry, inp):
                    i, a, b = inp
                    cc1, cc2, st, ext, st_stat, csh_ = carry
                    lp = read_layer(st, i - idx_offset)
                    ex = None if ext is None else read_layer(ext,
                                                             i - idx_offset)
                    dlp, dsh, d1, d2 = layer_vjp(
                        lambda lp_, sh_, a_, b_:
                        block_fwd(lp_, sh_, ctx, i, a_, b_),
                        (lp, shared, a, b), (cc1, cc2))
                    st, new_ex, st_stat, csh_ = consume_write(
                        i, lp, dlp, st, ex, st_stat, csh_, dsh)
                    if new_ex is not None:
                        ext = write_layer(ext, new_ex, i - idx_offset)
                    return (d1, d2, st, ext, st_stat, csh_), None
                (c1, c2, stacked, extras, stat, csh), _ = jax.lax.scan(
                    body, (c1, c2, stacked, extras, stat, csh),
                    (idxs, x1s, x2s),
                    reverse=True, unroll=settings.SCAN_UNROLL)
                y1, y2 = x1s[0], x2s[0]
        return (stacked, extras, stat), (y1, y2), (c1, c2), csh

    return run


# ----------------------------------------------- layer-group (lean) walks
#
# Grouped stacks (models.spec.GroupLayout, DESIGN.md §14) replace the flat
# "leading axis = n_layers" param layout with {"base" (one slice per
# group), "delta" (per-layer low-rank), "per" (non-shared keys)}.  The
# walks below mirror their flat counterparts, but the param tree can no
# longer ride the scan xs (its leading dims are G and L, not the scanned
# range) — instead it is closed over / carried, and each layer's effective
# unit weights are materialised inside the body via ``read_unit``.  For
# paths that rely on standard autodiff (plain scan, store/remat/offload
# policies) that is the whole story: the base gather differentiates to a
# scatter-add automatically.  The reversible custom_vjp and the fused walk
# accumulate manually: delta/per cotangents land in their own layer slice
# (``write_layer``), base cotangents scatter-add into the group slice
# (``.at[g].add``) so each shared matrix's gradient is the sum over its
# layers — and the fused optimizer updates it exactly ONCE per group.


def read_unit(layout, gp, i):
    """Effective unit-param tree of (stack-local) layer ``i`` of a grouped
    stack: base[group_map[i]] + delta[i], merged with per[i].  ``i`` may be
    traced (gather through the group map)."""
    from repro.models.spec import materialize_unit
    g = jnp.take(jnp.asarray(layout.group_map, jnp.int32), i)
    return materialize_unit(read_layer(gp["base"], g),
                            read_layer(gp["delta"], i),
                            read_layer(gp["per"], i))


def _grouped_vjp(block_fwd, layout, gp, shared, ctx, i, x1, x2, cts):
    """Per-layer vjp of a grouped block w.r.t. its (base, delta, per)
    slices — materialisation happens INSIDE the differentiated function so
    delta grads are per layer while the base slice's grad is exactly this
    layer's contribution (summed into the group accumulator by callers)."""
    from repro.models.spec import materialize_unit
    g = jnp.take(jnp.asarray(layout.group_map, jnp.int32), i)
    b_sl = read_layer(gp["base"], g)
    d_sl = read_layer(gp["delta"], i)
    p_sl = read_layer(gp["per"], i)

    def f(b_, d_, p_, sh_, a, b):
        return block_fwd(materialize_unit(b_, d_, p_), sh_, ctx, i, a, b)

    db, dd, dp, dsh, d1, d2 = layer_vjp(
        f, (b_sl, d_sl, p_sl, shared, x1, x2), cts)
    return g, (b_sl, d_sl, p_sl), (db, dd, dp), dsh, (d1, d2)


def _scatter_base(acc, g, db):
    return jax.tree_util.tree_map(
        lambda A, u: A.at[g].add(u.astype(A.dtype)), acc, db)


def _zeros_grouped(gp):
    return jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), gp)


def grouped_reversible_stack(block_fwd: Callable, block_inv: Callable,
                             layout, save_memory=True, start: int = 0,
                             end: int = None):
    """Grouped analogue of ``reversible_stack`` over layers [start, end).

    apply(gp, shared, ctx, x1, x2) -> (y1, y2) with
    gp = {"base", "delta", "per"}.  The whole grouped tree is passed (never
    sliced per segment): the custom_vjp's backward returns a full
    grouped-shaped cotangent, so segment applications compose by JAX's own
    cotangent summation.  ``save_memory="half"`` is not supported for
    grouped stacks (callers fall back to full inversion).
    """
    from repro.core import settings
    if end is None:
        end = layout.n_layers
    idxs = jnp.arange(start, end, dtype=jnp.int32)
    assert save_memory in (True, False), \
        "grouped stacks support save_memory True/False (no 'half')"

    def plain(gp, shared, ctx, x1, x2):
        def body(carry, i):
            lp = read_unit(layout, gp, i)
            return block_fwd(lp, shared, ctx, i, *carry), None
        (y1, y2), _ = jax.lax.scan(body, (x1, x2), idxs,
                                   unroll=settings.SCAN_UNROLL)
        return y1, y2

    if save_memory is False:
        return plain

    @jax.custom_vjp
    def apply(gp, shared, ctx, x1, x2):
        with jax.named_scope(scopes.WALK_FORWARD):
            return plain(gp, shared, ctx, x1, x2)

    def fwd_rule(gp, shared, ctx, x1, x2):
        with jax.named_scope(scopes.WALK_FORWARD):
            y1, y2 = plain(gp, shared, ctx, x1, x2)
        return (y1, y2), (gp, shared, ctx, y1, y2)

    def bwd_rule(res, cts):
        gp, shared, ctx, y1, y2 = res
        ct1, ct2 = cts

        def body(carry, i):
            cy1, cy2, c1, c2, dgp, csh = carry
            with jax.named_scope(scopes.REV_INVERT):
                lp = read_unit(layout, gp, i)
                x1, x2 = block_inv(lp, shared, ctx, i, cy1, cy2)
            x1 = jax.lax.stop_gradient(x1)
            x2 = jax.lax.stop_gradient(x2)
            g, _, (db, dd, dp), dsh, (d1, d2) = _grouped_vjp(
                block_fwd, layout, gp, shared, ctx, i, x1, x2, (c1, c2))
            dgp = {"base": _scatter_base(dgp["base"], g, db),
                   "delta": write_layer(dgp["delta"], dd, i),
                   "per": write_layer(dgp["per"], dp, i)}
            return (x1, x2, d1, d2, dgp, accumulate_shared(csh, dsh)), None

        from repro.core import settings as _s
        init = (y1, y2, ct1, ct2, _zeros_grouped(gp), zero_shared(shared))
        with jax.named_scope(scopes.WALK_UPDATE):
            (_, _, d1, d2, dgp, dsh), _ = jax.lax.scan(
                body, init, idxs, reverse=True, unroll=_s.SCAN_UNROLL)
        return (dgp, shared_cotangent(dsh, shared),
                _zeros_tangent(ctx), d1, d2)

    apply.defvjp(fwd_rule, bwd_rule)
    return apply


def grouped_mixed_policy_stack(block_fwd: Callable, block_inv: Callable,
                               layout, policies):
    """Grouped analogue of ``mixed_policy_stack``.  Non-reversible segments
    read units inline and lean on standard autodiff (the base gather's
    cotangent is a scatter-add); reversible segments go through the grouped
    custom_vjp above.  Cotangents from multiple segments touching the same
    group sum via JAX's multi-use accumulation of ``gp``."""
    from repro.core import settings
    n_layers = len(policies)
    assert n_layers == layout.n_layers, (n_layers, layout.n_layers)
    segs = policy_segments(policies)
    if any(p == "reversible" for p in policies):
        assert block_inv is not None, "reversible policy needs block_inv"

    def apply(gp, shared, ctx, x1, x2):
        from repro.memory.offload import offload_block
        for start, end, pol in segs:
            n = end - start
            if pol == "reversible":
                f = grouped_reversible_stack(block_fwd, block_inv, layout,
                                             save_memory=True,
                                             start=start, end=end)
                x1, x2 = f(gp, shared, ctx, x1, x2)
            elif pol in ("store", "remat"):
                def unit_fwd(gp_, sh, ctx_, i, a, b):
                    return block_fwd(read_unit(layout, gp_, i), sh, ctx_,
                                     i, a, b)
                body_fn = unit_fwd
                if pol == "remat":
                    # rematerialise the effective weights too: only the
                    # segment's stream inputs persist
                    body_fn = jax.checkpoint(unit_fwd)
                idxs = jnp.arange(start, end, dtype=jnp.int32)

                def body(carry, i, fn=body_fn):
                    return fn(gp, shared, ctx, i, *carry), None
                (x1, x2), _ = jax.lax.scan(body, (x1, x2), idxs,
                                           unroll=settings.SCAN_UNROLL)
            else:                                       # offload
                ob = offload_block(block_fwd)
                for j in range(n):
                    lp = read_unit(layout, gp, jnp.int32(start + j))
                    x1, x2 = ob(lp, shared, ctx, jnp.int32(start + j),
                                x1, x2)
        return x1, x2

    return apply


def grouped_fused_stack_forward(block_fwd: Callable, layout, policies):
    """Grouped analogue of ``fused_stack_forward`` (gradient-free)."""
    from repro.core import settings
    from repro.memory.offload import to_host
    segs = policy_segments(policies)

    def run(gp, shared, ctx, x1, x2):
        saves = []
        for start, end, pol in segs:
            idxs = jnp.arange(start, end, dtype=jnp.int32)
            if pol == "reversible":
                def body(carry, i):
                    lp = read_unit(layout, gp, i)
                    return block_fwd(lp, shared, ctx, i, *carry), None
                (x1, x2), _ = jax.lax.scan(body, (x1, x2), idxs,
                                           unroll=settings.SCAN_UNROLL)
                saves.append(None)
            else:
                def body(carry, i):
                    a, b = carry
                    lp = read_unit(layout, gp, i)
                    return block_fwd(lp, shared, ctx, i, a, b), (a, b)
                (x1, x2), ins = jax.lax.scan(body, (x1, x2), idxs,
                                             unroll=settings.SCAN_UNROLL)
                saves.append(to_host(ins) if pol == "offload" else ins)
        return (x1, x2), saves

    return run


def grouped_fused_stack_backward(block_fwd: Callable, block_inv: Callable,
                                 layout, policies, consume: Callable):
    """Grouped analogue of ``fused_stack_backward``.

    ``consume(i, lay_sl, dlay_sl, ex)`` sees only the PER-LAYER trainables
    — ``lay_sl = {"delta": ..., "per": ...}`` slices — and updates them in
    place exactly like the flat walk.  Base cotangents instead scatter-add
    into ``acc_base`` (grouped shape, zeros-initialised here): the shared
    slice's gradient is only complete once every layer of its group has
    been walked, so the caller applies the base update exactly once per
    group AFTER the walk (repro.train.fused's group loop).  ``acc_base``
    is 1/sharing-factor the size of a flat gradient, so the fused memory
    claim degrades only by the already-shrunk base tree.

    Returns ``run(gp, extras, saves, shared, ctx, y1, y2, ct1, ct2) ->
    ((gp, extras, stat, acc_base), (x1, x2), (d1, d2), csh)`` where
    ``extras``/``stat`` cover the per-layer part only.
    """
    from repro.core import settings
    from repro.memory.offload import to_device
    segs = policy_segments(policies)

    def run(gp, extras, saves, shared, ctx, y1, y2, ct1, ct2):
        assert len(saves) == len(segs), \
            f"saves/segment mismatch: {len(saves)} vs {len(segs)}"
        csh = zero_shared(shared)
        c1, c2 = ct1, ct2
        stat = jnp.zeros((), jnp.float32)
        acc_base = _zeros_grouped(gp["base"])

        def layer_step(i, gp_, ext, acc_b, st_stat, csh_, x1, x2, cc1, cc2):
            g, (_, d_sl, p_sl), (db, dd, dp), dsh, (d1, d2) = _grouped_vjp(
                block_fwd, layout, gp_, shared, ctx, i, x1, x2, (cc1, cc2))
            acc_b = _scatter_base(acc_b, g, db)
            ex = None if ext is None else read_layer(ext, i)
            new_lay, new_ex, s = consume(i, {"delta": d_sl, "per": p_sl},
                                         {"delta": dd, "per": dp}, ex)
            if new_lay is not None:
                gp_ = {"base": gp_["base"],
                       "delta": write_layer(gp_["delta"], new_lay["delta"],
                                            i),
                       "per": write_layer(gp_["per"], new_lay["per"], i)}
            if new_ex is not None:
                ext = write_layer(ext, new_ex, i)
            return (gp_, ext, acc_b, st_stat + s,
                    accumulate_shared(csh_, dsh), d1, d2)

        for k in range(len(segs) - 1, -1, -1):
            start, end, pol = segs[k]
            idxs = jnp.arange(start, end, dtype=jnp.int32)
            if pol == "reversible":
                def body(carry, i):
                    cy1, cy2, cc1, cc2, gp_, ext, acc_b, st_stat, csh_ = \
                        carry
                    with jax.named_scope(scopes.REV_INVERT):
                        lp = read_unit(layout, gp_, i)
                        x1, x2 = block_inv(lp, shared, ctx, i, cy1, cy2)
                    x1 = jax.lax.stop_gradient(x1)
                    x2 = jax.lax.stop_gradient(x2)
                    gp_, ext, acc_b, st_stat, csh_, d1, d2 = layer_step(
                        i, gp_, ext, acc_b, st_stat, csh_, x1, x2, cc1, cc2)
                    return (x1, x2, d1, d2, gp_, ext, acc_b, st_stat,
                            csh_), None
                (y1, y2, c1, c2, gp, extras, acc_base, stat, csh), _ = \
                    jax.lax.scan(
                        body, (y1, y2, c1, c2, gp, extras, acc_base, stat,
                               csh),
                        idxs, reverse=True, unroll=settings.SCAN_UNROLL)
            else:
                ins = saves[k]
                assert ins is not None, f"segment {k} ({pol}) has no saves"
                if pol == "offload":
                    ins = to_device(ins)
                x1s, x2s = ins

                def body(carry, inp):
                    i, a, b = inp
                    cc1, cc2, gp_, ext, acc_b, st_stat, csh_ = carry
                    gp_, ext, acc_b, st_stat, csh_, d1, d2 = layer_step(
                        i, gp_, ext, acc_b, st_stat, csh_, a, b, cc1, cc2)
                    return (d1, d2, gp_, ext, acc_b, st_stat, csh_), None
                (c1, c2, gp, extras, acc_base, stat, csh), _ = jax.lax.scan(
                    body, (c1, c2, gp, extras, acc_base, stat, csh),
                    (idxs, x1s, x2s), reverse=True,
                    unroll=settings.SCAN_UNROLL)
                y1, y2 = x1s[0], x2s[0]
        return (gp, extras, stat, acc_base), (y1, y2), (c1, c2), csh

    return run


# ------------------------------------------------------------ audit hooks
#
# The reversible audit mode (repro.obs.audit, DESIGN.md §12) re-walks a
# stack layer by layer OUTSIDE the custom_vjp: forward collecting each
# layer's true input streams, then inverting from the outputs exactly the
# way ``bwd_rule`` does — including error ACCUMULATION across a contiguous
# reversible segment (layer k's inversion is seeded with layer k+1's
# reconstructed, not true, inputs; non-reversible policies reset to stored
# values, mirroring the segment boundaries of ``mixed_policy_stack``).


def layer_slice(stacked, j: int):
    """Layer ``j``'s param tree out of a stacked (leading-dim n_layers)
    tree — the per-layer view the audit walk feeds to block_fwd/block_inv."""
    return jax.tree_util.tree_map(lambda a: a[j], stacked)


def reconstruction_metrics(r1, r2, x1, x2):
    """Per-layer inversion-quality scalars: (max_abs, mean_abs, rel) error
    of the reconstructed streams (r1, r2) against the true inputs (x1, x2).
    ``rel`` normalizes the max error by the true streams' max magnitude —
    the quantity the ``validate --max-reconstruction-err`` CI gate bounds
    (fixed-point cross-coupling inversion converges to ~dtype eps; see
    DESIGN.md §3)."""
    d1 = jnp.abs(r1.astype(jnp.float32) - x1.astype(jnp.float32))
    d2 = jnp.abs(r2.astype(jnp.float32) - x2.astype(jnp.float32))
    max_abs = jnp.maximum(jnp.max(d1), jnp.max(d2))
    mean_abs = (jnp.sum(d1) + jnp.sum(d2)) / (d1.size + d2.size)
    scale = jnp.maximum(jnp.max(jnp.abs(x1.astype(jnp.float32))),
                        jnp.max(jnp.abs(x2.astype(jnp.float32))))
    rel = max_abs / (scale + 1e-12)
    return max_abs, mean_abs, rel


def split_streams(h):
    """H (B,S,d) -> X1, X2 (B,S,d/2) along features (paper §3.1)."""
    d = h.shape[-1]
    return h[..., : d // 2], h[..., d // 2:]


def merge_streams(y1, y2):
    return jnp.concatenate([y1, y2], axis=-1)
