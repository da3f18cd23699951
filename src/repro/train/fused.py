"""Fused optimizer-in-backward train step (DESIGN.md §13).

The unfused step materialises the entire gradient tree (and, under grad
accumulation, a second full accumulator) before one monolithic
``optimizer.update``.  But the reversible backward already walks the stack
one layer at a time — so this step hands each layer's parameter cotangent
to the optimizer the moment it exists, inside the backward scan
(``repro.core.reversible.fused_stack_backward``), and lets it die with the
scan iteration.  Peak grad memory is one layer's slice plus the small
non-stack remainder (embed / norms / LM head / shared), never the model.

Phases per step (n_micro == 1), each under the ``jax.named_scope`` of the
same name (``repro.obs.scopes``):

  prelude   — ``jax.vjp`` over the non-stack prefix (embed, shared tree,
              encoder for encdec): produces the stream inputs + a vjp
              closure for later.
  walk.forward — gradient-free forward over the main stacks
              (``fused_stack_forward``), saving per-layer inputs only for
              non-reversible policy segments.
  head_loss — ``jax.vjp`` over final-norm + LM head + CE
              (``model.loss_from_streams``): loss, tail grads, and the
              output-stream cotangents that seed the walk.
  walk.probe — (only when the optimizer clips) a backward walk whose
              consumer reduces each layer's grads to a squared-norm
              scalar: global norm with deferred scale, the two-pass
              clipping strategy LOMO uses (arXiv:2306.09782).
  walk.update — backward walk whose consumer applies
              ``optimizer.update_leaf`` per layer (``opt.update``, as are
              the group and final updates); the stacked params and
              optimizer state ride the scan CARRY and each layer's result
              lands in place (``write_layer``), so donation keeps the
              update in the parameters' own buffers — no old+new double
              buffer (DESIGN.md §13).

Under grad accumulation the per-microbatch walk's consumer adds raw grad
sums into a layer-sliced accumulator in place (instead of a whole-tree
f32 clone), and the update phase is a per-layer fori_loop over
(params, acc, state) with no model recompute, averaging one layer slice
at a time.

Parity: identical math to the unfused step (same clip expression, same
update-leaf ordering, the optimizer's ``update`` delegates to the same
``update_leaf``) — tests gate max|Δparams| ≤ 1e-6 at f32 for
n_micro ∈ {1, 4}.  Non-finite global norms skip the update (params AND
moments frozen) instead of writing NaN everywhere; the driver counts such
steps via the ``train.nonfinite_grad_steps`` counter.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.reversible import (accumulate_shared, fused_stack_backward,
                                   fused_stack_forward,
                                   grouped_fused_stack_backward,
                                   grouped_fused_stack_forward, read_layer,
                                   shared_cotangent, write_layer,
                                   zero_shared)
from repro.obs import scopes
from repro.optim.adamw import apply_subtree, clip_guard, global_norm_sq

TAIL_KEYS = ("final_norm", "lm_head")


def split_like(tree, main_names):
    """Split a params-shaped tree into (pre, main, tail): the main stacks'
    stacked subtrees by name, the tail (final norm + LM head), and
    everything else (embed, shared, encoder stacks, enc_norm...).  Works on
    any tree mirroring the params structure down to these keys — masks,
    optimizer-state components, accumulators."""
    stacks = tree["stacks"]
    main = {n: stacks[n] for n in main_names}
    other = {n: v for n, v in stacks.items() if n not in main_names}
    pre = {k: v for k, v in tree.items()
           if k != "stacks" and k not in TAIL_KEYS}
    if other:
        pre["stacks"] = other
    tail = {k: tree[k] for k in TAIL_KEYS}
    return pre, main, tail


def merge_like(pre, main, tail):
    """Inverse of ``split_like``."""
    out = {k: v for k, v in pre.items() if k != "stacks"}
    stacks = dict(pre.get("stacks", {}))
    stacks.update(main)
    out["stacks"] = stacks
    out.update(tail)
    return out


def _stack_policies(model, save_memory):
    mains = [s for s in model.stacks if s.role == "main"]
    if isinstance(save_memory, (list, tuple)):
        pl = list(save_memory)
        n_main = sum(s.n for s in mains)
        if len(pl) != n_main:
            raise ValueError(
                f"plan has {len(pl)} policies for {n_main} main units")
        per = []
        for s in mains:
            per.append([str(p) for p in pl[:s.n]])
            pl = pl[s.n:]
        return mains, per
    if save_memory is True:
        return mains, [["reversible"] * s.n for s in mains]
    raise ValueError(
        f"fused optimizer needs save_memory=True or a per-layer policy "
        f"list, got {save_memory!r}: 'half'/False have no per-layer "
        f"backward walk to fuse updates into")


def make_fused_train_step(model, optimizer, *, n_micro: int = 1,
                          mask_fn: Optional[Callable] = None,
                          save_memory=True, accum_dtype=jnp.float32):
    """Same signature/returns as ``trainer.make_train_step``:
    step(params, opt_state, batch) -> (params, opt_state, metrics)."""
    cfg = model.cfg
    if not cfg.reversible:
        raise ValueError(
            f"--fused-optimizer requires a reversible config (the update "
            f"hook lives in the reversible backward walk); {cfg.name} has "
            f"reversible=False — use the standard step")
    for attr in ("update_leaf", "per_param_trees", "build_state"):
        if not hasattr(optimizer, attr):
            raise ValueError(
                f"{type(optimizer).__name__} does not expose the layer-wise "
                f"update API ({attr}); fused training supports AdamW and "
                f"LoMo")
    if type(optimizer).__name__.lower() == "galore":
        raise ValueError(
            "GaLore cannot be fused: its projectors are fit to the "
            "layer-stacked gradient matrices, so per-layer updates would "
            "optimize in a different low-rank subspace than the unfused "
            "step; use --optimizer adamw or lomo with --fused-optimizer")
    from repro.train.trainer import validate_ep
    validate_ep(cfg)

    mains, policies = _stack_policies(model, save_memory)
    main_names = [s.name for s in mains]
    clip = float(getattr(optimizer, "clip_norm", 0.0) or 0.0)
    layouts = {s.name: s.layout for s in mains}
    gnames = [n for n in main_names if layouts[n] is not None]

    def walk_view(tree, name):
        """The per-layer trainable view of a stack-shaped tree: the whole
        tree for flat stacks, {"delta", "per"} for grouped stacks — base
        slices are updated exactly once per group AFTER the walk (the
        grouped fused walk only accumulates their cotangents)."""
        if layouts[name] is None or tree is None:
            return tree
        return {"delta": tree["delta"], "per": tree["per"]}

    def forward(pre_p, main_p, mbatch):
        tokens = mbatch["tokens"]
        bx = {k: v for k, v in mbatch.items() if k in ("enc_feats", "img")}

        def prelude(pre_):
            full = merge_like(pre_, main_p, {})
            x1, x2, ctx, shared = model.audit_streams(full, tokens,
                                                      bx or None)
            return (x1, x2, shared), ctx

        with jax.named_scope(scopes.PRELUDE):
            (x1, x2, shared), pre_vjp, ctx = jax.vjp(prelude, pre_p,
                                                     has_aux=True)
        y1, y2 = x1, x2
        saves_all = []
        with jax.named_scope(scopes.WALK_FORWARD):
            for s, pol in zip(mains, policies):
                if s.layout is not None:
                    runf = grouped_fused_stack_forward(s.fwd, s.layout, pol)
                else:
                    runf = fused_stack_forward(s.fwd, pol)
                (y1, y2), saves = runf(main_p[s.name], shared, ctx, y1, y2)
                saves_all.append(saves)
        return (y1, y2), saves_all, shared, ctx, pre_vjp

    def prelude_grads(pre_vjp, d1, d2, csh, shared):
        with jax.named_scope(scopes.PRELUDE):
            (dpre,) = pre_vjp((d1, d2, shared_cotangent(csh, shared)))
        return dpre

    def backward(main_p, extras_by_stack, saves_all, shared, ctx,
                 y1, y2, ct1, ct2, consume_factory):
        """Reverse over the main stacks; returns the (in-place updated)
        per-stack params/extras + per-stack stat scalars + per-stack base
        cotangent accumulators (grouped stacks only; None for flat), the
        prelude stream cotangents, and the shared-tree cotangent."""
        csh_total = zero_shared(shared)
        new_p, new_ex, stats, accs = {}, {}, {}, {}
        c1, c2 = ct1, ct2
        for k in range(len(mains) - 1, -1, -1):
            s = mains[k]
            ex = (None if extras_by_stack is None
                  else extras_by_stack[s.name])
            if s.layout is not None:
                runb = grouped_fused_stack_backward(
                    s.fwd, s.inv, s.layout, policies[k],
                    consume_factory(s.name))
                (new_p[s.name], new_ex[s.name], stats[s.name],
                 accs[s.name]), (y1, y2), (c1, c2), csh = runb(
                    main_p[s.name], ex, saves_all[k], shared, ctx,
                    y1, y2, c1, c2)
            else:
                runb = fused_stack_backward(s.fwd, s.inv, policies[k],
                                            consume_factory(s.name))
                (new_p[s.name], new_ex[s.name], stats[s.name]), (y1, y2), \
                    (c1, c2), csh = runb(main_p[s.name], ex, saves_all[k],
                                         shared, ctx, y1, y2, c1, c2)
                accs[s.name] = None
            csh_total = accumulate_shared(csh_total, csh)
        return (new_p, new_ex, stats, accs), (c1, c2), csh_total

    def run_micro(pre_p, main_p, tail_p, mbatch):
        """Forward + tail vjp for one microbatch."""
        (y1, y2), saves_all, shared, ctx, pre_vjp = forward(
            pre_p, main_p, mbatch)
        with jax.named_scope(scopes.HEAD_LOSS):
            loss, tvjp = jax.vjp(
                lambda t, a, b: model.loss_from_streams(t, a, b, mbatch),
                tail_p, y1, y2)
            dtail, ct1, ct2 = tvjp(jnp.ones((), loss.dtype))
        return (loss, saves_all, shared, ctx, pre_vjp, dtail,
                (y1, y2), (ct1, ct2))

    # the jitted module's name (``jit_fused_train_step``) is part of the
    # persistent compile cache's key; op names are not, so a step that differs
    # from another only in its scopes would load the other's executable and
    # trace with its names
    def fused_train_step(params, opt_state, batch):
        mask = mask_fn(params) if mask_fn else None
        pre_p, main_p, tail_p = split_like(params, main_names)
        if mask is not None:
            pre_mk, main_mk, tail_mk = split_like(mask, main_names)
        else:
            pre_mk = tail_mk = None
            main_mk = {}
        parts = optimizer.per_param_trees(opt_state)
        comp = {c: split_like(t, main_names) for c, t in parts.items()}
        pre_st = {c: v[0] for c, v in comp.items()}
        main_st = {n: {c: comp[c][1][n] for c in parts} for n in main_names}
        tail_st = {c: v[2] for c, v in comp.items()}
        step_no = opt_state["step"] + 1
        # grouped stacks: the walk only sees/updates the per-layer
        # {"delta", "per"} view; base params + state are held aside and
        # updated once per group after the walk
        main_st_walk = {n: {c: walk_view(main_st[n][c], n) for c in parts}
                        for n in main_names}

        def group_update(name, pb, acc, stb, scale, skip, n_div=1):
            """Apply the optimizer to every base group slice exactly once,
            from the walk's scatter-added cotangent accumulator."""
            mkn = main_mk.get(name)
            mk = None if mkn is None else mkn["base"]
            ng = layouts[name].n_groups

            def gbody(g, carry):
                pb_, stb_ = carry
                grad = jax.tree_util.tree_map(lambda a: a / n_div,
                                              read_layer(acc, g))
                new_sl, new_st = apply_subtree(
                    optimizer, read_layer(pb_, g), grad,
                    read_layer(stb_, g), step=step_no, scale=scale,
                    mask=mk, skip=skip)
                return (write_layer(pb_, new_sl, g),
                        write_layer(stb_, new_st, g))
            with jax.named_scope(scopes.OPT_UPDATE):
                return jax.lax.fori_loop(0, ng, gbody, (pb, stb))

        def finish_grouped(new_main, new_main_st, accs, scale, skip,
                           n_div=1):
            """Graft once-per-group base updates onto the walk results."""
            for n in gnames:
                base_st = {c: main_st[n][c]["base"] for c in parts}
                new_base, new_base_st = group_update(
                    n, new_main[n].get("base", main_p[n]["base"]),
                    accs[n], base_st, scale, skip, n_div)
                new_main[n] = dict(new_main[n], base=new_base)
                new_main_st[n] = {c: dict(new_main_st[n][c],
                                          base=new_base_st[c])
                                  for c in parts}
            return new_main, new_main_st

        def base_norm_sq(accs, n_div=1):
            return sum(global_norm_sq(accs[n]) for n in gnames) / (n_div *
                                                                   n_div)

        def upd_factory(scale, skip):
            def for_stack(name):
                mk = walk_view(main_mk.get(name), name)

                def consume(i, lp, dlp, ex):
                    with jax.named_scope(scopes.OPT_UPDATE):
                        new_lp, new_st = apply_subtree(
                            optimizer, lp, dlp, ex, step=step_no,
                            scale=scale, mask=mk, skip=skip)
                        return new_lp, new_st, global_norm_sq(dlp)
                return consume
            return for_stack

        def finish(new_main, new_main_st, dpre, dtail, scale, skip):
            with jax.named_scope(scopes.OPT_UPDATE):
                new_pre, new_pre_st = apply_subtree(
                    optimizer, pre_p, dpre, pre_st, step=step_no,
                    scale=scale, mask=pre_mk, skip=skip)
                new_tail, new_tail_st = apply_subtree(
                    optimizer, tail_p, dtail, tail_st, step=step_no,
                    scale=scale, mask=tail_mk, skip=skip)
            new_params = merge_like(new_pre, new_main, new_tail)
            new_parts = {c: merge_like(
                new_pre_st[c],
                {n: new_main_st[n][c] for n in main_names},
                new_tail_st[c]) for c in parts}
            return new_params, optimizer.build_state(new_parts, step_no)

        if n_micro == 1:
            (loss, saves_all, shared, ctx, pre_vjp, dtail,
             (y1, y2), (ct1, ct2)) = run_micro(pre_p, main_p, tail_p, batch)
            if clip:
                # probe walk: per-layer squared norms only — each layer's
                # grad is reduced to a scalar and freed before the next
                # (grouped stacks additionally return their base cotangent
                # accumulator, whose norm joins the global sum)
                probe = lambda name: (          # noqa: E731
                    lambda i, lp, dlp, ex: (None, None,
                                            global_norm_sq(dlp)))
                with jax.named_scope(scopes.WALK_PROBE):
                    (_, _, sumsq, p_accs), (d1, d2), csh = backward(
                        main_p, None, saves_all, shared, ctx, y1, y2, ct1,
                        ct2, probe)
                dpre = prelude_grads(pre_vjp, d1, d2, csh, shared)
                total_sq = (global_norm_sq((dpre, dtail))
                            + sum(sumsq.values()) + base_norm_sq(p_accs))
                scale, skip = clip_guard(total_sq, clip)
                with jax.named_scope(scopes.WALK_UPDATE):
                    (new_main, new_main_st, _, accs), _, _ = backward(
                        main_p, main_st_walk, saves_all, shared, ctx, y1,
                        y2, ct1, ct2, upd_factory(scale, skip))
            else:
                scale, skip = 1.0, None
                with jax.named_scope(scopes.WALK_UPDATE):
                    (new_main, new_main_st, sumsq, accs), (d1, d2), csh = \
                        backward(main_p, main_st_walk, saves_all, shared,
                                 ctx, y1, y2, ct1, ct2,
                                 upd_factory(scale, skip))
                dpre = prelude_grads(pre_vjp, d1, d2, csh, shared)
                total_sq = (global_norm_sq((dpre, dtail))
                            + sum(sumsq.values()) + base_norm_sq(accs))
            new_main, new_main_st = finish_grouped(new_main, new_main_st,
                                                   accs, scale, skip)
        else:
            gb = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if gb % n_micro != 0:
                raise ValueError(
                    f"global batch {gb} is not divisible by "
                    f"n_micro={n_micro} (remainder {gb % n_micro}); pick "
                    f"n_micro dividing the global batch or pad the batch")
            resh = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    (n_micro, x.shape[0] // n_micro) + x.shape[1:]), batch)
            zeros = lambda t: jax.tree_util.tree_map(   # noqa: E731
                lambda p: jnp.zeros(p.shape, accum_dtype), t)
            # accumulate RAW per-microbatch sums into the layer-sliced
            # buffers (in-place dynamic-update-slice inside the walk);
            # averaging happens per layer slice at update time, which is
            # elementwise-identical to averaging the whole tree first
            acc_factory = lambda name: (                # noqa: E731
                lambda i, lp, dlp, ex: (None, jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), ex, dlp),
                    jnp.zeros((), jnp.float32)))

            def body(carry, mbatch):
                acc_main, acc_base, acc_pre, acc_tail, loss_sum = carry
                (loss, saves_all, shared, ctx, pre_vjp, dtail,
                 (y1, y2), (ct1, ct2)) = run_micro(pre_p, main_p, tail_p,
                                                   mbatch)
                with jax.named_scope(scopes.WALK_UPDATE):
                    (_, acc_main, _, accs), (d1, d2), csh = backward(
                        main_p, acc_main, saves_all, shared, ctx, y1, y2,
                        ct1, ct2, acc_factory)
                dpre = prelude_grads(pre_vjp, d1, d2, csh, shared)
                add = lambda a, g: a + g.astype(a.dtype)    # noqa: E731
                acc_base = {n: jax.tree_util.tree_map(add, acc_base[n],
                                                      accs[n])
                            for n in gnames}
                acc_pre = jax.tree_util.tree_map(add, acc_pre, dpre)
                acc_tail = jax.tree_util.tree_map(add, acc_tail, dtail)
                return (acc_main, acc_base, acc_pre, acc_tail,
                        loss_sum + loss), None

            init = ({n: zeros(walk_view(main_p[n], n)) for n in main_names},
                    {n: zeros(main_p[n]["base"]) for n in gnames},
                    zeros(pre_p), zeros(tail_p), 0.0)
            (acc_main, acc_base, acc_pre, acc_tail, loss_sum), _ = \
                jax.lax.scan(body, init, resh)
            loss = loss_sum / n_micro
            avg = lambda t: jax.tree_util.tree_map(     # noqa: E731
                lambda a: a / n_micro, t)
            dpre, dtail = avg(acc_pre), avg(acc_tail)
            total_sq = (global_norm_sq((dpre, dtail))
                        + global_norm_sq(acc_main) / (n_micro * n_micro)
                        + base_norm_sq(acc_base, n_micro))
            scale, skip = (clip_guard(total_sq, clip) if clip
                           else (1.0, None))
            new_main, new_main_st = {}, {}
            for n in main_names:
                mk = walk_view(main_mk.get(n), n)
                acc_n = acc_main[n]
                lay = layouts[n]
                pw, stw = walk_view(main_p[n], n), main_st_walk[n]
                nl = (lay.n_layers if lay is not None else
                      jax.tree_util.tree_leaves(main_p[n])[0].shape[0])

                def ubody(j, carry, mk=mk, acc_n=acc_n):
                    pb, stb = carry
                    g = jax.tree_util.tree_map(lambda a: a / n_micro,
                                               read_layer(acc_n, j))
                    with jax.named_scope(scopes.OPT_UPDATE):
                        new_lp, new_st = apply_subtree(
                            optimizer, read_layer(pb, j), g,
                            read_layer(stb, j), step=step_no, scale=scale,
                            mask=mk, skip=skip)
                    return (write_layer(pb, new_lp, j),
                            write_layer(stb, new_st, j))
                with jax.named_scope(scopes.WALK_UPDATE):
                    new_main[n], new_main_st[n] = jax.lax.fori_loop(
                        0, nl, ubody, (pw, stw))
                if lay is not None:
                    new_main[n] = dict(new_main[n],
                                       base=main_p[n]["base"])
            new_main, new_main_st = finish_grouped(new_main, new_main_st,
                                                   acc_base, scale, skip,
                                                   n_micro)

        new_params, new_opt = finish(new_main, new_main_st, dpre, dtail,
                                     scale, skip)
        gnorm = jnp.sqrt(total_sq)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "grads_finite": jnp.isfinite(gnorm),
                   "step": new_opt["step"]}
        return new_params, new_opt, metrics

    return fused_train_step
