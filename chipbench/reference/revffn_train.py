"""The fine-tune step of ``RevFFN`` in float32, run for the first steps of
a cell, with the readings that the training comparison uses.

The optimizer is the one the job states (``"lomo"``: SGD on a float32 master
copy whose bfloat16 rounding the forward uses; ``"adamw"``: Adam on bfloat16
parameters with float32 moments), under a global-norm clip computed over
every gradient, with the ``frozen`` leaves left unmoved.  The gradient is
walked twice per step, once for the global norm and once for the update, so
that no more than one layer's gradient is held: the reference fits on the
chip beside nothing else.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.revffn import F32, RevFFN

B1_DEFAULT, B2_DEFAULT, EPS_DEFAULT = 0.9, 0.999, 1e-8


def _bf16(x):
    """``x`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    rather than a round trip through ``astype``: a compiler may drop a pair
    of converts as excess precision, and the rounding is what the
    reference models here."""
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=8,
                                    mantissa_bits=7)


def _ss(x):
    return jnp.sum(jnp.square(x.astype(F32)))


class _Jits:
    """The reference's jitted pieces, built once per (model, mode): one
    layer forward and one layer vjp per stack."""

    def __init__(self, ref: RevFFN):
        self.ref = ref
        self.shadow = jax.jit(lambda t: jax.tree_util.tree_map(_bf16, t))
        self.rows = jax.jit(lambda table, tok: _bf16(table[tok]))
        self.fwd, self.vjp = {}, {}
        for name, _, kind in ref.stacks:
            layer = functools.partial(ref.layer, kind)

            def vjp_layer(lp, x1, x2, d1, d2, layer=layer):
                _, f = jax.vjp(layer, lp, x1, x2)
                return f((d1, d2))
            self.fwd[name] = jax.jit(layer)
            self.vjp[name] = jax.jit(vjp_layer)

        def vjp_tail(top, y1, y2, tok, msk):
            loss, f = jax.vjp(lambda t, a, b: ref.tail(t, a, b, tok, msk),
                              top, y1, y2)
            dt, d1, d2 = f(jnp.ones((), F32))
            return loss, dt, d1, d2
        self.tail = jax.jit(vjp_tail)
        self.embed_grad = jax.jit(
            lambda dh, tok: jnp.zeros((ref.V, ref.d), F32).at[tok].add(dh))
        self.sumsq = jax.jit(lambda t: jax.tree_util.tree_map(_ss, t))


_JITS: Dict = {}


def _jits(ref: RevFFN) -> _Jits:
    key = (type(ref), ref.mode, json.dumps(ref.cfg, sort_keys=True))
    if key not in _JITS:
        _JITS[key] = _Jits(ref)
    return _JITS[key]


def _update_fn(opt: dict):
    name = opt["name"]
    lr = float(opt["lr"])
    if name == "lomo":
        def upd(w, g, st, scale, mask, t):
            return w - lr * scale * g * mask, st
    elif name == "adamw":
        b1 = float(opt.get("b1", B1_DEFAULT))
        b2 = float(opt.get("b2", B2_DEFAULT))
        eps = float(opt.get("eps", EPS_DEFAULT))
        wd = float(opt.get("weight_decay", 0.0))

        def upd(w, g, st, scale, mask, t):
            gf = g * scale
            m = b1 * st["m"] + (1 - b1) * gf
            v = b2 * st["v"] + (1 - b2) * gf * gf
            mh = m / (1 - b1 ** t)
            vh = v / (1 - b2 ** t)
            u = mh / (jnp.sqrt(vh) + eps)
            if wd:
                u = u + wd * w
            return _bf16(w - lr * u * mask), {"m": m, "v": v}
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    return jax.jit(upd, donate_argnums=(0, 2))


def run_steps(ref: RevFFN, opt: dict, seed: int, batches: List[dict],
              fault: Optional[str] = None) -> dict:
    """Run ``len(batches)`` steps from the seed's weights.

    Returns ``losses`` (one per step), per-leaf norms of the first step's
    gradient as the optimizer takes it (``first_grad``: clipped, and masked
    for LoMo, whose state keeps only the masked step) and unclipped
    (``raw_grad``), and ``change``: the norm of each leaf's move over all
    the steps.  Leaves are 'embed', 'final_norm', 'lm_head' and
    '<stack>.<i>.<path>' (``RevFFN.reading``).  ``fault="half"`` takes the
    loss over half of the batch (half of the rows, or half of the positions
    of a single row)."""
    J = _jits(ref)
    lomo = opt["name"] == "lomo"
    frozen = tuple(opt.get("frozen", ()))
    clip = float(opt.get("clip_norm", 0.0) or 0.0)
    upd = _update_fn(opt)
    masked = lambda name: any(p in frozen for p in name.split("/"))

    # every layer in the walk's order: (stack, index in the stack, leaves)
    order = [(name, i, ref.leaves_of(name))
             for name, depth, _ in ref.stacks for i in range(depth)]

    def fresh():
        top = {l.path[0]: ref.draw_top(seed, l) for l in ref.top_leaves()}
        layers = [{ref.lname(l): ref.draw_layer(seed, l, i) for l in leaves}
                  for _, i, leaves in order]
        return top, layers

    top, layers = fresh()
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    st_top = {k: {"m": jnp.zeros_like(v), "v": jnp.zeros_like(v)}
              for k, v in top.items()} if not lomo else {k: {} for k in top}
    st_lay = [{k: ({"m": jnp.zeros_like(v), "v": jnp.zeros_like(v)}
                   if not lomo else {}) for k, v in lp.items()}
              for lp in layers]
    del zeros
    weights = J.shadow if lomo else (lambda t: t)
    out = {"losses": [], "first_grad": {}, "raw_grad": {}}

    for t, batch in enumerate(batches, start=1):
        tok = jnp.asarray(batch["tokens"])
        msk = np.array(batch["loss_mask"], np.float32)
        if fault == "half":
            B, S = msk.shape
            if B >= 2:
                msk[B // 2:] = 0.0
            else:
                msk[:, S // 2:] = 0.0
        msk = jnp.asarray(msk)

        h0 = J.rows(top["embed"], tok)
        x1, x2 = h0[..., :ref.half], h0[..., ref.half:]
        xs = []
        for j, (name, _, _) in enumerate(order):
            xs.append((x1, x2))
            x1, x2 = J.fwd[name](weights(layers[j]), x1, x2)
        tw = weights({"final_norm": top["final_norm"],
                      "lm_head": top["lm_head"]})
        loss, dtop, dy1, dy2 = J.tail(tw, x1, x2, tok, msk)
        out["losses"].append(float(loss))

        # pass 1: per-leaf squared norms of the whole gradient
        sq = {k: float(v) for k, v in J.sumsq(dtop).items()}
        del dtop
        d1, d2 = dy1, dy2
        for j in reversed(range(len(order))):
            name, i, _ = order[j]
            dlp, d1, d2 = J.vjp[name](weights(layers[j]), *xs[j], d1, d2)
            for k, v in J.sumsq(dlp).items():
                sq[ref.reading(name, i, k)] = float(v)
            del dlp
        dh0 = jnp.concatenate([d1, d2], -1)
        sq["embed"] = float(J.sumsq(J.embed_grad(dh0, tok)))
        gnorm = float(np.sqrt(sum(sq.values())))
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
        if t == 1:
            for k, v in sq.items():
                out["raw_grad"][k] = float(np.sqrt(v))
                keep = 0.0 if (lomo and masked(k.split(".")[-1])) else 1.0
                out["first_grad"][k] = float(np.sqrt(v)) * scale * keep
            out["grad_norm"] = gnorm

        # pass 2: the update, one piece at a time
        tf = jnp.float32(t)
        _, dtop, _, _ = J.tail(tw, x1, x2, tok, msk)
        del tw
        for k in ("final_norm", "lm_head"):
            mk = 0.0 if masked(k) else 1.0
            top[k], st_top[k] = upd(top[k], dtop[k], st_top[k], scale, mk, tf)
        del dtop
        d1, d2 = dy1, dy2
        for j in reversed(range(len(order))):
            dlp, d1, d2 = J.vjp[order[j][0]](weights(layers[j]), *xs[j],
                                             d1, d2)
            for k in list(layers[j]):
                mk = 0.0 if masked(k) else 1.0
                layers[j][k], st_lay[j][k] = upd(layers[j][k], dlp[k],
                                                 st_lay[j][k], scale, mk, tf)
            del dlp
        g = J.embed_grad(jnp.concatenate([d1, d2], -1), tok)
        top["embed"], st_top["embed"] = upd(top["embed"], g, st_top["embed"],
                                            scale, 0.0 if masked("embed")
                                            else 1.0, tf)
        del g, xs

    # the move of every leaf over the steps, against the seed's weights
    change = {}
    for l in ref.top_leaves():
        k = l.path[0]
        change[k] = float(jnp.sqrt(_ss(top[k] - ref.draw_top(seed, l))))
    for (name, i, leaves), lp in zip(order, layers):
        for l in leaves:
            k = ref.lname(l)
            change[ref.reading(name, i, k)] = float(jnp.sqrt(_ss(
                lp[k] - ref.draw_layer(seed, l, i))))
    out["change"] = change
    return out
