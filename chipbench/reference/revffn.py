"""Plain float32 reference of the RevFFN model and its fine-tune step.

It follows the RevFFN paper (arXiv:2512.20920) on a published decoder:
the hidden state splits into two streams of width d/2, and each layer is

    Y1 = X1 + P_down_a( Attn( P_up_a(norm1(X1)) as queries,
                              P_up_a(norm2(X2)) as keys and values ) )
    Y2 = X2 + P_down_m( FFN( P_up_m(norm_mlp(Y1)) ) )

with RMS norms that scale by (1 + w), rotary positions (rotate-half), grouped
query attention (causal, and windowed where the configuration says so), and
an FFN that is SwiGLU (a ``dense`` layer) or a top-k mixture of SwiGLU
experts with shared experts (a ``moe`` layer).  The layers come as stacks in
order (``chipbench.shape``: leading dense layers, then the rest), and a MoE
layer routes over every published expert but adds only the part of the
experts this chip holds.  The output is rms_norm(concat(Y1, Y2)) @ lm_head,
and the loss is the masked next-token cross-entropy.  The gradients come
from the plain chain rule layer by layer (no inversion of the coupling), in
float32 with ``Precision.HIGHEST``.  Departures from the source model that
the program makes are stated in the configuration files (``reduced``).

It imports nothing of the program and takes nothing the program made: the
weights are drawn again from the seed (``chipbench.weights``).

``mode="fp8"`` is the control: every matmul operand goes through float8
(e4m3 forward, e5m2 for the cotangent) with a per-tensor scale, the step that
a lower-precision path would take.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import shape
from chipbench import weights as W
from chipbench.weights import Leaf

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16


# ------------------------------------------------------------------ fp8

def _fake_quant(x, dt):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dt).max), 1.0)
    return (x / s).astype(dt).astype(F32) * s


@jax.custom_vjp
def _q8(x):
    return _fake_quant(x, jnp.float8_e4m3fn)


def _q8_fwd(x):
    return _fake_quant(x, jnp.float8_e4m3fn), None


def _q8_bwd(_, ct):
    return (_fake_quant(ct, jnp.float8_e5m2),)


_q8.defvjp(_q8_fwd, _q8_bwd)


# ------------------------------------------------------------------ model

class RevFFN:
    """Sizes from a configuration file (Hugging Face key names), and the
    model as an ordered list of stacks (``chipbench.shape``): each stack has
    its own depth and kind, and the layer of each kind is its own function.

    A reference for another architecture subclasses this one and overrides
    ``attention`` (with ``attn_leaves``), ``route`` or ``shared``; the walk,
    the weights and the readings stay as they are."""

    def __init__(self, cfg: dict, mode: str = "f32"):
        assert mode in ("f32", "fp8"), mode
        self.cfg = cfg
        self.mode = mode
        self.d = cfg["hidden_size"]
        self.half = self.d // 2
        self.H = cfg["num_attention_heads"]
        self.KV = cfg["num_key_value_heads"]
        self.hd = cfg.get("head_dim") or self.d // self.H
        self.V = cfg["vocab_size"]
        self.eps = cfg["rms_norm_eps"]
        self.theta = float(cfg["rope_theta"])
        self.window = (cfg.get("sliding_window")
                       if cfg.get("use_sliding_window", True) else None)
        self.qkv_bias = bool(cfg.get("qkv_bias", False))
        self.stacks = shape.stacks(cfg)
        self.held, self.routed = shape.routed_experts(cfg)
        self.top_k = int(cfg.get("num_experts_per_tok", 0) or 0)
        self.fe = cfg.get("moe_intermediate_size")
        self.fs = shape.shared_width(cfg)
        self.shared_gate = shape.shared_gated(cfg)
        self.ff = cfg["intermediate_size"]
        self.norm_topk = bool(cfg.get("norm_topk_prob", False))
        self.q_chunk = 512
        self.loss_chunk = 1024

    # -------------------------------------------------------------- layout

    def layout(self) -> List[Leaf]:
        out = [Leaf(("embed",), (), (self.V, self.d), "embed"),
               Leaf(("final_norm",), (), (self.d,), "norm"),
               Leaf(("lm_head",), (), (self.d, self.V), "fan_in")]
        for name, _, kind in self.stacks:
            out += self.stack_leaves(name, kind)
        return out

    def stack_leaves(self, name: str, kind: str) -> List[Leaf]:
        """One stack's leaves, each with a leading axis over its layers."""
        d, half = self.d, self.half
        S, lay = ("stacks", name), ("layers",)
        out = [Leaf(S + (n,), lay, (half,), "norm")
               for n in ("norm1", "norm2", "norm_mlp")]
        for a in ("attn_ad", "mlp_ad"):
            out.append(Leaf(S + (a, "p_up"), lay, (half, d), "fan_in"))
            out.append(Leaf(S + (a, "p_down"), lay, (d, half), "small"))
        return out + self.attn_leaves(S, kind) + self.ffn_leaves(S, kind)

    def attn_leaves(self, S, kind: str) -> List[Leaf]:
        if shape.latent(self.cfg):
            raise NotImplementedError(
                "latent attention needs a reference that overrides "
                "attn_leaves and attention")
        d, H, KV, hd = self.d, self.H, self.KV, self.hd
        A, lay = S + ("attn",), ("layers",)
        out = [Leaf(A + ("wq",), lay, (d, H * hd), "fan_in"),
               Leaf(A + ("wk",), lay, (d, KV * hd), "fan_in"),
               Leaf(A + ("wv",), lay, (d, KV * hd), "fan_in"),
               Leaf(A + ("wo",), lay, (H * hd, d), "fan_in")]
        if self.qkv_bias:
            out += [Leaf(A + ("bq",), lay, (H * hd,), "bias"),
                    Leaf(A + ("bk",), lay, (KV * hd,), "bias"),
                    Leaf(A + ("bv",), lay, (KV * hd,), "bias")]
        return out

    def ffn_leaves(self, S, kind: str) -> List[Leaf]:
        """A ``moe`` layer's router has an axis over every published expert
        (``routed``), its experts one over the experts held (``experts``)."""
        d, lay = self.d, ("layers",)
        if kind == "dense":
            M, ff = S + ("mlp",), self.ff
            return [Leaf(M + ("w_gate",), lay, (d, ff), "fan_in"),
                    Leaf(M + ("w_up",), lay, (d, ff), "fan_in"),
                    Leaf(M + ("w_down",), lay, (ff, d), "fan_in")]
        M, fe, fs = S + ("moe",), self.fe, self.fs
        ex = ("layers", "experts")
        out = [Leaf(M + ("router",), ("layers", "routed"), (d,), "router",
                    experts_last=True),
               Leaf(M + ("w_gate",), ex, (d, fe), "fan_in"),
               Leaf(M + ("w_up",), ex, (d, fe), "fan_in"),
               Leaf(M + ("w_down",), ex, (fe, d), "fan_in")]
        if fs:
            out += [Leaf(M + ("shared", "w_gate"), lay, (d, fs), "fan_in"),
                    Leaf(M + ("shared", "w_up"), lay, (d, fs), "fan_in"),
                    Leaf(M + ("shared", "w_down"), lay, (fs, d), "fan_in")]
            if self.shared_gate:
                out.append(Leaf(M + ("shared", "gate"), lay, (d, 1),
                                "fan_in"))
        return out

    def top_leaves(self):
        return [l for l in self.layout() if not l.stack]

    def leaves_of(self, stack: str):
        return [l for l in self.layout() if l.stack and l.path[1] == stack]

    def depth(self, leaf: Leaf) -> int:
        """Layers in the leaf's stack."""
        return next(n for name, n, _ in self.stacks if name == leaf.path[1])

    def count(self, axis: str) -> int:
        """Entries along a stack axis after the layers: every published
        expert (``routed``) or the experts held (``experts``)."""
        return {"routed": self.routed, "experts": self.held}[axis]

    @staticmethod
    def lname(leaf: Leaf) -> str:
        """Name of a leaf inside one layer ('attn/wq', 'moe/shared/gate')."""
        return "/".join(leaf.path[2:])

    @staticmethod
    def reading(stack: str, i: int, lname: str) -> str:
        """Name of one layer's leaf among the readings ('layers.0.attn/wq')."""
        return f"{stack}.{i}.{lname}"

    # -------------------------------------------------------------- weights

    def draw_top(self, seed: int, leaf: Leaf):
        """The leaf as drawn for the program (bf16), as float32."""
        return jax.jit(lambda k: W.draw(W.path_key(k, leaf.path), leaf.core,
                                        leaf.kind, BF16).astype(F32))(
            W.seed_key(seed))

    def draw_layer(self, seed: int, leaf: Leaf, layer: int):
        """One layer of a stacked leaf, with the reference's experts, in
        the layout the reference computes with (router as (d, routed))."""
        counts = tuple(self.count(a) for a in leaf.stack[1:])
        ck = (leaf, counts)
        if ck not in _DRAW_CACHE:
            def f(k, layer):
                x = W.draw_stacked(W.path_key(k, leaf.path), leaf, counts,
                                   BF16, index=(layer,))
                return x.astype(F32)
            _DRAW_CACHE[ck] = jax.jit(f)
        return _DRAW_CACHE[ck](W.seed_key(seed), jnp.uint32(layer))

    # -------------------------------------------------------------- pieces

    def mm(self, eq, a, b):
        if self.mode == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=F32)

    def rms(self, x, s):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * (1.0 + s)

    def rope(self, x, pos):
        half = x.shape[-1] // 2
        freqs = jnp.exp(-math.log(self.theta)
                        * jnp.arange(half, dtype=F32) / half)
        ang = pos[..., None].astype(F32) * freqs
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def attention(self, kind, lp, x1, x2):
        B, S, _ = x1.shape
        H, KV, hd = self.H, self.KV, self.hd
        G = H // KV
        up = lp["attn_ad/p_up"]
        qi = self.mm("bsh,hd->bsd", self.rms(x1, lp["norm1"]), up)
        ki = self.mm("bsh,hd->bsd", self.rms(x2, lp["norm2"]), up)
        q = self.mm("bsd,dk->bsk", qi, lp["attn/wq"])
        k = self.mm("bsd,dk->bsk", ki, lp["attn/wk"])
        v = self.mm("bsd,dk->bsk", ki, lp["attn/wv"])
        if self.qkv_bias:
            q, k, v = (q + lp["attn/bq"], k + lp["attn/bk"],
                       v + lp["attn/bv"])
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        q = self.rope(q.reshape(B, S, H, hd), pos)
        k = self.rope(k.reshape(B, S, KV, hd), pos)
        v = v.reshape(B, S, KV, hd)
        kpos = jnp.arange(S)
        qc = min(self.q_chunk, S)
        assert S % qc == 0

        @jax.checkpoint
        def chunk(args):
            qb, q0 = args
            qg = qb.reshape(B, qc, KV, G, hd)
            s = self.mm("bqkgh,bskh->bkgqs", qg, k) * hd ** -0.5
            qpos = q0 + jnp.arange(qc)
            mask = qpos[:, None] >= kpos[None, :]
            if self.window:
                mask &= (qpos[:, None] - kpos[None, :]) < self.window
            s = jnp.where(mask, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return self.mm("bkgqs,bskh->bqkgh", p, v).reshape(B, qc, H * hd)

        nq = S // qc
        qs = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
        out = jax.lax.map(chunk, (qs, jnp.arange(nq) * qc))
        out = out.transpose(1, 0, 2, 3).reshape(B, S, H * hd)
        o = self.mm("bsk,kd->bsd", out, lp["attn/wo"])
        return self.mm("bsd,dh->bsh", o, lp["attn_ad/p_down"])

    def swiglu(self, x, wg, wu, wd):
        h = jax.nn.silu(self.mm("td,df->tf", x, wg)) * self.mm(
            "td,df->tf", x, wu)
        return self.mm("tf,fd->td", h, wd)

    def route(self, lp, x):
        """(gate values, expert ids), each (T, top_k), over every published
        expert: softmax of the router's logits, top-k, renormalised over
        the k chosen where the configuration says so."""
        if self.cfg.get("scoring_func", "softmax") != "softmax":
            raise NotImplementedError(
                f"scoring_func {self.cfg['scoring_func']!r} needs a "
                "reference that overrides route")
        logits = self.mm("td,de->te", x, lp["moe/router"])
        probs = jax.nn.softmax(logits, axis=-1)
        gv, gi = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk:
            gv = gv / (jnp.sum(gv, -1, keepdims=True) + 1e-9)
        return gv, gi

    def experts(self, lp, x, gv, gi):
        """The experts held, weighted by their gates: an id outside
        0 .. held-1 is an expert held elsewhere and adds nothing here."""
        held = lp["moe/w_gate"].shape[0]
        comb = jnp.sum(jax.nn.one_hot(gi, held, dtype=F32)
                       * gv[..., None], axis=1)                 # (T, held)
        return _experts(self, x, comb, lp["moe/w_gate"], lp["moe/w_up"],
                        lp["moe/w_down"])

    def shared(self, lp, x):
        """The shared experts, whole (None where the model has none)."""
        if not self.fs:
            return None
        ys = self.swiglu(x, lp["moe/shared/w_gate"], lp["moe/shared/w_up"],
                         lp["moe/shared/w_down"])
        if not self.shared_gate:
            return ys
        return jax.nn.sigmoid(self.mm("td,do->to", x,
                                      lp["moe/shared/gate"])) * ys

    def moe(self, lp, x):
        y = self.experts(lp, x, *self.route(lp, x))
        ys = self.shared(lp, x)
        return y if ys is None else y + ys

    def ffn(self, kind, lp, y1):
        B, S, _ = y1.shape
        h = self.mm("bsh,hd->bsd", self.rms(y1, lp["norm_mlp"]),
                    lp["mlp_ad/p_up"])
        x = h.reshape(B * S, self.d)
        if kind == "moe":
            y = self.moe(lp, x)
        else:
            y = self.swiglu(x, lp["mlp/w_gate"], lp["mlp/w_up"],
                            lp["mlp/w_down"])
        y = y.reshape(B, S, self.d)
        return self.mm("bsd,dh->bsh", y, lp["mlp_ad/p_down"])

    def layer(self, kind, lp, x1, x2):
        """One layer of a stack of ``kind``."""
        y1 = x1 + self.attention(kind, lp, x1, x2)
        y2 = x2 + self.ffn(kind, lp, y1)
        return y1, y2

    def tail(self, top, y1, y2, tokens, mask):
        """Masked next-token cross-entropy from the stack's output."""
        B, S = tokens.shape
        h = self.rms(jnp.concatenate([y1, y2], -1), top["final_norm"])
        tgt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        valid = jnp.concatenate([mask[:, 1:], mask[:, :1]], axis=1)
        valid = valid.astype(F32).at[:, -1].set(0.0)
        c = min(self.loss_chunk, S)
        nc = S // c
        hs = h.reshape(B, nc, c, self.d).transpose(1, 0, 2, 3)
        ts = tgt.reshape(B, nc, c).transpose(1, 0, 2)

        @jax.checkpoint
        def nll(args):
            hb, tb = args
            lg = self.mm("bsd,dv->bsv", hb, top["lm_head"])
            gold = jnp.take_along_axis(lg, tb[..., None], -1)[..., 0]
            return jax.nn.logsumexp(lg, -1) - gold

        n = jax.lax.map(nll, (hs, ts)).transpose(1, 0, 2).reshape(B, S)
        return jnp.sum(n * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    def logits(self, top, y1, y2):
        h = self.rms(jnp.concatenate([y1, y2], -1), top["final_norm"])
        return self.mm("bsd,dv->bsv", h, top["lm_head"])


# ------------------------------------------------------ experts, by hand

def _experts(ref: RevFFN, x, comb, wg, wu, wd):
    """sum_e comb[:, e] * SwiGLU_e(x) over every expert and every token.

    The backward is written out (SwiGLU's chain rule, one expert at a
    time) so that no per-expert activation is kept: autodiff of a scan
    would stack each expert's inputs.  ``tests/test_reference.py`` checks
    it against ``jax.grad`` of the plain sum."""
    return _experts_vjp(ref.mode, x, comb, wg, wu, wd)


def _mm(mode, eq, a, b):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts_vjp(mode, x, comb, wg, wu, wd):
    def body(y, xs):
        g, u, dn, c = xs
        a = _mm(mode, "td,df->tf", x, g)
        b = _mm(mode, "td,df->tf", x, u)
        o = _mm(mode, "tf,fd->td", jax.nn.silu(a) * b, dn)
        return y + c[:, None] * o, None
    y, _ = jax.lax.scan(body, jnp.zeros(x.shape, F32), (wg, wu, wd, comb.T))
    return y


def _experts_fwd(mode, x, comb, wg, wu, wd):
    return _experts_vjp(mode, x, comb, wg, wu, wd), (x, comb, wg, wu, wd)


def _experts_bwd(mode, res, dy):
    x, comb, wg, wu, wd = res

    def body(dx, xs):
        g, u, dn, c = xs
        a = _mm(mode, "td,df->tf", x, g)
        b = _mm(mode, "td,df->tf", x, u)
        sa = jax.nn.sigmoid(a)
        silu = a * sa
        hdn = silu * b
        o = _mm(mode, "tf,fd->td", hdn, dn)
        dc = jnp.sum(o * dy, axis=-1)
        do = c[:, None] * dy
        dwd = _mm(mode, "tf,td->fd", hdn, do)
        dh = _mm(mode, "td,fd->tf", do, dn)
        da = dh * b * (sa * (1.0 + a * (1.0 - sa)))
        db = dh * silu
        dwg = _mm(mode, "td,tf->df", x, da)
        dwu = _mm(mode, "td,tf->df", x, db)
        dx = dx + _mm(mode, "tf,df->td", da, g) + _mm(mode, "tf,df->td", db, u)
        return dx, (dwg, dwu, dwd, dc)

    dx, (dwg, dwu, dwd, dc) = jax.lax.scan(
        body, jnp.zeros(x.shape, F32), (wg, wu, wd, comb.T))
    return dx, dc.T, dwg, dwu, dwd


_experts_vjp.defvjp(_experts_fwd, _experts_bwd)


_DRAW_CACHE: Dict = {}


def train_steps(ref: RevFFN, opt: dict, seed: int, batches, fault=None):
    """The job's first steps in float32 (``revffn_train.run_steps``)."""
    from chipbench.reference.revffn_train import run_steps
    return run_steps(ref, opt, seed, batches, fault=fault)


def _served_logits(ref: RevFFN, seed: int, seqs, pad_to: int):
    """Float32 logits of every position of each sequence (a (1, pad_to)
    row each, padded at the end, which causal attention ignores), computed
    layer by layer with each layer's weights drawn once."""
    tops = {l.path[0]: l for l in ref.top_leaves()}
    emb = ref.draw_top(seed, tops["embed"])
    xs = []
    for s in seqs:
        tok = np.zeros((1, pad_to), np.int32)
        tok[0, :len(s)] = s
        h = emb[jnp.asarray(tok)]
        xs.append((h[..., :ref.half], h[..., ref.half:]))
    del emb
    for name, depth, kind in ref.stacks:
        fwd = jax.jit(functools.partial(ref.layer, kind))
        leaves = ref.leaves_of(name)
        for i in range(depth):
            lp = {ref.lname(l): ref.draw_layer(seed, l, i) for l in leaves}
            xs = [fwd(lp, a, b) for a, b in xs]
            del lp
    top = {k: ref.draw_top(seed, tops[k]) for k in ("final_norm", "lm_head")}
    head = jax.jit(ref.logits)
    return [head(top, a, b)[0] for a, b in xs]


def served_gap(ref: RevFFN, seed: int, items, control=None) -> float:
    """Widest gap, over every served token of ``items`` ((prompt, served)
    pairs), by which the served token's reference logit lies below the
    reference's best logit at its position.  With ``control`` (a reference
    in another precision), the gap is of the token the control puts first
    instead of the served one."""
    if not items:
        return float("inf")
    seqs = [np.concatenate([p, np.asarray(g[:-1], np.int32)])
            for p, g in items]
    pad_to = max(512, -(-max(len(s) for s in seqs) // 512) * 512)
    logits = _served_logits(ref, seed, seqs, pad_to)
    picks = None
    if control is not None:
        picks = [np.asarray(jnp.argmax(x, -1))
                 for x in _served_logits(control, seed, seqs, pad_to)]
    worst = 0.0
    for j, ((p, g), lg) in enumerate(zip(items, logits)):
        pos = np.arange(len(p) - 1, len(p) - 1 + len(g))
        lg = np.asarray(lg[pos[0]:pos[-1] + 1], np.float64)
        tok = (np.asarray(g) if picks is None
               else picks[j][pos[0]:pos[-1] + 1])
        gap = lg.max(-1) - lg[np.arange(len(g)), tok]
        worst = max(worst, float(gap.max()))
    return worst
