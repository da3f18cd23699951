"""Models whose layers differ: stacks of their own depth and kind, experts
held under a router over every published expert, and q.k and value heads
of different sizes, in the reference, the driver's layout check and the
counts.  ``data/tiny-dense-first.json`` has one dense layer, then two MoE
layers that route over 8 experts of which this chip holds 4."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, counts, shape, tracing
from chipbench.kinds.train import program_counts
from chipbench.peaks import peaks_for
from chipbench.reference import revffn
from tiny import DATA

SEED = 2**33 + 5
PV5E = {"kind": "TPU v5 lite"}
PEAKS = peaks_for(PV5E["kind"])


def _cfg():
    return json.load(open(DATA / "tiny-dense-first.json"))


class NoAttentionSigmoidRouting(revffn.RevFFN):
    """Another architecture by overrides alone: no attention, sigmoid
    router scores renormalised over the k chosen, no shared experts."""

    def attention(self, kind, lp, x1, x2):
        return jnp.zeros_like(x1)

    def route(self, lp, x):
        s = jax.nn.sigmoid(self.mm("td,de->te", x, lp["moe/router"]))
        gv, gi = jax.lax.top_k(s, self.top_k)
        return gv / jnp.sum(gv, -1, keepdims=True), gi

    def shared(self, lp, x):
        return None


def _batch(ref, B=2, S=64):
    rng = np.random.default_rng(7)
    tok = rng.integers(0, ref.V, (B, S), dtype=np.int32)
    msk = (rng.random((B, S)) < 0.8).astype(np.float32)
    return {"tokens": tok, "loss_mask": msk}


def _whole_model_grads(ref, batch):
    """Loss and gradient of the whole model as one function, by autodiff."""
    top = {l.path[0]: ref.draw_top(SEED, l) for l in ref.top_leaves()}
    stacks = {name: [{ref.lname(l): ref.draw_layer(SEED, l, i)
                      for l in ref.leaves_of(name)} for i in range(depth)]
              for name, depth, _ in ref.stacks}
    tok, msk = jnp.asarray(batch["tokens"]), jnp.asarray(batch["loss_mask"])

    def loss(top, stacks):
        h = top["embed"][tok]
        x1, x2 = h[..., :ref.half], h[..., ref.half:]
        for name, depth, kind in ref.stacks:
            for i in range(depth):
                x1, x2 = ref.layer(kind, stacks[name][i], x1, x2)
        return ref.tail(top, x1, x2, tok, msk)

    value, (gtop, gstacks) = jax.value_and_grad(loss, (0, 1))(top, stacks)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in gtop.items()}
    for name, layers in gstacks.items():
        for i, g in enumerate(layers):
            for k, v in g.items():
                norms[ref.reading(name, i, k)] = float(jnp.linalg.norm(v))
    return float(value), norms


@pytest.mark.parametrize("cls", [revffn.RevFFN, NoAttentionSigmoidRouting])
def test_run_steps_matches_autodiff_of_the_whole_model(cls):
    ref = cls(_cfg())
    batch = _batch(ref)
    out = revffn.train_steps(ref, {"name": "adamw", "lr": 1e-3}, SEED,
                             [batch])
    loss, norms = _whole_model_grads(ref, batch)
    np.testing.assert_allclose(out["losses"][0], loss, rtol=1e-5)
    assert set(out["raw_grad"]) == set(norms)
    assert {k.split(".")[0] for k in norms} >= {"dense", "layers"}
    for k, v in norms.items():
        np.testing.assert_allclose(out["raw_grad"][k], v, rtol=1e-4,
                                   atol=1e-7, err_msg=k)


def test_overrides_change_attention_routing_and_shared_experts():
    base, other = revffn.RevFFN(_cfg()), NoAttentionSigmoidRouting(_cfg())
    assert other.layout() == base.layout()
    batch = _batch(base)
    opt = {"name": "adamw", "lr": 1e-3}
    a = revffn.train_steps(base, opt, SEED, [batch])
    b = revffn.train_steps(other, opt, SEED, [batch])
    assert a["losses"][0] != b["losses"][0]
    for k, v in b["raw_grad"].items():
        lname = k.split(".")[-1]
        if lname.startswith("attn/") or "/shared/" in lname:
            assert v == 0.0, k
            assert a["raw_grad"][k] > 0.0, k


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two chips hold 4 experts each of a layer's 8: their shares, with the
    shared expert counted once, add up to the layer with all 8 held."""
    cfg = _cfg()
    mine = revffn.RevFFN(cfg)
    whole_cfg = dict(cfg, n_routed_experts=8)
    del whole_cfg["published"]
    whole = revffn.RevFFN(whole_cfg)
    assert (mine.held, mine.routed, whole.held, whole.routed) == (4, 8, 8, 8)
    experts = [whole.lname(l) for l in whole.leaves_of("layers")
               if l.stack[1:] == ("experts",)]
    assert experts == ["moe/w_gate", "moe/w_up", "moe/w_down"]
    lp = {whole.lname(l): whole.draw_layer(SEED, l, 1)
          for l in whole.leaves_of("layers")}
    held = {mine.lname(l): mine.draw_layer(SEED, l, 1)
            for l in mine.leaves_of("layers")}
    for k, v in held.items():      # chip 0's draw: the uncut layer's bits
        want = lp[k][:4] if k in experts else lp[k]
        np.testing.assert_array_equal(np.asarray(v), np.asarray(want))

    x = jax.random.normal(jax.random.PRNGKey(1), (48, whole.d))
    gv, gi = whole.route(lp, x)
    shares = [whole.experts(dict(lp, **{k: lp[k][4 * c:4 * c + 4]
                                        for k in experts}), x, gv, gi - 4 * c)
              for c in range(2)]
    assert all(float(jnp.abs(s).max()) > 0 for s in shares)
    shared = whole.shared(lp, x)
    np.testing.assert_allclose(shares[0] + shares[1] + shared,
                               whole.moe(lp, x), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(mine.moe(held, x), shares[0] + shared,
                               rtol=1e-6, atol=1e-7)


class _Program:
    """Stands in for the program's model: an abstract tree and a dtype."""

    def __init__(self, tree):
        self.tree = tree
        self.cfg = types.SimpleNamespace(dtype="bfloat16")

    def abstract_params(self):
        return self.tree


def _abstract(ref, depth=None, routed=None):
    """The reference's layout as the program would hold it, with the depth
    of stack ``dense`` or the router's width changed where given."""
    tree = {}
    for leaf in ref.layout():
        dims = tuple(leaf.core)
        if leaf.stack:
            n = ref.depth(leaf)
            if depth is not None and leaf.path[1] == "dense":
                n = depth
            extra = ()
            if len(leaf.stack) > 1:
                extra = (ref.count(leaf.stack[1]),)
                if routed is not None and leaf.stack[1] == "routed":
                    extra = (routed,)
            dims = ((n,) + dims + extra if leaf.experts_last
                    else (n,) + extra + dims)
        node = tree
        for p in leaf.path[:-1]:
            node = node.setdefault(p, {})
        node[leaf.path[-1]] = jax.ShapeDtypeStruct(dims, jnp.bfloat16)
    return _Program(tree)


def test_program_counts_take_stacks_of_their_own_depth():
    ref = revffn.RevFFN(_cfg())
    got = program_counts(_abstract(ref), ref)
    S = ("stacks",)
    assert got[S + ("dense", "mlp", "w_up")] == (1,)
    assert got[S + ("layers", "attn", "wq")] == (2,)
    assert got[S + ("layers", "moe", "router")] == (2, 8)
    assert got[S + ("layers", "moe", "w_up")] == (2, 4)
    assert got[("embed",)] == ()


@pytest.mark.parametrize("change", [{"depth": 2}, {"routed": 4}])
def test_program_counts_refuse_a_wrong_depth_or_a_narrow_router(change):
    ref = revffn.RevFFN(_cfg())
    with pytest.raises(ValueError, match="leaf"):
        program_counts(_abstract(ref, **change), ref)


@pytest.mark.parametrize("config,seq,flops", [
    ("qwen2-moe-a2.7b-l2", 8192, 3227467776.0),
    ("h2o-danube-1.8b-l4", 4096, 2803691520.0)])
def test_train_flops_per_token_of_the_cells(config, seq, flops):
    """Pinned to the values before configurations could state stacks."""
    cfg = json.load(open(bench.HERE / "configs" / f"{config}.json"))
    assert counts.train_flops_per_token(cfg, seq) == flops


#: Moonlight-16B-A3B (huggingface.co/moonshotai/Moonlight-16B-A3B,
#: config.json) cut to 1 dense + 5 MoE layers, 8 of 64 experts held
#: (8 chips share each layer) and an eighth of the vocabulary.
MOONLIGHT_CUT = {
    "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 16, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 6,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "kv_lora_rank": 512,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_hidden_layers": 6, "vocab_size": 20480,
    "published": {"n_routed_experts": 64, "num_hidden_layers": 27,
                  "vocab_size": 163840},
    "chips_per_layer": 8}


def test_moonlight_cut_hand_count():
    cfg = MOONLIGHT_CUT
    assert shape.stacks(cfg) == [("dense", 1, "dense"), ("layers", 5, "moe")]
    assert shape.routed_experts(cfg) == (8, 64)
    assert shape.head_sizes(cfg) == (192, 128)
    d, half, H = 2048, 1024, 16
    adapters = 2 * (2 * half * d + d * half) + 2 * (half * d + d * half)
    mla = (2 * d * H * 192              # q, 128 nope + 64 rope a head
           + 2 * d * (512 + 64)         # k/v down to 512, shared rope key
           + 2 * 512 * H * (128 + 128)  # k nope and v up, every head
           + 2 * H * 128 * d)           # o
    dense = 3 * 2 * d * 11264
    moe = (2 * d * 64                   # router over every published expert
           + 6 * 8 / 64 * 3 * 2 * d * 1408   # expected rows, 8 held
           + 3 * 2 * d * 2 * 1408)      # 2 shared experts, no gate
    assert counts.layer_matmul_flops(cfg, "dense") == adapters + mla + dense
    assert counts.layer_matmul_flops(cfg, "moe") == adapters + mla + moe
    keys = 4096.5                       # causal mean over 8192
    att = 2 * H * 192 * keys + 2 * H * 128 * keys
    per_tok = ((adapters + mla + dense + att)
               + 5 * (adapters + mla + moe + att) + 2 * d * 20480)
    assert counts.forward_flops_per_token(cfg, 8192) == per_tok
    assert counts.train_flops_per_token(cfg, 8192) == 3 * per_tok \
        == 3012519936.0


def test_flash_call_takes_qk_and_v_head_sizes_apart():
    B, H, KV, S, qk, v = 1, 16, 16, 8192, 192, 128
    pairs = 8193 / 2 * 8192
    q, o = B * H * S * qk * 2, B * H * S * v * 2
    k, vv = B * KV * S * qk * 2, B * KV * S * v * 2
    lse = B * H * S * 4
    want = {"fwd": (2 * B * H * pairs * (qk + v), q + k + vv + o + lse),
            "dq": (2 * B * H * pairs * (qk + v + qk),
                   q + k + vv + o + 2 * lse + q),
            "dkv": (2 * B * H * pairs * (qk + v + qk + v),
                    q + k + vv + o + 2 * lse + k + vv)}
    for kind, (f, b) in want.items():
        assert counts.flash_call(kind, B, H, KV, S, qk, v) == (f, b), kind


def _recorded():
    return tracing.load(str(DATA / "recorded.xplane.pb"), chips=1)


@pytest.mark.parametrize("cfg", [
    {"num_experts": 8, "num_experts_per_tok": 2},
    {"n_routed_experts": 4, "published": {"n_routed_experts": 8},
     "num_experts_per_tok": 2}])
def test_gmm_roofline_counts_the_experts_held(cfg):
    """The recorded trace's grouped GEMMs, (1024, 256) rows against
    (8, 256, 512) weights, read with either key style: held rows are
    1024 tokens x top-2 x held / published, weights those held."""
    s = _recorded()
    ops = s.kernel_ops(["gmm"])
    held = cfg.get("num_experts") or cfg["n_routed_experts"]
    rows = 1024 * 2 * held / 8
    least = len(ops) * counts.roofline_s(*counts.gmm_call(
        rows, 256, 512, held), PEAKS)
    want = 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
    got = bench.module("metrics", "moe_gmm_roofline").read(
        {"kind": "train", "config": cfg, "trace": s, "device": PV5E,
         "tokens_per_step": 1024})
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("cfg,qk,v", [
    ({"hidden_size": 512, "num_attention_heads": 4, "head_dim": 128},
     128, 128),
    ({"hidden_size": 512, "num_attention_heads": 4, "kv_lora_rank": 64,
      "qk_nope_head_dim": 64, "qk_rope_head_dim": 32, "v_head_dim": 64},
     96, 64)])
def test_flash_roofline_takes_head_sizes_from_the_configuration(cfg, qk, v):
    """The recorded trace's flash calls are (4, 512, 128) a tensor: 4
    heads of 512 positions.  The head sizes are the configuration's, not
    the 128 of the shapes (a latent configuration's 96 / 64 pad to it)."""
    s = _recorded()
    kinds = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}
    ops = s.kernel_ops(list(kinds))
    least = sum(counts.roofline_s(*counts.flash_call(
        kinds[tracing.kernel_kind(o.name)], 1, 4, 4, 512, qk, v),
        PEAKS) for o in ops)
    want = 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
    got = bench.module("metrics", "flash_attn_roofline").read(
        {"kind": "train", "config": cfg, "trace": s, "device": PV5E})
    assert got == pytest.approx(want, rel=1e-12)
