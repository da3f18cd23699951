"""Per-architecture smoke tests (reduced configs, CPU): one forward/train step
asserting output shapes + finite values; decode-vs-train consistency;
RevFFN-vs-plain-autodiff gradient equivalence on the real blocks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCHS, get_config
from repro.models.model import Model


def _batch(cfg, key, B=2, S=16):
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["enc_feats"] = jax.random.normal(
            ks[1], (B, cfg.encoder_seq_len, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["img"] = jax.random.normal(
            ks[1], (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_grad(arch):
    cfg = get_config(arch, reduced=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    B, S = batch["tokens"].shape

    logits = model.forward(params, batch["tokens"],
                           {k: v for k, v in batch.items() if k != "tokens"} or None)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    for g in jax.tree_util.tree_leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen2-moe-a2.7b",
                                  "rwkv6-3b", "zamba2-7b", "whisper-medium",
                                  "llama-3.2-vision-11b", "gemma2-27b"])
def test_decode_matches_train_forward(arch):
    cfg = get_config(arch, reduced=True)
    if cfg.num_experts:
        cfg = cfg.replace(capacity_factor=8.0)   # avoid train-path token drops
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    batch = _batch(cfg, jax.random.PRNGKey(1), B=B, S=S)
    extras = {k: v for k, v in batch.items() if k != "tokens"} or None
    full = model.forward(params, batch["tokens"], extras)
    cache = model.init_cache(params, B, S + 2, extras=extras)
    outs = []
    step = jax.jit(model.decode_step)
    for t in range(S):
        lg, cache = step(params, cache, batch["tokens"][:, t:t + 1])
        outs.append(lg[:, 0])
    inc = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen2-moe-a2.7b",
                                  "rwkv6-3b", "zamba2-7b"])
def test_revffn_grads_match_plain_autodiff(arch):
    """The paper's memory mechanism must not change gradients."""
    cfg = get_config(arch, reduced=True).replace(inverse_fp_iters=8)
    if cfg.num_experts:
        cfg = cfg.replace(capacity_factor=8.0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1), B=2, S=16)
    g1 = jax.grad(lambda p: model.loss(p, batch, save_memory=True))(params)
    g2 = jax.grad(lambda p: model.loss(p, batch, save_memory=False))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "qwen2-moe-a2.7b",
                                  "zamba2-7b"])
def test_adapter_folding_is_exact(arch):
    """Beyond-paper: folding P_up/P_down into the pretrained matmuls must not
    change logits or gradients (linearity/associativity)."""
    cfg = get_config(arch, reduced=True)
    if cfg.num_experts:
        cfg = cfg.replace(capacity_factor=8.0)
    m1, m2 = Model(cfg), Model(cfg.replace(fold_adapters=True))
    params = m1.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(m1.forward(params, toks)),
                               np.asarray(m2.forward(params, toks)),
                               rtol=1e-4, atol=1e-4)
    g1 = jax.grad(lambda p: m1.loss(p, {"tokens": toks}))(params)
    g2 = jax.grad(lambda p: m2.loss(p, {"tokens": toks}))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_standard_baseline_path_runs():
    """SFT baseline: non-reversible blocks, optional remat."""
    cfg = get_config("qwen2-moe-a2.7b", reduced=True).replace(
        reversible=False, remat_policy="block")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    loss, grads = jax.value_and_grad(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))


def test_chunked_attention_and_loss_match_unchunked():
    cfg = get_config("h2o-danube-1.8b", reduced=True)
    m1 = Model(cfg.replace(attn_q_chunk=0, loss_chunk=0))
    m2 = Model(cfg.replace(attn_q_chunk=8, loss_chunk=8))
    params = m1.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1), B=2, S=32)
    l1, l2 = m1.loss(params, batch), m2.loss(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_sliding_window_rolling_cache_long_decode():
    """SWA arch decodes past the window with a rolling buffer == window."""
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(sliding_window=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 24                                  # 3x window
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    full = model.forward(params, toks)            # windowed mask applies
    cache = model.init_cache(params, B, S)        # buffer clamps to window=8
    outs = []
    for t in range(S):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    inc = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc),
                               rtol=2e-3, atol=2e-3)
    assert cache["layers"]["kv"]["k"].shape[2] == 8   # (L, B, buf, kv, hd)


def test_prefill_longer_than_rolling_buffer():
    """SWA: prefill a prompt longer than the window buffer, keep decoding."""
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(sliding_window=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, P, G = 1, 20, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P + G), 0,
                              cfg.vocab_size)
    full = model.forward(params, toks)
    cache = model.init_cache(params, B, P)        # buffer clamps to window
    lg, cache = model.decode_step(params, cache, toks[:, :P])
    outs = [lg[:, i] for i in range(P)]
    for t in range(P, P + G):
        lg, cache = model.decode_step(params, cache, toks[:, t:t + 1])
        outs.append(lg[:, 0])
    inc = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(inc),
                               rtol=2e-3, atol=2e-3)


def test_embed_lookup_accumulates_gradient_rows_in_f32():
    """A token seen 4096 times: its bf16 gradient row is the f32 sum of all
    contributions, rounded once (a bf16 running sum stalls far below)."""
    from repro.models.common import embed_lookup
    table = jnp.zeros((8, 4), jnp.bfloat16)
    tokens = jnp.zeros((2, 2048), jnp.int32).at[:, :3].set(5)
    ct = jnp.full((2, 2048, 4), 0.01, jnp.bfloat16)
    out, vjp = jax.vjp(lambda t: embed_lookup(t, tokens), table)
    (g,) = vjp(ct)
    assert out.shape == (2, 2048, 4) and g.dtype == jnp.bfloat16
    exact = float(jnp.asarray(0.01, jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(g[0], np.float32),
                               exact * (4096 - 6), rtol=4e-3)
    np.testing.assert_allclose(np.asarray(g[5], np.float32), exact * 6,
                               rtol=4e-3)
    assert not np.asarray(g[1:5], np.float32).any()
