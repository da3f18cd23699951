"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 4, 128, 64),       # MHA
    (2, 8, 2, 256, 64),       # GQA 4:1
    (1, 4, 1, 128, 80),       # MQA, non-128 head dim (danube)
    (1, 16, 8, 128, 128),     # 128 head dim
    (1, 2, 2, 512, 112),      # zamba head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(B, H, KV, S, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 4, 256, 64))
    v = jax.random.normal(ks[2], (1, 4, 256, 64))
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap_and_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 64))
    k = jax.random.normal(ks[1], (2, 4, 128, 64))
    v = jax.random.normal(ks[2], (2, 4, 128, 64))
    for kw in (dict(causal=True, softcap=50.0), dict(causal=False)):
        out = ops.flash_attention(q, k, v, **kw)
        want = ref.flash_attention_ref(q, k, v, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(block_q, block_k):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = ops.flash_attention(q, k, v, block_q=block_q, block_k=block_k)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _dense_lse(q, k, *, causal, window, softcap):
    """Per-row logsumexp of the masked scores, (B, H, Sq), as the dense
    reference computes them."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    qg = q.reshape(B, KV, H // KV, Sq, hd)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qg, k) * hd ** -0.5
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    pq, pk = jnp.arange(Sq)[:, None], jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= pq >= pk
    if window is not None:
        mask &= (pq - pk) < window
    s = jnp.where(mask, s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).reshape(B, H, Sq)


@pytest.mark.parametrize("S,H,KV,block_q,block_k,window,softcap", [
    (512, 2, 2, 128, 128, None, None),    # causal, folded: 6 of 16 dead
    (384, 2, 2, 128, 128, None, None),    # odd q-block count: unfolded
    (512, 2, 2, 128, 256, None, None),    # bq != bk
    (512, 2, 2, 256, 128, None, None),
    (512, 2, 2, 128, 128, 130, None),     # window < S: leading tiles dead
    (512, 4, 2, 128, 128, None, 30.0),    # GQA with softcap
])
def test_flash_forward_skips_dead_tiles(S, H, KV, block_q, block_k, window,
                                        softcap):
    """The Pallas forward skips fully masked tiles (and fetches no kv block
    for them); its o and lse still match the tiled fallback and the dense
    reference."""
    hd = 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, H, S, hd))
    k = jax.random.normal(ks[1], (1, KV, S, hd))
    v = jax.random.normal(ks[2], (1, KV, S, hd))
    kw = dict(causal=True, window=window, softcap=softcap)
    o, lse = fa.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                                interpret=True, return_lse=True, **kw)
    o_jax, lse_jax = fa.flash_attention_fwd_jax(q, k, v, block_q=block_q,
                                                **kw)
    want_o = ref.flash_attention_ref(q, k, v, **kw)
    want_lse = _dense_lse(q, k, **kw)
    for got, want in ((o, o_jax), (lse, lse_jax), (o, want_o),
                      (lse, want_lse)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,window,bq,bk,S", [
    (True, None, 128, 128, 8192),     # folded: no dead step
    (True, None, 128, 128, 384),      # odd q-block count: unfolded
    (True, None, 64, 128, 1024),
    (True, 130, 128, 128, 512),
    (True, 4096, 128, 128, 8192),
    (False, 200, 64, 64, 512),
])
def test_flash_forward_walk_visits_each_live_tile_once(causal, window, bq,
                                                        bk, S):
    """The forward's grid walk computes every live tile once, each q block's
    kv tiles in ascending order between its first and last step; a dead
    step fetches the block its predecessor holds (the row's first live
    block at the row's first step), so the pipeline copies nothing for it."""
    nq, nk = S // bq, S // bk
    tile = dict(causal=causal, window=window, bq=bq, bk=bk)
    grid, step = fa._fwd_walk(nq=nq, nk=nk, **tile)
    folded = causal and window is None and bq == bk and nq % 2 == 0
    assert grid == ((nq // 2, nq + 1) if folded else (nq, nk))
    rows = {}
    for a in range(grid[0]):
        for b in range(grid[1]):
            iq, jk, kv, first, last = (int(x) for x in step(a, b))
            if first:
                assert iq not in rows
                rows[iq] = []
                live = [j for j in range(nk) if fa._tile_live(iq, j, **tile)]
                prev = live[0]
            if fa._tile_live(iq, jk, **tile):
                assert kv == jk
                rows[iq].append(jk)
            else:
                assert not folded and kv == prev
            prev = kv
            if last:
                assert rows[iq] == live
    assert sorted(rows) == list(range(nq))


@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 300), (1, 128, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    s = (jax.random.normal(jax.random.PRNGKey(1), shape[-1:]) * 0.2).astype(dtype)
    out = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,H,S,C,chunk", [
    (1, 2, 64, 64, 16), (2, 3, 128, 64, 32), (1, 1, 256, 64, 64),
    (1, 2, 128, 32, 32),
])
def test_rwkv6_scan_sweep(B, H, S, C, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, H, S, C)) * 0.5
    k = jax.random.normal(ks[1], (B, H, S, C)) * 0.5
    v = jax.random.normal(ks[2], (B, H, S, C)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, C))) * 0.98 + 0.01
    u = jax.random.normal(ks[4], (H, C)) * 0.3
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    want, _ = ref.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_flash_backend_in_model_matches_jnp_path():
    """cfg.use_flash_kernel swaps the train-path attention for the Pallas
    kernel (interpret mode on CPU); logits and grads must be unchanged."""
    import jax as _jax
    from repro.configs.base import get_config
    from repro.models.model import Model
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(
        num_layers=2, attn_q_chunk=0)
    m1, m2 = Model(cfg), Model(cfg.replace(use_flash_kernel=True))
    params = m1.init(_jax.random.PRNGKey(0))
    toks = _jax.random.randint(_jax.random.PRNGKey(1), (2, 128), 0,
                               cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(m1.forward(params, toks)),
                               np.asarray(m2.forward(params, toks)),
                               rtol=1e-4, atol=1e-4)
    g1 = _jax.grad(lambda p: m1.loss(p, {"tokens": toks}))(params)
    g2 = _jax.grad(lambda p: m2.loss(p, {"tokens": toks}))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_rwkv_kernel_backend_in_model_matches_jnp_path():
    import jax as _jax
    from repro.configs.base import get_config
    from repro.models.model import Model
    cfg = get_config("rwkv6-3b", reduced=True).replace(num_layers=2)
    m1, m2 = Model(cfg), Model(cfg.replace(use_flash_kernel=True))
    params = m1.init(_jax.random.PRNGKey(0))
    toks = _jax.random.randint(_jax.random.PRNGKey(1), (2, 64), 0,
                               cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(m1.forward(params, toks)),
                               np.asarray(m2.forward(params, toks)),
                               rtol=1e-4, atol=1e-4)
    g1 = _jax.grad(lambda p: m1.loss(p, {"tokens": toks}))(params)
    g2 = _jax.grad(lambda p: m2.loss(p, {"tokens": toks}))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,H,S,P,N,chunk", [
    (1, 2, 128, 64, 32, 32), (2, 3, 256, 64, 64, 64), (1, 1, 128, 32, 16, 128),
])
def test_mamba_ssd_kernel_sweep(B, H, S, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, H, S, P)) * 0.5
    Bt = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Ct = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, H, S)))
    la = -jnp.exp(jax.random.normal(ks[4], (B, H, S)) * 0.5) * dt
    out = ops.mamba_ssd(x, Bt, Ct, dt, la, chunk=chunk)
    want, _ = ref.mamba_ssd_ref(x, Bt, Ct, dt, la)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_mamba_ssd_trainable_grads():
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    B, H, S, P, N = 1, 2, 128, 32, 16
    x = jax.random.normal(ks[0], (B, H, S, P)) * 0.5
    Bt = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Ct = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, H, S)))
    la = -jnp.exp(jax.random.normal(ks[4], (B, H, S)) * 0.3) * dt

    def f_kernel(*a):
        return jnp.sum(jnp.square(ops.mamba_ssd_trainable(*a)))

    def f_ref(*a):
        return jnp.sum(jnp.square(ref.mamba_ssd_ref(*a)[0]))

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(x, Bt, Ct, dt, la)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(x, Bt, Ct, dt, la)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_mamba_ssd_trainable_grads_all_inputs():
    """Full-argnum gradient parity for the oracle-backward wrapper (the
    original test stops at argnums 0-2; dt and log_a ride the same vjp)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    B, H, S, P, N = 1, 2, 64, 32, 16
    x = jax.random.normal(ks[0], (B, H, S, P)) * 0.5
    Bt = jax.random.normal(ks[1], (B, S, N)) * 0.5
    Ct = jax.random.normal(ks[2], (B, S, N)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, H, S)))
    la = -jnp.exp(jax.random.normal(ks[4], (B, H, S)) * 0.3) * dt

    def f_kernel(*a):
        return jnp.sum(jnp.square(ops.mamba_ssd_trainable(*a)))

    def f_ref(*a):
        return jnp.sum(jnp.square(ref.mamba_ssd_ref(*a)[0]))

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2, 3, 4))(x, Bt, Ct, dt, la)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3, 4))(x, Bt, Ct, dt, la)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_rwkv6_scan_trainable_grads():
    """Gradient parity of rwkv6_scan_trainable (Pallas forward, oracle
    backward) vs the pure-ref vjp across every input including the decay w
    and bonus u — previously only the forward was parity-tested."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, H, S, C = 1, 2, 128, 32
    r = jax.random.normal(ks[0], (B, H, S, C)) * 0.5
    k = jax.random.normal(ks[1], (B, H, S, C)) * 0.5
    v = jax.random.normal(ks[2], (B, H, S, C)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, C))) * 0.9 + 0.05
    u = jax.random.normal(ks[4], (H, C)) * 0.3

    def f_kernel(*a):
        return jnp.sum(jnp.square(ops.rwkv6_scan_trainable(*a)))

    def f_ref(*a):
        return jnp.sum(jnp.square(ref.rwkv6_ref(*a)[0]))

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2, 3, 4))(r, k, v, w, u)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3, 4))(r, k, v, w, u)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_rwkv6_extreme_decay_is_stable():
    """Strong decays (w -> 0) must not overflow the chunked form."""
    B, H, S, C = 1, 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    r = jax.random.normal(ks[0], (B, H, S, C))
    k = jax.random.normal(ks[1], (B, H, S, C))
    v = jax.random.normal(ks[2], (B, H, S, C))
    w = jnp.full((B, H, S, C), 0.01)
    u = jax.random.normal(ks[4], (H, C))
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=64)
    want, _ = ref.rwkv6_ref(r, k, v, w, u)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
