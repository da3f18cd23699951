"""Multi-device suite: sharding rules on REAL meshes and the expert-parallel
MoE dispatch path (kernels/moe/ep, DESIGN.md §10).

Every test here carries ``@pytest.mark.multidevice``: tests/conftest.py
re-execs it in a subprocess with 8 forced CPU host devices, so the suite
runs on single-device CI without disturbing the smoke tests.  The CI
``multidevice`` leg pre-sets the flags and runs the whole module in-process.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings as hyp_settings, st
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCHS, get_config
from repro.core import settings
from repro.core.reversible import make_coupled
from repro.distributed import sharding as shd
from repro.launch.mesh import make_debug_mesh, make_mesh
from repro.models import moe as moe_lib
from repro.models.model import Model
from repro.models.spec import initialize

pytestmark = pytest.mark.multidevice


@pytest.fixture(autouse=True)
def _reset_ep_mesh():
    yield
    settings.set_ep_mesh(None)


def _moe_cfg(ep: int = 0, **kw):
    cfg = get_config("qwen2-moe-a2.7b", reduced=True).replace(
        expert_parallel=ep, **kw)
    return cfg


def _ep_mesh(ep: int):
    assert len(jax.devices()) % ep == 0
    mesh = make_debug_mesh(data=len(jax.devices()) // ep, expert=ep)
    settings.set_ep_mesh(mesh)
    return mesh


# ================================================= sharding on real meshes

@pytest.mark.parametrize("shape,axes", [((2, 4), ("data", "model")),
                                        ((8, 1), ("data", "model"))],
                         ids=["2x4", "8x1"])
def test_param_pspecs_place_on_real_mesh(shape, axes):
    """Every arch's param specs must be *placeable* on a real mesh: each
    NamedSharding shard_shape call validates divisibility against actual
    devices, not the _FakeMesh arithmetic of tests/test_sharding.py."""
    mesh = make_mesh(shape, axes)
    for arch in ARCHS:
        model = Model(get_config(arch))
        aparams = model.abstract_params()
        pspecs = shd.param_pspecs(model.logical_axes(), aparams, mesh)
        for sds, sp in zip(
                jax.tree_util.tree_leaves(aparams),
                jax.tree_util.tree_leaves(
                    pspecs, is_leaf=lambda x: isinstance(x, P))):
            shard = NamedSharding(mesh, sp).shard_shape(sds.shape)
            assert all(s >= 1 for s in shard), (arch, sds.shape, sp)


def test_jit_loss_sharded_2x4():
    """End-to-end: params placed per param_pspecs, batch per batch_pspec,
    jitted reversible loss + grad on a real 2x4 mesh — the
    reversible-recompute-under-sharding interaction that used to ship
    untested (conftest pinned everything to one device)."""
    mesh = make_debug_mesh(data=2, model=4)
    cfg = get_config("qwen2-moe-a2.7b", reduced=True).replace(
        num_layers=2, moe_backend="grouped")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shardings = shd.param_shardings(model.logical_axes(),
                                    model.abstract_params(), mesh)
    params = jax.device_put(params, shardings)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64),
                                          0, cfg.vocab_size)}
    bspec = shd.batch_pspec(mesh, 4, 2)
    assert bspec == P("data", None)
    batch = jax.device_put(batch, NamedSharding(mesh, bspec))
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, b: model.loss(p, b)))(params, batch)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_model_only_tp_mesh_cache_placement():
    """GQA kv fallback of cache_pspecs on a REAL model-only TP mesh: the
    decode cache must be placeable when kv heads don't divide the model
    axis (sequence-dim fallback) and the batch has no data axis to take."""
    mesh = make_mesh((8,), ("model",))
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(num_layers=2)
    assert cfg.num_kv_heads == 2                    # 2 % 8 != 0 -> fallback
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(params, batch_size=2, buf_len=64)
    cspecs = shd.cache_pspecs(cache, mesh, 2, kv_heads=cfg.num_kv_heads)
    placed = jax.tree_util.tree_map(
        lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)),
        cache, cspecs)
    assert len(jax.tree_util.tree_leaves(placed)) == \
        len(jax.tree_util.tree_leaves(cache))
    assert shd.batch_pspec(mesh, 4, 2) == P()       # nothing to shard over


# ================================================= expert-parallel dispatch

@pytest.mark.parametrize("ep", [2, 4, 8])
def test_ep_forward_matches_oracle(ep):
    """EP ∈ {2,4,8} (8 = one expert per device) against the dense oracle."""
    cfg = _moe_cfg(ep=ep)
    _ep_mesh(ep)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 0.5
    y, _aux = moe_lib.moe_apply(p, cfg, x)
    want = moe_lib.moe_apply_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ep_matches_grouped_backend_bitwise_path():
    """EP runs the same permute/GEMM/f32-combine chain as the grouped
    backend — outputs should agree to fp32 rounding, not just 1e-4."""
    ep = 4
    cfg = _moe_cfg(ep=ep)
    _ep_mesh(ep)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(2), "float32")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, cfg.d_model)) * 0.5
    y_ep, aux_ep = moe_lib.moe_apply(p, cfg, x)
    y_g, aux_g = moe_lib.moe_apply(p, cfg.replace(expert_parallel=0), x,
                                   backend="grouped")
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_g),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(aux_ep), float(aux_g), rtol=1e-6)


def test_ep_with_tp_model_axis_matches_oracle():
    """EP composed with expert-ffn TP: on a mesh with a real "model" axis
    the weights' f dim stays sharded inside the shard_map (partial
    down-projections psum over "model") — forward AND grad must still match
    the oracle."""
    cfg = _moe_cfg(ep=2)
    assert cfg.d_ff_expert % 4 == 0
    mesh = make_debug_mesh(data=1, model=4, expert=2)
    settings.set_ep_mesh(mesh)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5
    y, _ = moe_lib.moe_apply(p, cfg, x)
    want = moe_lib.moe_apply_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.square(
        moe_lib.moe_apply(p, cfg, x)[0])), argnums=(0, 1)))(p, x)
    g_or = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.square(
        moe_lib.moe_apply_oracle(p, cfg, x))), argnums=(0, 1)))(p, x)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g),
            jax.tree_util.tree_leaves_with_path(g_or)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-4, err_msg=str(ka))


def test_ep_grad_parity_all_argnums():
    """jax.grad through moe_apply under expert_parallel vs the oracle, for
    every differentiable argument (params tree AND activations)."""
    ep = 4
    cfg = _moe_cfg(ep=ep)
    _ep_mesh(ep)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5

    def loss_ep(p, x):
        y, _ = moe_lib.moe_apply(p, cfg, x)
        return jnp.sum(jnp.square(y))

    def loss_oracle(p, x):
        return jnp.sum(jnp.square(moe_lib.moe_apply_oracle(p, cfg, x)))

    g_ep = jax.jit(jax.grad(loss_ep, argnums=(0, 1)))(p, x)
    g_or = jax.jit(jax.grad(loss_oracle, argnums=(0, 1)))(p, x)
    for (ka, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_ep),
            jax.tree_util.tree_leaves_with_path(g_or)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=1e-4, err_msg=str(ka))


@hyp_settings(max_examples=6, deadline=None)
@given(mode=st.sampled_from(["cross", "standard"]), seed=st.sampled_from([0, 7]))
def test_ep_reversible_roundtrip_property(mode, seed):
    """Coupling inversion stays exact (<1e-5) when the MoE coupling runs
    the EP dispatch path — across both mixer families (cross fixed-point
    and standard/RevNet exact inverse)."""
    ep = 4
    cfg = _moe_cfg(ep=ep).replace(d_model=64, num_heads=2, head_dim=32)
    _ep_mesh(ep)
    key = jax.random.PRNGKey(seed)
    p_moe = initialize(moe_lib.moe_specs(cfg), key, "float32")
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (cfg.d_model, cfg.d_model)) / np.sqrt(cfg.d_model)

    def F(p, sh, ctx, i, x1, x2):
        src = (x1 + x2) if mode == "cross" else x2
        return 0.1 * jnp.tanh(src @ p["w"])

    def G(p, sh, ctx, i, y1, _=None):
        y, _aux = moe_lib.moe_apply(p["moe"], cfg, y1)
        return 0.1 * y

    fwd, inv = make_coupled(F, G, mode=mode, fp_iters=5)
    fwd_j = jax.jit(lambda p, a, b: fwd(p, {}, {}, 0, a, b))
    inv_j = jax.jit(lambda p, a, b: inv(p, {}, {}, 0, a, b))
    params = {"w": w, "moe": p_moe}
    x1 = jax.random.normal(jax.random.fold_in(key, 2), (2, 16, cfg.d_model))
    x2 = jax.random.normal(jax.random.fold_in(key, 3), (2, 16, cfg.d_model))
    y1, y2 = fwd_j(params, x1, x2)
    r1, r2 = inv_j(params, y1, y2)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(x1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(r2), np.asarray(x2), atol=1e-5)


def test_ep_indivisible_experts_actionable_error():
    """Satellite regression: experts not dividing the EP size must raise a
    ValueError naming both quantities, not a raw reshape/psum failure."""
    ep = 4
    _ep_mesh(ep)
    cfg = _moe_cfg(ep=ep).replace(num_experts=6, top_k=2)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.zeros((1, 32, cfg.d_model))
    with pytest.raises(ValueError, match="num_experts=6.*ep=4"):
        moe_lib.moe_apply(p, cfg, x)


def test_ep_indivisible_tokens_actionable_error():
    ep = 4
    _ep_mesh(ep)
    cfg = _moe_cfg(ep=ep)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.zeros((1, 30, cfg.d_model))             # 30 % 4 != 0
    with pytest.raises(ValueError, match="token count 30.*ep=4"):
        moe_lib.moe_apply(p, cfg, x)


def test_ep_mesh_missing_actionable_error():
    cfg = _moe_cfg(ep=4)
    p = initialize(moe_lib.moe_specs(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.zeros((1, 32, cfg.d_model))
    settings.set_ep_mesh(None)
    with pytest.raises(ValueError, match="set_ep_mesh"):
        moe_lib.moe_apply(p, cfg, x)


def test_ep_train_step_end_to_end():
    """Full jitted train step (reversible stack + EP dispatch + optimizer)
    on the 8-device mesh; also the trainer's early EP-mesh validation."""
    from repro.optim.adamw import AdamW
    from repro.train.trainer import make_train_step
    ep = 4
    cfg = _moe_cfg(ep=ep).replace(num_layers=2, moe_backend="grouped")
    model = Model(cfg)

    settings.set_ep_mesh(None)
    with pytest.raises(ValueError, match="set_ep_mesh"):
        make_train_step(model, AdamW(lr=1e-3))

    mesh = _ep_mesh(ep)
    params = model.init(jax.random.PRNGKey(0))
    shardings = shd.param_shardings(model.logical_axes(),
                                    model.abstract_params(), mesh)
    # the expert axis actually takes the experts dim on this mesh
    moe_spec = shd.param_pspecs(model.logical_axes(),
                                model.abstract_params(), mesh)
    leaf = moe_spec["stacks"]["layers"]["moe"]["w_gate"]
    assert tuple(leaf)[1] == "expert", leaf          # dim 0 is the layer stack
    params = jax.device_put(params, shardings)
    opt = AdamW(lr=1e-3)
    step = jax.jit(make_train_step(model, opt))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64),
                                          0, cfg.vocab_size)}
    with jax.set_mesh(mesh):
        params, state, metrics = step(params, opt.init(params), batch)
    assert bool(jnp.isfinite(metrics["loss"]))


def test_flash_kernel_partitions_under_a_mesh():
    """GSPMD cannot partition a Pallas call: under a multi-device mesh the
    flash kernels run per batch shard inside a shard_map.  Output and grads
    must match the tiled jax path on the same sharded inputs."""
    from repro.kernels import ops as kops
    mesh = make_debug_mesh(data=4, model=2)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, ct = (jax.random.normal(kk, (4, 4, 128, 32)) for kk in keys)
    sharded = NamedSharding(mesh, P("data"))
    q, k, v, ct = (jax.device_put(a, sharded) for a in (q, k, v, ct))

    def run(impl):
        def f(q, k, v, ct):
            out, vjp = jax.vjp(lambda *a: kops.flash_attention_trainable(
                *a, True, None, None, 64, 64, impl), q, k, v)
            return (out,) + vjp(ct)
        with jax.set_mesh(mesh):
            return jax.jit(f)(q, k, v, ct)

    for a, b in zip(run("pallas"), run("jax")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_chip_smoke_ep_parity_reduced():
    """chip_smoke.py --four-chips at reduced widths, in bf16 on the Zipf
    data: with a bf16 scatter-add the one-device embedding gradient drifted
    8% from the FSDP one (frequent tokens' rows stalled); the f32 row sums
    of ``embed_lookup`` keep every leaf inside the check's limits."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import chip_smoke as cs
    cfg = _moe_cfg().replace(num_layers=2, dtype="bfloat16",
                             use_flash_kernel=True, moe_backend="grouped")
    out = cs.phase_ep_parity(cfg, ep=4, batch=4, seq=1024)
    assert out["failed"] == [], out
    assert out["leaves_checked"] > 0 and "['embed']" not in out["noise_leaves"]
