"""Sharding rules: divisibility fallback, per-arch spec coverage, and a real
jitted step on a debug mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_debug_mesh
from repro.models.model import Model


class _FakeMesh:
    """Shape-only stand-in (rules never touch devices)."""
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH1 = _FakeMesh({"data": 16, "model": 16})
MESH2 = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_divisible_dims_get_sharded():
    spec = shd.spec_for((4096, 8192), ("embed", "heads"), MESH2)
    assert spec == P(("pod", "data"), "model")


def test_indivisible_vocab_replicates():
    # whisper vocab 51865 is not divisible by 16
    spec = shd.spec_for((1024, 51865), ("embed", "vocab"), MESH1)
    assert spec == P("data")


def test_kv_heads_fallback():
    # kv_dim = 8 heads * 128 = 1024, divisible; but 8 heads alone would not be.
    spec = shd.spec_for((4096, 1024), ("embed", "kv_heads"), MESH1)
    assert spec == P("data", "model")
    spec = shd.spec_for((4096, 8), ("embed", "kv_heads"), MESH1)
    assert spec == P("data")                       # 8 % 16 != 0 -> replicated


def test_mesh_axis_used_once_per_tensor():
    # expert tensor: experts take "model"; expert_mlp must not reuse it
    spec = shd.spec_for((64, 2048, 1408), ("experts", "embed", "expert_mlp"), MESH1)
    assert spec[0] == "model"
    rest = tuple(spec)[1:]
    assert "model" not in rest          # expert dim already took "model"


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["1pod", "2pod"])
def test_param_specs_cover_all_leaves(arch, mesh):
    cfg = get_config(arch)
    model = Model(cfg)
    aparams = model.abstract_params()
    pspecs = shd.param_pspecs(model.logical_axes(), aparams, mesh)
    n_leaves = len(jax.tree_util.tree_leaves(aparams))
    n_specs = len(jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P)))
    assert n_specs == n_leaves
    # every spec's sharded dims must divide the dimension
    for sds, sp in zip(
            jax.tree_util.tree_leaves(aparams),
            jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P))):
        for d, ax in zip(sds.shape, tuple(sp) + (None,) * len(sds.shape)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert d % size == 0, (arch, sds.shape, sp)


def test_jit_step_on_debug_mesh():
    """End-to-end sharded train step on the (1,1) debug mesh."""
    mesh = make_debug_mesh(1, 1)
    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(num_layers=2)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pspecs = shd.param_pspecs(model.logical_axes(), model.abstract_params(), mesh)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, cfg.vocab_size)}
    with jax.set_mesh(mesh):
        step = jax.jit(lambda p, b: model.loss(p, b),
                       in_shardings=(pspecs, None))
        loss = step(params, batch)
    assert bool(jnp.isfinite(loss))


MESH_TP = _FakeMesh({"model": 8})


def test_model_only_mesh_replicates_batch():
    """Regression: batch_pspec/cache_pspecs crashed with IndexError (fa[0])
    on a mesh with no pod/data axes (model-only TP serving mesh)."""
    assert shd.batch_pspec(MESH_TP, 4, 2) == P()
    assert shd.batch_pspec(MESH_TP, 1, 2, dim1=1) == P()


def test_model_only_mesh_cache_pspecs():
    import types
    shapes = {
        "kv": {"k": types.SimpleNamespace(shape=(4, 2, 256, 8, 32)),
               "pos": types.SimpleNamespace(shape=(256,))},
        "t": types.SimpleNamespace(shape=()),
    }
    specs = shd.cache_pspecs(shapes, MESH_TP, batch_size=2, kv_heads=8)
    # no data axes: batch stays unsharded, but the kv-heads dim still takes
    # the model axis (8 % 8 == 0)
    assert specs["kv"]["k"] == P(None, None, None, "model")
    assert specs["t"] == P()
    # GQA kv heads that don't divide the model axis: sequence-dim fallback
    specs = shd.cache_pspecs(shapes, MESH_TP, batch_size=2, kv_heads=2)
    assert specs["kv"]["k"] == P(None, None, "model")


def test_spec_for_on_model_only_mesh():
    # fsdp candidates expand to no axes -> embed replicates, heads shard
    spec = shd.spec_for((4096, 1024), ("embed", "heads"), MESH_TP)
    assert spec == P(None, "model")


def test_batch_pspec_fallbacks():
    assert shd.batch_pspec(MESH2, 256, 2) == P(("pod", "data"), None)
    # batch=1 long-context: a long divisible sequence dim takes the data axes
    assert shd.batch_pspec(MESH2, 1, 2, dim1=524288) == P(None, ("pod", "data"))
    # but a (1,1) decode token stays replicated
    assert shd.batch_pspec(MESH2, 1, 2, dim1=1) == P()
