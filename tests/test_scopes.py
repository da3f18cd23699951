"""The program's named scopes (``repro.obs.scopes``) reach the compiled
train step, and telemetry spans reach the profiler's host timeline.

The fused step of a tiny reversible MoE config (grouped-GEMM experts, LoMo)
and of a tiny dense config (AdamW), both clipping, is compiled on the CPU;
every device op's HLO ``op_name`` metadata holds its scope path, which is
what a profiler trace on the chip reports as the op's ``tf_op``.
"""
import glob
import re

import jax
import pytest

from repro import obs
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, packed_batches
from repro.models.model import Model
from repro.obs import scopes
from repro.optim.adamw import AdamW
from repro.optim.lomo import LoMo
from repro.train.trainer import make_train_step

_OP_NAME = re.compile(r'op_name="([^"]*)"')

STEPS = {
    "moe": ("qwen2-moe-a2.7b", {"moe_backend": "grouped"},
            lambda: LoMo(lr=1e-3, clip_norm=1.0)),
    "dense": ("h2o-danube-1.8b", {},
              lambda: AdamW(lr=1e-4, clip_norm=1.0)),
}


def scope_path(op_name):
    """Names on an op's scope path, transforms unwrapped, op left out."""
    out = []
    for comp in op_name.split("/")[:-1]:
        out.extend(t for t in re.split(r"[()]", comp) if t)
    return out


@pytest.fixture(scope="module")
def op_names():
    """{step: the op_name of every instruction of its compiled HLO}."""
    out = {}
    for key, (arch, over, make_opt) in STEPS.items():
        cfg = get_config(arch, reduced=True).replace(**over)
        model = Model(cfg)
        opt = make_opt()
        params = model.abstract_params()
        state = jax.eval_shape(opt.init, params)
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                        global_batch=2)
        batch = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            next(packed_batches(dc)))
        step = make_train_step(model, opt, fused=True)
        text = jax.jit(step).lower(params, state, batch).compile().as_text()
        out[key] = _OP_NAME.findall(text)
    return out


@pytest.mark.parametrize("name", scopes.SCOPES)
def test_every_scope_reaches_the_compiled_step(op_names, name):
    assert any(name in scope_path(n)
               for names in op_names.values() for n in names)


@pytest.mark.parametrize("step", sorted(STEPS))
def test_fused_step_module_has_its_own_name(op_names, step):
    """The module name keys the persistent compile cache; op names do not."""
    assert all(n.startswith("jit(fused_train_step)/")
               for n in op_names[step] if scopes.WALK_UPDATE in n)


@pytest.mark.parametrize("step", sorted(STEPS))
def test_attention_runs_under_inversion_in_both_walks(op_names, step):
    for walk in (scopes.WALK_PROBE, scopes.WALK_UPDATE):
        assert any({walk, scopes.REV_INVERT, scopes.ATTN}
                   <= set(scope_path(n)) for n in op_names[step]), walk


@pytest.mark.parametrize("step", sorted(STEPS))
def test_optimizer_updates_inside_the_update_walk_only(op_names, step):
    paths = [set(scope_path(n)) for n in op_names[step]]
    assert any({scopes.WALK_UPDATE, scopes.OPT_UPDATE} <= p for p in paths)
    assert not any({scopes.WALK_PROBE, scopes.OPT_UPDATE} <= p
                   for p in paths)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("enabled", [True, False])
def test_span_lands_on_the_profiler_host_timeline(tmp_path, enabled):
    tel = (obs.Telemetry(str(tmp_path / "run.jsonl")) if enabled
           else obs.NullTelemetry())
    x = jax.numpy.ones((8,))
    with jax.profiler.trace(str(tmp_path / "trace")):
        with tel.span("scoped.span", fence=lambda: x * 2) as sp:
            x = x + 1
    assert sp["dur_s"] >= 0
    assert ("scoped.span" in _host_events(tmp_path / "trace")) == enabled
    tel.close()


@pytest.mark.parametrize("name", ["mamba_ssd", "rwkv6_scan"])
def test_scan_kernels_carry_their_names(name):
    """The scan kernels do not lower for the TPU yet (cumsum in the body),
    so their name is checked on the interpret-mode lowering."""
    from repro.kernels import mamba_ssd, rwkv6_scan
    f32 = jax.numpy.float32
    if name == "mamba_ssd":
        fn = lambda *a: mamba_ssd.mamba_ssd(*a, chunk=128,   # noqa: E731
                                            interpret=True)
        shapes = [(1, 2, 256, 128), (1, 256, 128), (1, 256, 128),
                  (1, 2, 256), (1, 2, 256)]
    else:
        fn = lambda *a: rwkv6_scan.rwkv6_scan(*a, chunk=64,  # noqa: E731
                                              interpret=True)
        shapes = [(1, 2, 128, 128)] * 4 + [(2, 128)]
    text = jax.jit(fn).lower(
        *[jax.ShapeDtypeStruct(s, f32) for s in shapes]).as_text(
            debug_info=True)
    assert f"/{name}/pallas_call" in text


def test_kernel_names_are_the_listed_ones():
    import pathlib
    src = pathlib.Path(scopes.__file__).resolve().parents[1] / "kernels"
    names = set()
    for f in src.rglob("*.py"):
        names.update(re.findall(r'\bname="([a-z0-9_]+)"', f.read_text()))
    assert names == set(scopes.KERNELS)
