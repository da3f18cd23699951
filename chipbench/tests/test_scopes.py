"""The program-scope reader (``chipbench/scopes.py``) and the per-layer
metrics that read it, on the two recorded traces: neither carries the
program's scopes, so every such metric reads None there."""
import pytest

from chipbench import bench, scopes, tracing
from tiny import DATA

METRICS = ("train_invert_pct", "train_clip_probe_pct", "train_attn_pct",
           "moe_route_pct")


def test_reads_the_grouped_gemm_tf_op():
    path = str(DATA / "recorded.xplane.pb")
    s = tracing.load(path, chips=1)
    gmm = s.kernel_ops(["gmm"])[0]
    assert scopes.tf_ops(path)[gmm.name] == \
        "jit(grouped_matmul_pallas)/pallas_call:"


def test_scope_path_unwraps_transforms():
    p = scopes.scope_path("jit(step)/walk.update/while/body/closed_call/"
                          "rev.vjp/transpose(jvp(attn))/dot_general:")
    assert {"walk.update", "rev.vjp", "attn"} <= set(p)
    assert "dot_general" not in p
    assert scopes.scope_path("jit(fl)/jvp()/pallas_call:") == \
        ("jit", "fl", "jvp")


@pytest.mark.parametrize("trace", ["synthetic", "recorded"])
@pytest.mark.parametrize("metric", METRICS)
def test_reads_none_without_program_scopes(monkeypatch, trace, metric):
    path = str(DATA / f"{trace}.xplane.pb")
    monkeypatch.setattr(scopes, "trace_of", lambda ctx: path)
    ctx = {"kind": "train", "trace": tracing.load(path, chips=1)}
    assert bench.module("metrics", metric).read(ctx) is None


def test_share_of_a_scoped_trace(monkeypatch):
    """Scopes given by hand to the synthetic trace's ops: a flash dq
    kernel at 20-30 us and fusions at 25-35 us and 80-90 us."""
    path = str(DATA / "synthetic.xplane.pb")
    s = tracing.load(path, chips=1)
    names = {o.name: f"jit(step)/walk.probe/rev.invert/{i}/op:"
             for i, o in enumerate(s.ops[0])}
    first = s.ops[0][0].name
    names[first] = "jit(step)/walk.probe/rev.vjp/transpose(jvp(attn))/op:"
    monkeypatch.setattr(scopes, "trace_of", lambda ctx: path)
    monkeypatch.setattr(scopes, "tf_ops", lambda p: names)
    ctx = {"kind": "train", "trace": s}
    assert scopes.share(ctx, ("walk.probe",)) == pytest.approx(100.0)
    attn = scopes.share(ctx, ("attn",))
    assert 0 < attn < 100
    assert scopes.share(ctx, ("moe.router",)) == 0.0


def test_finds_the_trace_of_the_running_cell(monkeypatch, tmp_path):
    import shutil
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    cell = bench.load_cell("danube-ft-4k")
    ctx = {"config": cell.config, "traffic": cell.traffic}
    assert scopes.trace_of(ctx) is None
    d = tmp_path / "danube-ft-4k" / "plugins"
    d.mkdir(parents=True)
    shutil.copy(DATA / "synthetic.xplane.pb", d / "t.xplane.pb")
    assert scopes.trace_of(ctx) == str(d / "t.xplane.pb")
    other = bench.load_cell("qwen2moe-ft-8k")
    assert scopes.trace_of({"config": other.config,
                            "traffic": other.traffic}) is None
