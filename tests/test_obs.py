"""Telemetry subsystem (repro.obs): registry semantics, JSONL schema
round-trip, span fencing, jit compile instrumentation, recompile/memory
watchdogs, and driver + engine integration emitting the expected event keys
on the reduced config (DESIGN.md §11)."""
import json
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.launch import trace
from repro.obs import trajectory
from repro.obs.registry import Registry
from repro.obs.sink import (SCHEMA_VERSION, JsonlSink, read_events,
                            validate_events, write_bench_json)


# --------------------------------------------------------------- registry

def test_counter_semantics():
    r = Registry()
    c = r.counter("x")
    c.inc()
    c.inc(4)
    assert r.counter("x") is c          # idempotent by name
    assert c.value == 5
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)


def test_gauge_high_low_water():
    g = Registry().gauge("g")
    for v in (3.0, 7.0, 1.0):
        g.set(v)
    assert g.value == 1.0 and g.max == 7.0 and g.min == 1.0


def test_histogram_buckets_and_percentiles():
    h = Registry().histogram("h", buckets=[1.0, 2.0, 4.0])
    for v in (0.5, 1.5, 1.7, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["counts"] == [1, 2, 1, 1]     # 3 buckets + overflow
    assert snap["count"] == 5 and snap["max"] == 100.0
    assert h.percentile(50) == 2.0            # bucket upper bound
    assert h.percentile(100) == 100.0
    with pytest.raises(ValueError, match="NaN"):
        h.observe(float("nan"))
    with pytest.raises(ValueError, match="increasing"):
        Registry().histogram("bad", buckets=[2.0, 1.0])


def test_registry_kind_clash_raises():
    r = Registry()
    r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x")


# ------------------------------------------------------- sink / schema

def test_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = obs.Telemetry(path=path, role="test", config="tiny")
    tel.counter("n").inc(3)
    tel.emit("train_step", step=1, loss=1.5)
    tel.emit("train_step", step=2, loss=1.25)
    tel.close()
    events = read_events(path)
    assert events == tel.sink.events          # in-memory tap == file
    assert events[0]["kind"] == "run_start"
    assert events[0]["v"] == SCHEMA_VERSION
    assert events[0]["role"] == "test"
    assert "device_platform" in events[0]["meta"]
    assert events[-1]["kind"] == "run_end"
    assert events[-1]["metrics"]["counters"]["n"] == 3
    assert validate_events(events) == []


def test_validation_catches_nan_and_step_regression(tmp_path):
    sink = JsonlSink()
    sink.emit("run_start", meta={})
    sink.emit("train_step", step=5, loss=float("nan"))
    sink.emit("train_step", step=3, loss=1.0)
    errors = validate_events(sink.events)
    assert any("non-finite" in e for e in errors)
    assert any("not >" in e for e in errors)
    assert validate_events([]) == ["empty event stream"]
    # NaN is serialised as a string marker, not an invalid JSON literal
    assert sink.events[1]["loss"] == "NaN"


def test_validation_recompile_and_drift_gates():
    sink = JsonlSink()
    sink.emit("run_start", meta={})
    sink.emit("train_window", step=2, mem_drift_x=3.5)
    sink.emit("recompile", scope="serve", name="step")
    errs = validate_events(sink.events, require_zero_recompiles=True,
                           max_drift=2.0)
    assert any("recompile" in e for e in errs)
    assert any("drift" in e for e in errs)
    ok = JsonlSink()
    ok.emit("run_start", meta={})
    ok.emit("train_window", step=2, mem_drift_x=0.8)
    assert validate_events(ok.events, require_zero_recompiles=True,
                           max_drift=2.0) == []


def test_validation_prefix_hit_floor():
    sink = JsonlSink()
    sink.emit("run_start", meta={})
    sink.emit("run_end", metrics={"counters": {"serve.prefix_hits": 2}})
    assert validate_events(sink.events, min_prefix_hits=1) == []
    assert any("prefix_hits 2 < 3" in e
               for e in validate_events(sink.events, min_prefix_hits=3))
    bare = JsonlSink()
    bare.emit("run_start", meta={})
    bare.emit("run_end", metrics={"counters": {}})
    assert any("never engaged" in e
               for e in validate_events(bare.events, min_prefix_hits=1))


def test_bench_json_writer(tmp_path):
    path = str(tmp_path / "BENCH_x.json")
    write_bench_json(path, "x", {"tok_s": 12.5}, config="tiny")
    doc = json.load(open(path))
    assert doc["bench_schema"] == obs.BENCH_SCHEMA_VERSION
    assert doc["bench"] == "x" and doc["config"] == "tiny"
    assert doc["result"] == {"tok_s": 12.5}
    assert "timestamp" in doc and "jax" in doc["meta"]


# ------------------------------------------------------------- spans

class _Fence:
    def __init__(self, delay=0.0):
        self.delay = delay
        self.called_at = None

    def block_until_ready(self):
        self.called_at = time.perf_counter()
        time.sleep(self.delay)
        return self


def test_span_fencing_actually_blocks():
    tel = obs.Telemetry()
    fence = _Fence(delay=0.15)
    with tel.span("work", fence=fence) as sp:
        pass                                   # block is instant ...
    assert fence.called_at is not None         # ... but the fence ran
    assert sp["dur_s"] >= 0.15                 # and the span waited on it
    with tel.span("work") as sp2:
        pass
    assert sp2["dur_s"] < 0.15                 # unfenced span doesn't
    ev = [e for e in tel.sink.events if e["kind"] == "span"]
    assert [e["name"] for e in ev] == ["work", "work"]
    assert tel.registry.histogram("span.work").count == 2


def test_span_fence_callable_and_null_telemetry():
    fence = _Fence()
    with obs.Telemetry().span("w", fence=lambda: fence):
        pass
    assert fence.called_at is not None
    null = obs.NullTelemetry()
    with null.span("w", fence=_Fence(delay=0.05)) as sp:
        pass
    assert sp["dur_s"] >= 0.05                 # Null still times + fences
    null.counter("c").inc()                    # and all hooks are no-ops
    null.gauge("g").set(1)
    null.close()


# ------------------------------------------------- jit instrumentation

def test_jit_cache_size_guarded():
    f = jax.jit(lambda x: x + 1)
    assert obs.jit_cache_size(f) == 0
    f(np.zeros((2,), np.float32))
    assert obs.jit_cache_size(f) == 1

    class NoProbe:                             # version without _cache_size
        pass

    assert obs.jit_cache_size(NoProbe()) == -1

    class RaisingProbe:
        def _cache_size(self):
            raise AttributeError("renamed in this jax")

    assert obs.jit_cache_size(RaisingProbe()) == -1


def test_instrument_jit_counts_compiles():
    tel = obs.Telemetry()
    w = obs.instrument_jit(jax.jit(lambda x: x * 2), "f", tel)
    w(np.zeros((2,), np.float32))
    assert w.compiles == 1 and w.last_call_compiled
    w(np.ones((2,), np.float32))               # same signature: cached
    assert w.compiles == 1 and not w.last_call_compiled
    w(np.zeros((3,), np.float32))              # new shape: recompile
    assert w.compiles == 2
    assert tel.counter("jit.compiles.f").value == 2
    names = [e["kind"] for e in tel.sink.events]
    assert names.count("compile") == 2
    assert w.compile_s > 0


def test_recompile_watchdog():
    tel = obs.Telemetry()
    f = jax.jit(lambda x: x + 1)
    wd = obs.RecompileWatchdog({"f": f}, telemetry=tel, scope="t")
    f(np.zeros((2,), np.float32))
    assert wd.check() == 0                     # not armed yet
    wd.mark_warm()
    f(np.zeros((2,), np.float32))
    assert wd.check() == 0                     # cached call: quiet
    f(np.zeros((5,), np.float32))
    assert wd.check() == 1                     # post-warmup compile flagged
    assert wd.check() == 0                     # counted exactly once
    assert tel.counter("t.recompiles_post_warmup").value == 1
    assert any(e["kind"] == "recompile" for e in tel.sink.events)


def test_memory_watchdog_measures_and_drifts():
    tel = obs.Telemetry()
    keep = jax.numpy.ones((256, 256), jax.numpy.float32)   # noqa: F841
    wd = obs.MemoryWatchdog(tel, predicted_bytes=None)
    b = wd.sample()
    assert b is not None and b >= 256 * 256 * 4   # live_arrays fallback sees it
    assert wd.drift() is None                     # no prediction -> no drift
    wd.predicted_bytes = 2 * wd.peak_bytes
    fields = wd.window_fields()
    assert 0.0 < fields["mem_drift_x"] <= 1.0
    assert fields["mem_measured_peak_bytes"] == wd.peak_bytes


# --------------------------------------------------- driver integration

SLOW_SAVE_S = 0.5


@pytest.fixture(scope="module")
def train_run(tmp_path_factory):
    """One reduced 4-step train with telemetry + an artificially slow
    checkpoint save (the steps/s-skew regression fixture)."""
    from repro.checkpoint import manager as ckpt_mod
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.train.driver import RunConfig, train

    tmp = tmp_path_factory.mktemp("obs_train")
    cfg = get_config("qwen2-moe-a2.7b", reduced=True)
    model = Model(cfg)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=2)
    rc = RunConfig(total_steps=4, stage1_steps=2, ckpt_every=2,
                   ckpt_dir=str(tmp / "ckpt"), log_every=2)
    path = str(tmp / "run.jsonl")

    real_save = ckpt_mod.save

    def slow_save(*a, **k):
        time.sleep(SLOW_SAVE_S)
        return real_save(*a, **k)

    ckpt_mod.save = slow_save
    try:
        train(model, AdamW(lr=1e-3), dc, rc, telemetry=path,
              log_fn=lambda *_: None)
    finally:
        ckpt_mod.save = real_save
    return path, read_events(path)


def test_driver_emits_expected_events(train_run):
    _, events = train_run
    kinds = {e["kind"] for e in events}
    assert {"run_start", "train_step", "train_window", "ckpt_save",
            "compile", "run_end"} <= kinds
    steps = [e for e in events if e["kind"] == "train_step"]
    assert [e["step"] for e in steps] == [1, 2, 3, 4]
    assert [e["stage"] for e in steps] == [1, 1, 2, 2]
    for e in steps:
        assert np.isfinite(e["loss"]) and np.isfinite(e["grad_norm"])
    # both stage steps compiled exactly once, flagged on their first step
    assert [e["step"] for e in steps if e["compiled"]] == [1, 3]
    assert validate_events(events, max_drift=2.0) == []


def test_driver_steps_per_s_excludes_save_and_compile(train_run):
    """Regression (ISSUE 6 satellite): the logged/emitted steps-per-second
    must exclude checkpoint-save wall time and jit compile time.  Saves are
    slowed to 0.5 s here; with the old accounting every window's implied
    step time would be >= 0.5 s."""
    _, events = train_run
    saves = [e for e in events if e["kind"] == "ckpt_save"]
    assert len(saves) == 2
    assert all(e["dur_s"] >= SLOW_SAVE_S for e in saves)
    windows = [e for e in events if e["kind"] == "train_window"]
    assert len(windows) == 2
    for w in windows:
        implied_step_s = 1.0 / w["steps_per_s"]
        assert implied_step_s < SLOW_SAVE_S / 2, (
            f"window at step {w['step']}: implied step {implied_step_s:.3f}s "
            f"includes save/compile time")
    # compile time is reported on its own, not inside the windows
    compiles = [e for e in events if e["kind"] == "compile"]
    assert {e["name"] for e in compiles} == {"train_step_stage1",
                                             "train_step_stage2"}
    assert all(e["dur_s"] > 0 for e in compiles)


def test_driver_window_has_throughput_mfu_and_drift(train_run):
    _, events = train_run
    w = [e for e in events if e["kind"] == "train_window"][-1]
    assert w["tokens_per_s"] > 0
    assert w["steady_steps"] >= 1
    # MFU is a device metric: a CPU run has no peak and emits none
    assert "mfu" not in w and "achieved_flops_per_s" not in w
    assert w["mem_measured_peak_bytes"] > 0
    assert w["mem_predicted_bytes"] > 0
    assert 0.5 <= w["mem_drift_x"] <= 2.0   # acceptance: within 2x


# --------------------------------------------------- engine integration

@pytest.fixture(scope="module")
def engine_run():
    from repro.configs.base import get_config
    from repro.models.model import Model
    from repro.serving.engine import Request, ServingEngine

    cfg = get_config("h2o-danube-1.8b", reduced=True).replace(num_layers=2)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tel = obs.Telemetry(role="serve", config=cfg.name)
    eng = ServingEngine(model, params, slots=2, buf_len=64, telemetry=tel)
    rng = np.random.default_rng(0)
    for uid in range(3):
        p = rng.integers(4, cfg.vocab_size, size=6 + uid).astype(np.int32)
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=4, eos_id=-1))
    eng.run()
    return cfg, eng, tel


def test_engine_emits_request_records(engine_run):
    _, eng, tel = engine_run
    reqs = [e for e in tel.sink.events if e["kind"] == "serve_request"]
    assert sorted(e["uid"] for e in reqs) == [0, 1, 2]
    for e in reqs:
        assert e["tokens"] == 4
        assert e["ttft_s"] > 0 and e["total_s"] >= e["ttft_s"]
        assert e["queue_s"] >= 0
        assert e["tpot_s"] >= 0
    assert tel.counter("serve.requests_submitted").value == 3
    assert tel.counter("serve.requests_done").value == 3
    assert tel.counter("serve.tokens_generated").value == 12
    snap = tel.registry.snapshot()
    assert snap["gauges"]["serve.queue_depth"]["max"] >= 1  # 3 reqs, 2 slots
    assert snap["gauges"]["serve.slot_utilization"]["max"] == 1.0
    span_names = {e["name"] for e in tel.sink.events if e["kind"] == "span"}
    assert {"serve.prefill_admit", "serve.decode_window"} <= span_names
    assert tel.registry.histogram("serve.drain_s").count > 0
    assert tel.registry.histogram("serve.ttft_s").count == 3


def test_engine_counts_admission_rejects(engine_run):
    from repro.serving.engine import Request

    _, eng, tel = engine_run
    before = tel.counter("serve.admission_rejects").value
    # oversize is a TERMINAL reject, not an exception: the request
    # completes with an empty generation and the rejected flag set
    req = eng.submit(Request(uid=99, prompt=np.arange(60, dtype=np.int32),
                             max_new_tokens=30))
    assert req.rejected and req.generated == []
    assert eng.done[99] is req
    assert tel.counter("serve.admission_rejects").value == before + 1
    ev = [e for e in tel.sink.events if e["kind"] == "admission_reject"]
    assert ev and ev[-1]["uid"] == 99 and ev[-1]["what"] == "buf_len"


def test_engine_recompile_watchdog_flags_new_bucket(engine_run):
    from repro.serving.engine import Request

    cfg, eng, tel = engine_run
    eng.done.clear()
    eng.mark_warm()
    # same bucket as warmup traffic: must stay silent
    eng.submit(Request(uid=10, prompt=np.arange(4, 10, dtype=np.int32),
                       max_new_tokens=2, eos_id=-1))
    eng.run()
    assert tel.counter("serve.recompiles_post_warmup").value == 0
    # a never-seen (larger) bucket forces a prefill compile -> flagged
    eng.submit(Request(uid=11, prompt=np.arange(4, 40, dtype=np.int32),
                       max_new_tokens=2, eos_id=-1))
    eng.run()
    assert tel.counter("serve.recompiles_post_warmup").value >= 1
    rec = [e for e in tel.sink.events if e["kind"] == "recompile"]
    assert rec and rec[-1]["name"] == "admit"


def test_engine_jit_cache_sizes_never_raises(engine_run):
    _, eng, _ = engine_run
    sizes = eng.jit_cache_sizes()
    assert set(sizes) == {"step", "admit"}
    assert all(isinstance(v, int) for v in sizes.values())
    assert sizes["step"] >= 1 and sizes["admit"] >= 1


# ------------------------------------------------------------ trace CLI

def test_trace_validate_and_summarize(train_run, capsys):
    path, _ = train_run
    assert trace.main(["validate", path, "--max-drift", "2.0"]) == 0
    assert trace.main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "step_time (steady)" in out
    assert "drift" in out
    assert "ckpt_save" in out


def test_trace_export_chrome_trace(train_run, tmp_path):
    path, events = train_run
    out = str(tmp_path / "trace.json")
    assert trace.main(["export", path, "--out", out]) == 0
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    x = [e for e in evs if e.get("ph") == "X"]
    c = [e for e in evs if e.get("ph") == "C"]
    assert len(x) >= 6                      # steps + saves + compiles
    assert any(e["name"] == "train_step" for e in x)
    assert any("mem_drift_x" in e.get("args", {}) for e in c)
    assert all(e["ts"] >= 0 for e in x)


def test_trace_validate_fails_on_corrupt_run(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    sink = JsonlSink(path)
    sink.emit("run_start", meta={})
    sink.emit("train_step", step=1, loss=float("inf"))
    sink.close()
    assert trace.main(["validate", path]) == 1


# ------------------------------------------------- bench trajectory

def _append_run(tmp_path, i, traj, **metrics):
    write_bench_json(str(tmp_path / f"BENCH_r{i}.json"), "train_bench",
                     dict(metrics), config="tiny", trajectory=traj)


def test_trajectory_entry_schema_and_flatten(tmp_path):
    traj = str(tmp_path / "hist" / "BENCH_TRAJECTORY.jsonl")
    payload = {"step_s": 0.5, "note": "metadata", "ok": True,
               "rows": [{"name": "a", "loss": 1.0}, {"loss": 2.0}]}
    write_bench_json(str(tmp_path / "BENCH_x.json"), "x", payload,
                     config="tiny", trajectory=traj)
    entries = trajectory.read_trajectory(traj)
    assert len(entries) == 1
    e = entries[0]
    assert e["v"] == obs.TRAJECTORY_SCHEMA_VERSION
    assert e["bench"] == "x" and e["config"] == "tiny"
    assert "host" in e and "ts" in e
    # nested dicts flatten to dotted keys; list items key by their "name";
    # strings/bools are dropped (the trajectory tracks magnitudes)
    assert e["metrics"] == {"step_s": 0.5, "rows.a.loss": 1.0,
                            "rows.1.loss": 2.0}


def test_trajectory_path_resolution(tmp_path, monkeypatch):
    monkeypatch.delenv(trajectory.TRAJECTORY_ENV, raising=False)
    sib = trajectory.trajectory_path(str(tmp_path / "BENCH_x.json"))
    assert sib == str(tmp_path / "BENCH_TRAJECTORY.jsonl")
    monkeypatch.setenv(trajectory.TRAJECTORY_ENV, "/ci/cache/T.jsonl")
    assert trajectory.trajectory_path("whatever") == "/ci/cache/T.jsonl"
    assert trajectory.trajectory_path("x", "/explicit.jsonl") == "/explicit.jsonl"
    # default write appends next to the bench artifact
    monkeypatch.delenv(trajectory.TRAJECTORY_ENV, raising=False)
    write_bench_json(str(tmp_path / "BENCH_x.json"), "x", {"a_s": 1.0})
    assert len(trajectory.read_trajectory(sib)) == 1


def test_metric_direction_rules():
    assert trajectory.metric_direction("step_s") == "lower"
    assert trajectory.metric_direction("ttft_p90_s") == "lower"
    assert trajectory.metric_direction("eval_loss") == "lower"
    # higher-better patterns win over the greedy "_s" suffix rule
    assert trajectory.metric_direction("steps_per_s") == "higher"
    assert trajectory.metric_direction("tok_s") == "higher"
    assert trajectory.metric_direction("mfu") == "higher"
    # unclassifiable metrics are exempt from the gate
    assert trajectory.metric_direction("n_layers") is None


def test_trend_and_regress_roundtrip(tmp_path, capsys):
    """Acceptance: a synthetic flat 3-run trajectory passes the regression
    gate; an injected 25% step-time (and -25% throughput) regression on the
    next run fails it."""
    traj = str(tmp_path / "BENCH_TRAJECTORY.jsonl")
    for i in range(3):
        _append_run(tmp_path, i, traj, step_s=1.0, steps_per_s=10.0)
    assert trace.main(["regress", traj]) == 0
    assert trace.main(["trend", traj]) == 0
    out = capsys.readouterr().out
    assert "step_s" in out and "steps_per_s" in out
    assert "▁" in out                            # sparkline rendered

    _append_run(tmp_path, 3, traj, step_s=1.25, steps_per_s=7.5)
    assert trace.main(["regress", traj]) == 1    # default gate is 20%
    out = capsys.readouterr().out
    assert "regression" in out
    bad = trajectory.regressions(trajectory.read_trajectory(traj),
                                 max_regression_pct=20.0)
    by_metric = {r["metric"]: r for r in bad}
    assert by_metric["step_s"]["regression_pct"] == pytest.approx(25.0)
    assert by_metric["steps_per_s"]["regression_pct"] == pytest.approx(25.0)
    # a *better* latest point never fails the gate
    _append_run(tmp_path, 4, traj, step_s=0.5, steps_per_s=20.0)
    assert trace.main(["regress", traj, "--max-regression-pct", "30"]) == 0


def test_regress_short_series_is_report_only(tmp_path, capsys):
    """Series below --min-points never gate: a fresh trajectory (first CI
    runs after this lands) reports instead of blocking."""
    traj = str(tmp_path / "BENCH_TRAJECTORY.jsonl")
    _append_run(tmp_path, 0, traj, step_s=1.0)
    _append_run(tmp_path, 1, traj, step_s=2.0)   # 100% worse, but n=2
    assert trace.main(["regress", traj]) == 0
    assert "report-only" in capsys.readouterr().out
    assert trajectory.regressions(trajectory.read_trajectory(traj),
                                  max_regression_pct=20.0, min_points=2)


def test_trajectory_tolerates_torn_tail(tmp_path):
    traj = str(tmp_path / "BENCH_TRAJECTORY.jsonl")
    for i in range(2):
        _append_run(tmp_path, i, traj, step_s=1.0)
    with open(traj, "a") as f:
        f.write('{"v": 1, "bench": "train_be')     # killed mid-append
    assert len(trajectory.read_trajectory(traj)) == 2


# ------------------------------------------- truncated run files (trace)

def test_trace_tolerates_prefix_truncated_run(train_run, tmp_path):
    """Regression: a run killed mid-write tears the final JSONL line; the
    trace CLI must degrade to the valid prefix, not error out."""
    path, events = train_run
    data = open(path).read()
    cut = str(tmp_path / "torn.jsonl")
    with open(cut, "w") as f:
        f.write(data[:-25])                  # tear the final line mid-record
    with pytest.raises(ValueError, match="undecodable"):
        read_events(cut)                     # strict mode still raises
    kept = read_events(cut, on_error="skip")
    assert kept == events[:-1]               # exactly the valid prefix
    assert trace.main(["summarize", cut]) == 0
    assert trace.main(["validate", cut, "--max-drift", "2.0"]) == 0


# --------------------------------------------- reversible audit (driver)

@pytest.fixture(scope="module")
def audit_run(tmp_path_factory):
    """One reduced 4-step train with ``audit_every=2`` (two audit windows:
    step 2 in stage 1, step 4 in stage 2), with every Telemetry.emit call
    timed so the telemetry-overhead gate has a deterministic measurement
    (a wall-clock A/B against NullTelemetry would be compile-noise-bound)."""
    from repro.configs.base import get_config
    from repro.data.pipeline import DataConfig
    from repro.models.model import Model
    from repro.optim.adamw import AdamW
    from repro.train.driver import RunConfig, train

    tmp = tmp_path_factory.mktemp("obs_audit")
    cfg = get_config("qwen2-moe-a2.7b", reduced=True)
    model = Model(cfg)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=2)
    rc = RunConfig(total_steps=4, stage1_steps=2, ckpt_every=100,
                   ckpt_dir=str(tmp / "ckpt"), log_every=2, audit_every=2)
    path = str(tmp / "run.jsonl")
    tel = obs.Telemetry(path=path, role="train", config=cfg.name)

    overhead = {"s": 0.0, "n": 0}
    real_emit = tel.emit

    def timed_emit(kind, **fields):
        t0 = time.perf_counter()
        ev = real_emit(kind, **fields)
        overhead["s"] += time.perf_counter() - t0
        overhead["n"] += 1
        return ev

    tel.emit = timed_emit
    t0 = time.perf_counter()
    train(model, AdamW(lr=1e-3), dc, rc, telemetry=tel,
          log_fn=lambda *_: None)
    wall = time.perf_counter() - t0
    tel.close()
    return path, read_events(path), overhead, wall


def test_audit_emits_per_layer_attribution(audit_run):
    _, events, _, _ = audit_run
    la = [e for e in events if e["kind"] == "layer_audit"]
    assert len(la) == 8                      # 2 audit windows x 4 layers
    assert sorted({e["step"] for e in la}) == [2, 4]
    assert sorted(e["layer"] for e in la if e["step"] == 2) == [0, 1, 2, 3]
    for e in la:
        assert e["policy"] == "reversible"   # paper-default all-reversible
        assert 0.0 <= e["recon_rel"] <= 1e-3     # acceptance: <= 1e-3 rel
        assert e["recon_max_abs"] >= e["recon_mean_abs"] >= 0.0
        assert e["inv_s"] > 0 and e["bwd_s"] > 0
        assert "residual_bytes" in e
    summaries = [e for e in events if e["kind"] == "audit_summary"]
    assert len(summaries) == 2
    for s in summaries:
        assert s["n_layers"] == 4
        pp = s["per_policy"]["reversible"]
        assert pp["layers"] == 4
        assert pp["bwd_s"] > 0 and pp["inv_s"] > 0
        assert s["recon_rel_max"] <= 1e-3
        assert s["recon_rel_mean"] <= s["recon_rel_max"]
        assert s["audit_s"] > 0


def test_audit_emits_moe_routing_telemetry(audit_run):
    _, events, _, _ = audit_run
    moe = [e for e in events if e["kind"] == "moe_route"]
    assert len(moe) == 8                     # every reduced layer is MoE
    assignments = 2 * 64 * 2                 # micro-batch tokens x top_k
    for e in moe:
        assert e["imbalance"] >= 1.0         # max/mean load, 1.0 = uniform
        assert e["entropy"] >= 0.0
        assert 0.0 <= e["dropped_fraction"] <= 1.0
        assert sum(e["expert_load"]) == assignments
        assert "ep_payload_drift_x" not in e     # no EP on this config
    end = events[-1]
    assert end["kind"] == "run_end"
    assert end["metrics"]["counters"]["audit.runs"] == 2
    gauges = end["metrics"]["gauges"]
    assert "moe.imbalance" in gauges and "audit.recon_rel_max" in gauges


def test_audit_validate_gate_and_summarize(audit_run, capsys):
    path, _, _, _ = audit_run
    assert trace.main(["validate", path,
                       "--max-reconstruction-err", "1e-3"]) == 0
    # an absurdly tight bound must FAIL on real float32 inversion error
    assert trace.main(["validate", path,
                       "--max-reconstruction-err", "1e-12"]) == 1
    capsys.readouterr()
    assert trace.main(["summarize", path]) == 0
    out = capsys.readouterr().out
    assert "layer audit" in out
    assert "per-policy totals" in out
    assert "worst reconstruction" in out
    assert "moe routing" in out


def test_audit_does_not_perturb_train_jit(audit_run):
    """Acceptance: audit mode re-walks layers in its own jitted fns; the
    train step's caches must not grow (the watchdog brackets each audit)."""
    _, events, _, _ = audit_run
    assert not [e for e in events if e["kind"] == "recompile"]
    assert validate_events(events, require_zero_recompiles=True,
                           max_reconstruction_err=1e-3) == []
    # the watchdog armed once per audit window
    assert len([e for e in events if e["kind"] == "warmup_done"]) == 2


def test_audit_off_emits_nothing(train_run):
    path, events = train_run
    kinds = {e["kind"] for e in events}
    assert not kinds & {"layer_audit", "moe_route", "audit_summary"}
    # the gate flag on an audit-less run is an error, not a silent pass
    assert trace.main(["validate", path,
                       "--max-reconstruction-err", "1e-3"]) == 1


def test_telemetry_overhead_bounded(audit_run):
    """Acceptance (satellite): telemetry costs <= ~5% of train wall time on
    the reduced config.  Measured as accumulated emit-path seconds over the
    whole audited run (the strictest window: compile + audit included)."""
    _, _, overhead, wall = audit_run
    assert overhead["n"] >= 20                   # it actually measured
    assert overhead["s"] <= 0.05 * wall, (
        f"telemetry emit path took {overhead['s']:.3f}s of {wall:.1f}s "
        f"({100 * overhead['s'] / wall:.2f}% > 5%)")

