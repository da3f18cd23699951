"""Unified telemetry: structured metrics, spans, JSONL events, watchdogs.

One ``Telemetry`` object per run ties together a metric ``Registry``
(counters / gauges / fixed-bucket histograms), a schema-versioned JSONL
``JsonlSink``, and a ``span()`` context manager that can fence on
``jax.block_until_ready`` so spans measure device work rather than async
dispatch.  Dependency-free (stdlib + the already-present jax), and
fail-open: a disabled run costs a few no-op calls via ``NullTelemetry``.

Typical wiring (train driver / serving engine / benchmarks all follow it)::

    tel = obs.as_telemetry(path_or_none, role="train", config=cfg.name)
    with tel.span("step", fence=lambda: metrics["loss"]):
        ... dispatch device work ...
    tel.counter("train.steps").inc()
    tel.emit("train_step", step=i, loss=loss)
    tel.close()

The event taxonomy, schema, and CI validation gates are DESIGN.md §11; the
``repro.launch.trace`` CLI summarizes/validates/exports the run files.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

from repro.obs.jit import (InstrumentedJit, RecompileWatchdog,  # noqa: F401
                           instrument_jit, jit_cache_size)
from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                Registry)
from repro.obs.sink import (BENCH_SCHEMA_VERSION, SCHEMA_VERSION,  # noqa: F401
                            JsonlSink, host_device_meta, read_events,
                            validate_events, write_bench_json)
from repro.obs.trajectory import (TRAJECTORY_SCHEMA_VERSION,  # noqa: F401
                                  append_bench, flatten_metrics,
                                  metric_direction, read_trajectory,
                                  regressions, trajectory_path, trend_rows)
from repro.obs.watchdog import MemoryWatchdog  # noqa: F401


class Span(dict):
    """Result handle yielded by ``Telemetry.span``: after the block exits it
    carries ``t0``/``dur_s`` (callers like benchmarks read the fenced
    duration straight off it)."""


class Telemetry:
    enabled = True

    def __init__(self, path: Optional[str] = None, *,
                 sink: Optional[JsonlSink] = None,
                 registry: Optional[Registry] = None, **meta):
        self.sink = sink or JsonlSink(path)
        self.registry = registry or Registry()
        self._closed = False
        self.emit("run_start", meta=host_device_meta(), **meta)

    # -------------------------------------------------------- instruments

    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str, buckets=None) -> Histogram:
        return self.registry.histogram(name, buckets)

    # ------------------------------------------------------------- events

    def emit(self, kind: str, **fields) -> dict:
        return self.sink.emit(kind, **fields)

    @contextlib.contextmanager
    def span(self, name: str, fence=None, observe: bool = True, **labels):
        """Timed block.  ``fence`` (a pytree of arrays, or a zero-arg
        callable returning one) is passed to ``jax.block_until_ready`` at
        exit so the span covers device execution, not just dispatch; without
        it the span measures host wall time of the block.  The duration also
        lands in the ``span.<name>`` histogram unless ``observe=False``.
        The block also runs inside ``jax.profiler.TraceAnnotation(name)``,
        so a profiler trace shows the span on its host timeline."""
        import jax
        sp = Span(name=name, t0=time.time(), **labels)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield sp
            finally:
                if fence is not None:
                    jax.block_until_ready(fence() if callable(fence)
                                          else fence)
                sp["dur_s"] = time.perf_counter() - t0
                self.emit("span", **sp)
                if observe:
                    self.histogram(f"span.{name}").observe(sp["dur_s"])

    def flush_metrics(self, **labels) -> dict:
        """Emit a full registry snapshot as a ``metrics`` event."""
        return self.emit("metrics", metrics=self.registry.snapshot(),
                         **labels)

    def close(self):
        """Final snapshot (``run_end`` carries flat counter values plus the
        full registry) and file close; idempotent."""
        if self._closed:
            return
        self._closed = True
        snap = self.registry.snapshot()
        self.emit("run_end",
                  metrics={"counters": {k: v for k, v in
                                        snap["counters"].items()},
                           "gauges": snap["gauges"],
                           "histograms": snap["histograms"]})
        self.sink.close()


class _NullInstrument:
    def inc(self, n: int = 1):
        pass

    def set(self, v):
        pass

    def observe(self, v):
        pass


class NullTelemetry:
    """Same surface as ``Telemetry``, zero work: hooks stay unconditional in
    the hot paths (no ``if telemetry:`` branching at call sites)."""

    enabled = False
    _instrument = _NullInstrument()

    def __init__(self, *a, **k):
        self.sink = None
        self.registry = None

    def counter(self, name):
        return self._instrument

    def gauge(self, name):
        return self._instrument

    def histogram(self, name, buckets=None):
        return self._instrument

    def emit(self, kind, **fields):
        return {}

    @contextlib.contextmanager
    def span(self, name, fence=None, observe: bool = True, **labels):
        # still times (and fences) so callers may read sp["dur_s"]
        # unconditionally; nothing is recorded anywhere
        sp = Span(name=name, **labels)
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            if fence is not None:
                import jax
                jax.block_until_ready(fence() if callable(fence) else fence)
            sp["dur_s"] = time.perf_counter() - t0

    def flush_metrics(self, **labels):
        return {}

    def close(self):
        pass


def as_telemetry(t, **meta):
    """Normalize a user-facing telemetry argument: None -> no-op, a path ->
    a fresh file-backed ``Telemetry`` (caller owns closing it), an existing
    Telemetry/NullTelemetry passes through."""
    if t is None:
        return NullTelemetry()
    if isinstance(t, (Telemetry, NullTelemetry)):
        return t
    return Telemetry(path=str(t), **meta)
