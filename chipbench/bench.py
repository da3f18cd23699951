"""What every cell shares: the benchmark file, the cell's configuration and
traffic found by name, the device check, the compile counter, the host spans
and the result line.

Files are found by name, so a later cell or metric adds files and edits
none: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<cell>.json`` (what the comparison allows), ``kinds/<kind>.py``
(the driver for a traffic file's ``kind``), ``metrics/<metric>.py`` (one
per per-layer metric) and ``reference/<name>.py`` (a configuration's plain
reference).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device without published peaks."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # configs/<config>.json
    traffic: dict            # traffic/<traffic>.json
    limits: dict             # limits/<cell>.json
    end_to_end: List[dict]   # the benchmark's metrics this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str, cells_reporting: Dict[str, set]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return cell in cells_reporting.get(metric.get("moves", metric["name"]),
                                       {cell})


def load_cell(name: str, bench_path: Optional[pathlib.Path] = None) -> Cell:
    spec = load_json(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reporting = {m["name"]: set(m.get("workloads", cells)) for m in
                 spec["end_to_end"]}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name, reporting)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


# ------------------------------------------------------------------ device

def check_device(chips: int) -> dict:
    """The device record of the result line; raises ``NoChip`` off a TPU,
    with fewer chips than the cell asks for, or without published peaks."""
    import jax
    from chipbench.peaks import peaks_for
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peaks_for(d.device_kind)
    except ValueError as e:
        raise NoChip(str(e)) from None
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


def cache_dir() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` at the checkout's root (a fixed
    path, so the next run finds what this one wrote)."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(ROOT / ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    return d


class CompileCounter:
    """Counts executables built or loaded (backend compiles and persistent
    cache hits) through JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0

        def on(event, duration, **_):
            if event in self.EVENTS:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on)


# ------------------------------------------------------------------ spans

class Spans:
    """Host spans of the benchmark's own calls.  Each span is written into
    the profiler's trace as a ``TraceAnnotation`` (so an idle gap on the
    device can be named by what the host was doing) and kept in memory."""

    def __init__(self):
        self.items: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.items.append((name, t0, time.perf_counter()))


# ------------------------------------------------------------------ result

def module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold '-')."""
    full = f"chipbench.{kind}.{name.replace('-', '_').replace('.', '_')}"
    if full not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            full, HERE / kind / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[full] = mod
        spec.loader.exec_module(mod)
    return sys.modules[full]


def emit(result: dict) -> None:
    """Print the comparison's numbers as the last lines on stderr, then the
    result as the last line on stdout."""
    for k, v in result.get("checks", {}).items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
