#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic file; the
traffic file's ``kind`` names the driver (``chipbench/kinds/<kind>.py``).
With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of the
window by ``chipbench/metrics/<metric>.py``.  The last line on stdout is one
JSON object; the numbers that decided ``correct`` are the last lines on
stderr.  Off a TPU, with fewer chips than the cell asks for, or on a device
without published peaks, the run prints no result and exits with 3.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import shutil
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Ctx:
    def __init__(self, t0, spans, compiles, trace_dir):
        self.t0, self.spans, self.compiles = t0, spans, compiles
        self.trace_dir = trace_dir


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             t0: float) -> dict:
    """Drive the cell and assemble the result line (no device check here:
    the tests call this on the CPU at a small size)."""
    from chipbench import bench, tracing
    compiles = bench.CompileCounter()
    trace_dir = ROOT / ".chipbench_trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    ctx = Ctx(t0, bench.Spans(), compiles, trace_dir)
    kind = bench.module("kinds", cell.traffic["kind"])
    out = kind.run(cell, seed, seconds, trace, ctx)
    print(f"compiles in window: {out['compiles_in_window']}; "
          f"set-up {out['setup_s']:.3f} s; window {out['window_s']:.3f} s; "
          f"reference {out['reference_s']:.3f} s", file=sys.stderr)
    dev = dict(device, memory_peak_bytes=int(out["peak_bytes"]))
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if trace:
        summary = tracing.summarize(trace_dir, ctx.spans, cell.chips)
        lctx = dict(out["layer_ctx"], trace=summary, config=cell.config,
                    traffic=cell.traffic, device=device,
                    program=out.get("program_events"))
        metrics = {}
        for m in cell.per_layer:
            v = bench.module("metrics", m["name"]).read(lctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=dev,
                      breakdown=summary.breakdown())
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result.update(metrics={m["name"]: {"value": out["e2e"][m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end}, device=dev)
    result["compiles_in_window"] = out["compiles_in_window"]
    result["reference_s"] = out["reference_s"]
    result["numbers"] = {k: v for k, v in out["numbers"].items()}
    result["checks"] = out["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import bench
    cell = bench.load_cell(args.workload)
    bench.cache_dir()
    try:
        device = bench.check_device(cell.chips)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, T0)
    bench.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
