#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from, on the chip
at the cell's own size (the benchmark's runs never call this).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...
        [--control-seeds 1,2,3] [--seconds 2]

For every seed, one run of the cell as the benchmark runs it (a short
window), printing the numbers it compared.  For each control seed, the
reference computed in float8 put in the program's place (training: the
same first steps; serving: the token the float8 reference puts first at
each position of the served requests) and, for training, the reference with
half of the batch left out.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from chipbench import bench, compare, traffic
    from chipbench.run import run_cell
    cell = bench.load_cell(args.workload)
    bench.cache_dir()
    device = bench.check_device(cell.chips)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    for s in seeds:
        t = time.perf_counter()
        r = run_cell(cell, s, args.seconds, False, device, t)
        print(json.dumps({"reading": "program", "seed": s,
                          "correct": r["correct"], "numbers": r["numbers"],
                          "metrics": r["metrics"],
                          "reference_s": r["reference_s"],
                          "wall_s": time.perf_counter() - t}), flush=True)
    import importlib
    refmod = importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")
    for s in ctl:
        t = time.perf_counter()
        ref = refmod.RevFFN(cell.config)
        if cell.traffic["kind"] == "train":
            job = cell.traffic
            batches = [b for _, b in zip(range(job["check_steps"]),
                                         traffic.train_batches(job, ref.V,
                                                               s))]
            want = refmod.train_steps(ref, job["optimizer"], s, batches)
            for name, mode, fault in (("control_fp8", "fp8", None),
                                      ("fault_half_batch", "f32", "half")):
                got = refmod.train_steps(refmod.RevFFN(cell.config, mode),
                                         job["optimizer"], s, batches,
                                         fault=fault)
                print(json.dumps({"reading": name, "seed": s,
                                  "numbers": compare.training_numbers(
                                      got, want),
                                  "wall_s": time.perf_counter() - t}),
                      flush=True)
        else:
            from chipbench.kinds import serve as S
            seen = {}
            real = S.pick_sample

            def keep(served, seed, n):
                out = real(served, seed, n)
                seen["items"] = [served[u] for u in out]
                return out
            S.pick_sample = keep
            try:
                r = run_cell(cell, s, args.seconds, False, device, t)
            finally:
                S.pick_sample = real
            gap = refmod.served_gap(ref, s, seen["items"],
                                    control=refmod.RevFFN(cell.config, "fp8"))
            print(json.dumps({"reading": "control_fp8", "seed": s,
                              "numbers": {"served_logit_gap": gap},
                              "program_numbers": r["numbers"],
                              "wall_s": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
