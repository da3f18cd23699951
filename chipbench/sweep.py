#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains (its knee), once,
on the chip: one engine, the cell's mix offered at each rate in turn.

    python3 chipbench/sweep.py --workload <cell> --rates 1,2,3 \
        [--seconds 30] [--seed 1]

A rate is sustained when the backlog does not grow: the engine's queue at
the last arrival is no longer than at the middle one, and every request
finishes within 30 s of the last arrival.  One JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from chipbench import bench, traffic
    cell = bench.load_cell(args.workload)
    bench.cache_dir()
    bench.check_device(cell.chips)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import numpy as np
    from chipbench.kinds import serve as S

    _, _, eng, _ = S.build(cell, args.seed)
    rates = [float(r) for r in args.rates.split(",")]
    allreq = []
    for r in rates:
        mix = dict(cell.traffic, rate_per_s=r)
        allreq += traffic.serve_requests(mix, eng.model.cfg.vocab_size,
                                         args.seed, args.seconds)
    S.warm_up(eng, allreq, eng.model.cfg.vocab_size)
    for r in rates:
        mix = dict(cell.traffic, rate_per_s=r)
        reqs = traffic.serve_requests(mix, eng.model.cfg.vocab_size,
                                      args.seed, args.seconds)
        depth = {}
        real_step = eng.step
        t0 = time.perf_counter()

        def step():
            t = time.perf_counter() - t0
            for mark in (0.5, 1.0):
                if t >= mark * reqs[-1]["due_s"] and mark not in depth:
                    depth[mark] = len(eng.queue)
            return real_step()
        eng.step = step
        rec, elapsed = S.serve_window(eng, reqs, bench.Spans(),
                                      tail_s=30.0)
        eng.step = real_step
        eng.done.clear()
        fin = [x for x in rec.values() if x["done"] is not None]
        ttft = [(x["first"] - x["due"]) * 1e3 for x in fin]
        toks = sum(len(x["req"].generated) for x in fin)
        print(json.dumps({
            "rate_per_s": r, "requests": len(reqs), "finished": len(fin),
            "elapsed_s": elapsed, "done_per_s": len(fin) / elapsed,
            "tokens_per_s": toks / elapsed,
            "queue_mid": depth.get(0.5), "queue_end": depth.get(1.0),
            "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p90_ms": float(np.percentile(ttft, 90)) if ttft else None,
            "sustained": len(fin) == len(reqs)
            and (depth.get(1.0) or 0) <= (depth.get(0.5) or 0) + 1}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
