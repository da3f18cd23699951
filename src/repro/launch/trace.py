"""Telemetry run-file CLI: summarize / validate / export / trend / regress
(DESIGN.md §11-§12).

    PYTHONPATH=src python -m repro.launch.trace summarize RUN.jsonl
    PYTHONPATH=src python -m repro.launch.trace validate RUN.jsonl \
        [--require-zero-recompiles] [--max-drift 2.0] \
        [--max-reconstruction-err 1e-3] [--min-prefix-hits N]
    PYTHONPATH=src python -m repro.launch.trace export RUN.jsonl \
        [--out trace.json]
    PYTHONPATH=src python -m repro.launch.trace trend BENCH_TRAJECTORY.jsonl \
        [--bench NAME] [--window 8]
    PYTHONPATH=src python -m repro.launch.trace regress \
        BENCH_TRAJECTORY.jsonl --max-regression-pct 20 [--min-points 3]

``summarize`` renders p50/p99 tables from the raw events (exact, not the
bucket-resolution registry histograms): train step time / loss trajectory /
throughput + memory drift, per-layer reversible-audit attribution and
MoE routing telemetry, serving TTFT / TPOT / queue wait, span durations,
compiles and checkpoint I/O.  ``validate`` applies the schema gates CI runs
(see repro.obs.sink.validate_events); ``--max-reconstruction-err`` bounds
the worst per-layer relative reconstruction error of the reversible audit.
``export`` writes a chrome://tracing / Perfetto-compatible trace: spans
become complete ("X") events on per-name tracks, gauges become counter
("C") tracks.  ``trend``/``regress`` read the append-only bench trajectory
(repro.obs.trajectory): trend prints each metric series' latest value vs
its trailing median with a sparkline; regress exits nonzero when a metric
moved more than the threshold in its bad direction — series shorter than
``--min-points`` only report, so a fresh trajectory never blocks CI.

Run files are read in skip mode: a torn final line (killed run) degrades to
the valid prefix.  No jax import: this must run on a machine that never saw
the run.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

from repro.obs.sink import read_events, validate_events


def _pct(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    pos = q / 100.0 * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def _fmt(v, unit="") -> str:
    if v is None:
        return "-"
    if unit == "ms":
        return f"{v * 1e3:.2f} ms"
    if unit == "s":
        return f"{v:.3f} s"
    if unit == "x":
        return f"{v:.3f}x"
    if unit == "GiB":
        return f"{v / 2**30:.3f} GiB"
    if unit == "MiB":
        return f"{v / 2**20:.1f} MiB"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _table(title: str, rows: List[tuple], header=("metric", "count", "p50",
                                                  "p99", "mean")):
    if not rows:
        return
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    print(f"\n{title}")
    print("  " + "  ".join(h.ljust(w) for h, w in zip(header, widths)))
    for r in rows:
        print("  " + "  ".join(str(c).ljust(w) for c, w in zip(r, widths)))


def _lat_row(name: str, xs: List[float], unit="ms") -> tuple:
    return (name, len(xs), _fmt(_pct(xs, 50), unit), _fmt(_pct(xs, 99), unit),
            _fmt(sum(xs) / len(xs) if xs else None, unit))


def _by_kind(events: List[dict]) -> Dict[str, List[dict]]:
    out: Dict[str, List[dict]] = {}
    for ev in events:
        out.setdefault(ev.get("kind", "?"), []).append(ev)
    return out


def summarize(events: List[dict]) -> None:
    kinds = _by_kind(events)
    head = kinds.get("run_start", [{}])[0]
    meta = head.get("meta", {})
    print(f"run: role={head.get('role', '?')} config={head.get('config', '?')}"
          f" schema v{head.get('v', '?')} | {meta.get('device_platform', '?')}"
          f" x{meta.get('device_count', '?')} jax {meta.get('jax', '?')}"
          f" on {meta.get('host', '?')}")
    print(f"events: {len(events)} "
          f"({', '.join(f'{k}:{len(v)}' for k, v in sorted(kinds.items()))})")

    # ----- train
    steps = kinds.get("train_step", [])
    if steps:
        steady = [e["step_s"] for e in steps if not e.get("compiled")]
        compile_s = [e["step_s"] for e in steps if e.get("compiled")]
        rows = [_lat_row("step_time (steady)", steady)]
        if compile_s:
            rows.append(_lat_row("step_time (compile)", compile_s))
        _table("train", rows)
        print(f"  loss: {steps[0]['loss']:.4f} -> {steps[-1]['loss']:.4f} "
              f"over steps {steps[0]['step']}..{steps[-1]['step']}")
    wins = kinds.get("train_window", [])
    if wins:
        last = wins[-1]
        print(f"  last window: {_fmt(last.get('steps_per_s'))} steps/s, "
              f"{_fmt(last.get('tokens_per_s'))} tok/s")
        if last.get("mem_measured_peak_bytes") is not None:
            print(f"  memory: measured peak "
                  f"{_fmt(last['mem_measured_peak_bytes'], 'GiB')} vs "
                  f"predicted {_fmt(last.get('mem_predicted_bytes'), 'GiB')}"
                  f" -> drift {_fmt(last.get('mem_drift_x'), 'x')}")
    saves = [e["dur_s"] for e in kinds.get("ckpt_save", [])]
    restores = [e["dur_s"] for e in kinds.get("ckpt_restore", [])]
    rows = []
    if saves:
        rows.append(_lat_row("ckpt_save", saves))
    if restores:
        rows.append(_lat_row("ckpt_restore", restores))
    _table("checkpoint", rows)

    # ----- reversible audit (per-layer attribution, DESIGN.md §12)
    audits = kinds.get("layer_audit", [])
    if audits:
        per: Dict[int, List[dict]] = {}
        for e in audits:
            per.setdefault(e.get("layer", -1), []).append(e)
        rows = []
        for layer in sorted(per):
            evs = per[layer]
            rels = [e["recon_rel"] for e in evs
                    if isinstance(e.get("recon_rel"), (int, float))]
            invs = [e["inv_s"] for e in evs if isinstance(e.get("inv_s"),
                                                          (int, float))]
            bwds = [e["bwd_s"] for e in evs if isinstance(e.get("bwd_s"),
                                                          (int, float))]
            res = next((e["residual_bytes"] for e in evs
                        if e.get("residual_bytes") is not None), None)
            rows.append((layer, evs[-1].get("policy", "?"), len(evs),
                         _fmt(max(rels) if rels else None),
                         _fmt(_pct(invs, 50), "ms"),
                         _fmt(_pct(bwds, 50), "ms"), _fmt(res, "MiB")))
        _table("layer audit (reversible backward attribution)", rows,
               header=("layer", "policy", "audits", "recon_rel",
                       "inv p50", "bwd p50", "residual"))
    summaries = kinds.get("audit_summary", [])
    if summaries:
        last = summaries[-1]
        rows = [(pol, agg.get("layers"), _fmt(agg.get("bwd_s"), "s"),
                 _fmt(agg.get("inv_s"), "s"),
                 _fmt(agg.get("residual_bytes"), "MiB"))
                for pol, agg in sorted(
                    (last.get("per_policy") or {}).items())]
        _table(f"audit per-policy totals (step {last.get('step')})", rows,
               header=("policy", "layers", "bwd", "inv", "residual"))
        if last.get("recon_rel_max") is not None:
            print(f"  worst reconstruction: rel {last['recon_rel_max']:.3e} "
                  f"(mean {last.get('recon_rel_mean', 0):.3e}) over "
                  f"{len(summaries)} audit(s)")

    # ----- MoE routing telemetry
    routes = kinds.get("moe_route", [])
    if routes:
        per = {}
        for e in routes:
            per.setdefault(e.get("layer", -1), []).append(e)
        rows = []
        for layer in sorted(per):
            evs = per[layer]
            imb = [e["imbalance"] for e in evs if "imbalance" in e]
            ent = [e["entropy"] for e in evs if "entropy" in e]
            drop = [e["dropped_fraction"] for e in evs
                    if "dropped_fraction" in e]
            drift = [e["ep_payload_drift_x"] for e in evs
                     if e.get("ep_payload_drift_x") is not None]
            rows.append((layer, len(evs),
                         _fmt(max(imb) if imb else None, "x"),
                         _fmt(min(ent) if ent else None),
                         _fmt(max(drop) if drop else None),
                         _fmt(drift[-1] if drift else None, "x")))
        _table("moe routing (imbalance max / entropy min / drop max)", rows,
               header=("layer", "samples", "imbalance", "entropy",
                       "dropped", "ep drift"))

    # ----- serving
    reqs = kinds.get("serve_request", [])
    if reqs:
        rows = [
            _lat_row("ttft", [e["ttft_s"] for e in reqs if "ttft_s" in e]),
            _lat_row("tpot", [e["tpot_s"] for e in reqs if "tpot_s" in e]),
            _lat_row("queue_wait",
                     [e["queue_s"] for e in reqs if "queue_s" in e]),
            _lat_row("request_total",
                     [e["total_s"] for e in reqs if "total_s" in e]),
        ]
        _table("serving", rows)
        toks = sum(e.get("tokens", 0) for e in reqs)
        print(f"  {len(reqs)} requests, {toks} tokens")
    recompiles = kinds.get("recompile", [])
    if kinds.get("warmup_done") or recompiles:
        print(f"  post-warmup recompiles: {len(recompiles)}"
              + ("".join(f"\n    {e.get('name')}: {e.get('baseline')} -> "
                         f"{e.get('entries')}" for e in recompiles)))

    # ----- spans / compiles
    spans: Dict[str, List[float]] = {}
    for ev in kinds.get("span", []):
        spans.setdefault(ev["name"], []).append(ev["dur_s"])
    _table("spans", [_lat_row(n, xs) for n, xs in sorted(spans.items())])
    compiles = kinds.get("compile", [])
    if compiles:
        _table("jit compiles", [
            (e.get("name"), 1, _fmt(e["dur_s"], "s"), "-", "-")
            for e in compiles])


def export_chrome_trace(events: List[dict], out_path: str) -> int:
    """Spans -> "X" (complete) events, gauges/window rates -> "C" (counter)
    tracks; timestamps are microseconds relative to run_start so Perfetto's
    view starts at zero."""
    t0 = events[0].get("ts", 0.0) if events else 0.0
    trace = []
    pid = 0
    tids: Dict[str, int] = {}

    def tid(track: str) -> int:
        if track not in tids:
            tids[track] = len(tids) + 1
            trace.append({"ph": "M", "pid": pid, "tid": tids[track],
                          "name": "thread_name", "args": {"name": track}})
        return tids[track]

    for ev in events:
        kind = ev.get("kind")
        ts_us = (ev.get("ts", t0) - t0) * 1e6
        if kind == "span":
            start = ev.get("t0")
            start_us = (start - t0) * 1e6 if start is not None \
                else ts_us - ev["dur_s"] * 1e6
            trace.append({"ph": "X", "pid": pid, "tid": tid(ev["name"]),
                          "name": ev["name"], "ts": start_us,
                          "dur": ev["dur_s"] * 1e6,
                          "args": {k: v for k, v in ev.items()
                                   if k not in ("v", "kind", "ts", "t0",
                                                "name", "dur_s")}})
        elif kind in ("train_step", "ckpt_save", "ckpt_restore", "compile"):
            name = {"train_step": "train_step", "compile": ev.get("name",
                                                                 "compile"),
                    "ckpt_save": "ckpt_save",
                    "ckpt_restore": "ckpt_restore"}[kind]
            dur = ev.get("step_s", ev.get("dur_s", 0.0))
            trace.append({"ph": "X", "pid": pid, "tid": tid(kind),
                          "name": name, "ts": ts_us - dur * 1e6,
                          "dur": dur * 1e6,
                          "args": {k: v for k, v in ev.items()
                                   if k not in ("v", "kind", "ts")}})
        elif kind == "train_window":
            for key in ("steps_per_s", "tokens_per_s", "mem_drift_x"):
                if ev.get(key) is not None:
                    trace.append({"ph": "C", "pid": pid, "name": key,
                                  "ts": ts_us, "args": {key: ev[key]}})
        elif kind == "serve_request":
            if "ttft_s" in ev:
                trace.append({"ph": "C", "pid": pid, "name": "ttft_ms",
                              "ts": ts_us,
                              "args": {"ttft_ms": ev["ttft_s"] * 1e3}})
    with open(out_path, "w") as f:
        json.dump({"traceEvents": trace, "displayTimeUnit": "ms"}, f)
    return len(trace)


def trend(traj_path: str, bench: Optional[str], window: int) -> int:
    from repro.obs import trajectory as traj
    entries = traj.read_trajectory(traj_path)
    if not entries:
        print(f"[trace] {traj_path}: no trajectory entries")
        return 0
    rows = []
    for r in traj.trend_rows(entries, bench=bench, window=window):
        arrow = {"higher": "^good", "lower": "v good", None: ""}[r["direction"]]
        rows.append((r["bench"], r["config"] or "-", r["metric"], r["n"],
                     _fmt(r["latest"]), _fmt(r["median"]),
                     "-" if r["delta_pct"] is None
                     else f"{r['delta_pct']:+.1f}%", r["spark"], arrow))
    _table(f"bench trajectory ({len(entries)} entries, "
           f"latest vs trailing median of {window})", rows,
           header=("bench", "config", "metric", "n", "latest", "median",
                   "delta", "trend", "dir"))
    return 0


def regress(traj_path: str, max_regression_pct: float, min_points: int,
            window: int, bench: Optional[str]) -> int:
    from repro.obs import trajectory as traj
    entries = traj.read_trajectory(traj_path)
    gated = [r for r in traj.trend_rows(entries, bench=bench, window=window)
             if r["direction"] is not None]
    short = sum(1 for r in gated if r["n"] < min_points)
    bad = traj.regressions(entries, max_regression_pct,
                           min_points=min_points, window=window, bench=bench)
    if bad:
        print(f"[trace] {traj_path}: {len(bad)} regression(s) "
              f"> {max_regression_pct:.0f}% vs trailing median")
        for r in bad:
            print(f"  - {r['bench']}/{r['config']}/{r['metric']}: "
                  f"{_fmt(r['median'])} -> {_fmt(r['latest'])} "
                  f"({r['regression_pct']:+.1f}% worse, n={r['n']}) "
                  f"{r['spark']}")
        return 1
    note = (f" ({short} series still < {min_points} points, report-only)"
            if short else "")
    print(f"[trace] {traj_path}: no regressions > {max_regression_pct:.0f}% "
          f"across {len(gated)} gated series{note}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.trace")
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("summarize", "validate", "export"):
        p = sub.add_parser(name)
        p.add_argument("run", help="telemetry RUN.jsonl file")
        if name == "validate":
            p.add_argument("--require-zero-recompiles", action="store_true")
            p.add_argument("--max-drift", type=float, default=None,
                           help="bound the last-window estimator drift to "
                                "[1/x, x]")
            p.add_argument("--max-reconstruction-err", type=float,
                           default=None,
                           help="bound the worst per-layer relative "
                                "reconstruction error across layer_audit "
                                "events (fails too when audit never ran)")
            p.add_argument("--min-prefix-hits", type=int, default=None,
                           help="floor the final serve.prefix_hits counter "
                                "(paged radix prefix cache, DESIGN.md §15)")
        if name == "export":
            p.add_argument("--out", default=None,
                           help="output trace path (default: RUN.trace.json)")
    for name in ("trend", "regress"):
        p = sub.add_parser(name)
        p.add_argument("trajectory", help="BENCH_TRAJECTORY.jsonl file")
        p.add_argument("--bench", default=None,
                       help="restrict to one benchmark name")
        p.add_argument("--window", type=int, default=8,
                       help="trailing-median window (prior points)")
        if name == "regress":
            p.add_argument("--max-regression-pct", type=float, default=20.0)
            p.add_argument("--min-points", type=int, default=3,
                           help="series shorter than this only report "
                                "(non-blocking until history accumulates)")
    args = ap.parse_args(argv)

    if args.cmd == "trend":
        return trend(args.trajectory, args.bench, args.window)
    if args.cmd == "regress":
        return regress(args.trajectory, args.max_regression_pct,
                       args.min_points, args.window, args.bench)

    events = read_events(args.run, on_error="skip")
    if args.cmd == "summarize":
        summarize(events)
        return 0
    if args.cmd == "validate":
        errors = validate_events(
            events, require_zero_recompiles=args.require_zero_recompiles,
            max_drift=args.max_drift,
            max_reconstruction_err=args.max_reconstruction_err,
            min_prefix_hits=args.min_prefix_hits)
        if errors:
            print(f"[trace] {args.run}: INVALID")
            for e in errors:
                print(f"  - {e}")
            return 1
        print(f"[trace] {args.run}: OK ({len(events)} events, schema "
              f"v{events[0].get('v')})")
        return 0
    out = args.out or (args.run.rsplit(".jsonl", 1)[0] + ".trace.json")
    n = export_chrome_trace(events, out)
    print(f"[trace] wrote {n} trace events -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
