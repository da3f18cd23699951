"""Serving cells: the program's paged ``ServingEngine`` under an open loop.

Requests come from the traffic file (``chipbench.traffic.serve_requests``):
each has a due time in the window, and the loop submits it once it is due,
whatever the engine is doing (an open loop: a stall delays every later
request and the delay counts).  TTFT runs from the due time to the moment
the host first sees a token of the request; TPOT is a request's mean time
between tokens after its first.  Both are taken over every request due in
the window, each waited for to its end.

Set-up makes the weights on the device from the seed, builds the engine, and
warms up one request per prefill bucket that this seed's prompts use, and
the decode step.  After the window, a sample of the finished requests (drawn
from the seed, the longest among them) is run through the plain reference,
and each served token is held against the reference's best logit at its
position.
"""
from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, compare, traffic
from chipbench import weights as W
from chipbench.kinds.train import program_config, program_counts


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _spanned(spans, name, fn):
    def call(*a, **k):
        with spans(name):
            return fn(*a, **k)
    return call


def build(cell, seed: int, spans=None):
    from repro import obs
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine
    mix, cfgj = cell.traffic, cell.config
    ref = importlib.import_module(
        f"chipbench.reference.{cfgj['reference']}").RevFFN(cfgj)
    model = Model(program_config(cfgj))
    counts = program_counts(model, ref)
    params = W.make_tree(seed, ref.layout(), counts,
                         jnp.dtype(model.cfg.dtype))
    tel = obs.Telemetry(path=None, role="serve", config=cfgj["name"])
    eng = ServingEngine(model, params, slots=mix["slots"],
                        buf_len=mix["buf_len"], paged=True,
                        page_size=mix["page_size"],
                        drain_every=mix["drain_every"],
                        prefix_cache=mix.get("prefix_cache", True),
                        telemetry=tel)
    if spans is not None:       # name the engine's host phases in the trace
        eng._admit = _spanned(spans, "admit", eng._admit)
        eng._drain = _spanned(spans, "drain", eng._drain)
    return ref, model, eng, tel


def _request(r, uid=None):
    from repro.serving.engine import Request
    return Request(uid=r["uid"] if uid is None else uid, prompt=r["prompt"],
                   max_new_tokens=r["max_new_tokens"], eos_id=-1,
                   temperature=0.0, seed=0)


def warm_up(eng, reqs, vocab: int):
    """One short request per prefill bucket the prompts use, then the
    compiled set is frozen."""
    buckets = sorted({min(_pow2(len(r["prompt"])), eng.buf_len)
                      for r in reqs})
    for i, b in enumerate(buckets):
        p = np.full((b // 2 + 1,), traffic.FIRST_TOKEN + i, np.int32)
        eng.submit(_request({"uid": 0, "prompt": p, "max_new_tokens": 2},
                            uid=-1 - i))
    eng.run()
    eng.done.clear()
    return buckets


def serve_window(eng, reqs, spans, clock=time.perf_counter,
                 tail_s: float = 120.0):
    """Submit each request once it is due and step the engine until every
    request is finished (or ``tail_s`` past the last due time).  Returns per
    request: due, submitted, first token seen, finished, tokens."""
    n = len(reqs)
    rec = {r["uid"]: {"due": r["due_s"], "sub": None, "first": None,
                      "done": None, "req": None} for r in reqs}
    t0 = clock()
    i, n_done = 0, 0
    live = []
    while n_done < n:
        now = clock() - t0
        while i < n and reqs[i]["due_s"] <= now:
            with spans("submit"):
                q = eng.submit(_request(reqs[i]))
            rec[reqs[i]["uid"]].update(sub=clock() - t0, req=q)
            live.append(reqs[i]["uid"])
            i += 1
        if eng.queue or any(a is not None for a in eng.active):
            with spans("engine_step"):
                eng.step()
            now = clock() - t0
            still = []
            for u in live:
                r = rec[u]
                if r["first"] is None and r["req"].generated:
                    r["first"] = now
                if u in eng.done:
                    r["done"] = now
                    n_done += 1
                else:
                    still.append(u)
            live = still
        elif i < n:
            with spans("wait_arrival"):
                time.sleep(max(0.0, min(reqs[i]["due_s"] - now, 0.002)))
        if i == n and clock() - t0 > reqs[-1]["due_s"] + tail_s:
            break
    return rec, clock() - t0


def _p(values, q):
    return float(np.percentile(np.asarray(values, float), q)) if values \
        else float("nan")


def run(cell, seed: int, seconds: float, trace: bool, ctx) -> dict:
    mix, cfgj = cell.traffic, cell.config
    spans = ctx.spans
    with spans("setup.build"):
        ref, model, eng, tel = build(cell, seed, spans)
    win = min(seconds, float(mix.get("trace_seconds", seconds))) if trace \
        else seconds
    reqs = traffic.serve_requests(mix, ref.V, seed, win)
    with spans("setup.warm_up"):
        warm_up(eng, reqs, ref.V)
    warm = eng.mark_warm()
    setup_s = time.perf_counter() - ctx.t0

    compiles0 = ctx.compiles.n
    if trace:
        jax.profiler.start_trace(str(ctx.trace_dir))
    with jax.profiler.TraceAnnotation("bench.window"):
        rec, elapsed = serve_window(eng, reqs, spans)
    if trace:
        jax.profiler.stop_trace()
    compiles = ctx.compiles.n - compiles0
    now_sizes = eng.jit_cache_sizes()
    compiles += sum(max(now_sizes[k] - warm[k], 0) for k in now_sizes)
    peak = bench.memory_peak_bytes(cell.chips)

    finished = {u: r for u, r in rec.items() if r["done"] is not None}
    ttft = [(r["first"] - r["due"]) * 1e3 for r in finished.values()]
    tpot = [(r["done"] - r["first"]) * 1e3 / (len(r["req"].generated) - 1)
            for r in finished.values() if len(r["req"].generated) > 1]
    late = [(r["sub"] - r["due"]) * 1e3 for r in rec.values()
            if r["sub"] is not None]
    served = {u: (np.asarray(r["req"].prompt), list(r["req"].generated))
              for u, r in finished.items()}
    short = [u for u, r in finished.items()
             if len(r["req"].generated) != r["req"].max_new_tokens]
    events = [e for e in tel.sink.events if e.get("kind") == "serve_request"
              and e.get("uid", -1) >= 0]
    tokens = sum(len(g) for _, g in served.values())
    prefill_tokens = sum(len(p) for p, _ in served.values())

    del eng, tel
    gc.collect()
    t_ref = time.perf_counter()
    refmod = importlib.import_module(
        f"chipbench.reference.{cfgj['reference']}")
    sample = pick_sample(served, seed, mix["check"]["sample"])
    gap = refmod.served_gap(ref, seed, [served[u] for u in sample])
    ref_s = time.perf_counter() - t_ref
    numbers = {"served_logit_gap": gap,
               "requests_unfinished": float(len(reqs) - len(finished)
                                            + len(short))}
    correct, checks = compare.judge(numbers, cell.limits["checks"])
    correct = correct and compiles == 0

    return {
        "correct": correct, "attempted": len(reqs),
        "failed": len(reqs) - len(finished) + len(short),
        "setup_s": setup_s, "window_s": elapsed, "peak_bytes": peak,
        "compiles_in_window": compiles, "reference_s": ref_s,
        "numbers": dict(numbers, sample=len(sample),
                        generator_late_p90_ms=_p(late, 90),
                        ttft_p50_ms=_p(ttft, 50), tpot_p50_ms=_p(tpot, 50),
                        served_tokens=tokens),
        "checks": checks,
        "e2e": {"serve_ttft_p90_ms": _p(ttft, 90),
                "serve_tpot_p90_ms": _p(tpot, 90), "setup_s": setup_s},
        "layer_ctx": {"kind": "serve", "window_s": elapsed,
                      "served_tokens": tokens,
                      "prefill_tokens": prefill_tokens,
                      "requests": served, "slots": mix["slots"]},
        "program_events": events,
    }


def pick_sample(served: dict, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest (prompt and
    served tokens) always among them."""
    uids = sorted(served)
    if not uids:
        return []
    longest = max(uids, key=lambda u: len(served[u][0]) + len(served[u][1]))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 3])
    k = min(n - 1, len(rest))
    pick = list(rng.choice(rest, size=k, replace=False)) if k > 0 else []
    return [longest] + [int(u) for u in pick]
