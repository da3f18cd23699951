"""Serving launcher: continuous-batching engine with on-device sampling.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        [--no-reduced] [--requests 16] [--slots 4] [--gen 32] \
        [--temperature 0.8] [--top-k 40] [--top-p 0.95] [--drain-every 4] \
        [--paged] [--page-size 16] [--kv-pages N | --kv-budget-gb G] \
        [--shared-prefix N] [--no-prefix-cache]

Submits ``--requests`` requests with mixed prompt lengths to a
``ServingEngine`` (length-bucketed batched prefill, per-request seeded
sampling, EOS/length termination on device) and reports throughput.
``--paged`` serves from the block-paged KV pool with radix prefix sharing
(DESIGN.md §15); ``--shared-prefix N`` gives every request the same
N-token system prefix so the prefix cache has something to hit.  Reduced
(smoke-scale) configs are the default, for CPU runs; ``--no-reduced`` serves
the full config (TPU).
"""
from __future__ import annotations

import argparse
import time

#: smallest token id the synthetic prompts use (ids below are special)
LO_TOKEN = 4


def serve_requests(eng, prompts, *, gen: int, vocab_size: int,
                   temperature: float = 0.0, top_k: int = 0,
                   top_p: float = 1.0, warmup: bool = True):
    """Serve ``prompts`` (one request each, ``gen`` new tokens, no EOS).

    The warmup pass MIRRORS the workload — same prompt lengths, same
    shared-prefix structure, shifted token values — so admission touches
    every prefill bucket the real run will use, including the
    radix-shortened SUFFIX buckets in paged mode.  Then the compiled-signature
    set is frozen: any further compile is a post-warmup recompile.

    Returns (done requests by uid, wall seconds of the measured run,
    post-warmup recompiles)."""
    import numpy as np
    from repro.serving.engine import Request

    if warmup:
        span = max(vocab_size - LO_TOKEN, 1)
        for i, p in enumerate(prompts):
            wp = (LO_TOKEN + (p - LO_TOKEN + 1) % span).astype(np.int32)
            eng.submit(Request(uid=1_000_000 + i, prompt=wp,
                               max_new_tokens=2, eos_id=-1,
                               temperature=temperature, seed=i))
        eng.run()
        eng.done.clear()
    warm = eng.mark_warm()

    for uid, prompt in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=prompt, max_new_tokens=gen,
                           eos_id=-1, temperature=temperature,
                           top_k=top_k, top_p=top_p, seed=uid))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    now = eng.jit_cache_sizes()
    recompiles = sum(max(now[k] - warm[k], 0) for k in now)
    return done, dt, recompiles


def make_prompts(rng, n: int, *, lo_len: int, hi_len: int,
                 shared_prefix: int, vocab_size: int):
    """``n`` random prompts of ``lo_len..hi_len`` tokens, each behind the
    same ``shared_prefix``-token system prefix (none when 0)."""
    import numpy as np
    sys_prefix = rng.integers(LO_TOKEN, vocab_size,
                              size=shared_prefix).astype(np.int32)
    prompts = []
    for _ in range(n):
        plen = int(rng.integers(lo_len, max(lo_len + 1, hi_len + 1)))
        tail = rng.integers(LO_TOKEN, vocab_size, size=plen).astype(np.int32)
        prompts.append(np.concatenate([sys_prefix, tail]))
    return prompts


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    # BooleanOptionalAction so the default can actually be turned off
    # (--reduced used to be store_true with default=True: a no-op flag)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="max prompt length (prompts are mixed 4..this)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--buf-len", type=int, default=0,
                    help="cache buffer (0 -> prompt-len + gen)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--drain-every", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="serve from the block-paged KV pool with radix "
                         "prefix sharing (DESIGN.md §15)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="physical KV pages in the pool (0 -> dense-parity: "
                         "slots * pages-per-slot)")
    ap.add_argument("--kv-budget-gb", type=float, default=None,
                    help="size the page pool from an HBM budget via "
                         "memory.estimator.kv_page_cost")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix prefix sharing across requests (paged only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same N-token system prefix "
                         "(exercises the prefix cache)")
    ap.add_argument("--lookahead", type=int, default=8,
                    help="admission queue lookahead window for same-bucket "
                         "batching")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write a telemetry JSONL to PATH: per-request "
                         "TTFT/TPOT, queue depth / slot utilization / page "
                         "pool gauges, prefix-hit counters, prefill+decode "
                         "spans, and a post-warmup recompile watchdog "
                         "(repro.obs; inspect with `python -m "
                         "repro.launch.trace summarize PATH`)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the workload-mirroring warmup pass (the "
                         "recompile watchdog then has no baseline)")
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import Model
    from repro.serving.engine import ServingEngine

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    extras = None
    if cfg.family == "encdec":
        extras = {"enc_feats": jax.random.normal(
            jax.random.PRNGKey(2), (1, cfg.encoder_seq_len, cfg.d_model))}
    if cfg.family == "vlm":
        extras = {"img": jax.random.normal(
            jax.random.PRNGKey(2), (1, cfg.num_image_tokens, cfg.d_model))}

    from repro import obs

    tel = obs.as_telemetry(args.telemetry, role="serve", config=cfg.name,
                           slots=args.slots, drain_every=args.drain_every,
                           paged=args.paged)
    buf = args.buf_len or (args.shared_prefix + args.prompt_len + args.gen)
    eng = ServingEngine(model, params, slots=args.slots, buf_len=buf,
                        extras=extras, drain_every=args.drain_every,
                        telemetry=tel, lookahead=args.lookahead,
                        paged=args.paged, page_size=args.page_size,
                        kv_pages=args.kv_pages or None,
                        kv_budget_gb=args.kv_budget_gb,
                        prefix_cache=args.prefix_cache)

    prompts = make_prompts(np.random.default_rng(0), args.requests,
                           lo_len=4, hi_len=args.prompt_len,
                           shared_prefix=args.shared_prefix,
                           vocab_size=cfg.vocab_size)
    done, dt, recompiles = serve_requests(
        eng, prompts, gen=args.gen, vocab_size=cfg.vocab_size,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        warmup=not args.no_warmup)
    n_tok = sum(len(r.generated) for r in done.values())
    print(f"[serve] {args.arch}: {len(done)} requests, {n_tok} tokens, "
          f"{n_tok / dt:.1f} tok/s (slots={args.slots}, "
          f"drain_every={args.drain_every}, "
          f"temperature={args.temperature}, top_k={args.top_k}, "
          f"top_p={args.top_p})")
    print(f"[serve] jit cache: {eng.jit_cache_sizes()} "
          f"(post-warmup recompiles: {recompiles})")
    if args.paged:
        hits = (tel.counter("serve.prefix_hits").value
                if tel.enabled else "n/a")
        hit_tok = (tel.counter("serve.prefix_hit_tokens").value
                   if tel.enabled else "n/a")
        print(f"[serve] paged: {eng.kv_pages} pages x {eng.page_size} tok "
              f"(used {eng.page_pool.n_used}, free {eng.page_pool.n_free}), "
              f"prefix hits {hits} ({hit_tok} tokens skipped)")
    sample = done[0].generated[:12]
    print(f"[serve] request 0 tokens: {sample}")
    if tel.enabled:
        tel.close()
        print(f"[serve] telemetry -> {args.telemetry} "
              f"(python -m repro.launch.trace summarize {args.telemetry})")


if __name__ == "__main__":
    main()
