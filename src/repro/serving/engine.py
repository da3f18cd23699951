"""Continuous-batching serving engine: batched bucketed prefill, on-device
sampling and termination, host drains every k steps.

Production pattern mapped to JAX: a fixed number of decode SLOTS batched by
vmap — every slot tracks its own ``t`` so rope positions and cache writes
stay correct under staggered admission.  Design points (DESIGN.md §9, §15):

* **On-device sampling/termination** (`repro.serving.sampling`): each engine
  step decodes all slots AND samples the next token per slot (temperature /
  top-k / top-p, greedy at zero temperature, per-request seeded keys) inside
  one jitted call; EOS and token-budget termination also run on device.  The
  host never syncs per step — it drains the device-side output buffers every
  ``drain_every`` steps (one transfer), so decode dispatch is free of the
  per-step ``argmax`` + host round-trip the old engine paid.

* **Length-bucketed batched prefill**: queued requests are padded to
  power-of-two length buckets and prefilled together in one vmapped call over
  the slot axis — admission compiles once per bucket, never per prompt
  length, and a backlog drains in O(buckets) compiled calls.  Padding is
  causal-masked out during prefill; afterwards the padded cache entries are
  invalidated (`pos -> -1`) and the slot's ``t`` is set to the real prompt
  length, so decode numerics match an unpadded per-sequence prefill exactly.
  Sliding-window prompts longer than the rolling buffer prefill at bucketed
  length too: the real token count rides into the decode step (``seq_len``)
  so the window buffer keeps the real tail, not pad tokens.  Families with
  recurrent state (ssm / hybrid) cannot absorb padding tokens (the state
  integrates them), so they bucket by exact length instead — still batched
  across same-length prompts.

* **Lookahead admission batching**: admission scans a bounded window of the
  queue (``lookahead``) and admits the largest same-bucket group in it, so
  a queue-head prompt whose bucket differs from the requests behind it no
  longer forces every bucket into its own prefill launch.  FIFO fairness is
  bounded: the head's bucket wins ties, and after two skipped rounds the
  head's bucket is forced.

* **Paged KV cache + radix prefix sharing** (``paged=True``, DESIGN.md §15):
  instead of one dense ``buf_len`` cache per slot, KV state lives in a pool
  of fixed-size physical pages; per-slot page tables map logical positions
  into the pool, decode attention reads through the table
  (`kernels/paged_attention.py` — Pallas gather kernel on TPU, exact dense
  math off-TPU), and admission books pages against the pool instead of
  assuming worst-case length — concurrency becomes HBM-bound, not
  slot-grid-bound.  A radix trie keyed on page-granular token chunks maps
  shared prompt prefixes to the same reference-counted physical pages, so a
  repeated system prompt is prefilled once and subsequent requests only
  prefill their (bucketed) suffix.  Pages return to the free list when the
  last holder (request or trie node) releases them; the trie evicts only
  fully-released pages, LRU-first, under pool pressure.

A request longer than the cache buffer (or the whole page pool) is
terminally REJECTED at submit — an ``admission_reject`` event plus an empty
generation, never an exception that would orphan the rest of the queue.  A
request whose FIRST token already terminates it (EOS at prefill, or
``max_new_tokens == 1``) is finished at admission and never burns decode
steps.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.serving import paged as paged_mod
from repro.serving import sampling


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (P,) int32
    max_new_tokens: int = 32
    eos_id: int = 2
    temperature: float = 0.0        # 0 -> greedy
    top_k: int = 0                  # 0 -> disabled
    top_p: float = 1.0
    seed: int = 0
    generated: Optional[List[int]] = None   # filled by the engine
    rejected: bool = False          # terminally rejected at admission


def _is_key(entry, name: str) -> bool:
    return getattr(entry, "key", None) == name


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class ServingEngine:
    def __init__(self, model, params, *, slots: int = 4, buf_len: int = 256,
                 extras=None, drain_every: int = 4,
                 pad_prefill: Optional[bool] = None, telemetry=None,
                 lookahead: int = 8,
                 paged: bool = False, page_size: int = 16,
                 kv_pages: Optional[int] = None,
                 kv_budget_gb: Optional[float] = None,
                 prefix_cache: bool = True):
        self.model = model
        self.params = params
        self.tel = obs.as_telemetry(telemetry, role="serve",
                                    config=model.cfg.name)
        # host-side request timestamps for TTFT/TPOT (drain-granular: the
        # host only observes tokens at drain boundaries, so TTFT is
        # quantized by drain_every — the price of syncless decode)
        self._submit_t: Dict[int, float] = {}
        self._admit_t: Dict[int, float] = {}
        self._first_tok_t: Dict[int, float] = {}
        self.slots = slots
        self.buf_len = buf_len
        self.drain_every = drain_every
        self.lookahead = max(lookahead, 1)
        self._head_skips = 0
        # extras (encoder output / image features feeding cross-attention
        # caches) are engine-level: the fresh-cache template is built from
        # them ONCE — admission reuses it instead of re-running the encoder
        self.extras = extras
        # recurrent-state families integrate padding tokens into the state;
        # exact-length buckets keep batched prefill (same-length runs) without
        # corrupting it
        if pad_prefill is None:
            pad_prefill = model.cfg.family not in ("ssm", "hybrid")
        self.pad_prefill = pad_prefill
        w = model.cfg.sliding_window
        # logical per-slot context length (what Model.init_cache allocates)
        self.ctx_len = min(buf_len, w) if w else buf_len

        # per-slot cache trees stacked on a leading slot axis (slot batch=1);
        # the SAME layout is used for live and fresh caches so admission can
        # splice whole prefilled slots with one masked where over the tree
        one = model.init_cache(params, 1, buf_len, extras=extras)
        stack = lambda a: jnp.stack([a] * slots)
        self._fresh = jax.tree_util.tree_map(stack, one)

        self.paged = paged
        if paged:
            self.page_size = page_size
            self.max_pages = -(-self.ctx_len // page_size)
            if kv_pages is None:
                if kv_budget_gb is not None:
                    from repro.memory import estimator as est_mod
                    cost = est_mod.kv_page_cost(model.cfg, page_size=page_size,
                                                seq=self.ctx_len)
                    kv_pages = max(
                        int(kv_budget_gb * est_mod.GiB)
                        // cost["page_bytes"], 1)
                else:
                    kv_pages = slots * self.max_pages
            self.kv_pages = kv_pages
            self.pool = model.init_kv_pool(kv_pages, page_size)
            self.page_pool = paged_mod.PagePool(kv_pages, page_size)
            # prefix reuse is unsound once a rolling window wraps into a
            # shared page, so windowed configs run paged-without-radix
            self.prefix = (paged_mod.RadixCache(self.page_pool)
                           if prefix_cache and not w else None)
            self._pt_host = np.full((slots, self.max_pages), -1, np.int32)
            self._pt = jnp.asarray(self._pt_host)
            self._tvec = jnp.zeros((slots,), jnp.int32)
            # per-slot (logical page list, matched prefix tokens)
            self._slot_pages: List[Optional[tuple]] = [None] * slots
        else:
            self.cache = self._fresh
        self.sstate = sampling.init_state(slots, buf_len)

        self.active: List[Optional[Request]] = [None] * slots
        self.queue: deque = deque()
        self.done: Dict[int, Request] = {}

        # the jitted entry points take ``params`` as an argument: closed
        # over, the weights would be baked into each program as constants
        # (GBs of host memory per compile at published widths)
        def _decode_hidden(params, cache_slot, tok):
            return model.decode_step_hidden(params, cache_slot, tok)

        def _prefill_hidden(params, cache_slot, tok, n):
            return model.decode_step_hidden(params, cache_slot, tok,
                                            seq_len=n)

        def _first_token(params, h, lengths, seeds, temps, top_ks, top_ps):
            """Sample token 0 for every slot from the last real prefill
            position (shared by the dense and paged admission paths)."""
            idx = jnp.clip(lengths - 1, 0, h.shape[2] - 1)
            hg = h[jnp.arange(slots), 0, idx]                  # (slots, d)
            logits = model.lm_logits(params, hg)
            keys = jax.vmap(jax.random.PRNGKey)(seeds.astype(jnp.uint32))
            keys0 = jax.vmap(jax.random.fold_in)(keys,
                                                 jnp.zeros_like(lengths))
            return jax.vmap(sampling.sample_token)(
                logits.astype(jnp.float32), keys0, temps, top_ks, top_ps)

        def _steps(params, cache, st):
            def one(carry, _):
                cache, st = carry
                tok_in = st["last_tok"].reshape(slots, 1, 1)
                h, cache = jax.vmap(_decode_hidden, in_axes=(None, 0, 0))(
                    params, cache, tok_in)
                logits = model.lm_logits(params, h[:, 0, -1])   # (slots, V)
                tok = sampling.sample(logits, st)
                return (cache, sampling.advance(st, tok)), None
            (cache, st), _ = jax.lax.scan(one, (cache, st), None,
                                          length=self.drain_every)
            return cache, st

        def _prefill_admit(params, cache, fresh, st, tokens, lengths, admit,
                           seeds, temps, top_ks, top_ps, eos_ids, max_news):
            """Batched bucketed prefill + admission splice, one compile per
            bucket length.  tokens: (slots, 1, Lb) right-padded; only rows
            selected by ``admit`` are spliced in."""
            h, pre = jax.vmap(_prefill_hidden, in_axes=(None, 0, 0, 0))(
                params, fresh, tokens, lengths)
            tok0 = _first_token(params, h, lengths, seeds, temps, top_ks,
                                top_ps)

            def splice(path, eng, new):
                m = admit.reshape((slots,) + (1,) * (eng.ndim - 1))
                out = jnp.where(m, new, eng)
                if _is_key(path[-1], "pos"):
                    # invalidate padded cache entries: positions >= the real
                    # prompt length were written by padding tokens
                    lb = lengths.reshape((slots,) + (1,) * (eng.ndim - 1))
                    out = jnp.where(m & (out >= lb), -1, out)
                elif _is_key(path[-1], "t"):
                    out = jnp.where(admit, lengths, out)
                return out

            cache = jax.tree_util.tree_map_with_path(splice, cache, pre)
            st = sampling.admit_row(st, admit, seed=seeds, temperature=temps,
                                    top_k=top_ks, top_p=top_ps,
                                    eos_id=eos_ids, max_new=max_news,
                                    first_tok=tok0)
            return cache, st

        # ------------------------------------------------ paged jitted fns

        def _steps_paged(params, pool, pt, tvec, st):
            def one(carry, _):
                pool, tvec, st = carry
                tok_in = st["last_tok"].reshape(slots, 1)
                h, pool = model.decode_step_hidden_paged(
                    params, pool, pt, tvec, tok_in, st["active"],
                    kv_len=self.ctx_len)
                logits = model.lm_logits(params, h[:, 0])       # (slots, V)
                tok = sampling.sample(logits, st)
                return (pool, tvec + 1, sampling.advance(st, tok)), None
            (pool, tvec, st), _ = jax.lax.scan(one, (pool, tvec, st), None,
                                               length=self.drain_every)
            return pool, tvec, st

        def _prefill_admit_paged(params, pool, pt, tvec, fresh, st, tokens,
                                 suffix_lens, plens, m_vec, admit, seeds,
                                 temps, top_ks, top_ps, eos_ids, max_news):
            """Paged admission, one compile per SUFFIX bucket: gather the
            radix-matched prefix pages into the dense prefill workspace,
            prefill only the (bucketed) suffix, sample token 0, then scatter
            the dense K/V into this slot's private pages.  Shared pages are
            never rewritten — ``j >= m`` masks them out of the scatter."""
            C, pg, maxp = self.ctx_len, self.page_size, self.max_pages
            P = self.kv_pages
            jidx = jnp.arange(C, dtype=jnp.int32)
            safe_pt = jnp.clip(pt, 0, P - 1)
            in_pref = jidx[None, :] < m_vec[:, None]            # (slots, C)

            seeded = {"t": jnp.where(admit, m_vec, fresh["t"])}
            for name, pool_s in pool.items():
                fkv = fresh[name]["kv"]
                out = {}
                for key in ("k", "v"):
                    leaf = pool_s["kv"][key]                # (L, P, pg, KV, hd)
                    gat = jnp.moveaxis(leaf[:, safe_pt], 1, 0)
                    gat = gat.reshape(slots, leaf.shape[0], maxp * pg,
                                      *leaf.shape[3:])[:, :, :C]
                    m = in_pref[:, None, None, :, None, None]
                    out[key] = jnp.where(m, gat[:, :, None], fkv[key])
                out["pos"] = jnp.where(in_pref[:, None, :],
                                       jidx[None, None, :], fkv["pos"])
                seeded[name] = {"kv": out}

            h, pre = jax.vmap(_prefill_hidden, in_axes=(None, 0, 0, 0))(
                params, seeded, tokens, suffix_lens)
            tok0 = _first_token(params, h, suffix_lens, seeds, temps, top_ks,
                                top_ps)

            pageof = jnp.clip(jidx // pg, 0, maxp - 1)
            phys = pt[:, pageof]                                # (slots, C)
            dest = phys * pg + (jidx % pg)[None, :]
            ok = admit[:, None] & (jidx[None, :] >= m_vec[:, None]) & (phys >= 0)
            dflat = jnp.where(ok, dest, P * pg).reshape(-1)

            new_pool = {}
            for name, pool_s in pool.items():
                pkv = pre[name]["kv"]
                L = pool_s["kv"]["k"].shape[0]
                out = {}
                for key in ("k", "v"):
                    vals = jnp.moveaxis(pkv[key][:, :, 0], 0, 1)
                    vals = vals.reshape(L, slots * C, *vals.shape[3:])
                    flat = pool_s["kv"][key].reshape(
                        L, P * pg, *pool_s["kv"][key].shape[3:])
                    out[key] = flat.at[:, dflat].set(
                        vals, mode="drop").reshape(pool_s["kv"][key].shape)
                posv = pkv["pos"]                               # (slots, L, C)
                posv = jnp.where((posv >= 0) & (posv < plens[:, None, None]),
                                 posv, -1)
                posv = jnp.moveaxis(posv, 0, 1).reshape(L, slots * C)
                pflat = pool_s["kv"]["pos"].reshape(L, P * pg)
                out["pos"] = pflat.at[:, dflat].set(
                    posv, mode="drop").reshape(pool_s["kv"]["pos"].shape)
                new_pool[name] = {"kv": out}

            st = sampling.admit_row(st, admit, seed=seeds, temperature=temps,
                                    top_k=top_ks, top_p=top_ps,
                                    eos_id=eos_ids, max_new=max_news,
                                    first_tok=tok0)
            tvec = jnp.where(admit, plens, tvec)
            return new_pool, tvec, st

        if paged:
            self._step_fn = jax.jit(_steps_paged)
            self._admit_fn = jax.jit(_prefill_admit_paged)
        else:
            self._step_fn = jax.jit(_steps)
            self._admit_fn = jax.jit(_prefill_admit)
        self._recompile_wd = obs.RecompileWatchdog(
            {"step": self._step_fn, "admit": self._admit_fn},
            telemetry=self.tel, scope="serve")

    # ------------------------------------------------------------ submit

    def _reject(self, req: Request, need: int, capacity: int, what: str):
        """Terminal rejection: the request completes with an empty
        generation instead of raising (an exception here would crash the
        caller mid-run and orphan every queued request)."""
        req.generated = []
        req.rejected = True
        self.done[req.uid] = req
        self.tel.counter("serve.admission_rejects").inc()
        self.tel.emit("admission_reject", uid=req.uid, need=need,
                      capacity=capacity, what=what)

    def submit(self, req: Request):
        need = int(req.prompt.size + req.max_new_tokens)
        if need > self.buf_len:
            self._reject(req, need, self.buf_len, "buf_len")
            return req
        if self.paged:
            total = min(-(-need // self.page_size), self.max_pages)
            if total > self.kv_pages:
                self._reject(req, total, self.kv_pages, "kv_pages")
                return req
        req.generated = []
        self._submit_t[req.uid] = time.perf_counter()
        self.tel.counter("serve.requests_submitted").inc()
        self.queue.append(req)
        return req

    # ------------------------------------------------------------ admission

    def _bucket(self, n: int) -> int:
        if not self.pad_prefill:
            return n
        return min(_pow2(n), self.buf_len)

    def _gather_batch(self, capacity: int) -> List[Request]:
        """Pop up to ``capacity`` same-bucket requests from a bounded
        lookahead window of the queue.  The largest bucket group in the
        window wins (fewest prefill launches); the head's bucket breaks
        ties and is forced outright after two skipped rounds, so the queue
        head is admitted within three admission rounds — the FIFO fairness
        bound."""
        if not self.queue or capacity <= 0:
            return []
        W = min(len(self.queue), max(self.lookahead, capacity))
        counts: Dict[int, list] = {}
        for i in range(W):
            b = self._bucket(self.queue[i].prompt.size)
            info = counts.setdefault(b, [0, i])
            info[0] += 1
        head_b = self._bucket(self.queue[0].prompt.size)
        best = max(counts,
                   key=lambda b: (min(counts[b][0], capacity), b == head_b,
                                  -counts[b][1]))
        if best != head_b and self._head_skips >= 2:
            best = head_b
        self._head_skips = 0 if best == head_b else self._head_skips + 1

        picked, keep = [], []
        for _ in range(W):
            r = self.queue.popleft()
            if (len(picked) < capacity
                    and self._bucket(r.prompt.size) == best):
                picked.append(r)
            else:
                keep.append(r)
        for r in reversed(keep):
            self.queue.appendleft(r)
        return picked

    def _admit(self):
        if self.paged:
            return self._admit_paged()
        while self.queue:
            free = [s for s in range(self.slots) if self.active[s] is None]
            if not free:
                return
            batch = self._gather_batch(len(free))
            if not batch:
                return
            lb = self._bucket(batch[0].prompt.size)

            tokens = np.zeros((self.slots, 1, lb), np.int32)
            lengths = np.ones((self.slots,), np.int32)
            admit = np.zeros((self.slots,), bool)
            seeds = np.zeros((self.slots,), np.int32)
            temps = np.zeros((self.slots,), np.float32)
            top_ks = np.zeros((self.slots,), np.int32)
            top_ps = np.ones((self.slots,), np.float32)
            eos_ids = np.full((self.slots,), -1, np.int32)
            max_news = np.ones((self.slots,), np.int32)
            for req, s in zip(batch, free):
                p = np.asarray(req.prompt, np.int32)
                tokens[s, 0, :p.size] = p
                lengths[s] = p.size
                admit[s] = True
                seeds[s] = req.seed
                temps[s] = req.temperature
                top_ks[s] = req.top_k
                top_ps[s] = req.top_p
                eos_ids[s] = req.eos_id
                max_news[s] = req.max_new_tokens
                self.active[s] = req
            now = time.perf_counter()
            for req in batch:
                self._admit_t[req.uid] = now
            self.tel.counter("serve.prefill_batches").inc()
            with self.tel.span("serve.prefill_admit", bucket=int(lb),
                               n=len(batch)):
                self.cache, self.sstate = self._admit_fn(
                    self.params, self.cache, self._fresh, self.sstate,
                    jnp.asarray(tokens),
                    jnp.asarray(lengths), jnp.asarray(admit),
                    jnp.asarray(seeds), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(eos_ids), jnp.asarray(max_news))
            self.tel.counter("serve.requests_admitted").inc(len(batch))

    # --------------------------------------------------- paged admission

    def _book_pages(self, req: Request) -> Optional[tuple]:
        """Reserve this request's pages: radix-matched prefix pages are
        shared (one new reference each); the remainder is allocated, with
        LRU eviction of fully-released trie pages under pressure.  Returns
        (logical page list, matched prefix tokens) or None when the pool
        cannot serve the request right now (queue backpressure)."""
        pg, maxp = self.page_size, self.max_pages
        plen = int(req.prompt.size)
        shared, m = ([], 0)
        if self.prefix is not None:
            shared, m = self.prefix.match(req.prompt)
            # keep at least one suffix token: the first sampled token needs
            # the last prompt position's hidden state
            mcap = ((plen - 1) // pg) * pg
            if m > mcap:
                drop = (m - mcap) // pg
                self.page_pool.release(shared[len(shared) - drop:])
                shared, m = shared[:len(shared) - drop], mcap
        if self.model.cfg.sliding_window:
            total = maxp          # rolling writes cycle through every page
        else:
            total = min(-(-(plen + req.max_new_tokens) // pg), maxp)
        need = total - len(shared)
        priv = self.page_pool.alloc(need)
        if priv is None and self.prefix is not None:
            evicted = self.prefix.evict(need - self.page_pool.n_free)
            if evicted:
                self.tel.counter("serve.prefix_evicted_pages").inc(
                    len(evicted))
            priv = self.page_pool.alloc(need)
        if priv is None:
            if shared:
                self.page_pool.release(shared)
            return None
        if m > 0:
            self.tel.counter("serve.prefix_hits").inc()
            self.tel.counter("serve.prefix_hit_tokens").inc(m)
        return shared + priv, m

    def _admit_paged(self):
        while self.queue:
            free = [s for s in range(self.slots) if self.active[s] is None]
            if not free:
                return
            batch = self._gather_batch(len(free))
            if not batch:
                return
            placed, blocked = [], None
            for i, req in enumerate(batch):
                booking = self._book_pages(req)
                if booking is None:
                    blocked = batch[i:]
                    break
                placed.append((req, booking))
            if blocked:
                for r in reversed(blocked):
                    self.queue.appendleft(r)
            if not placed:
                return              # decode frees pages; admit again later

            lb = _pow2(max(int(r.prompt.size) - m
                           for r, (_, m) in placed))
            tokens = np.zeros((self.slots, 1, lb), np.int32)
            suffix_lens = np.ones((self.slots,), np.int32)
            plens = np.ones((self.slots,), np.int32)
            m_vec = np.zeros((self.slots,), np.int32)
            admit = np.zeros((self.slots,), bool)
            seeds = np.zeros((self.slots,), np.int32)
            temps = np.zeros((self.slots,), np.float32)
            top_ks = np.zeros((self.slots,), np.int32)
            top_ps = np.ones((self.slots,), np.float32)
            eos_ids = np.full((self.slots,), -1, np.int32)
            max_news = np.ones((self.slots,), np.int32)
            for (req, (pages, m)), s in zip(placed, free):
                p = np.asarray(req.prompt, np.int32)
                tokens[s, 0, :p.size - m] = p[m:]
                suffix_lens[s] = p.size - m
                plens[s] = p.size
                m_vec[s] = m
                admit[s] = True
                seeds[s] = req.seed
                temps[s] = req.temperature
                top_ks[s] = req.top_k
                top_ps[s] = req.top_p
                eos_ids[s] = req.eos_id
                max_news[s] = req.max_new_tokens
                self.active[s] = req
                row = np.full((self.max_pages,), -1, np.int32)
                row[:len(pages)] = pages
                self._pt_host[s] = row
                self._slot_pages[s] = (pages, m)
            self._pt = jnp.asarray(self._pt_host)
            now = time.perf_counter()
            for req, _ in placed:
                self._admit_t[req.uid] = now
            self.tel.counter("serve.prefill_batches").inc()
            with self.tel.span("serve.prefill_admit", bucket=int(lb),
                               n=len(placed)):
                self.pool, self._tvec, self.sstate = self._admit_fn(
                    self.params, self.pool, self._pt, self._tvec, self._fresh,
                    self.sstate, jnp.asarray(tokens),
                    jnp.asarray(suffix_lens), jnp.asarray(plens),
                    jnp.asarray(m_vec), jnp.asarray(admit),
                    jnp.asarray(seeds), jnp.asarray(temps),
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(eos_ids), jnp.asarray(max_news))
            self.tel.counter("serve.requests_admitted").inc(len(placed))
            if blocked:
                return

    def _release_slot(self, s: int, req: Request):
        """Return a finished request's pages to the pool, publishing its
        full prompt pages in the radix cache first so future requests with
        the same prefix skip that prefill."""
        entry = self._slot_pages[s]
        if entry is None:
            return
        pages, _m = entry
        if self.prefix is not None:
            n_full = int(req.prompt.size) // self.page_size
            if n_full:
                self.prefix.insert(req.prompt, pages[:n_full])
        self.page_pool.release(pages)
        self._slot_pages[s] = None

    # ------------------------------------------------------------ stepping

    def _drain(self):
        """One host sync: pull token buffers + termination flags, append new
        tokens to their requests, finalise finished slots.  This is where
        the host first OBSERVES tokens, so per-request TTFT / TPOT are
        stamped here (quantized by the drain cadence)."""
        t_dr = time.perf_counter()
        out, gen, alive = jax.device_get(
            (self.sstate["out"], self.sstate["gen"], self.sstate["active"]))
        now = time.perf_counter()
        self.tel.histogram("serve.drain_s").observe(now - t_dr)
        for s, req in enumerate(self.active):
            if req is None:
                continue
            n = int(gen[s])
            have = len(req.generated)
            req.generated.extend(int(t) for t in out[s, have:n])
            if n > 0 and have == 0:
                self._first_tok_t[req.uid] = now
                sub = self._submit_t.get(req.uid)
                if sub is not None:
                    self.tel.histogram("serve.ttft_s").observe(now - sub)
            if not bool(alive[s]):
                self.done[req.uid] = req
                self.active[s] = None
                if self.paged:
                    self._release_slot(s, req)
                self._finalize(req, now)

    def _finalize(self, req: Request, now: float):
        """Emit the per-request record: TTFT (submit -> first observed
        token), TPOT (mean inter-token time after the first), queue wait
        (submit -> admitted) and totals."""
        n = len(req.generated)
        self.tel.counter("serve.requests_done").inc()
        self.tel.counter("serve.tokens_generated").inc(n)
        sub = self._submit_t.pop(req.uid, None)
        adm = self._admit_t.pop(req.uid, None)
        first = self._first_tok_t.pop(req.uid, None)
        fields = {"uid": req.uid, "tokens": n}
        if sub is not None:
            fields["total_s"] = now - sub
            if adm is not None:
                fields["queue_s"] = adm - sub
            if first is not None:
                fields["ttft_s"] = first - sub
                if n > 1:
                    fields["tpot_s"] = (now - first) / (n - 1)
                    self.tel.histogram("serve.tpot_s").observe(
                        fields["tpot_s"])
        self.tel.emit("serve_request", **fields)

    def step(self) -> int:
        """Admit + ``drain_every`` fused decode steps + one drain.
        Returns #active slots (host view, post-drain)."""
        self._admit()
        self.tel.gauge("serve.queue_depth").set(len(self.queue))
        n_active = sum(1 for r in self.active if r is not None)
        self.tel.gauge("serve.active_slots").set(n_active)
        self.tel.gauge("serve.slot_utilization").set(n_active / self.slots)
        if self.paged:
            self.tel.gauge("serve.kv_pages_used").set(self.page_pool.n_used)
            self.tel.gauge("serve.kv_pages_free").set(self.page_pool.n_free)
            if self.prefix is not None:
                self.tel.gauge("serve.prefix_nodes").set(len(self.prefix))
        if n_active == 0:
            return 0
        with self.tel.span("serve.decode_window", steps=self.drain_every):
            if self.paged:
                self.pool, self._tvec, self.sstate = self._step_fn(
                    self.params, self.pool, self._pt, self._tvec,
                    self.sstate)
            else:
                self.cache, self.sstate = self._step_fn(self.params,
                                                        self.cache,
                                                        self.sstate)
        self._drain()
        self._recompile_wd.check()
        return sum(1 for r in self.active if r is not None)

    def run(self, max_steps: int = 10_000):
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        return self.done

    # ------------------------------------------------------------ telemetry

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-signature counts of the engine's jitted entry points —
        the serving benchmark gates on these being frozen after warmup (the
        admit function holds one entry per prefill bucket).  Uses the
        guarded ``obs.jit_cache_size`` probe (``-1`` sentinel when this JAX
        version exposes none) so telemetry degrades instead of raising."""
        return {"step": obs.jit_cache_size(self._step_fn),
                "admit": obs.jit_cache_size(self._admit_fn)}

    def mark_warm(self) -> Dict[str, int]:
        """Freeze the expected compiled-signature set: every jit-cache
        growth after this is counted in ``serve.recompiles_post_warmup``
        and emitted as a ``recompile`` event.  Call after a warmup pass has
        touched every prefill bucket the workload will use."""
        return self._recompile_wd.mark_warm()
