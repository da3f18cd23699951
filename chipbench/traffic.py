"""The general generators that read traffic files (``traffic/<name>.json``).

A training job (``"kind": "train"``) is packed instruction/response
documents; a serving mix (``"kind": "serve"``) is a list of requests with
due times.  Both are functions of the file's parameters and ``--seed`` alone:
the same seed gives the same inputs, and every seed gives the same amount of
work drawn in another order or with other token values.

The document generator follows ``repro.data.pipeline.packed_batches`` (Zipf
token ids over the real vocabulary, BOS/instruction/SEP/response/EOS records,
loss on response tokens), copied here so that no change to the program can
change the yardstick.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

BOS, EOS, SEP, PAD = 1, 2, 3, 0
FIRST_TOKEN = 4


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def _zipf_tokens(rng, n: int, vocab: int, a: float) -> np.ndarray:
    z = rng.zipf(a, size=n * 2)
    z = z[z < vocab - FIRST_TOKEN][:n]
    while z.size < n:
        z = np.concatenate([z, rng.integers(FIRST_TOKEN, vocab, size=n)])[:n]
    return (z + FIRST_TOKEN).clip(FIRST_TOKEN, vocab - 1).astype(np.int32)


def train_batches(job: dict, vocab: int, seed: int) -> Iterator[Dict]:
    """Yields {'tokens': (B, S) int32, 'loss_mask': (B, S) float32} forever;
    batch ``i`` depends on (seed, i) only."""
    B, S = job["batch"], job["seq_len"]
    docs = job["docs"]
    a = docs["zipf_a"]
    i_lo, i_hi = docs["instruction_len"]       # as fractions of S
    r_lo, r_hi = docs["response_len"]
    step = 0
    while True:
        rng = _rng(seed, 1, step)
        tokens = np.full((B, S), PAD, np.int32)
        mask = np.zeros((B, S), np.float32)
        for b in range(B):
            pos = 0
            while pos < S - 8:
                ilen = int(rng.integers(i_lo, max(i_lo + 1, int(S * i_hi))))
                rlen = int(rng.integers(r_lo, max(r_lo + 1, int(S * r_hi))))
                ins = _zipf_tokens(rng, ilen, vocab, a)
                res = _zipf_tokens(rng, rlen, vocab, a)
                rec = np.concatenate([[BOS], ins, [SEP], res, [EOS]])
                n = min(rec.size, S - pos)
                tokens[b, pos:pos + n] = rec[:n]
                lo, hi = pos + 1 + ins.size + 1, pos + n
                if hi > lo:
                    mask[b, lo:hi] = 1.0
                pos += n
        yield {"tokens": tokens, "loss_mask": mask}
        step += 1


def _lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def serve_requests(mix: dict, vocab: int, seed: int,
                   seconds: float) -> List[dict]:
    """Open-loop arrivals for a window of ``seconds``: requests with a due
    time (s from the window's start), a prompt of unique token ids and a
    number of new tokens.  The count is fixed by the rate and the window
    (``rate * seconds``, rounded), the lengths are drawn from the mix, and
    the gaps are exponential, scaled so that the last request is due at
    the window's end at the latest: every seed offers the same load."""
    if mix.get("rate_per_s") is None:
        raise ValueError("the mix states no rate: sweep its knee on the chip "
                         "(chipbench/sweep.py) and write 4/5 of it into "
                         "rate_per_s")
    n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    rng = _rng(seed, 2)
    plens = _lognormal_lengths(rng, n, mix["prompt_len"])
    olens = _lognormal_lengths(rng, n, mix["output_len"])
    gaps = rng.exponential(1.0, size=n)
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * (n - 1) / n if n > 1 else np.zeros(1)
    out = []
    for i in range(n):
        toks = _zipf_tokens(rng, int(plens[i]), vocab, mix["zipf_a"])
        toks[0] = BOS
        out.append({"uid": i, "due_s": float(due[i]), "prompt": toks,
                    "max_new_tokens": int(olens[i])})
    return out
