"""The comparison that decides ``correct``: numbers read from the program
and from the plain reference, each held to its limit (``limits/<cell>.json``).

Training numbers (the same three for every training cell):

  loss_gap    max over the compared steps of |loss - loss_ref| / |loss_ref|
  grad_gap    worst leaf of |‖g‖ - ‖g_ref‖| / max(‖g_ref‖, median leaf's
              ‖g_ref‖), g the first step's gradient as the optimizer got it
  change_gap  the same of each leaf's move over the compared steps

A leaf whose reference gradient is under ``LEAF_FLOOR`` of the median
leaf's is left out of both norm numbers: its gradient is nought but for
rounding (a key's bias under softmax), and Adam moves it by round-off.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import numpy as np

LEAF_FLOOR = 1e-3


def counted_leaves(raw_ref: Dict[str, float]) -> set:
    med = float(np.median(list(raw_ref.values())))
    return {k for k, v in raw_ref.items() if v >= LEAF_FLOOR * med}


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: set) -> Tuple[float, str]:
    """Worst leaf's gap and the leaf."""
    keys = sorted(keep)
    missing = [k for k in keys if k not in prog]
    if missing:
        return math.inf, f"missing:{missing[0]}"
    med = float(np.median([ref[k] for k in keys]))
    worst, at = 0.0, ""
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not math.isfinite(g):
            return math.inf, k
        if g > worst:
            worst, at = g, k
    return worst, at


def loss_gap(prog, ref) -> float:
    n = min(len(prog), len(ref))
    if n == 0:
        return math.inf
    return max(abs(a - b) / max(abs(b), 1e-30) if math.isfinite(a) else
               math.inf for a, b in zip(prog[:n], ref[:n]))


def training_numbers(prog: dict, ref: dict) -> Dict[str, dict]:
    keep = counted_leaves(ref["raw_grad"])
    g, g_at = norm_gap(prog["first_grad"], ref["first_grad"], keep)
    c, c_at = norm_gap(prog["change"], ref["change"], keep)
    at = lambda d, k: d.get(k, math.nan)
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": g, "grad_gap_leaf": g_at,
            "grad_gap_norms": [at(prog["first_grad"], g_at),
                               at(ref["first_grad"], g_at)],
            "change_gap": c, "change_gap_leaf": c_at,
            "change_gap_norms": [at(prog["change"], c_at),
                                 at(ref["change"], c_at)],
            "losses": [prog["losses"], ref["losses"]],
            "leaves_counted": len(keep), "leaves": len(ref["raw_grad"])}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): every limited number at or under its limit."""
    checks = {k: {"value": float(numbers[k]), "limit": float(lim)}
              for k, lim in limits.items()}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
