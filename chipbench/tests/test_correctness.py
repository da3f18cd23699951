"""The comparison that decides ``correct``, at a size a CPU can hold: the
control (the reference in float8 put in the program's place) and the
faults a training cell can have, each planted under a run that skips only
the look for a chip, must come out not correct against the cell's limits."""
import json

import jax
import numpy as np
import pytest

from chipbench import bench, compare, traffic
from chipbench.kinds import train as K
from chipbench.reference import revffn
from tiny import run, tiny_cell

TRAIN = [w["name"] for w in json.load(open(bench.ROOT / "BENCHMARK.json"))[
    "workloads"] if bench.load_cell(w["name"]).traffic["kind"] == "train"]
SEED = 2**33 + 17


def _broken_build(kind):
    real_build = K.build

    def build(cell):
        model, opt, step = real_build(cell)
        if kind == "state_unchanged":
            from repro.core import schedule
            from repro.train.trainer import make_train_step
            plain = jax.jit(make_train_step(
                model, opt, mask_fn=schedule.stage2_mask, fused=True))

            def broken(p, s, b):
                return p, s, plain(p, s, b)[2]
        elif kind == "half_batch":
            def broken(p, s, b):
                m = np.array(b["loss_mask"])
                if m.shape[0] >= 2:
                    m[m.shape[0] // 2:] = 0.0
                else:
                    m[:, m.shape[1] // 2:] = 0.0
                return step(p, s, dict(b, loss_mask=m))
        return model, opt, broken
    return build


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setattr(K, "build", _broken_build(fault))
    r = run(tiny_cell(cell))
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("cell", TRAIN)
def test_control_reads_above_the_program(cell):
    """At this size the readings are not the chip's (the limits were set
    from the chip's), so the test holds the control against the program:
    float8 in the program's place reads at least three times as far from
    the reference on one of the compared numbers."""
    c = tiny_cell(cell)
    prog = run(c, seed=SEED)
    ref = revffn.RevFFN(c.config)
    batches = [b for _, b in zip(range(c.traffic["check_steps"]),
                                 traffic.train_batches(c.traffic, ref.V,
                                                       SEED))]
    want = revffn.train_steps(ref, c.traffic["optimizer"], SEED, batches)
    ctrl = compare.training_numbers(
        revffn.train_steps(revffn.RevFFN(c.config, "fp8"),
                           c.traffic["optimizer"], SEED, batches), want)
    ratios = {k: ctrl[k] / max(prog["numbers"][k], 1e-12)
              for k in c.limits["checks"]}
    assert max(ratios.values()) >= 3.0, (ratios, prog["numbers"])


def SERVE():
    """The chat mix on a tiny dense model.  No declared cell serves yet, so
    the gap is held to a limit of this test's own: a served token that is
    not the reference's best within a twentieth of a logit."""
    return tiny_cell("tiny-serve", traffic="chat-azure", moe=False,
                     limits={"served_logit_gap": 0.05,
                             "requests_unfinished": 0})


def test_served_token_altered_is_not_correct(monkeypatch):
    from chipbench.kinds import serve as S
    real = S.serve_window

    def altered(eng, reqs, spans, **kw):
        rec, t = real(eng, reqs, spans, **kw)
        for r in rec.values():            # every served request: the
            g = r["req"].generated         # sample always holds one
            g[len(g) // 2] = (g[len(g) // 2] + 1) % 512
        return rec, t
    monkeypatch.setattr(S, "serve_window", altered)
    r = run(SERVE(), seconds=2.0)
    assert r["correct"] is False, r["checks"]


def test_serving_control_reads_above_the_program():
    c = SERVE()
    from chipbench.kinds import serve as S
    seen = {}
    real = S.pick_sample

    def keep(served, seed, n):
        out = real(served, seed, n)
        seen["items"] = [served[u] for u in out]
        return out
    S.pick_sample = keep
    try:
        prog = run(c, seconds=2.0)
    finally:
        S.pick_sample = real
    ref = revffn.RevFFN(c.config)
    ctrl = revffn.served_gap(ref, 2**33 + 17, seen["items"],
                             control=revffn.RevFFN(c.config, "fp8"))
    assert prog["correct"] is True, prog["checks"]
    assert ctrl > 3 * prog["numbers"]["served_logit_gap"], ctrl
