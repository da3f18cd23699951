"""The trace reduction, on two traces:

* ``data/synthetic.xplane.pb``, whose answer is known by hand: a 100 us
  window; host spans ``data`` over its first 20 us and ``loss_sync`` over
  40-70 us; on the device a ``while`` op over 15-95 us (a loop, which
  encloses its body's ops and is left out), a flash dq kernel at 20-30 us
  and fusions at 25-35 us and 80-90 us.
* ``data/recorded.xplane.pb``, recorded on a TPU v5e: three rounds of a
  grouped GEMM, a flash forward + backward and a matmul, dispatched inside
  a ``bench.window`` span.
"""
import pytest

from chipbench import tracing
from tiny import DATA

US = 1e-6


@pytest.fixture(scope="module")
def summary():
    return tracing.load(str(DATA / "synthetic.xplane.pb"),
                        span_names={"data", "loss_sync"})


def test_busy_is_the_union_of_device_ops(summary):
    assert summary.window_s == pytest.approx(100 * US)
    assert summary.busy_s == pytest.approx(25 * US)      # 20-35 and 80-90


def test_idle_gaps_are_named_by_the_benchmark_span(summary):
    gaps = summary.breakdown()["idle_gaps"]
    assert [g[0] for g in gaps] == ["loss_sync", "data", "other"]
    assert [g[1] for g in gaps] == pytest.approx([45 * US, 20 * US, 10 * US])


def test_kernels_are_told_apart_by_their_shapes(summary):
    ops = summary.kernel_ops(["flash_dq"])
    assert len(ops) == 1 and ops[0].dur_ns == pytest.approx(10_000)
    assert summary.kernel_ops(["gmm", "flash_fwd"]) == []


def test_device_ops_breakdown(summary):
    ops = dict(summary.breakdown()["device_ops"])
    assert ops["%fusion.2 fusion"] == pytest.approx(20 * US)
    assert ops["flash_dq"] == pytest.approx(10 * US)
    assert not any("while" in k for k in ops)


def test_recorded_trace():
    s = tracing.load(str(DATA / "recorded.xplane.pb"), chips=1)
    kinds = {}
    for o in s.ops[0]:
        k = tracing.kernel_kind(o.name)
        if k:
            kinds[k] = kinds.get(k, 0) + 1
    # every round runs each kernel once; the device clock's offset against
    # the host span leaves two whole rounds inside it
    assert kinds == {"gmm": 2, "flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2}
    assert 0 < s.busy_s < s.window_s
    _, ins = tracing.custom_call_shapes(s.kernel_ops(["gmm"])[0].name)
    assert ins == [("s32", (8,)), ("bf16", (1024, 256)),
                   ("bf16", (8, 256, 512))]
