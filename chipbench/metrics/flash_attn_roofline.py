"""Share of its roofline that flash attention
(``kernels/flash_attention.py``: forward, dq and dkv kernels) reaches: the
least time the chip needs for every call in the traced window (causal,
windowed pairs only; ``chipbench.counts.flash_call``) over the calls'
device time.  Heads, kv heads and length come from each call's shapes; the
q.k and value head sizes come from the configuration
(``chipbench.shape.head_sizes``), never from the shapes, which a wrapper
may pad past the useful width."""
import math

from chipbench import counts, shape
from chipbench.peaks import peaks_for
from chipbench.tracing import custom_call_shapes, kernel_kind

KINDS = {"flash_fwd": "fwd", "flash_dq": "dq", "flash_dkv": "dkv"}


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    ops = ctx["trace"].kernel_ops(list(KINDS))
    if not ops:
        return None
    peaks = peaks_for(ctx["device"]["kind"])
    window = counts.window_of(ctx["config"])
    qk, v = shape.head_sizes(ctx["config"])
    least = 0.0
    for o in ops:
        _, ins = custom_call_shapes(o.name)
        q, k = ins[0][1], ins[1][1]
        seq = q[-2]
        heads = math.prod(q[:-2])
        kv_heads = math.prod(k[:-2])
        least += counts.roofline_s(*counts.flash_call(
            KINDS[kernel_kind(o.name)], 1, heads, kv_heads, seq, qk, v,
            window), peaks)
    return 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
