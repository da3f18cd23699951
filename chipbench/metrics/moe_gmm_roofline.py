"""Share of its roofline that the grouped-GEMM kernel
(``kernels/moe/grouped_gemm.py``) reaches: the least time the chip needs
for every grouped-GEMM call in the traced window (the larger of its FLOPs
over peak and its bytes over peak bandwidth, from its shapes and the routed
rows, with no tile padding counted; ``chipbench.counts``) over the calls'
device time.  The rows are those this chip's experts expect, tokens x
top_k x held / published, and the weight bytes those of the experts held
(``chipbench.shape.routed_experts``).  The forward projections and the lhs
cotangents run through the kernel; the weight cotangent is an XLA einsum
and is not here."""
from chipbench import counts, shape
from chipbench.peaks import peaks_for
from chipbench.tracing import custom_call_shapes


def read(ctx):
    held, routed = shape.routed_experts(ctx.get("config") or {})
    if ctx.get("kind") != "train" or not held:
        return None
    ops = ctx["trace"].kernel_ops(["gmm"])
    if not ops:
        return None
    peaks = peaks_for(ctx["device"]["kind"])
    rows = (ctx["tokens_per_step"] * ctx["config"]["num_experts_per_tok"]
            * held / routed)
    least = 0.0
    for o in ops:
        _, ins = custom_call_shapes(o.name)
        _, k, n = ins[2][1]
        least += counts.roofline_s(*counts.gmm_call(rows, k, n, held), peaks)
    return 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
