"""Share of its roofline that the grouped-GEMM kernel
(``kernels/moe/grouped_gemm.py``) reaches: the least time the chip needs
for every grouped-GEMM call in the traced window (the larger of its FLOPs
over peak and its bytes over peak bandwidth, from its shapes and the routed
rows, tokens x top_k, with no tile padding counted; ``chipbench.counts``)
over the calls' device time.  The forward projections and the lhs
cotangents run through the kernel; the weight cotangent is an XLA einsum
and is not here."""
from chipbench import counts
from chipbench.peaks import peaks_for
from chipbench.tracing import custom_call_shapes


def read(ctx):
    cfg = ctx.get("config") or {}
    if ctx.get("kind") != "train" or not cfg.get("num_experts"):
        return None
    ops = ctx["trace"].kernel_ops(["gmm"])
    if not ops:
        return None
    peaks = peaks_for(ctx["device"]["kind"])
    rows = ctx["tokens_per_step"] * cfg["num_experts_per_tok"]
    least = 0.0
    for o in ops:
        _, ins = custom_call_shapes(o.name)
        _, k, n = ins[2][1]
        least += counts.roofline_s(*counts.gmm_call(
            rows, k, n, cfg["num_experts"]), peaks)
    return 100.0 * least / (sum(o.dur_ns for o in ops) * 1e-9)
