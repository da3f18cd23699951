"""Model FLOP utilisation of the training step, from the device trace:
useful FLOPs (routed experts only, causal attention, no recomputation;
``chipbench.counts``) of every step in the traced window, over the time in
which an operation ran on the device in that window (``busy_s``, the union
of the device op intervals) times the chip's bf16 peak.  Idle time is left
out here and read by ``train_device_idle_pct``, so that ``train_tokens_per_s``
moves with ``train_mfu_pct x (1 - idle)``."""
from chipbench import counts
from chipbench.peaks import peaks_for


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("steps"):
        return None
    busy = ctx["trace"].busy_s
    if busy <= 0:
        return None
    flops = (ctx["steps"] * ctx["tokens_per_step"]
             * counts.train_flops_per_token(ctx["config"], ctx["seq_len"]))
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (busy * peak * ctx["device"]["count"])
