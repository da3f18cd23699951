"""Model FLOP utilisation of serving: the model FLOPs of every prompt token
prefilled and every token decoded in the traced window (each through every
layer and the LM head, attending to the tokens before it;
``chipbench.counts``), over the window times the chip's bf16 peak.  Padding
and recomputation do not count."""
from chipbench import counts
from chipbench.peaks import peaks_for


def served_flops(cfg, requests) -> float:
    mm = counts.matmul_flops_per_token(cfg)
    att = counts.attention_flops_per_token(cfg, 1.0) * cfg["num_hidden_layers"]
    w = counts.window_of(cfg)
    total = 0.0
    for prompt, served in requests.values():
        n = len(prompt) + max(len(served) - 1, 0)   # positions computed
        keys = sum(min(i + 1, w or i + 1) for i in range(n))
        total += n * mm + att * keys
    return total


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("requests"):
        return None
    peak = peaks_for(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * served_flops(ctx["config"], ctx["requests"]) / (
        ctx["window_s"] * peak * ctx["device"]["count"])
