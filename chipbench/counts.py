"""Operations and bytes the algorithm needs, from a configuration file's
sizes (Hugging Face key names) and the shapes of a call.

Model FLOPs count the useful work only: the experts a token is routed to
(not every expert), causal attention (each query against the keys before it,
within the window), the two adapter projections around every block, and no
recomputation (no reversible inversion, no rematerialised forward).  A
training step is three times the forward (forward, and a backward of twice
its operations).  A multiply-add is two operations.
"""
from __future__ import annotations


def _sizes(cfg: dict):
    d = cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    return d, H, KV, hd


def attended_keys(seq: int, window=None) -> float:
    """Mean number of keys a query attends to in a causal row of ``seq``."""
    w = window or seq
    if w >= seq:
        return (seq + 1) / 2.0
    # positions 0..w-1 see i+1 keys, the rest see w
    return (w * (w + 1) / 2.0 + (seq - w) * w) / seq


def layer_matmul_flops(cfg: dict) -> float:
    """Forward FLOPs per token of one layer's weight matmuls (no attention
    scores)."""
    d, H, KV, hd = _sizes(cfg)
    half = d // 2
    f = 2 * (2 * half * d + d * half)          # attn: 2 x P_up, P_down
    f += 2 * (half * d + d * half)             # ffn: P_up, P_down
    f += 2 * (d * H * hd + 2 * d * KV * hd + H * hd * d)
    E = cfg.get("num_experts", 0) or 0
    if E:
        k, fe = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
        f += 2 * d * E                          # router
        f += k * 3 * 2 * d * fe                 # routed experts
        fs = cfg.get("shared_expert_intermediate_size") or 0
        if fs:
            f += 3 * 2 * d * fs + 2 * d         # shared experts + gate
    else:
        f += 3 * 2 * d * cfg["intermediate_size"]
    return float(f)


def window_of(cfg: dict):
    if not cfg.get("use_sliding_window", True):
        return None
    return cfg.get("sliding_window")


def attention_flops_per_token(cfg: dict, keys: float) -> float:
    """Scores and weighted values of one layer, one query over ``keys``."""
    _, H, _, hd = _sizes(cfg)
    return 2.0 * 2.0 * H * hd * keys


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token of a packed training row of length ``seq``."""
    L = cfg["num_hidden_layers"]
    keys = attended_keys(seq, window_of(cfg))
    per_layer = layer_matmul_flops(cfg) + attention_flops_per_token(cfg, keys)
    return L * per_layer + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq)


# ------------------------------------------------------------------ kernels

def gmm_call(rows: int, k: int, n: int, experts: int, itemsize: int = 2):
    """One grouped GEMM over ``rows`` routed rows (no tile padding): the
    rows, every expert's (k, n) weight, and the output move once."""
    flops = 2.0 * rows * k * n
    bytes_ = itemsize * (rows * k + experts * k * n + rows * n)
    return flops, bytes_


def flash_call(kind: str, batch: int, heads: int, kv_heads: int, seq: int,
               head_dim: int, window=None, itemsize: int = 2):
    """One flash-attention kernel call over causal (windowed) pairs.
    ``fwd`` does two matmuls per pair (scores, values), ``dq`` three
    (scores, dP, dQ), ``dkv`` four (scores, dP, dK, dV)."""
    pairs = attended_keys(seq, window) * seq
    mm = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    flops = 2.0 * mm * batch * heads * head_dim * pairs
    q = batch * heads * seq * head_dim * itemsize
    kv = batch * kv_heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    if kind == "fwd":                    # q, k, v in; o, lse out
        bytes_ = 2 * q + 2 * kv + lse
    elif kind == "dq":                   # q, k, v, do, lse, delta in; dq out
        bytes_ = 3 * q + 2 * kv + 2 * lse
    else:                                # ... in; dk, dv out
        bytes_ = 2 * q + 4 * kv + 2 * lse
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
