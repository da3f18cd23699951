"""Operations and bytes the algorithm needs, from a configuration file's
sizes (Hugging Face key names) and the shapes of a call.

Model FLOPs count the useful work only: the experts a token is routed to
(not every expert), causal attention (each query against the keys before it,
within the window), the two adapter projections around every block, and no
recomputation (no reversible inversion, no rematerialised forward).  A
training step is three times the forward (forward, and a backward of twice
its operations).  A multiply-add is two operations.  A model is summed over
its stacks (``chipbench.shape``); where this chip holds some of a layer's
experts, it counts what this chip computes: the router over every published
expert, and the expected rows of the experts held.
"""
from __future__ import annotations

from chipbench import shape


def attended_keys(seq: int, window=None) -> float:
    """Mean number of keys a query attends to in a causal row of ``seq``."""
    w = window or seq
    if w >= seq:
        return (seq + 1) / 2.0
    # positions 0..w-1 see i+1 keys, the rest see w
    return (w * (w + 1) / 2.0 + (seq - w) * w) / seq


def attn_matmul_flops(cfg: dict) -> float:
    """Forward FLOPs per token of one layer's attention projections: q, k,
    v and o, or in latent attention (``kv_lora_rank``) q (through
    ``q_lora_rank`` where stated), the joint k/v down-projection with the
    shared rope key, the k/v up-projection of every head, and o."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, v = shape.head_sizes(cfg)
    if shape.latent(cfg):
        r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        ql = cfg.get("q_lora_rank")
        f = 2 * (d * ql + ql * H * qk) if ql else 2 * d * H * qk
        f += 2 * d * (r + rope)
        f += 2 * r * H * (cfg["qk_nope_head_dim"] + v)
        return f + 2 * H * v * d
    KV = cfg["num_key_value_heads"]
    return 2 * (d * H * qk + 2 * d * KV * qk + H * qk * d)


def layer_matmul_flops(cfg: dict, kind: str) -> float:
    """Forward FLOPs per token of one layer of ``kind`` (``dense`` or
    ``moe``): its weight matmuls, no attention scores.  A MoE layer's
    routed experts count top_k x held / published rows a token: the rows
    this chip's experts expect when the router spreads tokens evenly over
    every published expert."""
    d = cfg["hidden_size"]
    half = d // 2
    f = 2 * (2 * half * d + d * half)          # attn: 2 x P_up, P_down
    f += 2 * (half * d + d * half)             # ffn: P_up, P_down
    f += attn_matmul_flops(cfg)
    if kind == "moe":
        held, routed = shape.routed_experts(cfg)
        k, fe = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
        f += 2 * d * routed                     # router
        f += k * held * 3 * 2 * d * fe / routed  # routed experts held
        f += 3 * 2 * d * shape.shared_width(cfg)    # shared experts
        if shape.shared_gated(cfg):
            f += 2 * d                          # their gate
    else:
        f += 3 * 2 * d * cfg["intermediate_size"]
    return float(f)


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight matmuls of every layer and the LM head, one token."""
    f = 0.0
    for _, depth, kind in shape.stacks(cfg):
        f += depth * layer_matmul_flops(cfg, kind)
    return f + 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def window_of(cfg: dict):
    if not cfg.get("use_sliding_window", True):
        return None
    return cfg.get("sliding_window")


def attention_flops_per_token(cfg: dict, keys: float) -> float:
    """Scores (q.k head size) and weighted values (v head size) of one
    layer, one query over ``keys``."""
    qk, v = shape.head_sizes(cfg)
    return 2.0 * cfg["num_attention_heads"] * (qk + v) * keys


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One token of a packed training row of length ``seq``."""
    att = attention_flops_per_token(cfg, attended_keys(seq, window_of(cfg)))
    return matmul_flops_per_token(cfg) + cfg["num_hidden_layers"] * att


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
               qk_dim: int, v_dim: int, window=None, itemsize: int = 2):
    """One flash-attention kernel call over causal (windowed) pairs, with
    q.k heads of ``qk_dim`` and value heads of ``v_dim``.  ``fwd`` does two
    matmuls per pair (scores over qk_dim, values over v_dim), ``dq`` three
    (scores, dP over v_dim, dQ over qk_dim), ``dkv`` four (scores, dP, dK
    over qk_dim, dV over v_dim)."""
    pairs = attended_keys(seq, window) * seq
    width = {"fwd": qk_dim + v_dim, "dq": 2 * qk_dim + v_dim,
             "dkv": 2 * qk_dim + 2 * v_dim}[kind]
    flops = 2.0 * batch * heads * pairs * width
    q = batch * heads * seq * qk_dim * itemsize       # q, dq
    o = batch * heads * seq * v_dim * itemsize        # o, do
    k = batch * kv_heads * seq * qk_dim * itemsize    # k, dk
    v = batch * kv_heads * seq * v_dim * itemsize     # v, dv
    lse = batch * heads * seq * 4
    if kind == "fwd":                    # q, k, v in; o, lse out
        bytes_ = q + k + v + o + lse
    elif kind == "dq":                   # q, k, v, do, lse, delta in; dq out
        bytes_ = 2 * q + k + v + o + 2 * lse
    else:                                # ... in; dk, dv out
        bytes_ = q + o + 2 * k + 2 * v + 2 * lse
    return flops, bytes_


def roofline_s(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])
