"""How a configuration file (Hugging Face key names) states the shape of its
model: read here once, for the plain reference and for the counts.

Stacks.  A model is an ordered list of stacks ``(name, depth, kind)``.  The
``first_k_dense_replace`` (or ``num_dense_layers``) leading layers form the
stack ``dense``, of kind ``dense``; the other layers form the stack
``layers``, of kind ``moe`` where the file has routed experts and ``dense``
otherwise.  A layer of kind ``dense`` runs a SwiGLU of ``intermediate_size``.

Experts held.  The key that counts routed experts (``num_experts``, or
``n_routed_experts`` in the DeepSeek style) gives how many experts of each
layer this chip holds.  ``published`` may give the published count of that
key beside it, and ``chips_per_layer`` the chips that share each layer.  The
router scores every published expert; the chip holds experts 0 .. held-1
(chip 0's share) and adds only their part of the layer.  Shared experts are
held whole: ``shared_expert_intermediate_size`` wide behind a sigmoid gate
(Qwen), or ``n_shared_experts`` x ``moe_intermediate_size`` with no gate
(DeepSeek).

Head sizes.  ``head_dim`` (or hidden size over heads) serves the q.k
product and the values alike, except in latent attention (``kv_lora_rank``
stated), whose q.k head is ``qk_nope_head_dim + qk_rope_head_dim`` wide and
whose value head is ``v_head_dim`` wide.
"""
from __future__ import annotations

from typing import List, Tuple

EXPERT_KEYS = ("num_experts", "n_routed_experts")


def routed_experts(cfg: dict) -> Tuple[int, int]:
    """(held, published) routed experts of a MoE layer; (0, 0) for none."""
    for key in EXPERT_KEYS:
        if cfg.get(key):
            held = int(cfg[key])
            return held, int(cfg.get("published", {}).get(key, held))
    return 0, 0


def stacks(cfg: dict) -> List[Tuple[str, int, str]]:
    """The model's stacks in order, each ``(name, depth, kind)``."""
    for key in ("moe_layer_freq", "decoder_sparse_step"):
        if cfg.get(key, 1) != 1:
            raise ValueError(f"{key}={cfg[key]}: only every layer after the "
                             "leading dense ones can hold experts")
    n = int(cfg.get("first_k_dense_replace",
                    cfg.get("num_dense_layers", 0)) or 0)
    if not 0 <= n < cfg["num_hidden_layers"]:
        raise ValueError(f"{n} leading dense layers of "
                         f"{cfg['num_hidden_layers']}")
    kind = "moe" if routed_experts(cfg)[0] else "dense"
    out = [("dense", n, "dense")] if n else []
    return out + [("layers", cfg["num_hidden_layers"] - n, kind)]


def shared_width(cfg: dict) -> int:
    """Width of a MoE layer's shared experts, taken together (0: none)."""
    if cfg.get("shared_expert_intermediate_size"):
        return int(cfg["shared_expert_intermediate_size"])
    return int(cfg.get("n_shared_experts") or 0) * int(
        cfg.get("moe_intermediate_size") or 0)


def shared_gated(cfg: dict) -> bool:
    """Whether the shared experts' output passes a sigmoid gate."""
    return bool(cfg.get("shared_expert_intermediate_size"))


def latent(cfg: dict) -> bool:
    return bool(cfg.get("kv_lora_rank"))


def head_sizes(cfg: dict) -> Tuple[int, int]:
    """(q.k head size, value head size)."""
    if latent(cfg):
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]
    return hd, hd
