"""Parameters and bytes of the OLMoE block, from the configuration file's
published sizes alone (``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` read as one expert's width, ``num_hidden_layers``,
``vocab_size``). ``costs.py`` counts the dense GPT-2 block; this file is its
sibling for the sparse one, and the source of ``moe_decode_roofline``.

Gains of the norms (four vectors a block, one at the end) are counted in
``total_params`` and left out of what a step must stream: a rounding error.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv = int(cfg.get("num_key_value_heads", h))
    return d, d // h * kv


def attention_params(cfg: dict) -> int:
    """Wq, Wk, Wv and Wo of one block (no bias)."""
    d, d_kv = _sizes(cfg)
    return d * (d + 2 * d_kv) + d * d


def router_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["num_experts"])


def expert_params(cfg: dict) -> int:
    """Gate, up and down projection of ONE expert."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def block_params(cfg: dict) -> int:
    """Every matrix of one block: attention, router, all the experts."""
    return (attention_params(cfg) + router_params(cfg)
            + int(cfg["num_experts"]) * expert_params(cfg))


def total_params(cfg: dict) -> int:
    """As built: embedding, blocks with their four gains (two block norms,
    the q and k norms), the final gain, the untied bias-free head."""
    d, d_kv = _sizes(cfg)
    gains = 2 * d + d + d_kv
    return (head_params(cfg)
            + int(cfg["num_hidden_layers"]) * (block_params(cfg) + gains)
            + d + head_params(cfg))


def decode_step_bytes(cfg: dict, touched_share: float,
                      dtype_bytes: int = 2) -> float:
    """The bytes of weights one decode step must stream: per layer the
    attention and router matrices and the touched share of the experts'
    (``touched_share`` in 0..1: experts some token of the step was routed
    to), plus the head; at the parameters' width. The KV cache, the
    embedding rows and the activations are left out, so the time this gives
    at the memory's peak rate is a LOWER bound of the step."""
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + touched_share * int(cfg["num_experts"]) * expert_params(cfg))
    return dtype_bytes * (int(cfg["num_hidden_layers"]) * per_layer
                          + head_params(cfg))
