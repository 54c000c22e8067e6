"""Parameters and bytes of the GLM-4.7-Flash block (``glm4_moe_lite``), from
the configuration file's published sizes alone (``hidden_size``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``n_routed_experts``,
``n_shared_experts``, ``first_k_dense_replace``, ``num_hidden_layers``,
``vocab_size``). The sibling of ``costs_olmoe.py`` for the block with latent
attention, a shared expert and a leading dense layer, and the source of
``mla_moe_decode_roofline``.

Gains of the norms (four vectors a block, one at the end) and the 64
selection biases are left out of what a step must stream: a rounding error.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The five matrices of latent attention: W_qa, W_qb, W_kva, W_kvb, W_o
    (no bias)."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    rq, rkv = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    v = int(cfg["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d)


def router_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["n_routed_experts"])


def expert_params(cfg: dict) -> int:
    """Gate, up and down projection of ONE routed expert."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def shared_params(cfg: dict) -> int:
    """The shared experts: one SwiGLU of ``n_shared_experts`` widths."""
    return int(cfg["n_shared_experts"]) * expert_params(cfg)


def routed_params(cfg: dict) -> int:
    """All the routed experts of one layer."""
    return int(cfg["n_routed_experts"]) * expert_params(cfg)


def expert_layer_params(cfg: dict) -> int:
    """An expert layer OUTSIDE its routed experts: attention, router, the
    shared expert."""
    return attention_params(cfg) + router_params(cfg) + shared_params(cfg)


def dense_layer_params(cfg: dict) -> int:
    """A leading dense layer: attention and one SwiGLU of
    ``intermediate_size``."""
    return (attention_params(cfg)
            + 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"]))


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def vocabulary_params(cfg: dict) -> int:
    """Embedding and untied head."""
    return 2 * head_params(cfg)


def layers(cfg: dict):
    """(dense layers, expert layers) of the stack as the file runs it."""
    dense = min(int(cfg["first_k_dense_replace"]),
                int(cfg["num_hidden_layers"]))
    return dense, int(cfg["num_hidden_layers"]) - dense


def total_params(cfg: dict) -> int:
    """Every matrix as built (the gains and biases left out)."""
    dense, sparse = layers(cfg)
    return (vocabulary_params(cfg) + dense * dense_layer_params(cfg)
            + sparse * (expert_layer_params(cfg) + routed_params(cfg)))


def cache_token_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What one token takes in the cache of all layers: the latent and the
    one rope key, no heads."""
    return (int(cfg["num_hidden_layers"]) * dtype_bytes
            * (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])))


def decode_step_bytes(cfg: dict, touched_share: float, live_cache_bytes: float,
                      dtype_bytes: int = 2) -> float:
    """The bytes one decode step must stream: per expert layer the five
    attention matrices, the router, the shared expert and the touched share
    of the routed experts (``touched_share`` in 0..1: experts some token of
    the step was routed to); the dense layers whole; the head; at the
    parameters' width; PLUS the live cache, which every step reads once.
    The embedding rows and the activations are left out, so the time this
    gives at the memory's peak rate is a LOWER bound of the step."""
    dense, sparse = layers(cfg)
    weights = (dense * dense_layer_params(cfg)
               + sparse * (expert_layer_params(cfg)
                           + touched_share * routed_params(cfg))
               + head_params(cfg))
    return dtype_bytes * weights + live_cache_bytes
