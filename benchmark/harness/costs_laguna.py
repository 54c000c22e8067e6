"""Parameters and bytes of the Laguna block (``laguna``), from the
configuration file's published sizes alone (``hidden_size``, ``head_dim``,
``num_key_value_heads``, ``num_attention_heads_per_layer``, ``layer_types``,
``mlp_layer_types``, ``intermediate_size``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``num_hidden_layers``, ``vocab_size``;
``published.num_experts`` is the router's width and ``num_experts`` /
``experts_held`` the experts THIS chip holds of them). The sibling of
``costs_glm4_moe_lite.py`` for the block whose layers differ in head count and
in how far back their cache reaches, and the source of
``swa_moe_decode_roofline``.

Gains of the norms (two vectors a block, one at the end) are left out of what
a step must stream: a rounding error.
"""

from __future__ import annotations


def layers(cfg: dict):
    """``[(kind, dense), ...]`` of the stack as the file runs it: the first
    ``num_hidden_layers`` entries of the published lists."""
    n = int(cfg["num_hidden_layers"])
    return [(cfg["layer_types"][i], cfg["mlp_layer_types"][i] == "dense")
            for i in range(n)]


def heads(cfg: dict, i: int) -> int:
    return int(cfg["num_attention_heads_per_layer"][i])


def attention_params(cfg: dict, i: int) -> int:
    """Layer ``i``'s W_q, W_k, W_v, W_o and the head-wise gate (no bias)."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    h, kv = heads(cfg, i), int(cfg["num_key_value_heads"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + d * h


def router_width(cfg: dict) -> int:
    """Experts the router chooses among: the published count."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def held_experts(cfg: dict) -> int:
    """Routed experts of one layer whose weights this chip holds."""
    return int(cfg["num_experts"])


def router_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * router_width(cfg)


def expert_params(cfg: dict) -> int:
    """Gate, up and down projection of ONE routed expert."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def shared_params(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) \
        * int(cfg["shared_expert_intermediate_size"])


def routed_params(cfg: dict) -> int:
    """The HELD routed experts of one layer."""
    return held_experts(cfg) * expert_params(cfg)


def layer_params(cfg: dict, i: int, touched_share: float = 1.0) -> float:
    """Layer ``i`` with ``touched_share`` of its held routed experts."""
    _, dense = layers(cfg)[i]
    if dense:
        return (attention_params(cfg, i)
                + 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"]))
    return (attention_params(cfg, i) + router_params(cfg)
            + shared_params(cfg) + touched_share * routed_params(cfg))


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def vocabulary_params(cfg: dict) -> int:
    """Embedding and untied head."""
    return 2 * head_params(cfg)


def total_params(cfg: dict) -> int:
    """Every matrix as built (the gains left out)."""
    return vocabulary_params(cfg) + sum(
        int(layer_params(cfg, i)) for i in range(len(layers(cfg))))


def cache_token_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What one token takes in the cache of all layers, both block groups:
    k and v of ``num_key_value_heads`` heads a layer."""
    return (len(layers(cfg)) * 2 * int(cfg["num_key_value_heads"])
            * int(cfg["head_dim"]) * dtype_bytes)


def decode_step_bytes(cfg: dict, touched_share: float, live_cache_bytes: float,
                      dtype_bytes: int = 2) -> float:
    """The bytes one decode step must stream: per layer the attention
    matrices (by its kind's head count) and the gate; per expert layer the
    router, the shared expert and the TOUCHED share of the HELD routed
    experts (``touched_share`` in 0..1: held experts some token of the step
    was routed to); the dense layer whole; the head; at the parameters'
    width; PLUS the live cache of BOTH block groups, which every step reads
    once. The embedding rows and the activations are left out, so the time
    this gives at the memory's peak rate is a LOWER bound of the step."""
    weights = sum(layer_params(cfg, i, touched_share)
                  for i in range(len(layers(cfg)))) + head_params(cfg)
    return dtype_bytes * weights + live_cache_bytes
