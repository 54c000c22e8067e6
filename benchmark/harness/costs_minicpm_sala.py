"""Parameters and bytes of the MiniCPM-SALA block (``minicpm_sala``), from the
configuration file's published sizes alone (``hidden_size``, ``head_dim``,
``num_attention_heads``, ``num_key_value_heads``, ``lightning_nh``,
``lightning_head_dim``, ``intermediate_size``, ``vocab_size``,
``mixer_types``, of which ``num_hidden_layers`` entries run from
``first_layer`` on; ``sparse_config.kernel_stride``). The sibling of
``costs_laguna.py`` for the block whose layers are either block-sparse
attention over few KV heads or linear attention with a recurrent state, and
the source of ``sala_decode_roofline``.

Gains of the norms (two vectors a block, three or four of a head's width in a
mixer, one at the end) are left out of what a step must stream: a rounding
error.
"""

from __future__ import annotations


def layers(cfg: dict):
    """The mixer of each layer the file runs, in order."""
    first = int(cfg.get("first_layer", 0))
    return list(cfg["mixer_types"][first:first + int(cfg["num_hidden_layers"])])


def mixer_params(cfg: dict, mixer: str) -> int:
    """W_q, W_k, W_v, W_o and the output gate of one mixer (no bias)."""
    d = int(cfg["hidden_size"])
    if mixer == "lightning-attn":
        width = int(cfg["lightning_nh"]) * int(cfg["lightning_head_dim"])
        kv = int(cfg["lightning_nkv"]) * int(cfg["lightning_head_dim"])
    else:
        width = int(cfg["num_attention_heads"]) * int(cfg["head_dim"])
        kv = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    return 3 * d * width + 2 * d * kv        # q, o, gate; k, v


def mlp_params(cfg: dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def layer_params(cfg: dict, mixer: str) -> int:
    return mixer_params(cfg, mixer) + mlp_params(cfg)


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def vocabulary_params(cfg: dict) -> int:
    """Embedding and untied head."""
    return 2 * head_params(cfg)


def total_params(cfg: dict) -> int:
    """Every matrix as built (the gains left out)."""
    return vocabulary_params(cfg) + sum(layer_params(cfg, m)
                                        for m in layers(cfg))


def cache_token_bytes(cfg: dict, dtype_bytes: int = 2) -> int:
    """What one token takes in the sparse layers' cache: k and v of
    ``num_key_value_heads`` heads, and one pooled key of that shape every
    ``kernel_stride`` tokens."""
    kv = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]) * dtype_bytes
    stride = int(cfg["sparse_config"]["kernel_stride"])
    return layers(cfg).count("minicpm4") * (2 * kv + kv // stride)


def state_slot_bytes(cfg: dict, dtype_bytes: int = 4) -> int:
    """The recurrent state one sequence holds over the linear layers:
    ``lightning_nh`` matrices of ``lightning_head_dim`` squared a layer."""
    hd = int(cfg["lightning_head_dim"])
    return (layers(cfg).count("lightning-attn") * int(cfg["lightning_nh"])
            * hd * hd * dtype_bytes)


def decode_step_bytes(cfg: dict, positions_read: float, positions_live: float,
                      live_slots: float, slot_bytes: float,
                      dtype_bytes: int = 2) -> float:
    """The bytes one decode step must stream: every layer's matrices and the
    head at the parameters' width; the selected keys and values
    (``positions_read``: cached positions the step's sparse layers attended,
    summed over rows, layers and KV heads: k and v of ``head_dim`` each); the
    pooled keys the selection scored (``positions_live`` the same sum of the
    positions those rows hold: one key of ``head_dim`` every
    ``kernel_stride`` of them); and the live slots' recurrent state
    (``slot_bytes`` a slot, all linear layers) once read and once written.
    The embedding rows, the activations and whatever a step reads twice are
    left out, so the time this gives at the memory's peak rate is a LOWER
    bound of the step."""
    weights = sum(layer_params(cfg, m) for m in layers(cfg)) + head_params(cfg)
    hd = int(cfg["head_dim"])
    stride = int(cfg["sparse_config"]["kernel_stride"])
    return (dtype_bytes * weights
            + positions_read * 2 * hd * dtype_bytes
            + positions_live / stride * hd * dtype_bytes
            + 2 * live_slots * slot_bytes)
