"""Operations and bytes the algorithm needs, from shapes alone. XLA's
``cost_analysis()`` counts recomputation and knows nothing of a Mosaic call,
so MFU and roofline shares are computed here, from the configuration file's
sizes (``n_layer``, ``n_embd``, ``n_head``, ``n_inner``, ``vocab_size``,
``n_positions``, ``multi_query``).

A multiply-add is two operations. The embedding lookup is a gather, not a
matrix product, and counts nothing; the output head (untied here) counts.
"""

from __future__ import annotations


def kv_heads(cfg: dict) -> int:
    return 1 if cfg.get("multi_query") else int(cfg.get("n_kv_head", cfg["n_head"]))


def head_dim(cfg: dict) -> int:
    return int(cfg["n_embd"]) // int(cfg["n_head"])


def block_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied with (biases and
    LayerNorm left out: they are a rounding error of the count)."""
    d, f = int(cfg["n_embd"]), int(cfg["n_inner"])
    d_kv = head_dim(cfg) * kv_heads(cfg)
    return d * (d + 2 * d_kv) + d * d + 2 * d * f


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied with: the blocks and the head."""
    return (int(cfg["n_layer"]) * block_params(cfg)
            + int(cfg["n_embd"]) * int(cfg["vocab_size"]))


def total_params(cfg: dict) -> int:
    """As built here: untied head, learned positions, biases, LayerNorms."""
    d, f, v = int(cfg["n_embd"]), int(cfg["n_inner"]), int(cfg["vocab_size"])
    d_kv = head_dim(cfg) * kv_heads(cfg)
    per_block = block_params(cfg) + (d + 2 * d_kv) + d + f + d + 4 * d
    return (v * d + max(int(cfg["n_positions"]), 512) * d
            + int(cfg["n_layer"]) * per_block + 2 * d + d * v + v)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward: 6 per multiplied weight, plus causal attention
    (QK^T and PV forward, twice that backward, half of the square)."""
    attn = 6 * int(cfg["n_layer"]) * seq_len * int(cfg["n_embd"])
    return 6.0 * matmul_params(cfg) + attn


def decode_flops_per_token(cfg: dict, context: int) -> float:
    """One generated token with ``context`` tokens in the cache."""
    attn = 4 * int(cfg["n_layer"]) * context * int(cfg["n_embd"])
    return 2.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 4) -> int:
    """K and V of every layer for one token."""
    return 2 * int(cfg["n_layer"]) * kv_heads(cfg) * head_dim(cfg) * dtype_bytes


def flash_fwd_call(batch: int, heads: int, seq: int, dim: int,
                   dtype_bytes: int = 2, causal: bool = True) -> dict:
    """One forward flash-attention call on (batch, seq, heads, dim): the
    operations of QK^T and PV (half of the square when causal), and the
    bytes of reading q, k, v and writing o once plus the f32 log-sum-exp."""
    flops = 4.0 * batch * heads * seq * seq * dim * (0.5 if causal else 1.0)
    nbytes = 4.0 * batch * heads * seq * dim * dtype_bytes \
        + 4.0 * batch * heads * seq
    return {"flops": flops, "bytes": nbytes}


def roofline_s(flops: float, nbytes: float, peak) -> dict:
    """The least time the chip could take, and which limit sets it."""
    t_f, t_b = flops / peak.bf16_flops, nbytes / peak.hbm_bytes_s
    return {"seconds": max(t_f, t_b),
            "bound": "compute" if t_f >= t_b else "memory"}
