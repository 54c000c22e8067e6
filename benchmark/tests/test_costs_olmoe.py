"""``harness/costs_olmoe.py`` against the program's own model at a small
size: the parameter counts are the built tree's, leaf for leaf, and the bytes
of a decode step are what those counts say."""

import jax
import numpy as np

from harness import costs_olmoe as co

CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_experts": 8, "num_experts_per_tok": 2, "intermediate_size": 32,
       "num_hidden_layers": 3, "vocab_size": 128}


def built():
    from deeplearning4j_tpu import models

    m = models.OlmoeLM(seed=0, input_shape=(32,), num_layers=3, d_model=64,
                       num_heads=4, num_kv_heads=2, num_experts=8, top_k=2,
                       expert_width=32, vocab=128).build()
    shapes, _ = jax.eval_shape(m.init, 0)
    return shapes


def count(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_counts_are_the_built_tree():
    p = built()
    blk = p["layer_1"]
    assert co.attention_params(CFG) == count(blk["attn"]["w_qkv"]) \
        + count(blk["attn"]["w_o"])
    assert co.router_params(CFG) == count(blk["moe"]["w_router"])
    assert co.expert_params(CFG) * 8 == count(blk["moe"]) \
        - count(blk["moe"]["w_router"])
    assert co.head_params(CFG) == count(p["layer_5"])
    assert co.total_params(CFG) == count(p)


def test_decode_step_bytes():
    full = co.decode_step_bytes(CFG, 1.0, 2)
    assert full == 2 * (3 * co.block_params(CFG) + co.head_params(CFG))
    none = co.decode_step_bytes(CFG, 0.0, 2)
    assert none == 2 * (3 * (co.attention_params(CFG) + co.router_params(CFG))
                        + co.head_params(CFG))
    half = co.decode_step_bytes(CFG, 0.5, 2)
    assert abs(half - (full + none) / 2) < 1e-6
    assert co.decode_step_bytes(CFG, 1.0, 4) == 2 * full


def test_published_sizes():
    """OLMoE-1B-7B as published: 6.92 B parameters in 16 layers, of which a
    token is multiplied with 1.28 B."""
    from harness import env

    cfg = dict(env.load_json(env.BENCH_DIR + "/configs/olmoe-1b-7b.json"),
               num_hidden_layers=16)
    assert co.expert_params(cfg) * 64 == 402_653_184
    assert co.block_params(cfg) == 419_561_472
    assert round(co.total_params(cfg) / 1e9, 2) == 6.92
    active = 16 * (co.attention_params(cfg) + co.router_params(cfg)
                   + 8 * co.expert_params(cfg)) + 2 * co.head_params(cfg)
    assert round(active / 1e9, 2) == 1.28
