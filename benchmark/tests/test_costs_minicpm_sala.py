"""``harness/costs_minicpm_sala.py`` against the counts ISSUE 45 worked out by
hand from the published config, and against the program's own tree."""

import os

import pytest

from harness import costs_minicpm_sala as costs, env

CFG = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                 "minicpm-sala.json"))


def test_counts_reproduce_the_published_sizes():
    # the cut: published layers 9-16, two whole periods
    assert "".join("M" if m == "minicpm4" else "L"
                   for m in costs.layers(CFG)) == "MLLLLLLM"
    # a sparse mixer: q, o, gate 16.78 M each, k + v 2.10 M: 52.4 M
    assert costs.mixer_params(CFG, "minicpm4") \
        == 3 * 4096 * 4096 + 2 * 4096 * 256 == 52_428_800
    # a linear mixer: five matrices of 4096 x 4096: 83.9 M
    assert costs.mixer_params(CFG, "lightning-attn") == 5 * 4096 * 4096
    assert costs.mlp_params(CFG) == 3 * 4096 * 16384 == 201_326_592
    assert round(costs.layer_params(CFG, "minicpm4") / 1e6, 1) == 253.8
    assert round(costs.layer_params(CFG, "lightning-attn") / 1e6, 1) == 285.2
    assert round(costs.vocabulary_params(CFG) / 1e6, 1) == 601.7
    # 2 x 253.8 + 6 x 285.2 + 601.7 M: 2.82 B, 5.64 GB in bf16
    assert round(costs.total_params(CFG) / 1e9, 2) == 2.82
    assert round(2 * costs.total_params(CFG) / 1e9, 2) == 5.64
    # two sparse layers x (k and v of 2 x 128 in bf16 + a pooled key every 16)
    assert costs.cache_token_bytes(CFG) == 2 * (1024 + 32) == 2112
    # six linear layers x 32 heads x 128 x 128 in float32
    assert costs.state_slot_bytes(CFG) == 12_582_912


def test_counts_are_the_programs_tree_and_its_pools():
    """The model as the benchmark builds it, shapes only; and the pools'
    sizes as the batcher's ledger counts them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.serve import paged
    from harness import model as modelmod

    mdl = modelmod.build(CFG)
    params, _ = jax.eval_shape(mdl.init, jnp.uint32(0))
    built = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    # two gains a block and one at the end; a head's width: q and k in every
    # mixer, the output's besides in a linear one
    gains = 8 * 2 * 4096 + 4096 + 2 * 2 * 128 + 6 * 3 * 128
    assert built == costs.total_params(CFG) + gains
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(params))
    assert nbytes == 2 * built            # held once, in bf16
    full = next(g for g in paged.cache_groups(mdl) if g.name == "full")
    assert paged.block_bytes(mdl, 16, mdl.dtype, full.layers) \
        == 16 * costs.cache_token_bytes(CFG)
    assert paged.state_slot_bytes(mdl) == costs.state_slot_bytes(CFG)


@pytest.mark.parametrize("read,live,slots", [
    (0.0, 0.0, 0.0), (12 * 2 * 2 * 4096, 12 * 2 * 2 * 26000, 12.0),
    (16 * 2 * 2 * 4096, 16 * 2 * 2 * 49152, 16.0)])
def test_decode_step_bytes(read, live, slots):
    got = costs.decode_step_bytes(CFG, read, live, slots,
                                  costs.state_slot_bytes(CFG))
    weights = 2 * (costs.total_params(CFG) - costs.head_params(CFG))
    want = weights + read * 512 + live / 16 * 256 + slots * 2 * 12_582_912
    assert got == want
    # the weights are most of a step: 5.04 GB; a full batch at full capacity
    # adds 0.13 GB of selected k and v, 0.05 of pooled keys, 0.40 of state
    assert round(weights / 1e9, 2) == 5.04
    if slots == 16:
        assert round((got - weights) / 1e9, 2) == 0.59
