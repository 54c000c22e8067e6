"""``harness/costs_glm4_moe_lite.py`` against the counts ISSUE 31 worked out
by hand from the published config, and against the program's own tree."""

import json
import os

import pytest

from harness import costs_glm4_moe_lite as costs, env

CFG = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                 "glm-4.7-flash.json"))


def test_counts_reproduce_the_published_sizes():
    # attention 2048x768 + 768x5120 + 2048x576 + 512x8960 + 5120x2048
    assert costs.attention_params(CFG) == 21_757_952
    assert costs.shared_params(CFG) == 3 * 2048 * 1536 == 9_437_184
    assert costs.router_params(CFG) == 131_072
    assert round(costs.expert_layer_params(CFG) / 1e6, 1) == 31.3
    assert round(costs.routed_params(CFG) / 1e6, 1) == 604.0
    assert round(costs.dense_layer_params(CFG) / 1e6, 1) == 84.7
    assert round(costs.vocabulary_params(CFG) / 1e6, 1) == 634.4
    assert costs.layers(CFG) == (1, 7)
    # one dense + 7 expert layers + vocabulary, at 2 bytes: 10.33 GB
    assert round(2 * costs.total_params(CFG) / 1e9, 2) == 10.33
    assert costs.cache_token_bytes(CFG) == 8 * 1152


def test_counts_are_the_programs_tree():
    """The model as the benchmark builds it, shapes only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import model as modelmod

    mdl = modelmod.build(CFG)
    params, _ = jax.eval_shape(mdl.init, jnp.uint32(0))
    built = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    d = 2048
    gains = 8 * (2 * d + 768 + 512) + d      # four a block, one at the end
    biases = 7 * 64
    assert built == costs.total_params(CFG) + gains + biases


@pytest.mark.parametrize("share,cache", [(0.0, 0.0), (0.79, 1.2e9), (1.0, 2.4e9)])
def test_decode_step_bytes(share, cache):
    got = costs.decode_step_bytes(CFG, share, cache)
    want = 2 * (84.7e6 + 7 * (31.3e6 + share * 604.0e6) + 317.2e6) + cache
    assert got == pytest.approx(want, rel=2e-3)
    # never more than every weight but the embedding, and the whole pool
    assert got <= 2 * (costs.total_params(CFG) - costs.head_params(CFG)) \
        + 2.42e9 + 1


def test_the_file_states_its_cut():
    published = CFG["published"]
    assert published == {"num_hidden_layers": 47, "num_nextn_predict_layers": 1}
    with open(env.MANIFEST) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "glm-4.7-flash")
    assert sorted(entry["reduced"]) == sorted(published)
