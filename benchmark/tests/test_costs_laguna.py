"""``harness/costs_laguna.py`` against the counts ISSUE 33 worked out by hand
from the published config, and against the program's own tree."""

import json
import os

import pytest

from harness import costs_laguna as costs, env

CFG = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                 "laguna-s-2.1.json"))


def test_counts_reproduce_the_published_sizes():
    assert costs.expert_params(CFG) == 3 * 3072 * 1024 == 9_437_184
    assert costs.shared_params(CFG) == 9_437_184
    assert costs.router_params(CFG) == 3072 * 256        # the router is whole
    assert costs.held_experts(CFG) == 32 and costs.router_width(CFG) == 256
    # a full layer has 48 query heads, a sliding layer 72, 8 KV heads of 128
    assert costs.attention_params(CFG, 0) \
        == 2 * 3072 * 6144 + 2 * 3072 * 1024 + 3072 * 48
    assert costs.attention_params(CFG, 1) \
        == 2 * 3072 * 9216 + 2 * 3072 * 1024 + 3072 * 72
    # outside the routed experts: full 54.4 M, sliding 73.4 M; dense 157.4 M
    assert round(costs.layer_params(CFG, 4, 0.0) / 1e6, 1) == 54.4
    assert round(costs.layer_params(CFG, 1, 0.0) / 1e6, 1) == 73.4
    assert round(costs.layer_params(CFG, 0) / 1e6, 1) == 157.4
    assert round(costs.vocabulary_params(CFG) / 1e6, 1) == 616.6
    assert costs.layers(CFG) == [
        ("full_attention", True)] + [
        ("sliding_attention", False)] * 3 + [("full_attention", False)] + [
        ("sliding_attention", False)] * 3 + [("full_attention", False)]
    # 8 x 32 experts 2.416 B + 706 M outside them + 616.6 M: 3.74 B, 7.48 GB
    assert round(8 * costs.routed_params(CFG) / 1e9, 3) == 2.416
    assert round(2 * costs.total_params(CFG) / 1e9, 2) == 7.48
    assert costs.cache_token_bytes(CFG) == 9 * 4096


def test_counts_are_the_programs_tree():
    """The model as the benchmark builds it, shapes only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import model as modelmod

    mdl = modelmod.build(CFG)
    params, _ = jax.eval_shape(mdl.init, jnp.uint32(0))
    built = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    gains = 9 * 2 * 3072 + 3072           # two a block, one at the end
    assert built == costs.total_params(CFG) + gains
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(params))
    assert nbytes == 2 * built            # held once, in bf16


@pytest.mark.parametrize("share,cache", [(0.0, 0.0), (0.6, 2.0e9), (1.0, 3.7e9)])
def test_decode_step_bytes(share, cache):
    got = costs.decode_step_bytes(CFG, share, cache)
    want = 2 * (157.4e6 + 2 * 54.4e6 + 6 * 73.4e6
                + share * 8 * 32 * 9.437e6 + 308.3e6) + cache
    assert got == pytest.approx(want, rel=2e-3)
    # never more than every weight but the embedding, and both groups' pools
    assert got <= 2 * (costs.total_params(CFG) - costs.head_params(CFG)) \
        + 4.1e9 + 1


def test_the_file_states_its_cut():
    published = CFG["published"]
    assert published == {"num_hidden_layers": 48, "num_experts": 256}
    assert CFG["experts_held"] == [0, 32] == [0, CFG["num_experts"]]
    with open(env.MANIFEST) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "laguna-s-2.1")
    assert sorted(entry["reduced"]) == sorted(published)
    # every other published number stands, and the build follows the file
    k = CFG["build"]["kwargs"]
    assert (k["d_model"], k["full_heads"], k["sliding_heads"], k["num_kv_heads"],
            k["head_dim"], k["window"], k["dense_width"], k["num_experts"],
            k["top_k"], k["expert_width"], k["shared_width"], k["vocab"]) \
        == (3072, 48, 72, 8, 128, 512, 12288, 256, 10, 1024, 1024, 100352)
    assert (CFG["hidden_size"], CFG["sliding_window"], CFG["intermediate_size"],
            CFG["num_experts_per_tok"], CFG["moe_intermediate_size"],
            CFG["vocab_size"]) == (3072, 512, 12288, 10, 1024, 100352)
    assert k["num_layers"] == CFG["num_hidden_layers"] == 9
    assert k["experts_held"] == CFG["experts_held"]
