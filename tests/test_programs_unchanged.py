"""The served programs of the models the benchmark has, pinned.

PR 31 taught the cache spec to name a layer's parts (a latent cache is one
array, not ``k`` and ``v``) and moved the experts' einsums and the routing
sums out of ``layers/olmoe.py``. Neither may change what a dense or an OLMoE
model is served by: the lowered text of the sampler, every prefill-chunk
bucket and the decode step, at a small size, hashes to what the parent commit
(31e153b) gave. A hash says nothing about speed; it says the compiler is
handed the same program, so the benchmark's old cells cannot move.

PR 33 gave the cache contract a second block group (a window layer's ring)
and the routing sums a held share; the three pins above it stand as they
were, GLM's programs are pinned from PR 33's parent, and Laguna's from the
commit that brought them.

PR 41 re-took the four ``gen_decode_paged`` pins, and only those: the decode
step now takes its tokens, positions and keys from the step before it, on the
device, and selects (integer ``where`` on a per-slot mask) the rows the host
sets; it returns positions + 1 beside the keys. The sampler's and the
chunk's texts are the parent's to the byte (their hashes stand as taken).

PR 45 gave the cache contract a third group (a linear-attention layer's
recurrent state: slots, not blocks), parts held once a stride of tokens, and
the chunk program two more operands FOR SUCH A MODEL; every pin above it
stands as it was, and MiniCPM-SALA's programs are pinned from the commit that
brought them.

PR 46 re-took the ``gen_decode_paged`` pins of ``dense``, ``olmoe`` and
``laguna``, and only those: a decode step over a paged k/v cache kept whole
now reads the pools in place through the Pallas kernel of
``ops/paged_attention.py`` (on the CPU its interpreter-mode lowering is part
of the text) where it gathered at capacity. The other twelve hashes stand byte
for byte: ``glm``'s and ``sala``'s decode steps (a latent pool, a sparse
layer's selected blocks: they gather themselves) and every sampler and chunk
program are the parent's, which is that PR's proof that
``glm47f-longchat-decode`` and ``sala-longctx-decode`` cannot move by its doing.

A change that is MEANT to alter these programs re-takes the hashes (run this
file with ``-s`` and copy what it prints) and says so in ``PERF.md``.
"""

import hashlib

import jax
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher

# taken at 31e153b (jax 0.9.0, CPU): model -> store tag -> sha256 of the
# lowered texts, joined in the order ``signatures()`` lists them
PARENT = {
    "dense": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "692e89af3a245104f15535b4916e73408869949827f4091bd721e0f586e9b99e",  # re-taken in PR 46
        "gen_prefill_chunk": "a92cab8705c9d83886433ac489b4f39741c788bf32c9a104358879481d95d7be",
    },
    "olmoe": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "efbf78042ed01f74e53d48c06b8aba181e135219eaf862c194c0d8f93e5fc81c",  # re-taken in PR 46
        "gen_prefill_chunk": "1024b3e649c2900012e645a75ebc0fb4f18852f8583051d01eb38f6c58b1d026",
    },
    # taken at 531c641 (PR 33's parent), when PR 33 touched the cache
    # contract and the experts' routing sums it shares
    "glm": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "07134ea7a66eb2bd407e63be7469bd6e1c2d722af04313e7aef273d8902a9ef1",
        "gen_prefill_chunk": "9d64b447c7c2161e6eb1d371ed37589bae546c1341d3c7e88e490b9f685c4c85",
    },
    # taken at PR 33, the commit that brought the model
    "laguna": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "361e270ad88421c770c8d26cccd8a547046aca6b7e5918a02a0352793339aa9e",  # re-taken in PR 46
        "gen_prefill_chunk": "6647156ad2ac696815543b0566d60f6b244e5dddf5017cce5888993901fbefe2",
    },
    # taken at PR 45, the commit that brought the model
    "sala": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "a9f8352287d28ba1acba17b354b5db6266276c95f61e59571a74a4f413277967",
        "gen_prefill_chunk": "4fd9c65628c5f40979a31b87688c3e7cf5e8ab2933aef5a754a03f70bb707a23",
    },
}


def _dense():
    """The block ``starcoderbase-1b`` is: learned positions, multi-query
    attention, f32 parameters served through a bf16 copy."""
    m = models.CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=1, vocab=128).build()
    m.init()
    m.config.compute_dtype = "bfloat16"
    return m


def _olmoe():
    """``olmoe-1b-7b``'s: parameters held once in bf16."""
    m = models.OlmoeLM(seed=0, input_shape=(64,), num_layers=2, d_model=64,
                       num_heads=4, num_experts=8, top_k=2, expert_width=32,
                       vocab=128, dtype="bfloat16").build()
    m.init()
    return m


def _glm():
    """``glm-4.7-flash``'s: a latent cache, a dense layer and expert layers,
    parameters held once in bf16."""
    m = models.Glm4MoeLiteLM(
        seed=3, input_shape=(96,), num_layers=3, first_k_dense=1, d_model=64,
        num_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=16, dense_width=96, num_experts=8,
        top_k=2, expert_width=32, vocab=128, dtype="bfloat16").build()
    m.init()
    return m


def _laguna():
    """``laguna-s-2.1``'s (PR 33): two block groups, a share of the experts
    held, parameters held once in bf16. Pinned from its first commit on, so
    that a later change to the shared stack shows here too."""
    m = models.LagunaLM(seed=0, input_shape=(64,), num_layers=5, d_model=64,
                        full_heads=4, sliding_heads=6, num_kv_heads=2,
                        head_dim=16, window=16, dense_width=96,
                        num_experts=16, top_k=3, expert_width=32,
                        shared_width=32, experts_held=(4, 8),
                        full_rotary_dim=8, yarn_factor=4.0, yarn_original=32,
                        vocab=128, dtype="bfloat16").build()
    m.init()
    return m


def _sala():
    """``minicpm-sala``'s (PR 45): sparse layers that select inside the paged
    cache (the pool's block size is the indexer's stride), linear layers with
    a state group, parameters held once in bf16. Pinned from its first commit
    on."""
    m = models.MiniCpmSalaLM(
        seed=0, input_shape=(64,), num_layers=4, published_layers=4,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"], d_model=64, num_heads=4, num_kv_heads=2,
        head_dim=16, lightning_heads=4, ffn_width=128, dim_model_base=16,
        sparse=dict(kernel_size=32, kernel_stride=16, block_size=32, topk=1,
                    init_blocks=1, window_size=16, dense_len=32),
        vocab=128, dtype="bfloat16").build()
    m.init()
    return m


def lowered_hashes(model):
    cb = ContinuousBatcher(model, slots=2, capacity=64, block_size=16,
                           prefill_chunk=16, metrics=MetricsRegistry())
    # the tests' conftest asks for "highest" matmuls; a served program is
    # lowered at the default precision, and that is the text pinned
    try:
        snap = cb.registry.current()
        progs = cb._programs
        fns = {"gen_sample": progs._sample, "gen_decode_paged": progs._decode,
               "gen_prefill_chunk": progs._prefill_chunk}
        sigs = progs.signatures(cb._params_for(snap), snap.state)
        with jax.default_matmul_precision("default"):
            return {tag: hashlib.sha256("\n".join(
                fns[tag].lower(*ops).as_text() for ops in lists).encode()
            ).hexdigest() for tag, lists in sigs.items()}
    finally:
        cb.shutdown()


@pytest.mark.skipif(jax.__version__ != "0.9.0",
                    reason="the hashes are of jax 0.9.0's lowering")
@pytest.mark.parametrize("tag", ["gen_sample", "gen_decode_paged",
                                 "gen_prefill_chunk"])
@pytest.mark.parametrize("name,build", [("dense", _dense), ("olmoe", _olmoe),
                                        ("glm", _glm), ("laguna", _laguna),
                                        ("sala", _sala)])
def test_lowered_text_is_the_parents(name, build, tag):
    got = lowered_hashes(build())
    print(name, got)
    assert got[tag] == PARENT[name][tag]
