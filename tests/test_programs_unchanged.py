"""The served programs of the models the benchmark already had, pinned.

PR 31 taught the cache spec to name a layer's parts (a latent cache is one
array, not ``k`` and ``v``) and moved the experts' einsums and the routing
sums out of ``layers/olmoe.py``. Neither may change what a dense or an OLMoE
model is served by: the lowered text of the sampler, every prefill-chunk
bucket and the decode step, at a small size, hashes to what the parent commit
(31e153b) gave. A hash says nothing about speed; it says the compiler is
handed the same program, so the benchmark's old cells cannot move.

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
        "gen_decode_paged": "50f7f66e676efdbc75296609cf6e37e92ace6a6d349696621a1b1cebcc2b4244",
        "gen_prefill_chunk": "a92cab8705c9d83886433ac489b4f39741c788bf32c9a104358879481d95d7be",
    },
    "olmoe": {
        "gen_sample": "5735026fd23a417e6ff6cd8d38548127448acbbb804e224ea67331c92424dc92",
        "gen_decode_paged": "2a57acd7ac55ca28dab5b7a43b6f98e5ce8e31d9f783f620767f918148deeb2f",
        "gen_prefill_chunk": "1024b3e649c2900012e645a75ebc0fb4f18852f8583051d01eb38f6c58b1d026",
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
@pytest.mark.parametrize("name,build", [("dense", _dense), ("olmoe", _olmoe)])
def test_lowered_text_is_the_parents(name, build, tag):
    got = lowered_hashes(build())
    print(name, got)
    assert got[tag] == PARENT[name][tag]
