"""``serve/programs.py`` against the scheduler that feeds it (ISSUE 28).

- The module's one source of operand signatures (``GenPrograms.signatures``,
  what ``warm`` compiles) is what a real tick, real prefill chunks of every
  bucket, a first-token sample and a fork's rows pass: traffic
  after a warm boot acquires no further executable, and a strict replica
  booted from that store serves the same tokens having compiled nothing.
  For a ``CausalLM`` with a compute dtype and for a small bf16 ``OlmoeLM``
  (whose programs return the routing sums as well).
- There is one KV layout: ``kv`` is no parameter of the batcher or of the
  server, and a tuned config stored when it was a knob still boots.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.aot import AotStore, put_tuned
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import (GEN_KNOBS, ContinuousBatcher,
                                                 gen_opts_from_config)
from deeplearning4j_tpu.serve.errors import ServeError

TAGS = ("gen_sample", "gen_decode_paged", "gen_prefill_chunk")


def _causal_lm(compute_dtype="bfloat16"):
    m = models.CausalLM(seed=0, input_shape=(32,), num_layers=2, d_model=32,
                        num_heads=4, num_kv_heads=1, vocab=50).build()
    m.init()
    m.config.compute_dtype = compute_dtype
    return m


def _olmoe():
    m = models.OlmoeLM(seed=3, input_shape=(32,), num_layers=2, d_model=64,
                       num_heads=4, num_experts=8, top_k=2, expert_width=32,
                       vocab=128, dtype="bfloat16").build()
    m.init()
    return m


def _batcher(model, store, **kw):
    # chunk buckets (4, 8): a 3-token tail, a 7-token tail and full chunks
    return ContinuousBatcher(model, slots=2, capacity=32, block_size=4,
                             prefill_chunk=8, prompt_buckets=(4, 8, 32),
                             seed=0, aot_store=store, **kw)


def _traffic(cb):
    """Every program and every bucket: prompts of 3, 7 and 19 tokens, greedy
    and sampled, and a fork mid-decode (both rows of the decode step live,
    a block copied on write unless the fork fell on a block's edge)."""
    rng = np.random.RandomState(2)
    outs = [cb.generate(rng.randint(1, 50, (n,)).astype(np.int32), 5, **kw)
            for n, kw in ((3, dict(temperature=0.0)),
                          (7, dict(temperature=0.9, top_k=5)),
                          (19, dict(temperature=0.0)))]
    real = cb._programs.decode

    def slow(*a):       # so that the fork lands while the parent decodes
        time.sleep(0.02)
        return real(*a)

    cb._programs.decode = slow
    try:
        req = cb.submit(rng.randint(1, 50, (6,)).astype(np.int32), 12,
                        temperature=0.0)
        child = None
        while child is None and not req.event.is_set():
            try:
                child = cb.fork(req)
            except ServeError:
                time.sleep(0)       # still queued or prefilling
        outs.append(req.wait())
        if child is not None:
            child.wait()
    finally:
        cb._programs.decode = real
    return outs, child is not None


@pytest.mark.parametrize("build", [_causal_lm, _olmoe],
                         ids=["causal_lm", "olmoe_bf16"])
def test_signatures_are_what_the_calls_pass(build, tmp_path):
    model = build()
    store = AotStore(tmp_path)
    m = MetricsRegistry()
    cb = _batcher(model, store, metrics=m)
    misses = m.counter("serve_compile_misses_total", {"component": "generate"})
    try:
        progs = cb._programs
        assert bool(progs.routed) == (build is _olmoe)
        snap = cb.registry.current()
        sigs = progs.signatures(cb._params_for(snap), snap.state)
        assert tuple(sigs) == TAGS
        assert [len(sigs[t]) for t in TAGS] == [1, 1, len(cb._chunk_buckets)]
        warmed = {t: set(f.executables) for t, f in cb.aot_functions().items()}
        assert {t: len(v) for t, v in warmed.items()} \
            == {t: len(sigs[t]) for t in TAGS}
        at_boot = misses.value
        assert at_boot == sum(len(v) for v in warmed.values())
        want, forked = _traffic(cb)
        assert forked
        assert {t: set(f.executables)
                for t, f in cb.aot_functions().items()} == warmed
        assert misses.value == at_boot
    finally:
        cb.shutdown()

    m = MetricsRegistry()
    cb = _batcher(model, AotStore(tmp_path), metrics=m, strict_aot=True)
    try:
        got, _ = _traffic(cb)
    finally:
        cb.shutdown()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    labels = {"component": "generate"}
    assert m.counter("serve_compile_misses_total", labels).value == 0
    assert m.counter("serve_aot_strict_misses_total", labels).value == 0
    assert m.counter("serve_aot_hits_total", labels).value == at_boot


# ------------------------------------------------------------ one KV layout
def test_kv_is_no_parameter_of_the_batcher():
    assert "kv" not in GEN_KNOBS
    with pytest.raises(TypeError, match="kv"):
        ContinuousBatcher(_causal_lm(), slots=2, capacity=16, kv="paged")


def test_kv_is_no_parameter_of_the_server():
    from deeplearning4j_tpu.serve.http import ModelServer

    with pytest.raises(TypeError, match="gen_kv"):
        ModelServer(_causal_lm(), port=0, input_dtype=np.int32,
                    gen_kv="paged")


@pytest.mark.parametrize("kv", ["paged", "dense"])
def test_a_stored_config_that_still_says_kv_boots_and_serves(kv, tmp_path):
    """Input from outside the program: a tuned config written when ``kv``
    was a knob. The filter drops the key; the rest of the group applies."""
    from deeplearning4j_tpu.nn.generation import generate

    stored = {"gen": {"kv": kv, "slots": 3, "capacity": 16, "block_size": 4,
                      "prefill_chunk": 8, "decode_chunks": 2}}
    opts = gen_opts_from_config(stored)
    assert "kv" not in opts and opts["slots"] == 3
    store = AotStore(str(tmp_path))
    assert put_tuned(store, "wl-old", stored)
    model = _causal_lm(compute_dtype=None)
    cb = ContinuousBatcher.from_tuned(model, store, "wl-old",
                                      metrics=MetricsRegistry(), seed=0)
    try:
        assert (cb.slots, cb.capacity, cb.block_size) == (3, 16, 4)
        assert cb.scheduler.decode_chunks == 2
        prompt = np.asarray([4, 9, 1, 30, 2], np.int32)
        got = cb.generate(prompt, 6, temperature=0.0)
        assert cb.kv_block_stats()["block_size"] == 4
    finally:
        cb.shutdown()
    want = generate(model, prompt[None], 6, temperature=0.0)[0]
    np.testing.assert_array_equal(got, want)
