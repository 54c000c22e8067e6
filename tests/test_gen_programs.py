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


# ------------------------------------- the sampler's threshold (ISSUE 29)
def _wide_lm():
    """A vocabulary wide enough for a ``top_k`` in the hundreds."""
    m = models.CausalLM(seed=0, input_shape=(32,), num_layers=1, d_model=16,
                        num_heads=2, num_kv_heads=1, vocab=300).build()
    m.init()
    return m


def _primitives(jaxpr):
    """The name of every equation of a jaxpr and of the jaxprs nested in
    it."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


@pytest.mark.parametrize("tag", ["gen_decode_paged", "gen_sample"])
def test_a_sampling_program_sorts_nothing(tag):
    """No sort and no ``top_k`` of the vocabulary anywhere in the program:
    the threshold is a loop that counts."""
    import jax

    cb = ContinuousBatcher(_wide_lm(), slots=2, capacity=16, block_size=4,
                           seed=0)
    try:
        progs = cb._programs
        snap = cb.registry.current()
        operands = progs.signatures(cb._params_for(snap), snap.state)[tag][0]
        fn = {"gen_decode_paged": progs._decode, "gen_sample": progs._sample}
        found = set(_primitives(jax.make_jaxpr(fn[tag])(*operands).jaxpr))
    finally:
        cb.shutdown()
    assert not found & {"sort", "top_k", "approx_top_k"}
    assert found & {"scan", "while"}


def test_the_batcher_serves_the_sort_formulas_tokens_at_every_top_k(
        monkeypatch):
    """Six requests decode side by side: greedy, and sampled at ``top_k``
    1, 40, 257, all of the vocabulary but one, and none. Served again by a
    batcher whose threshold is the parent's sort, every token is the same;
    the greedy row serves ``generate()``'s."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn import generation

    model = _wide_lm()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 300, (n,)).astype(np.int32)
               for n in (5, 3, 6, 4, 7, 2)]
    asks = [dict(temperature=0.0, top_k=257), dict(temperature=0.9, top_k=1),
            dict(temperature=0.8, top_k=40), dict(temperature=0.9, top_k=257),
            dict(temperature=1.1, top_k=299), dict(temperature=0.7)]

    def serve():
        cb = ContinuousBatcher(model, slots=8, capacity=32, block_size=4,
                               seed=0)
        try:
            reqs = [cb.submit(p, 10, **kw) for p, kw in zip(prompts, asks)]
            return [r.wait() for r in reqs]
        finally:
            cb.shutdown()

    got = serve()
    np.testing.assert_array_equal(
        got[0], generation.generate(model, prompts[0][None], 10,
                                    temperature=0.0)[0])
    sorts = []

    def by_sort(scaled, top_k):
        sorts.append(scaled.shape)
        V = scaled.shape[-1]
        return jnp.take_along_axis(
            jnp.sort(scaled, axis=-1),
            (V - jnp.clip(top_k, 1, V))[:, None], axis=-1)[:, 0]

    monkeypatch.setattr(generation, "top_k_threshold", by_sort)
    want = serve()
    assert sorts                                # the programs traced it
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("temperature,top_k",
                         [(0.0, 0), (0.0, 7), (0.8, 0), (0.8, 1), (0.8, 40),
                          (1.2, 256), (0.8, 257), (0.5, "V-1")])
def test_the_first_token_sampler_is_the_sort_formula(temperature, top_k):
    """``gen_sample`` (one row) against the parent's formula written out."""
    import jax
    import jax.numpy as jnp

    model = _wide_lm()
    cb = ContinuousBatcher(model, slots=2, capacity=16, block_size=4, seed=0)
    try:
        V = cb.vocab
        k = {"V-1": V - 1, 0: V}.get(top_k, top_k)
        logits = jnp.asarray(np.random.RandomState(k).standard_normal(V) * 3,
                             jnp.bfloat16).astype(jnp.float32)
        key = jax.random.PRNGKey(k)
        got = int(cb._programs.sample(logits, key, temperature, k))
    finally:
        cb.shutdown()
    scaled = logits / jnp.maximum(np.float32(temperature), 1e-6)
    kth = jnp.sort(scaled)[V - k]
    samp = jax.random.categorical(
        key, jnp.where(scaled >= kth, scaled, -1e30))
    assert got == int(jnp.argmax(logits) if temperature <= 0 else samp)


def test_signatures_and_tags_are_what_they_were():
    """One sampler, one decode step, one chunk program a bucket. The
    operand lists of the sampler and the chunk are PR 28's, written out; the
    decode step's is PR 41's: the nine it had (tokens, positions and keys
    now the step before's, on the device) and behind them the rows the host
    sets: a mask, and the tokens, positions and keys to take there."""
    cb = _batcher(_causal_lm(), None)
    try:
        progs = cb._programs
        snap = cb.registry.current()
        params = cb._params_for(snap)
        sigs = progs.signatures(params, snap.state)
    finally:
        cb.shutdown()
    S, B, V = 2, 8, 50

    def shapes(operands):
        return [(tuple(o.shape), np.dtype(o.dtype).name) for o in operands
                if hasattr(o, "shape")]

    assert tuple(sigs) == TAGS
    assert shapes(sigs["gen_sample"][0]) == [
        ((V,), "float32"), ((2,), "uint32"), ((), "float32"), ((), "int32")]
    (decode,) = sigs["gen_decode_paged"]
    assert decode[0] is params and decode[3] is progs._pools_sig
    assert shapes(decode) == [
        ((S,), "int32"), ((S, B), "int32"), ((S,), "int32"),
        ((S, 2), "uint32"), ((S,), "float32"), ((S,), "int32"),
        ((S,), "bool"), ((S,), "int32"), ((S,), "int32"), ((S, 2), "uint32")]
    assert [shapes(c) for c in sigs["gen_prefill_chunk"]] == [
        [((1, b), "int32"), ((1, B), "int32"), ((1,), "int32"),
         ((), "int32")] for b in (4, 8)]


# ------------------------- what a step hands the next, on the device (ISSUE 41)
def _prefilled(model, prompt, n=1):
    """A batcher whose worker is gone, ``prompt`` prefilled into slot 0 by
    hand: ``(cb, params, snap, pages, logits of its last token)``."""
    from deeplearning4j_tpu.serve.paged import SlotPages

    cb = _batcher(model, None, prefix_cache=False)
    cb.shutdown()
    snap = cb.registry.current()
    params = cb._params_for(snap)
    pages = SlotPages(cb._alloc, cb.block_size)
    for off, true_len, bucket in cb._plan_chunks(len(prompt)):
        pages.ensure(off + true_len)
        cb._write_table_row(0, pages.blocks)
        last = cb._programs.prefill_chunk(
            params, snap.state, prompt[off:off + true_len], bucket,
            cb._table_rows(0), off)
    return cb, params, snap, pages, last


def _steps(model, prompt, steps, temperature, carried, first_on_device=False):
    """``steps`` decode steps of slot 0 behind ``prompt``. ``carried``: only
    the first step sets the row, the rest take what the step before left on
    the device; else every step sets it from the host, the keys read back
    in between (the parent's order). Returns tokens and the last keys."""
    import jax

    cb, params, snap, pages, last = _prefilled(model, prompt)
    progs, S, V = cb._programs, cb.slots, cb.vocab
    key, sub = jax.random.split(jax.random.PRNGKey(9))
    tok0 = progs.sample(last[0], sub, temperature, V)
    mask = np.zeros(S, bool)
    mask[0] = True
    toks, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
    keys = np.zeros((S, 2), np.uint32)
    pos[0], keys[0] = len(prompt), np.asarray(key, np.uint32)
    temps = np.full(S, temperature, np.float32)
    tks = np.full(S, V, np.int32)
    first = {0: (tok0, key)} if first_on_device else {}
    toks[0] = 0 if first_on_device else int(np.asarray(tok0))
    out = []
    for i in range(steps):
        pages.ensure(len(prompt) + i + 1)
        cb._write_table_row(0, pages.blocks)
        tables = np.where(mask[:, None], cb._tables_np, 0)
        fresh = (mask, toks, pos, keys, temps, tks, first) \
            if i == 0 or not carried else None
        nxt = progs.decode(params, snap.state, tables, fresh)
        if not carried:
            toks[0] = int(np.asarray(nxt)[0])
            keys[0] = np.asarray(progs._carry[2])[0]
            pos[0] += 1
            first = {}
        out.append(nxt)         # read only now: nothing forced an order
    out = [int(np.asarray(o)[0]) for o in out]
    return [int(np.asarray(tok0))] + out, np.asarray(progs._carry[2])[0], \
        int(np.asarray(progs._carry[1])[0])


@pytest.mark.parametrize("temperature", [0.0, 0.9], ids=["greedy", "t0.9"])
@pytest.mark.parametrize("build", [_causal_lm, _olmoe],
                         ids=["causal_lm", "olmoe_bf16"])
def test_a_step_takes_the_step_befores_tokens_positions_and_keys(
        build, temperature):
    """Six steps enqueued one behind the other with nothing read in between
    give the tokens, the keys and the position of six steps that each go
    through the host."""
    model = build()
    prompt = np.random.RandomState(4).randint(1, 50, (7,)).astype(np.int32)
    want = _steps(model, prompt, 6, temperature, carried=False)
    got = _steps(model, prompt, 6, temperature, carried=True)
    assert got[0] == want[0]
    np.testing.assert_array_equal(got[1], want[1])
    assert got[2] == want[2] == len(prompt) + 6


def test_a_first_token_may_ride_as_the_samplers_device_scalar():
    model = _causal_lm()
    prompt = np.random.RandomState(5).randint(1, 50, (5,)).astype(np.int32)
    want = _steps(model, prompt, 4, 0.9, carried=True)
    got = _steps(model, prompt, 4, 0.9, carried=True, first_on_device=True)
    assert got[0] == want[0]
    np.testing.assert_array_equal(got[1], want[1])


def test_a_step_that_sets_no_row_uploads_the_tables_and_nothing_else(
        monkeypatch):
    """Between two admissions the host sends the block tables: the slot
    vectors and the sampling vectors are device values from the step (or
    the admission) before."""
    import jax.numpy as jnp

    model = _causal_lm()
    prompt = np.arange(1, 7, dtype=np.int32)
    cb, params, snap, pages, last = _prefilled(model, prompt)
    progs, S = cb._programs, cb.slots
    mask = np.zeros(S, bool)
    mask[0] = True
    zeros = np.zeros(S, np.int32)
    pos = zeros.copy()
    pos[0] = len(prompt)
    pages.ensure(len(prompt) + 2)
    cb._write_table_row(0, pages.blocks)
    tables = np.where(mask[:, None], cb._tables_np, 0)
    uploads = []
    real = jnp.asarray

    def counting(x, *a, **k):
        if isinstance(x, np.ndarray):
            uploads.append(x.shape)
        return real(x, *a, **k)

    monkeypatch.setattr(jnp, "asarray", counting)
    progs.decode(params, snap.state, tables,
                 (mask, zeros, pos, np.zeros((S, 2), np.uint32),
                  np.ones(S, np.float32), np.full(S, cb.vocab, np.int32), {}))
    assert len(uploads) == 7        # the tables and the six vectors of a set
    del uploads[:]
    nxt = progs.decode(params, snap.state, tables, None)
    assert uploads == [tables.shape]
    assert nxt.shape == (S,)


def test_chunk_routing_reads_the_oldest_sums_and_leaves_the_rest():
    model = _olmoe()
    cb, *_ = _prefilled(model, np.arange(1, 20, dtype=np.int32))
    progs = cb._programs
    assert len(progs._routing_pending) == 3     # 8 + 8 + 3 tokens
    (first,) = progs.chunk_routing(1)
    assert first.shape == (len(progs.routing_fields),)
    assert len(progs._routing_pending) == 2
    assert len(progs.chunk_routing(5)) == 2 and not progs._routing_pending
