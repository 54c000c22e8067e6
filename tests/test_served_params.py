"""The served weights are cast to the compute dtype once per params
generation (ISSUE 24), not inside every decode step and prefill chunk.

What has to hold, each tested directly on a tiny bf16-compute CausalLM:

- bit identity: the compiled programs fed the batcher's compute-dtype copy
  give the very logits, caches and tokens they give when fed the registry's
  f32 tree (``decode_forward`` casts whatever it is handed; on a tree that
  is already cast the cast traces to nothing);
- once per generation: one copy at construction, one per publish or
  rollback made in the pre-flip warmer, never one on the worker thread;
  the copy of a retired generation is let go, busy or idle;
- the executable set is the copy's: the warmed decode and prefill programs
  take compute-dtype parameter operands, and a second boot from the same
  ``AotStore`` compiles nothing;
- a model without ``compute_dtype`` makes no copy at all.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.aot import AotStore
from deeplearning4j_tpu.nn import generation as G
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.errors import PublishError

V = 50


def _lm(compute_dtype="bfloat16", seed=0):
    from deeplearning4j_tpu.models import CausalLM

    model = CausalLM(seed=seed, input_shape=(16,), num_layers=2, d_model=32,
                     num_heads=4, vocab=V).build()
    model.init()
    model.config.compute_dtype = compute_dtype
    return model


def _batcher(model, **kw):
    opts = dict(slots=2, capacity=32, seed=0, block_size=4, prefill_chunk=8,
                prompt_buckets=(8,))
    opts.update(kw)
    return ContinuousBatcher(model, **opts)


def _float_dtypes(tree):
    return {str(a.dtype) for a in jax.tree.leaves(tree)
            if jnp.issubdtype(a.dtype, jnp.floating)}


def _equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture(scope="module")
def lm():
    return _lm()


# ------------------------------------------------------------ (a) identity
def test_decode_params_casts_float_leaves_only(lm):
    copy = G.decode_params(lm, lm.params)
    assert _float_dtypes(lm.params) == {"float32"}
    assert _float_dtypes(copy) == {"bfloat16"}
    assert jax.tree.structure(copy) == jax.tree.structure(lm.params)
    _equal(copy, jax.tree.map(lambda a: a.astype(jnp.bfloat16), lm.params))
    # a tree that already has the compute dtype is passed through leaf by
    # leaf: casting twice makes no third tree
    again = G.decode_params(lm, copy)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(copy)))
    # host arrays (a checkpoint snapshotted by value) land on the device
    host = G.decode_params(lm, jax.tree.map(np.asarray, lm.params))
    assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(host))
    _equal(host, copy)


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_prefill_and_decode_logits_bit_identical(lm, layout):
    """Logits and caches of a prefill chunk and of six decode steps: the
    cast copy against the f32 tree, through ``decode_forward``, over the
    batcher's paged pools and over ``generate()``'s contiguous caches."""
    from deeplearning4j_tpu.serve.paged import build_pools

    cb = _batcher(lm)
    try:
        snap = cb.registry.current()
        copy = cb._params_for(snap)
        assert _float_dtypes(copy) == {"bfloat16"}
    finally:
        cb.shutdown()
    B, T = 2, 8
    if layout == "paged":
        pools = build_pools(lm, 17, 4, lm.dtype)
        tables = jnp.asarray(1 + np.arange(B * 8, dtype=np.int32)
                             ).reshape(B, 8)
        caches = {lk: {"k_pool": p["k"], "v_pool": p["v"], "tables": tables}
                  for lk, p in pools.items()}
    else:
        caches = G.init_caches(lm, B, 32, lm.dtype)
    fwd = jax.jit(lambda p, x, c, pos: G.decode_forward(
        lm, p, snap.state, x, c, pos))
    rng = np.random.RandomState(3)
    ids = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)
    lg_c, c_c = fwd(copy, ids, caches, zero)
    lg_f, c_f = fwd(snap.params, ids, caches, zero)
    assert lg_c.dtype == jnp.float32
    _equal(lg_c, lg_f)
    _equal(c_c, c_f)
    for step in range(6):
        tok = jnp.argmax(lg_f[:, -1], axis=-1)[:, None].astype(jnp.int32)
        pos = jnp.full((B,), T + step, jnp.int32)
        lg_c, c_c = fwd(copy, tok, c_c, pos)
        lg_f, c_f = fwd(snap.params, tok, c_f, pos)
        _equal(lg_c, lg_f)
        _equal(c_c, c_f)


def test_batcher_programs_bit_identical_to_f32_operands(lm):
    """The batcher's own compiled prefill, fed the copy, against the same
    function fed the registry's tree (the program every tick ran before)."""
    cb = _batcher(lm)
    try:
        snap = cb.registry.current()
        progs = cb._programs
        ids = np.zeros((1, 8), np.int32)
        ids[0, :5] = [7, 3, 11, 2, 9]
        outs = []
        for params in (cb._params_for(snap), snap.params):
            pools = jax.tree.map(jnp.copy, progs.pools)  # donated
            outs.append(progs._prefill_chunk(
                params, snap.state, jnp.asarray(ids), pools,
                jnp.asarray([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32),
                np.zeros((1,), np.int32), np.int32(5)))
        _equal(outs[0], outs[1])
    finally:
        cb.shutdown()


REQUESTS = [dict(temperature=0.0), dict(temperature=0.8, top_k=5),
            dict(temperature=1.0)]


def test_tokens_bit_identical_greedy_and_sampled(lm, monkeypatch):
    """Greedy and seeded sampled tokens from the batcher equal those of a
    batcher made to hand its programs the f32 tree."""
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, V, (n,)).astype(np.int32) for n in (5, 11, 3)]

    def run(cb):
        try:
            return [cb.generate(p, 9, **kw)
                    for p, kw in zip(prompts, REQUESTS)]
        finally:
            cb.shutdown()

    got = run(_batcher(lm))
    ref = _batcher(lm)
    monkeypatch.setattr(ref, "_params_for", lambda snap: snap.params)
    want = run(ref)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert len({tuple(o) for o in got}) > 1  # not one degenerate stream


# ------------------------------------------------- (b) once per generation
def _gen(cb, n=6):
    return cb.generate(np.asarray([4, 9, 1, 30, 2], np.int32), n,
                       temperature=0.0)


def test_one_cast_per_generation_none_on_the_worker(lm, monkeypatch):
    casts_on = []
    real = G.decode_params

    def spy(model, params):
        casts_on.append(threading.current_thread().name)
        return real(model, params)

    monkeypatch.setattr(G, "decode_params", spy)
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m, model_name="tiny")
    casts = m.counter("serve_params_cast_total", {"model": "tiny"})
    held = m.gauge("serve_params_compute_bytes", {"model": "tiny"})
    try:
        one_copy = held.value
        assert casts.value == 1 and one_copy > 0
        f32_bytes = sum(a.nbytes for a in jax.tree.leaves(lm.params))
        assert one_copy == f32_bytes / 2
        for _ in range(4):       # dozens of ticks and chunks
            _gen(cb, 12)
        assert casts.value == 1

        p2 = jax.tree.map(lambda a: a * 1.5, lm.params)
        cb.registry.publish(p2)
        assert casts.value == 2  # made before the flip, not by a tick
        after = _gen(cb)
        assert casts.value == 2
        assert held.value == one_copy, "the retired generation's copy stays"

        cb.registry.rollback()
        assert casts.value == 3  # a new generation, a new copy
        back = _gen(cb)
        assert casts.value == 3 and held.value == one_copy
        assert len(cb._copies) == 1
    finally:
        cb.shutdown()
    assert held.value == 0 and cb._copies == []
    assert not [t for t in casts_on if t.startswith("serve-continuous")], \
        casts_on

    # tokens after each flip are a fresh batcher's on those parameters
    fresh2 = _batcher(lm, params=p2)
    fresh1 = _batcher(lm)
    try:
        np.testing.assert_array_equal(after, _gen(fresh2))
        np.testing.assert_array_equal(back, _gen(fresh1))
    finally:
        fresh2.shutdown()
        fresh1.shutdown()


def test_idle_server_lets_go_of_the_retired_copy(lm):
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m)
    held = m.gauge("serve_params_compute_bytes")
    try:
        one_copy = held.value
        cb.registry.publish(jax.tree.map(lambda a: a * 0.5, lm.params))
        deadline = time.monotonic() + 10
        while held.value != one_copy and time.monotonic() < deadline:
            time.sleep(0.02)
        assert held.value == one_copy  # no request was sent
        assert cb._served[0] == 2
    finally:
        cb.shutdown()


def test_failed_publish_does_not_pile_up_copies(lm):
    """A publish that a LATER warmer aborts leaves its copy behind only
    until the next publish; the old generation keeps serving its own."""
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m)
    held = m.gauge("serve_params_compute_bytes")
    try:
        one_copy = held.value
        before = _gen(cb)
        boom = {"on": True}

        def later_warmer(params, state):
            if boom["on"]:
                raise RuntimeError("candidate refused")

        cb.registry.add_warmer(later_warmer)
        for _ in range(3):
            with pytest.raises(PublishError):
                cb.registry.publish(jax.tree.map(lambda a: a * 2, lm.params))
        assert held.value == 2 * one_copy  # the last failed one, not three
        np.testing.assert_array_equal(_gen(cb), before)
        boom["on"] = False
        cb.registry.publish(jax.tree.map(lambda a: a * 1.5, lm.params))
        _gen(cb)
        assert held.value == one_copy and len(cb._copies) == 1
    finally:
        cb.shutdown()


def test_publish_behind_the_warmers_back_still_serves_the_cast(lm):
    """A generation this batcher's warmer never saw (published while the
    batcher was being built) is cast at its first use, once."""
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m)
    casts = m.counter("serve_params_cast_total")
    try:
        cb.registry._warmers.clear()
        p2 = jax.tree.map(lambda a: a * 1.5, lm.params)
        cb.registry.publish(p2)
        out = _gen(cb)
        _gen(cb)
        assert casts.value == 2 and len(cb._copies) == 1
        assert _float_dtypes(cb._served[1]) == {"bfloat16"}
    finally:
        cb.shutdown()
    fresh = _batcher(lm, params=p2)
    try:
        np.testing.assert_array_equal(out, _gen(fresh))
    finally:
        fresh.shutdown()


def test_publishes_racing_ticks_keep_one_copy_per_generation(lm):
    """Three publishers and six clients against one worker, the interpreter
    switching threads every 10 us: every publish makes exactly one copy, no
    tick makes one, and when all is quiet one copy is left — the last
    generation's, serving what a fresh batcher serves."""
    import concurrent.futures as cf
    import sys

    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m, slots=4, queue_limit=256)
    casts = m.counter("serve_params_cast_total")
    held = m.gauge("serve_params_compute_bytes")
    one_copy = held.value
    trees = [jax.tree.map(lambda a, k=k: a * (1.0 + 0.1 * k), lm.params)
             for k in range(1, 4)]
    stop = threading.Event()

    def publisher(tree):
        for _ in range(4):
            cb.registry.publish(tree)
            time.sleep(0.01)
        return 4

    def client(i):
        n = 0
        while not stop.is_set():
            assert len(cb.generate(np.asarray([i, 3, 5], np.int32), 4,
                                   temperature=0.0)) == 4
            n += 1
        return n

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with cf.ThreadPoolExecutor(9) as ex:
            clients = [ex.submit(client, i) for i in range(6)]
            pubs = [ex.submit(publisher, t) for t in trees]
            published = sum(f.result(timeout=120) for f in pubs)
            stop.set()
            assert all(f.result(timeout=120) > 0 for f in clients)
        final = cb.registry.current()
        out = _gen(cb)
        assert casts.value == 1 + published
        assert held.value == one_copy and len(cb._copies) == 1
        assert cb._copies[0][0] is final.params
        assert cb._served[0] == final.generation == 1 + published
    finally:
        sys.setswitchinterval(interval)
        cb.shutdown()
    fresh = _batcher(lm, params=final.params)
    try:
        np.testing.assert_array_equal(out, _gen(fresh))
    finally:
        fresh.shutdown()


def test_shut_down_batcher_stops_casting(lm):
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m)
    casts = m.counter("serve_params_cast_total")
    cb.shutdown()
    cb.registry.publish(jax.tree.map(lambda a: a * 1.5, lm.params))
    assert casts.value == 1 and cb._copies == []


# ------------------------------------------------- (c) the executable set
def test_programs_take_compute_dtype_operands_and_warm_boot(lm, tmp_path):
    n_params = len(jax.tree.leaves(lm.params))
    outs = []
    for boot in ("cold", "warm"):
        m = MetricsRegistry()
        cb = _batcher(lm, metrics=m, aot_store=AotStore(tmp_path))
        try:
            misses = m.counter("serve_compile_misses_total",
                               {"component": "generate"})
            hits = m.counter("serve_aot_hits_total",
                             {"component": "generate"})
            at_boot = misses.value
            for tag in ("gen_decode_paged", "gen_prefill_chunk"):
                exes = cb.aot_functions()[tag].executables
                assert exes, tag
                for exe in exes.values():
                    # args are (params, state, ...): the parameters come
                    # first in the flattened operands
                    avals = jax.tree.leaves(exe.in_avals)[:n_params]
                    assert {str(a.dtype) for a in avals} == {"bfloat16"}, tag
            outs.append([_gen(cb), cb.generate(
                np.asarray([8, 8, 1], np.int32), 7, temperature=0.9,
                top_k=6)])
            # traffic and a hot-swap reuse the warmed set: nothing traced
            cb.registry.publish(jax.tree.map(lambda a: a * 1.5, lm.params))
            _gen(cb)
            assert misses.value == at_boot
            if boot == "warm":
                assert at_boot == 0 and hits.value > 0
            else:
                assert at_boot > 0
        finally:
            cb.shutdown()
    for c, w in zip(*outs):
        np.testing.assert_array_equal(c, w)


def test_strict_boot_from_a_prebuilt_store_serves(lm, tmp_path):
    """A store prebuilt by one batcher serves a strict replica: the keys it
    holds are the compute-dtype signatures the ticks ask for."""
    _batcher(lm, aot_store=AotStore(tmp_path)).shutdown()
    m = MetricsRegistry()
    cb = _batcher(lm, metrics=m, aot_store=AotStore(tmp_path),
                  strict_aot=True)
    try:
        assert len(_gen(cb)) == 6
        cb.registry.publish(jax.tree.map(lambda a: a * 1.5, lm.params))
        assert len(_gen(cb)) == 6
        assert m.counter("serve_aot_strict_misses_total",
                         {"component": "generate"}).value == 0
    finally:
        cb.shutdown()


# ------------------------------------------------- (d) no compute_dtype
def test_model_without_compute_dtype_makes_no_copy():
    model = _lm(compute_dtype=None)
    m = MetricsRegistry()
    cb = _batcher(model, metrics=m)
    try:
        snap = cb.registry.current()
        assert cb._params_for(snap) is snap.params
        assert G.decode_params(model, snap.params) is snap.params
        _gen(cb)
        cb.registry.publish(jax.tree.map(lambda a: a * 1.5, model.params))
        _gen(cb)
        assert m.counter("serve_params_cast_total").value == 0
        assert m.gauge("serve_params_compute_bytes").value == 0
        assert cb._copies == [] and cb.registry._warmers == []
    finally:
        cb.shutdown()
