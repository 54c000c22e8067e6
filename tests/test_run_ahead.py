"""The decode loop one step ahead of the host (ISSUE 41).

The worker enqueues decode step n+1 before it reads step n back; tokens,
positions and keys go from step to step on the device. Nothing of that may
change a token, and the few things the host now learns a step late must be
handled as ``serve/continuous.py`` says above ``_tick``:

(a) the same requests through the PARENT's order — kept here as a small loop
    over ``GenPrograms``: every step uploads its tokens, positions and keys
    from the host and is read back, keys too, before the next is enqueued;
    the first token is read at once — and through the batcher give equal
    tokens, greedy and sampled, for the three kinds of model the benchmark
    serves (one KV head; experts; two block groups);
(b) a request ended by ``eos_id`` gets exactly its tokens; the row the next
    step had for it already is counted and never pushed; what that row wrote
    changed no block ``_cache_answer`` cached, and a block released at the
    finish and handed to the next request holds that request's values;
(c) a request ended by ``max_new`` is given no row beyond its last;
(d) the order itself: the enqueue of step n+1 comes before the readback of
    step n, and a request that arrives while a step runs has its chunk
    enqueued before the step after it;
(e) a fork, a cancel, a publish and a restart, each with a step in flight.
"""

import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import generate
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.errors import ShedError, WorkerStallError
from deeplearning4j_tpu.serve.paged import FULL, WINDOW, SlotPages

BS, CHUNK = 4, 8


def _mqa():
    m = models.CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=32,
                        num_heads=4, num_kv_heads=1, vocab=64).build()
    m.init()
    return m


def _olmoe():
    m = models.OlmoeLM(seed=3, input_shape=(64,), num_layers=2, d_model=64,
                       num_heads=4, num_experts=8, top_k=2, expert_width=32,
                       vocab=64, dtype="bfloat16").build()
    m.init()
    return m


def _laguna():
    """Window 16 over blocks of 4: a ring of 7 columns beside the full
    group's table, a share of the experts held."""
    m = models.LagunaLM(seed=3, input_shape=(64,), num_layers=5, d_model=32,
                        full_heads=4, sliding_heads=6, num_kv_heads=2,
                        head_dim=8, window=16, dense_width=48, num_experts=8,
                        top_k=2, expert_width=16, shared_width=16,
                        full_rotary_dim=4, yarn_factor=4.0, yarn_original=32,
                        vocab=64).build()
    m.init()
    return m


MODELS = {"mqa": _mqa, "tiny-olmoe": _olmoe, "two-groups": _laguna}


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    return MODELS[request.param]()


@pytest.fixture(scope="module")
def mqa():
    return _mqa()


def _batcher(m, **kw):
    opts = dict(slots=3, capacity=64, block_size=BS, prefill_chunk=CHUNK,
                prefix_cache=False, seed=5, metrics=MetricsRegistry())
    opts.update(kw)
    return ContinuousBatcher(m, **opts)


def _count(cb, name, **labels):
    fam = cb.metrics.snapshot().get(name)
    if fam is None:
        return 0
    return int(sum(s.get("value", s.get("count", 0)) for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items())))


def _until(what, timeout=10.0):
    """A request's ``wait()`` returns at ITS last publish; the step enqueued
    behind that one is read a turn later."""
    end = time.monotonic() + timeout
    while not what() and time.monotonic() < end:
        time.sleep(0.002)
    return what()


def _requests(n=6, seed=1):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, 64, (int(t),)).astype(np.int32), int(k))
            for t, k in zip(rng.randint(2, 21, n), rng.randint(1, 14, n))]


# ------------------------------------------------------- (a) the parent's order
def parent_order(cb, requests, temperature, top_k):
    """What the parent's loop served, by hand over ``cb``'s programs and
    pools (its worker has been shut down): one request at a time in slot 0,
    admission number ``n`` as the batcher counts them."""
    progs, S, V = cb._programs, cb.slots, cb.vocab
    snap = cb.registry.current()
    params = cb._params_for(snap)
    outs = []
    for n, (prompt, max_new) in enumerate(requests, 1):
        pages = SlotPages(cb._alloc, cb.block_size)
        ring = cb._win.open(len(prompt) + max_new) if cb._win else None
        for off, true_len, bucket in cb._plan_chunks(len(prompt)):
            pages.ensure(off + true_len)
            cb._write_table_row(0, pages.blocks)
            if ring is not None:
                cb._ring_step(0, ring, off, off + true_len)
            last = progs.prefill_chunk(params, snap.state,
                                       prompt[off:off + true_len], bucket,
                                       cb._table_rows(0), off)
        key = jax.random.fold_in(cb._base_key, n)
        key, sub = jax.random.split(key)
        # the first token, read at once
        out = [int(np.asarray(progs.sample(last[0], sub, temperature,
                                           top_k or V)))]
        mask = np.zeros(S, bool)
        mask[0] = True
        toks, pos = np.zeros(S, np.int32), np.zeros(S, np.int32)
        keys = np.zeros((S, 2), np.uint32)
        pos[0], keys[0] = len(prompt), np.asarray(key, np.uint32)
        temps = np.full(S, temperature, np.float32)
        tks = np.full(S, top_k or V, np.int32)
        while len(out) < max_new:
            toks[0] = out[-1]
            pages.ensure(int(pos[0]) + 1)
            cb._write_table_row(0, pages.blocks)
            tables = np.where(mask[:, None], cb._tables_np, 0)
            if ring is not None:
                cb._ring_step(0, ring, int(pos[0]), int(pos[0]) + 1)
                tables = {FULL: tables, WINDOW: np.where(
                    mask[:, None], cb._win.tables_np, 0)}
            # every row's token, position and key from the host, every step
            nxt = progs.decode(params, snap.state, tables,
                               (mask, toks, pos, keys, temps, tks, {}))
            out.append(int(np.asarray(nxt)[0]))         # ... and read back,
            keys[0] = np.asarray(progs._carry[2])[0]    # the keys too
            pos[0] += 1
        pages.release()
        if ring is not None:
            cb._win.close(0, ring)
        outs.append(np.asarray(out, np.int32))
    return outs


@pytest.mark.parametrize("temperature,top_k", [(0.0, None), (0.8, 40)],
                         ids=["greedy", "t0.8-k40"])
def test_the_parents_order_and_the_loop_serve_the_same_tokens(
        model, temperature, top_k):
    requests = _requests()
    ref = _batcher(model)
    ref.shutdown()
    want = parent_order(ref, requests, temperature, top_k)
    cb = _batcher(model)
    try:
        reqs = [cb.submit(p, k, temperature=temperature, top_k=top_k)
                for p, k in requests]
        got = [r.wait() for r in reqs]
        steps = _count(cb, "serve_gen_ticks_total")
    finally:
        cb.shutdown()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    if temperature == 0.0:
        for (p, k), g in zip(requests, got):
            np.testing.assert_array_equal(
                g, generate(model, p[None], k, temperature=0.0)[0])
    # six requests on three slots shared steps: fewer steps than rows
    assert 0 < steps < sum(k - 1 for _, k in requests)
    assert _count(cb, "serve_gen_rows_discarded_total") == 0


# ------------------------------------------------------------- a slow device
class _Pending:
    """What ``GenPrograms.decode`` returns, made to behave like a step that
    takes ``step_s`` on the device: not ready until then, and a readback
    waits for it. Every question and readback is logged."""

    def __init__(self, arr, log, i, ready_at):
        self.arr, self.log, self.i, self.ready_at = arr, log, i, ready_at

    def is_ready(self):
        return time.perf_counter() >= self.ready_at and self.arr.is_ready()

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        self.log.append(("readback", self.i))
        return np.asarray(self.arr)


def _instrument(cb, step_s=0.0):
    """Log ``cb``'s enqueues (``("decode", i, rows)``, ``("chunk",)``) and
    readbacks in the order the worker makes them; with ``step_s`` the device
    is as slow as that (steps queue: one ends ``step_s`` after the later of
    its enqueue and the end of the step before it)."""
    log, progs = [], cb._programs
    real_decode, real_chunk = progs.decode, progs.prefill_chunk
    state = {"i": 0, "busy_until": 0.0}

    def decode(params, st, tables, fresh=None):
        full = tables[FULL] if isinstance(tables, dict) else tables
        state["i"] += 1
        log.append(("decode", state["i"],
                    tuple(int(s) for s in np.flatnonzero(full[:, 0]))))
        state["busy_until"] = max(state["busy_until"],
                                  time.perf_counter()) + step_s
        return _Pending(real_decode(params, st, tables, fresh), log,
                        state["i"], state["busy_until"])

    def chunk(*a):
        log.append(("chunk",))
        return real_chunk(*a)

    progs.decode, progs.prefill_chunk = decode, chunk
    return log


# ------------------------------------------------------------------ (b) eos_id
def _snapshot(pools):
    return {lk: {n: np.asarray(a) for n, a in pool.items()}
            for lk, pool in pools.items()}


def test_an_eos_hit_drops_the_row_in_flight_and_leaves_the_cache_as_it_was(
        mqa):
    prompt = np.random.RandomState(3).randint(1, 64, (9,)).astype(np.int32)
    plain = generate(mqa, prompt[None], 20, temperature=0.0)[0]
    # a token that first shows up in the middle of the answer
    at = next(i for i in range(4, 16) if plain[i] not in plain[:i])
    eos = int(plain[at])
    cb = _batcher(mqa, slots=2, prefix_cache=True)
    progs = cb._programs
    real = progs.decode
    before = {}         # step -> the pools as they were before its enqueue

    def decode(*a, **k):
        before[len(before) + 1] = _snapshot(progs.pools)
        return real(*a, **k)

    progs.decode = decode
    try:
        got = cb.submit(prompt, 20, temperature=0.0, eos_id=eos).wait()
        np.testing.assert_array_equal(got, plain[:at + 1])
        # `at` steps published tokens 2 .. at+1; one more was enqueued
        # before the host saw the eos_id, and its row was thrown away
        assert len(before) == at + 1
        assert _until(lambda: _count(cb, "serve_gen_ticks_total") == at + 1)
        assert _count(cb, "serve_gen_rows_discarded_total") == 1
        assert _count(cb, "serve_gen_tokens_total") == at + 1
        with cb._cond:
            cached = sorted(set(cb._prefix._runs.values()))
        assert len(cached) == (len(prompt) + at) // BS   # prompt ++ out[:-1]
        after = _snapshot(progs.pools)
        for lk, pool in after.items():
            for n, a in pool.items():
                np.testing.assert_array_equal(
                    a[cached], before[at + 1][lk][n][cached])
        # ... and it did write: the position behind the cached run
        wrote = [not np.array_equal(a, before[at + 1][lk][n])
                 for lk, pool in after.items() for n, a in pool.items()]
        assert all(wrote)
    finally:
        cb.shutdown()


def test_a_block_released_at_an_eos_hit_holds_its_next_owners_values(mqa):
    """A pool so small that the next request gets the very blocks the ended
    one released, the one its discarded row wrote included; a slow device,
    so that row is still queued when they change hands."""
    rng = np.random.RandomState(4)
    p1 = rng.randint(1, 64, (7,)).astype(np.int32)
    p2 = rng.randint(1, 64, (11,)).astype(np.int32)
    plain = generate(mqa, p1[None], 12, temperature=0.0)[0]
    at = next(i for i in range(2, 10) if plain[i] not in plain[:i])
    need = -(-(11 + 12) // BS)
    cb = _batcher(mqa, slots=2, kv_blocks=need + 1)     # + the trash block
    log = _instrument(cb, step_s=0.02)
    try:
        r1 = cb.submit(p1, 12, temperature=0.0, eos_id=int(plain[at]))
        r2 = cb.submit(p2, 12, temperature=0.0)     # waits for r1's blocks
        np.testing.assert_array_equal(r1.wait(), plain[:at + 1])
        np.testing.assert_array_equal(
            r2.wait(), generate(mqa, p2[None], 12, temperature=0.0)[0])
        assert _until(
            lambda: _count(cb, "serve_gen_rows_discarded_total") == 1)
        assert cb.kv_block_stats()["blocks_used"] == 0
    finally:
        cb.shutdown()
    assert ("chunk",) in log


# ----------------------------------------------------------------- (c) max_new
def test_a_request_ended_by_count_gets_no_row_beyond_its_last(model):
    requests = _requests(5, seed=2)
    cb = _batcher(model)
    log = _instrument(cb)
    try:
        reqs = [cb.submit(p, k, temperature=0.0) for p, k in requests]
        got = [r.wait() for r in reqs]
    finally:
        cb.shutdown()
    assert [len(g) for g in got] == [k for _, k in requests]
    rows = sum(len(e[2]) for e in log if e[0] == "decode")
    assert rows == sum(k - 1 for _, k in requests)    # the first is prefill's
    assert _count(cb, "serve_gen_rows_discarded_total") == 0
    assert _count(cb, "serve_gen_tokens_total") == sum(k for _, k in requests)


# ------------------------------------------------------------------- (d) order
def test_step_n_plus_1_is_enqueued_before_step_n_is_read(mqa):
    cb = _batcher(mqa, slots=2)
    log = _instrument(cb, step_s=0.01)
    try:
        rng = np.random.RandomState(6)
        reqs = [cb.submit(rng.randint(1, 64, (5,)).astype(np.int32), 16,
                          temperature=0.0) for _ in range(2)]
        for r in reqs:
            r.wait()
        ahead = _count(cb, "serve_gen_ticks_ahead_total")
        steps = _count(cb, "serve_gen_ticks_total")
    finally:
        cb.shutdown()
    where = {e[:2]: i for i, e in enumerate(log)}
    both = [e[1] for e in log if e[0] == "decode" and len(e[2]) == 2]
    assert len(both) >= 13
    for i in both[:-1]:
        # two slots decode in step i and in step i + 1: the device has the
        # second before the host reads the first
        assert where[("decode", i + 1)] < where[("readback", i)]
    # every step is read once, in order, and published once
    assert [e[1] for e in log if e[0] == "readback"] \
        == list(range(1, steps + 1))
    assert ahead >= len(both) - 2


def test_an_arrival_in_the_slack_has_its_chunk_in_front_of_the_next_step(mqa):
    """Steps of 60 ms; after a few the worker knows their length and waits
    out its slack before it enqueues the next. A request sent 10 ms into a
    step is admitted there and its chunk enqueued at once: behind the step
    that runs, in front of the one not enqueued yet."""
    cb = _batcher(mqa, slots=3)
    log = _instrument(cb, step_s=0.06)
    try:
        rng = np.random.RandomState(7)
        first = [cb.submit(rng.randint(1, 64, (5,)).astype(np.int32), 30,
                           temperature=0.0) for _ in range(2)]
        while sum(e[0] == "readback" for e in log) < 8:
            time.sleep(0.002)
        n_read = sum(e[0] == "readback" for e in log)
        while sum(e[0] == "readback" for e in log) == n_read:
            time.sleep(0.001)       # a step has just been read: the next runs
        time.sleep(0.01)
        mark = len(log)
        late = cb.submit(rng.randint(1, 64, (6,)).astype(np.int32), 4,
                         temperature=0.0)
        late.wait()
        for r in first:
            r.wait()
    finally:
        cb.shutdown()
    kinds = [e[0] for e in log[mark:]]
    # nothing was enqueued between the arrival and its chunk: the next step
    # was still being held back
    assert kinds.index("chunk") < kinds.index("decode")
    i_chunk = mark + kinds.index("chunk")
    enqueued = max(e[1] for e in log[:i_chunk] if e[0] == "decode")
    read = max(e[1] for e in log[:i_chunk] if e[0] == "readback")
    assert enqueued == read + 1     # one step running, none queued behind it
    # and the step after the chunk has the new slot's row
    nxt = next(e for e in log[i_chunk:] if e[0] == "decode")
    assert len(nxt[2]) == 3


# ------------------------------------------- (e) the rare, with a step in flight
def _ticks_are_published_once(cb):
    """One publish stamp a step: the clock's ticks, the step histogram's
    count and the readbacks agree."""
    snap = cb.metrics.snapshot()
    ticks = _count(cb, "serve_gen_ticks_total")
    assert snap["serve_gen_decode_seconds"]["series"][0]["count"] == ticks
    return ticks


def test_a_fork_mid_decode_waits_for_the_worker_and_copies_its_view(mqa):
    cb = _batcher(mqa, slots=2, kv_blocks=33)
    log = _instrument(cb, step_s=0.02)
    try:
        p = np.random.RandomState(8).randint(1, 64, (6,)).astype(np.int32)
        parent = cb.submit(p, 20, temperature=0.0)
        while len(parent.out) < 4:
            time.sleep(0.002)
        child = cb.fork(parent)
        out, cout = parent.wait(), child.wait()
        np.testing.assert_array_equal(
            out, generate(mqa, p[None], 20, temperature=0.0)[0])
        assert 1 <= len(cout) <= 16
        np.testing.assert_array_equal(cout, out[-len(cout):])
        at_pos = 6 + (20 - len(cout)) - 1
        st = cb.kv_block_stats()
        assert st["forks"] == 1
        assert st["cow_copies"] == (1 if at_pos % BS else 0)
        assert st["blocks_used"] == 0 and st["blocks_committed"] == 0
        assert st["blocks_shared"] == 0
        assert _count(cb, "serve_gen_rows_discarded_total") == 0
        assert _ticks_are_published_once(cb) \
            == sum(e[0] == "readback" for e in log)
    finally:
        cb.shutdown()


def test_a_cancel_mid_decode_frees_the_slot_and_drops_its_rows(mqa):
    cb = _batcher(mqa, slots=2)
    log = _instrument(cb, step_s=0.02)
    try:
        rng = np.random.RandomState(9)
        p1 = rng.randint(1, 64, (6,)).astype(np.int32)
        p2 = rng.randint(1, 64, (9,)).astype(np.int32)
        gone = cb.submit(p1, 40, temperature=0.0)
        stays = cb.submit(p2, 24, temperature=0.0)
        while len(gone.out) < 5:
            time.sleep(0.002)
        assert cb.cancel(gone)
        with pytest.raises(ShedError):
            gone.wait()
        n_gone = len(gone.out)
        np.testing.assert_array_equal(
            stays.wait(), generate(mqa, p2[None], 24, temperature=0.0)[0])
        assert len(gone.out) == n_gone < 40     # nothing pushed after the end
        np.testing.assert_array_equal(
            gone.out, generate(mqa, p1[None], n_gone, temperature=0.0)[0])
        # it had a row in the step that ran, perhaps in the one behind it
        assert _until(lambda: cb._clock._armed is False)    # all published
        assert 1 <= _count(cb, "serve_gen_rows_discarded_total") <= 2
        st = cb.kv_block_stats()
        assert st["blocks_used"] == 0 and st["blocks_committed"] == 0
        assert _ticks_are_published_once(cb) \
            == sum(e[0] == "readback" for e in log)
    finally:
        cb.shutdown()


def test_a_publish_mid_decode_drains_the_step_in_flight_first(mqa):
    """No step of the new params generation is enqueued behind one of the
    old: the worker reads the old one back first, its lease returns, and
    ``publish(drain=True)`` comes back."""
    cb = _batcher(mqa, slots=2, prefix_cache=True)
    log = _instrument(cb, step_s=0.02)
    try:
        p = np.random.RandomState(10).randint(1, 64, (6,)).astype(np.int32)
        req = cb.submit(p, 24, temperature=0.0)
        while len(req.out) < 5:
            time.sleep(0.002)
        snap = cb.registry.current()
        new = cb.registry.publish(snap.params, state=snap.state, drain=True,
                                  timeout=30)
        assert new.generation == snap.generation + 1
        at = len(req.out)
        np.testing.assert_array_equal(
            req.wait(), generate(mqa, p[None], 24, temperature=0.0)[0])
        assert 5 <= at < 24
        time.sleep(0.05)
        assert cb.registry.inflight() == {}
        # served under two generations: its answer is not cached
        assert _count(cb, "serve_prefix_answer_tokens_cached_total") == 0
        assert _count(cb, "serve_gen_rows_discarded_total") == 0
        assert _ticks_are_published_once(cb) == 23
    finally:
        cb.shutdown()
    # around the flip the device's queue was empty: a step was read back
    # with no step enqueued behind it
    reads = {e[1]: i for i, e in enumerate(log) if e[0] == "readback"}
    decodes = {e[1]: i for i, e in enumerate(log) if e[0] == "decode"}
    assert any(reads[i] < decodes[i + 1] for i in reads if i + 1 in decodes)


def test_a_restart_with_a_step_in_flight_drops_it_and_serves_on(mqa):
    cb = _batcher(mqa, slots=2, prefix_cache=True)
    log = _instrument(cb, step_s=0.05)
    try:
        p = np.random.RandomState(11).randint(1, 64, (9,)).astype(np.int32)
        req = cb.submit(p, 40, temperature=0.0)
        while len(req.out) < 6:
            time.sleep(0.002)
        assert cb.restart_worker("test")        # a step runs, one is queued
        with pytest.raises(WorkerStallError):
            req.wait()
        n = len(req.out)
        time.sleep(0.25)    # the staled worker's readback returns: dropped
        assert len(req.out) == n
        ticks = _ticks_are_published_once(cb)
        np.testing.assert_array_equal(
            cb.generate(p, 12, temperature=0.0),
            generate(mqa, p[None], 12, temperature=0.0)[0])
        assert _ticks_are_published_once(cb) == ticks + 11
        assert cb.registry.inflight() == {}
        st = cb.kv_block_stats()
        assert st["blocks_committed"] == 0 and st["blocks_shared"] == 0
        assert st["blocks_used"] == st["blocks_cached"]
    finally:
        cb.shutdown()


def test_a_first_token_reaches_its_first_step_without_the_host(mqa):
    """The sampler's scalar and the slot's key are handed to the slot's
    first decode step as device values (reading either back would wait for
    all the device has queued); the host reads the token when it is ready,
    and pushes it before the token that step makes."""
    cb = _batcher(mqa, slots=2)
    progs = cb._programs
    real = progs.decode
    firsts = []

    def decode(params, st, tables, fresh=None):
        if fresh is not None:
            firsts.append(dict(fresh[6]))
        return real(params, st, tables, fresh)

    progs.decode = decode
    try:
        p = np.random.RandomState(12).randint(1, 64, (5,)).astype(np.int32)
        req = cb.submit(p, 6, temperature=0.0)
        got = req.wait()
        np.testing.assert_array_equal(
            got, generate(mqa, p[None], 6, temperature=0.0)[0])
        assert req.pushed_ns == sorted(req.pushed_ns)
    finally:
        cb.shutdown()
    assert len(firsts) == 1 and list(firsts[0]) == [0]
    tok0, key = firsts[0][0]        # the sampler's scalar, the slot's key
    assert isinstance(tok0, jax.Array) and isinstance(key, jax.Array)
