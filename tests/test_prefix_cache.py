"""Tests for copy-on-write prefix caching + block-table forks (ISSUE 20).

The load-bearing properties, each tested directly:

- refcounted allocator: randomized alloc/retain/release sequences never
  double-free, never leak, never touch the trash block — checked against
  an independent host-side refcount mirror;
- prefix cache: rolling hashes commit to the whole run (a differing early
  block poisons every later hash); a generation flip invalidates
  wholesale; LRU entries whose only holder is the cache are reclaimed
  under pressure BEFORE anyone sheds, while adopted entries are left
  alone;
- admission charges only non-shared blocks: a cached-prefix request's
  worst-case commitment is visibly smaller than the uncached one;
- paged + cached greedy output stays BIT-identical to whole-batch dense
  ``nn.generation.generate``, hit/miss/saved counters move, and after a
  drain + cache flush every refcount returns to zero;
- ``fork()``: the child resumes the parent's exact decode state, returns
  exactly the parent's post-fork continuation at temperature 0, and the
  shared partial tail triggers exactly one copy-on-write block copy;
- the answer stays cached (ISSUE 35): a request that finishes normally
  leaves the whole blocks of ``prompt ++ out[:-1]`` in the cache, so the
  next turn of a session prefills its fresh tokens and nothing else; a
  request that saw two params generations, was aborted, shed or lost to a
  restart leaves nothing of its answer; a padded chunk never reaches past
  the capacity (what ROADMAP D12 was).
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.serve import (CapacityError, ContinuousBatcher,
                                      ServeError, ShedError)
from deeplearning4j_tpu.serve.paged import (BlockAllocator, PrefixCache,
                                            TRASH_BLOCK, blocks_needed,
                                            prefix_hashes)


@pytest.fixture(scope="module")
def lm():
    from deeplearning4j_tpu.models import CausalLM

    zm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                  num_heads=4, vocab=50)
    model = zm.build()
    model.init()
    return model


class TestAllocatorRefcounts:
    def test_randomized_retain_release_never_leaks_or_double_frees(self):
        """Property test: against an independent refcount mirror, random
        alloc/retain/release traffic keeps the allocator exactly
        consistent — no block is ever both free and live, the trash block
        never enters circulation, and full release drains to empty."""
        rng = np.random.RandomState(20)
        a = BlockAllocator(17)  # 16 usable
        mirror = {}  # block -> expected refcount
        for _ in range(400):
            op = rng.randint(3)
            if op == 0:  # alloc
                n = int(rng.randint(1, 4))
                if n <= a.available:
                    for b in a.alloc(n):
                        assert b != TRASH_BLOCK
                        assert b not in mirror  # never double-handed
                        mirror[b] = 1
            elif op == 1 and mirror:  # retain (prefix adoption / fork)
                b = int(rng.choice(list(mirror)))
                a.retain([b])
                mirror[b] += 1
            elif op == 2 and mirror:  # release one reference
                b = int(rng.choice(list(mirror)))
                a.release([b])
                mirror[b] -= 1
                if mirror[b] == 0:
                    del mirror[b]
            # invariants after every op
            assert a.used == len(mirror)
            assert a.available == a.usable - len(mirror)
            for b, c in mirror.items():
                assert a.refcount(b) == c
        # full drain: every outstanding reference released -> empty pool
        for b, c in list(mirror.items()):
            a.release([b] * c)
        assert a.used == 0 and a.available == a.usable

    def test_retain_free_block_and_trash_are_hard_errors(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        with pytest.raises(ValueError, match="trash"):
            a.retain([TRASH_BLOCK])
        with pytest.raises(ValueError, match="free block"):
            a.retain([b + 1])  # never allocated
        a.retain([b])
        a.release([b])
        a.release([b])  # second reference
        with pytest.raises(ValueError, match="double free"):
            a.release([b])

    def test_release_at_zero_returns_block_to_lifo_free_list(self):
        a = BlockAllocator(5)
        ids = a.alloc(2)
        a.retain([ids[0]])
        a.release(ids)  # ids[0] survives at refcount 1, ids[1] freed
        assert a.refcount(ids[0]) == 1 and a.refcount(ids[1]) == 0
        assert a.alloc(1) == [ids[1]]  # LIFO: freed block handed out next


class TestPrefixHashes:
    def test_hashes_commit_to_the_whole_run(self):
        toks = np.arange(12, dtype=np.int32)
        h = prefix_hashes(toks, 4)
        assert len(h) == 3
        # a differing FIRST block poisons every later hash: runs share an
        # entry only when everything before it matches too
        toks2 = toks.copy()
        toks2[0] += 1
        h2 = prefix_hashes(toks2, 4)
        assert all(x != y for x, y in zip(h, h2))
        # identical first block, differing second: prefix hash still shared
        toks3 = toks.copy()
        toks3[5] += 1
        h3 = prefix_hashes(toks3, 4)
        assert h3[0] == h[0] and h3[1] != h[1] and h3[2] != h[2]

    def test_partial_tail_never_hashed(self):
        assert len(prefix_hashes(np.arange(11, dtype=np.int32), 4)) == 2
        assert prefix_hashes(np.arange(3, dtype=np.int32), 4) == []


class TestPrefixCacheUnit:
    def test_generation_flip_invalidates_wholesale(self):
        a = BlockAllocator(8)
        pc = PrefixCache(a, 4)
        h = prefix_hashes(np.arange(8, dtype=np.int32), 4)
        blocks = a.alloc(2)
        pc.insert(h, blocks, generation=1)
        assert pc.match(h, 1, 2) == blocks
        # params flip: first new-generation lookup flushes the old entries
        assert pc.match(h, 2, 2) == []
        assert pc.flushes == 1 and len(pc) == 0
        a.release(blocks)  # owner retires; cache refs already dropped
        assert a.used == 0

    def test_match_is_pure_and_adopt_takes_references(self):
        a = BlockAllocator(8)
        pc = PrefixCache(a, 4)
        h = prefix_hashes(np.arange(12, dtype=np.int32), 4)
        blocks = a.alloc(3)
        pc.insert(h, blocks, generation=1)
        run = pc.match(h, 1, 2)  # limit caps adoption
        assert run == blocks[:2]
        assert all(a.refcount(b) == 2 for b in blocks)  # match took nothing
        pc.adopt(h, run)
        assert [a.refcount(b) for b in blocks] == [3, 3, 2]
        # a miss mid-run stops the match at the first absent hash
        h2 = prefix_hashes(np.r_[np.arange(4), 99, 5, 6, 7].astype(np.int32),
                           4)
        assert pc.match(h2, 1, 2) == blocks[:1]

    def test_lru_reclaim_frees_cache_only_entries_under_pressure(self):
        a = BlockAllocator(6)  # 5 usable
        pc = PrefixCache(a, 4)
        a.set_reclaimer(pc.reclaim)
        h = prefix_hashes(np.arange(12, dtype=np.int32), 4)
        blocks = a.alloc(3)
        pc.insert(h, blocks, generation=1)
        a.release(blocks)  # writer retires: cache is now the only holder
        assert a.available == 2
        # demand exceeds the free list -> the reclaimer evicts LRU cached
        # runs instead of shedding
        ids = a.alloc(4)
        assert len(ids) == 4 and pc.evictions == 2 and len(pc) == 1

    def test_reclaim_skips_entries_adopted_by_live_slots(self):
        a = BlockAllocator(6)
        pc = PrefixCache(a, 4)
        a.set_reclaimer(pc.reclaim)
        h = prefix_hashes(np.arange(12, dtype=np.int32), 4)
        blocks = a.alloc(3)
        pc.insert(h, blocks, generation=1)
        run = pc.match(h, 1, 2)
        pc.adopt(h, run)  # a live slot holds blocks[0:2]
        a.release(blocks)  # the writer retires
        # only blocks[2] is cache-only; evicting adopted entries would free
        # nothing, so the shortfall stays typed
        with pytest.raises(CapacityError):
            a.alloc(4)
        assert pc.evictions == 1 and len(pc) == 2
        assert a.alloc(3) is not None  # the reclaimed block is usable

    def test_insert_respects_max_blocks_with_lru_eviction(self):
        a = BlockAllocator(8)
        pc = PrefixCache(a, 4, max_blocks=2)
        h = prefix_hashes(np.arange(12, dtype=np.int32), 4)
        blocks = a.alloc(3)
        assert pc.insert(h, blocks, generation=1) == 3
        assert len(pc) == 2 and pc.evictions == 1
        # the LRU (first) entry was evicted: a fresh match starts cold
        assert pc.match(h, 1, 3) == []

    def test_insert_keeps_existing_entry_for_duplicate_hash(self):
        a = BlockAllocator(8)
        pc = PrefixCache(a, 4)
        h = prefix_hashes(np.arange(4, dtype=np.int32), 4)
        b1 = a.alloc(1)
        b2 = a.alloc(1)
        pc.insert(h, b1, generation=1)
        assert pc.insert(h, b2, generation=1) == 0  # newcomer stays private
        assert pc.match(h, 1, 1) == b1
        assert a.refcount(b2[0]) == 1  # no cache reference taken


class TestBatcherPrefixCache:
    def test_cached_prefix_hits_and_stays_bit_identical_to_dense(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        cb = ContinuousBatcher(lm, slots=2, capacity=16, block_size=4,
                               prefill_chunk=4, seed=0)
        try:
            p = np.random.RandomState(3).randint(0, 50, (8,)).astype(np.int32)
            want = generate(lm, p[None], 6, temperature=0.0)[0]
            o1 = cb.generate(p, 6, temperature=0.0)
            o2 = cb.generate(p, 6, temperature=0.0)  # adopts the cached run
            assert np.array_equal(o1, want) and np.array_equal(o2, want)
            stats = cb.kv_block_stats()
            px = stats["prefix_cache"]
            assert px["hits"] == 1 and px["misses"] == 1
            # both full prompt blocks, and the one the decode steps filled
            # (positions 8..11 of prompt ++ out[:-1] = 13 tokens)
            assert stats["blocks_cached"] == 3
            # hit adopted 1 block (adoption is capped at (tp-1)//bs so the
            # last real token still prefills): 4 prompt tokens skipped
            assert cb.metrics.counter(
                "serve_prefill_tokens_saved_total").value == 4
            # drain + flush returns every refcount to zero
            assert cb.flush_prefix_cache() == 3
            stats = cb.kv_block_stats()
            assert stats["blocks_used"] == 0 and stats["blocks_shared"] == 0
        finally:
            cb.shutdown()

    def test_admission_charges_only_unshared_blocks(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, block_size=4,
                               prefill_chunk=4, seed=0)
        try:
            p = np.random.RandomState(5).randint(0, 50, (8,)).astype(np.int32)
            cb.generate(p, 8, temperature=0.0)  # populates the cache
            full = blocks_needed(8 + 8, 4)  # uncached worst case: 4 blocks
            req = cb.submit(p, 8, temperature=0.0)
            seen = 0
            while not req.event.is_set():
                seen = max(seen, cb.kv_block_stats()["blocks_committed"])
                time.sleep(0)
            req.wait()
            # the cached-prefix request was charged strictly less than the
            # uncached worst case (1 adopted block rides the shared ledger)
            assert 0 < seen == full - 1
        finally:
            cb.shutdown()

    def test_generation_flip_flushes_batcher_cache(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        cb = ContinuousBatcher(lm, slots=1, capacity=16, block_size=4,
                               prefill_chunk=4, seed=0)
        try:
            p = np.random.RandomState(7).randint(0, 50, (8,)).astype(np.int32)
            want = generate(lm, p[None], 4, temperature=0.0)[0]
            assert np.array_equal(cb.generate(p, 4, temperature=0.0), want)
            snap = cb.registry.current()
            cb.registry.publish(snap.params, snap.state)  # same weights,
            # new generation: stale-generation KV must never be adopted
            assert np.array_equal(cb.generate(p, 4, temperature=0.0), want)
            px = cb.kv_block_stats()["prefix_cache"]
            assert px["hits"] == 0 and px["misses"] == 2
            assert px["flushes"] == 1
            assert px["generation"] == cb.registry.generation
        finally:
            cb.shutdown()

    def test_prefix_cache_off_keeps_legacy_shape(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        cb = ContinuousBatcher(lm, slots=1, capacity=16, block_size=4,
                               prefix_cache=False, seed=0)
        try:
            p = np.arange(1, 9, dtype=np.int32)
            want = generate(lm, p[None], 4, temperature=0.0)[0]
            assert np.array_equal(cb.generate(p, 4, temperature=0.0), want)
            assert np.array_equal(cb.generate(p, 4, temperature=0.0), want)
            stats = cb.kv_block_stats()
            assert "prefix_cache" not in stats
            assert stats["blocks_used"] == 0  # nothing retained
            assert cb.flush_prefix_cache() == 0
        finally:
            cb.shutdown()


@pytest.fixture(scope="module")
def lm64():
    from deeplearning4j_tpu.models import CausalLM

    model = CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=32,
                     num_heads=4, vocab=50).build()
    model.init()
    return model


def _count(cb, name):
    return cb.metrics.counter(name).value


def _slow(cb, what="decode", s=0.02):
    """Stretch every decode tick (or prefill chunk) so that the test thread
    can act between two of them."""
    real = getattr(cb._programs, what)

    def slow(*a, **k):
        time.sleep(s)
        return real(*a, **k)

    setattr(cb._programs, what, slow)


def _assert_only_the_cache_holds_blocks(cb):
    """No slot is live: every allocated block is a cached one at exactly
    the cache's one reference, and a flush empties the pool."""
    with cb._cond:
        assert cb._alloc._refs == {b: 1 for b in cb._prefix.blocks()}
        assert not cb._shared_ledger and cb._committed == 0
        assert (cb._tables_np == 0).all()
    cb.flush_prefix_cache()
    st = cb.kv_block_stats()
    assert st["blocks_used"] == 0 and st["blocks_shared"] == 0
    assert cb._alloc.available == cb._alloc.usable


class TestHashContinuation:
    def test_a_continued_state_gives_the_hashes_of_the_whole_run(self):
        import hashlib

        toks = np.random.RandomState(1).randint(0, 99, 43).astype(np.int32)
        whole = prefix_hashes(toks, 4)
        for cut in (0, 3, 4, 17, 40, 43):
            state = hashlib.sha256()
            head = prefix_hashes(toks[:cut], 4, state)
            # the state stands behind the head's last WHOLE block: the
            # head's partial tail is fed again with what follows it
            rest = prefix_hashes(toks[cut // 4 * 4:], 4, state)
            assert head + rest == whole


class TestAnswerStaysCached:
    BS = 4

    def _batcher(self, model, **kw):
        opts = dict(slots=2, capacity=64, block_size=self.BS,
                    prefill_chunk=4, seed=0)
        opts.update(kw)
        return ContinuousBatcher(model, **opts)

    @pytest.mark.parametrize("max_new", [4, 5, 7])
    def test_next_turn_adopts_the_answer_and_prefills_the_fresh_tokens(
            self, lm64, max_new):
        """9 prompt tokens + 4, 5, 7 answers: the last token FED sits 0, 1
        and ``block_size - 1`` past a block's end."""
        bs, tp = self.BS, 9
        cb = self._batcher(lm64)
        plain = self._batcher(lm64, prefix_cache=False)
        try:
            p = np.random.RandomState(3).randint(0, 50, tp).astype(np.int32)
            o1 = cb.generate(p, max_new, temperature=0.0)
            assert np.array_equal(o1, plain.generate(p, max_new,
                                                     temperature=0.0))
            n_end = (tp + max_new - 1) // bs
            assert (tp + max_new - 1) % bs == {4: 0, 5: 1, 7: bs - 1}[max_new]
            assert cb.kv_block_stats()["blocks_cached"] == n_end
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") \
                == (n_end - tp // bs) * bs
            # what is cached is prompt ++ out[:-1] and no block beyond: the
            # last sampled token was pushed, never fed, so its position
            # holds no KV — also where it would complete a block
            run = np.concatenate([p, o1])
            with cb._cond:
                assert list(cb._prefix._runs) \
                    == prefix_hashes(run[:-1], bs)
                assert prefix_hashes(run, bs)[n_end:] == [] \
                    or prefix_hashes(run, bs)[n_end] not in cb._prefix._runs
            # the next turn sends the answer back with 3 fresh tokens
            p2 = np.concatenate([run, [5, 6, 7]]).astype(np.int32)
            chunks0 = _count(cb, "serve_prefill_chunks_total")
            o2 = cb.generate(p2, 6, temperature=0.0)
            assert _count(cb, "serve_prefill_tokens_saved_total") == n_end * bs
            assert _count(cb, "serve_prefill_chunks_total") - chunks0 \
                == len(cb._plan_chunks(p2.shape[0], n_end * bs)) \
                == blocks_needed(p2.shape[0] - n_end * bs, 4)
            # KV a decode step wrote serves the next turn as a chunk's would
            assert np.array_equal(o2, plain.generate(p2, 6, temperature=0.0))
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()
            plain.shutdown()

    def test_eos_and_one_token_requests_cache_what_was_fed(self, lm64):
        cb = self._batcher(lm64)
        try:
            p = np.random.RandomState(5).randint(0, 50, 9).astype(np.int32)
            o = cb.generate(p, 12, temperature=0.0)
            cb.flush_prefix_cache()
            before = _count(cb, "serve_prefix_answer_tokens_cached_total")
            # stop at the token that first appears latest: 9 + n - 1
            # positions hold KV
            n = 1 + max(i for i in range(12) if o[i] not in o[:i])
            out = cb.generate(p, 12, temperature=0.0, eos_id=int(o[n - 1]))
            assert np.array_equal(out, o[:n]) and n >= 4
            assert cb.kv_block_stats()["blocks_cached"] == (9 + n - 1) // 4
            cb.flush_prefix_cache()
            # one token: sampled off the prefill, no decode tick, no answer
            cb.generate(p, 1, temperature=0.0)
            assert cb.kv_block_stats()["blocks_cached"] == 2
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") \
                - before == ((9 + n - 1) // 4 - 2) * 4
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    @pytest.mark.parametrize("after", [1, 4])
    def test_a_publish_before_the_last_tick_caches_nothing_of_the_answer(
            self, lm64, after):
        """A flip between prefill and the first tick's successors
        (``after`` = 1 token out) and one mid-decode (4 out): the answer's
        KV mixes two generations and retires with its slot."""
        cb = self._batcher(lm64)
        try:
            _slow(cb)
            p = np.random.RandomState(7).randint(0, 50, 9).astype(np.int32)
            req = cb.submit(p, 12, temperature=0.0)
            while len(req.out) < after:
                time.sleep(0.002)
            snap = cb.registry.current()
            cb.registry.publish(snap.params, snap.state)
            req.wait()
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") == 0
            # (the prompt's two blocks, of the old generation, go at the
            # next admission)
            assert cb.kv_block_stats()["blocks_cached"] == 2
            # served whole under the new generation, it is cached whole
            cb.generate(p, 12, temperature=0.0)
            st = cb.kv_block_stats()
            assert st["prefix_cache"]["flushes"] == 1
            assert st["blocks_cached"] == (9 + 12 - 1) // 4
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    def test_an_aborted_prefill_caches_nothing(self, lm64):
        cb = self._batcher(lm64)
        try:
            _slow(cb, "prefill_chunk")
            p = np.random.RandomState(9).randint(0, 50, 30).astype(np.int32)
            req = cb.submit(p, 8, temperature=0.0)
            while req.disp_t is None:      # its first chunk is dispatched
                time.sleep(0.002)
            assert cb.cancel(req)
            with pytest.raises(ShedError):
                req.wait()
            assert req.out == [] and cb.kv_block_stats()["blocks_cached"] == 0
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    def test_a_cancelled_decode_caches_what_it_wrote(self, lm64):
        cb = self._batcher(lm64)
        try:
            _slow(cb)
            p = np.random.RandomState(11).randint(0, 50, 9).astype(np.int32)
            req = cb.submit(p, 40, temperature=0.0)
            while len(req.out) < 9:
                time.sleep(0.002)
            assert cb.cancel(req)
            with pytest.raises(ShedError):
                req.wait()
            n = len(req.out)
            assert 9 <= n < 40
            run = np.concatenate([p, req.out]).astype(np.int32)
            with cb._cond:
                assert list(cb._prefix._runs) == prefix_hashes(run[:-1], 4)
            # and what it wrote is right: the rest of the answer follows
            o2 = cb.generate(run, 5, temperature=0.0)
            assert _count(cb, "serve_prefill_tokens_saved_total") \
                == (9 + n - 1) // 4 * 4
            from deeplearning4j_tpu.nn.generation import generate

            assert np.array_equal(
                o2, generate(lm64, run[None], 5, temperature=0.0)[0])
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    @pytest.mark.parametrize("how", ["restart", "shed"])
    def test_an_abandoned_slot_caches_nothing_of_the_answer(self, lm64, how):
        from deeplearning4j_tpu.serve.errors import (ServerClosingError,
                                                     WorkerStallError)

        cb = self._batcher(lm64)
        try:
            _slow(cb)
            p = np.random.RandomState(13).randint(0, 50, 9).astype(np.int32)
            req = cb.submit(p, 40, temperature=0.0)
            while len(req.out) < 9:
                time.sleep(0.002)
            if how == "restart":        # crash-only: the epoch stales the
                assert cb.restart_worker("test")   # tick in flight as well
                with pytest.raises(WorkerStallError):
                    req.wait()
            else:
                cb.shutdown(drain=False)
                with pytest.raises(ServerClosingError):
                    req.wait()
            time.sleep(0.1)             # the stale tick returns, drops its
            # bookkeeping at the epoch check, and caches nothing
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") == 0
            assert cb.kv_block_stats()["blocks_cached"] == 2   # the prompt's
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    def test_a_forks_child_is_left_out_and_its_parent_is_cached(self, lm64):
        """The child's tokens before the fork are not in its request, so its
        run is not hashed; the parent's run is its own and is cached."""
        cb = self._batcher(lm64)
        try:
            _slow(cb)
            p = np.random.RandomState(15).randint(0, 50, 9).astype(np.int32)
            parent = cb.submit(p, 20, temperature=0.0)
            while len(parent.out) < 6:
                time.sleep(0.002)
            child = cb.fork(parent)
            out, cout = parent.wait(), child.wait()
            assert child.cached_run is None and len(cout) >= 1
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") \
                == ((9 + 20 - 1) // 4 - 2) * 4
            with cb._cond:
                assert list(cb._prefix._runs) == prefix_hashes(
                    np.concatenate([p, out[:-1]]).astype(np.int32), 4)
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()

    def test_sessions_on_a_small_pool_never_write_a_cached_block(self, lm64):
        """Three sessions in lockstep rounds on a pool of exactly three full
        contexts, so the reclaimer runs: before every device call no block
        about to be WRITTEN is one the cache holds, every greedy answer is
        the plain one, and the pool drains."""
        from deeplearning4j_tpu.nn.generation import generate

        bs, cap = self.BS, 64
        cb = self._batcher(lm64, slots=3, kv_blocks=3 * cap // bs + 1)
        bad = []
        real_decode, real_chunk = cb._programs.decode, \
            cb._programs.prefill_chunk

        def cached():
            return set(cb._prefix._runs.values())

        def decode(params, state, tables, *rest):
            hold = cached()
            # the position a row of this step writes: the slot's published
            # position and the rows it has in flight, this one not counted
            pos = cb._pos + cb._unread - 1
            for s in range(tables.shape[0]):
                if tables[s, 0] and tables[s, pos[s] // bs] in hold:
                    bad.append(("decode", s, int(pos[s])))
            return real_decode(params, state, tables, *rest)

        def chunk(params, state, tokens, bucket, table_row, off):
            hold = cached()
            for b in range(off // bs, blocks_needed(off + len(tokens), bs)):
                if table_row[0, b] in hold:
                    bad.append(("chunk", off, b))
            return real_chunk(params, state, tokens, bucket, table_row, off)

        cb._programs.decode, cb._programs.prefill_chunk = decode, chunk
        try:
            rng = np.random.RandomState(17)
            bases = [rng.randint(0, 50, n).astype(np.int32)
                     for n in (9, 14, 11)]
            hist = list(bases)
            for _ in range(14):
                turns = []
                for i in range(3):
                    fresh = rng.randint(0, 50, rng.randint(2, 7))
                    n_out = int(rng.randint(3, 10))
                    if len(hist[i]) + len(fresh) + n_out > cap:
                        hist[i] = bases[i]        # the session starts over
                    prompt = np.concatenate([hist[i], fresh]).astype(np.int32)
                    turns.append((prompt, n_out,
                                  cb.submit(prompt, n_out, temperature=0.0)))
                for i, (prompt, n_out, req) in enumerate(turns):
                    out = req.wait()
                    assert np.array_equal(out, generate(
                        lm64, prompt[None], n_out, temperature=0.0)[0])
                    hist[i] = np.concatenate([prompt, out])
            assert bad == []
            st = cb.kv_block_stats()["prefix_cache"]
            assert st["evictions"] > 0 and st["hits"] > 30
            assert _count(cb, "serve_prefix_answer_tokens_cached_total") > 0
            _assert_only_the_cache_holds_blocks(cb)
        finally:
            cb.shutdown()


class TestPaddingStaysInsideTheCapacity:
    """ROADMAP D12, found by PR 22 and repaired in PR 35: behind a prefix hit
    a prompt's tail starts off the chunk grid, and its bucket reached past
    the capacity. A learned position table has no row there:
    ``decode_forward``'s ``jnp.take`` answers NaN, the NaN keys and values
    landed in the trash block, and every slot with an unallocated table
    entry read them at weight 0: a run of token 0, cached."""

    def test_plan_cuts_a_tail_whose_bucket_would_not_fit(self, lm64):
        cb = ContinuousBatcher(lm64, slots=1, capacity=64, block_size=4,
                               prefill_chunk=16, seed=0)
        try:
            assert cb._chunk_buckets == (8, 16)
            # on the grid nothing changes
            assert cb._plan_chunks(60) == [(0, 16, 16), (16, 16, 16),
                                           (32, 16, 16), (48, 12, 16)]
            assert cb._plan_chunks(41, 36) == [(36, 5, 8)]
            assert cb._plan_chunks(56, 44) == [(44, 12, 16)]    # ends at 60
            # 52 + 16 would reach 68: a whole bucket of 8, then the rest
            assert cb._plan_chunks(62, 36) == [(36, 16, 16), (52, 8, 8),
                                               (60, 2, 8)]
            for tp in range(2, 65):
                for start in range(0, tp, 4):
                    plan = cb._plan_chunks(tp, start)
                    assert plan[0][0] == start
                    assert sum(t for _, t, _ in plan) == tp - start
                    assert all(o2 == o1 + t1 for (o1, t1, _), (o2, _, _)
                               in zip(plan, plan[1:]))
                    assert all(t <= b and b in cb._chunk_buckets
                               for _, t, b in plan)
                    # what is left: blocks of 4 under a narrowest bucket
                    # of 8 let a tail of < 8 tokens start in the last 7
                    # positions (never with blocks >= the narrowest bucket)
                    assert all(o + b <= 64 or (t < 8 and o > 56)
                               for o, t, b in plan)
        finally:
            cb.shutdown()

    def test_a_tail_behind_a_hit_near_capacity_poisons_nobody(self):
        from deeplearning4j_tpu.models import CausalLM
        from deeplearning4j_tpu.nn.generation import generate

        m = CausalLM(seed=0, input_shape=(512,), num_layers=2, d_model=32,
                     num_heads=4, vocab=50).build()   # 512 learned positions
        m.init()
        cb = ContinuousBatcher(m, slots=2, capacity=512, block_size=16,
                               prefill_chunk=64, seed=0)
        try:
            rs = np.random.RandomState(3)
            p = rs.randint(0, 50, 464).astype(np.int32)
            cb.generate(p, 2, temperature=0.0)            # caches 29 blocks
            # the next turn: 40 tokens at offset 464 pad to 64 -> 528 > 512
            p2 = np.concatenate([p, rs.randint(0, 50, 40)]).astype(np.int32)
            assert np.array_equal(
                cb.generate(p2, 4, temperature=0.0),
                generate(m, p2[None], 4, temperature=0.0)[0])
            assert _count(cb, "serve_prefill_tokens_saved_total") == 464
            for pool in cb._programs.pools.values():
                for a in pool.values():       # nothing NaN in the trash block
                    assert np.isfinite(np.asarray(a[0])).all()
            # a short request gathers the trash block behind its own blocks
            p3 = rs.randint(0, 50, 10).astype(np.int32)
            assert np.array_equal(
                cb.generate(p3, 6, temperature=0.0),
                generate(m, p3[None], 6, temperature=0.0)[0])
        finally:
            cb.shutdown()


class TestFork:
    def test_fork_requires_paged_and_a_decoding_parent(self, lm):
        cb = ContinuousBatcher(lm, slots=2, capacity=16, block_size=4,
                               seed=0)
        try:
            req = cb.generate_request = cb.submit(
                np.arange(1, 5, dtype=np.int32), 2, temperature=0.0)
            req.wait()
            with pytest.raises(ServeError, match="decoding"):
                cb.fork(req)  # already finished
        finally:
            cb.shutdown()

    def test_fork_matches_parent_continuation_with_one_cow_copy(self, lm):
        """Greedy fork mid-decode: the child's output is exactly the
        parent's post-fork continuation, produced from the SAME physical
        prefix blocks, and the shared partial tail block is copied exactly
        once on first write (never the whole-block prefix)."""
        import jax

        cb = ContinuousBatcher(lm, slots=2, capacity=16, block_size=4,
                               kv_blocks=17, prefix_cache=False, seed=0)
        try:
            # warm every executable on the fork path so the retry loop
            # below races decode ticks, not XLA compilation
            cb.generate(np.arange(30, 36, dtype=np.int32), 2,
                        temperature=0.0)
            jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), 1),
                               2)
            # stretch each decode tick (dispatch runs OUTSIDE the batcher
            # lock) so the fork below reliably lands mid-decode
            orig_decode = cb._programs.decode

            def slow_decode(*a):
                time.sleep(0.02)
                return orig_decode(*a)

            cb._programs.decode = slow_decode
            p = np.random.RandomState(11).randint(0, 50, (6,)) \
                .astype(np.int32)
            req = cb.submit(p, 8, temperature=0.0)
            child = None
            while not req.event.is_set():
                try:
                    child = cb.fork(req)
                    break
                except ServeError:
                    time.sleep(0)  # still queued/prefilling — retry
            out = req.wait()
            assert len(out) == 8
            if child is None:
                pytest.skip("parent finished before a fork could land")
            cout = child.wait()
            # child returns ONLY post-fork tokens; greedy chains coincide,
            # so the child's output is exactly the parent's tail
            assert 1 <= len(cout) <= 8
            assert np.array_equal(cout, out[-len(cout):])
            stats = cb.kv_block_stats()
            assert stats["forks"] == 1
            # fork position is recoverable from the child's default
            # max_new budget: pos = len(prompt) + (8 - len(cout)) - 1.
            # An unaligned fork shares a partial tail -> exactly ONE
            # copy-on-write; a block-aligned fork shares only whole
            # blocks, which are never written again -> zero copies.
            pos_at_fork = 6 + (8 - len(cout)) - 1
            want_cow = 1 if pos_at_fork % 4 else 0
            assert stats["cow_copies"] == want_cow
            cb.flush_prefix_cache()
            assert cb.kv_block_stats()["blocks_used"] == 0
        finally:
            cb.shutdown()

    def test_fork_sheds_without_a_free_slot(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, block_size=4,
                               seed=0)
        try:
            req = cb.submit(np.arange(1, 7, dtype=np.int32), 8,
                            temperature=0.0)
            forked = False
            while not req.event.is_set() and not forked:
                try:
                    with pytest.raises(ShedError, match="no free"):
                        cb.fork(req)
                    forked = True
                except ServeError:
                    time.sleep(0)  # still queued/prefilling — retry
            req.wait()
            if not forked:
                pytest.skip("parent finished before the fork attempt")
        finally:
            cb.shutdown()
