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
  shared partial tail triggers exactly one copy-on-write block copy.
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
            assert stats["blocks_cached"] == 2  # both full prompt blocks
            # hit adopted 1 block (adoption is capped at (tp-1)//bs so the
            # last real token still prefills): 4 prompt tokens skipped
            assert cb.metrics.counter(
                "serve_prefill_tokens_saved_total").value == 4
            # drain + flush returns every refcount to zero
            assert cb.flush_prefix_cache() == 2
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
