"""The paged cache's groups (``serve/paged.py``, the layout contract in
``nn/generation.py``); at the end of the file the third, the STATE group of a
model whose layers keep a recurrent state (slots, not blocks), beside
``full`` and ``window``. First the two block groups: layers that state a cache window share a
ring table, pools of the ring's length and an allocator of their own beside
the full group's. A small Laguna (window 16, blocks of 4, chunks of 8: a ring
of 7 columns) through the units and through the batcher; and a model whose
layers state no window is laid out exactly as before."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import (Parts, cache_parts,
                                              causal_valid, generate,
                                              ring_blocks, ring_positions)
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.paged import (FULL, STATE, WINDOW,
                                            BlockAllocator, PrefixCache,
                                            RingPages, StateGroup,
                                            block_bytes, build_pools,
                                            cache_groups, prefix_hashes,
                                            state_slot_bytes)

W, BS, CHUNK = 16, 4, 8


def laguna(**kw):
    args = dict(seed=3, input_shape=(128,), num_layers=5, d_model=32,
                full_heads=4, sliding_heads=6, num_kv_heads=2, head_dim=8,
                window=W, dense_width=48, num_experts=8, top_k=2,
                expert_width=16, shared_width=16, full_rotary_dim=4,
                yarn_factor=4.0, yarn_original=32, vocab=64)
    args.update(kw)
    m = models.LagunaLM(**args).build()
    m.init()
    return m


def dense():
    m = models.CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=32,
                        num_heads=4, num_kv_heads=1, vocab=64,
                        window=8).build()
    m.init()
    return m


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 64, n).astype(np.int32)


def batcher(m, **kw):
    opts = dict(slots=2, capacity=128, block_size=BS, prefill_chunk=CHUNK,
                metrics=MetricsRegistry())
    opts.update(kw)
    return ContinuousBatcher(m, **opts)


def counter(cb, name, **labels):
    return sum(s["value"] for s in cb.metrics.snapshot()[name]["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


# ------------------------------------------------------------------ the spec
def test_layers_group_by_the_window_they_state():
    groups = cache_groups(laguna())
    assert [(g.name, g.window, g.layers) for g in groups] == [
        (FULL, None, ("layer_1", "layer_5")),
        (WINDOW, W, ("layer_2", "layer_3", "layer_4"))]
    # the ladder's band-masked attention states none: one group, as before
    m = dense()
    assert [(g.name, g.window) for g in cache_groups(m)] == [(FULL, None)]
    assert all(isinstance(p, Parts) and p.window is None
               for _, p in cache_parts(m))
    # pools follow the group's length, bytes are counted a group
    lm = laguna()
    pools = build_pools(lm, {FULL: 65, WINDOW: 15}, BS, lm.dtype)
    assert {lk: pool["k"].shape for lk, pool in pools.items()} == {
        "layer_1": (65, BS, 2, 8), "layer_5": (65, BS, 2, 8),
        "layer_2": (15, BS, 2, 8), "layer_3": (15, BS, 2, 8),
        "layer_4": (15, BS, 2, 8)}
    one_layer = BS * 2 * 2 * 8 * 4
    assert block_bytes(lm, BS, lm.dtype) == 5 * one_layer
    assert block_bytes(lm, BS, lm.dtype, groups[1].layers) == 3 * one_layer


def test_two_different_windows_are_refused():
    m = laguna()
    odd = m.layers[3].__class__(**{**m.layers[3].__dict__, "window": 8})
    m.layers[3] = odd
    with pytest.raises(ValueError, match="different windows"):
        cache_groups(m)


# ------------------------------------------------------------- the device
def test_ring_columns_and_positions():
    assert ring_blocks(512, 64, 16) == 37      # the published sizes
    assert ring_blocks(W, CHUNK, BS) == 7
    tables = jnp.zeros((2, 7), jnp.int32)
    # a decode step at positions 3 and 45: newest blocks 0 and 11
    kpos = np.asarray(ring_positions(tables, BS, jnp.asarray([3, 45]), 1))
    assert kpos.shape == (2, 28)
    blocks = kpos.reshape(2, 7, BS)[:, :, 0] // BS
    # column c holds the newest block b <= newest with b % 7 == c
    assert blocks[0].tolist() == [0, -6, -5, -4, -3, -2, -1]
    assert blocks[1].tolist() == [7, 8, 9, 10, 11, 5, 6]
    assert (kpos.reshape(2, 7, BS)[1, 4] == [44, 45, 46, 47]).all()
    # a chunk's newest block is that of its LAST (padded) position
    kpos = np.asarray(ring_positions(tables, BS, jnp.asarray([40]), 8))
    assert (kpos[0].reshape(7, BS)[:, 0] // BS).tolist() \
        == [7, 8, 9, 10, 11, 5, 6]


@pytest.mark.parametrize("pos,Tq", [(0, 1), (3, 1), (15, 1), (16, 1), (45, 1),
                                    (0, 8), (24, 8), (40, 5), (123, 1)])
def test_the_ring_mask_is_the_band_mask(pos, Tq):
    """What a query may see through the ring's columns is what the band mask
    over a cache kept at capacity lets it see: causal, within the window,
    and nothing of the previous lap."""
    kpos = ring_positions(jnp.zeros((1, 7), jnp.int32), BS,
                          jnp.asarray([pos]), Tq)
    ring = np.asarray(causal_valid(jnp.asarray([pos]), Tq, 28, W, kpos))[0]
    band = np.asarray(causal_valid(pos, Tq, 160, W))
    kpos = np.asarray(kpos)[0]
    for t in range(Tq):
        seen = sorted(int(kpos[c]) for c in range(28) if ring[t, c])
        assert seen == np.flatnonzero(band[t]).tolist()


# --------------------------------------------------------------- the host
def test_ring_pages_release_behind_the_window_and_map_by_column():
    alloc = BlockAllocator(12)
    ring = RingPages(alloc, BS, W, 7)
    ring.release_behind(0)
    new = ring.ensure(8)                     # a chunk of 8 at offset 0
    assert len(new) == 2 and ring.row().tolist() == new + [0] * 5
    for off in range(8, 64, 8):              # seven more chunks: 64 tokens
        released = ring.release_behind(off)
        ring.ensure(off + 8)
        # what lies wholly behind off - 15 is gone, nothing else
        assert sorted(ring.blocks) == list(range(max(0, (off - 15) // BS),
                                                 (off + 8) // BS))
        assert released == (2 if off >= 24 else 0)
        row = ring.row()
        for b, phys in ring.blocks.items():
            assert row[b % 7] == phys
        assert np.count_nonzero(row) == len(ring.blocks) <= 7
    assert alloc.used == len(ring.blocks) == 6
    # single steps: a block is released by the step whose window its LAST
    # position has left (position 70 still sees 55, the last of block 13)
    for pos in range(64, 71):
        ring.release_behind(pos)
        ring.ensure(pos + 1)
    assert min(ring.blocks) == 13 and max(ring.blocks) == 17
    assert ring.release_behind(71) == 1 and min(ring.blocks) == 14
    ring.release()
    assert alloc.used == 0 and alloc.available == 11


def test_a_ring_that_is_too_short_is_an_error_not_a_corruption():
    ring = RingPages(BlockAllocator(20), BS, W, 4)     # 4 < 7 columns
    with pytest.raises(ValueError, match="two in one column"):
        ring.ensure(24)
        ring.row()


def _cache_with_window(tail=4, cap=8):
    full, win = BlockAllocator(40), BlockAllocator(20)
    return full, win, PrefixCache(full, BS, window_allocator=win,
                                  window_tail=tail, window_max_blocks=cap)


def test_a_hit_is_whole_with_the_tail_and_shortened_without():
    full, win, cache = _cache_with_window()
    hashes = prefix_hashes(tokens(40, seed=1), BS)           # 10 blocks
    blocks = full.alloc(10)
    held = dict(zip(range(6, 10), win.alloc(4)))             # the ring's tail
    assert cache.insert(hashes, blocks, 1, held) == 10
    assert cache.stats()["window_entries"] == 4 and win.used == 4
    # the whole run: its tail [6, 10) is there
    run = cache.match(hashes, 1, 10)
    assert cache.match_window(hashes, len(run)) == (10, list(held.values()))
    # a prompt that leaves the run after 8 blocks needs [4, 8): not held
    assert cache.match_window(hashes, 8) == (0, [])
    # a later insert of an earlier tail makes a shorter hit usable
    early = dict(zip(range(1, 5), win.alloc(4)))
    cache.insert(hashes, blocks, 1, early)
    assert cache.match_window(hashes, 8) == (5, [early[b] for b in (1, 2, 3, 4)])
    assert cache.match_window(hashes, 4) == (0, [])    # block 0 never cached
    # adoption takes a reference in BOTH allocators
    n, tail = cache.match_window(hashes, 10)
    cache.adopt(hashes, run[:n], tail)
    # (the writer's own, the cache's, and now the adopter's)
    assert all(full.refcount(b) == 3 for b in run)
    assert all(win.refcount(b) == 3 for b in tail)
    assert all(win.refcount(b) == 2 for b in early.values())
    # a longer prompt's tail replaces the run's older one at once
    longer = prefix_hashes(np.concatenate([tokens(40, seed=1),
                                           tokens(24, seed=9)]), BS)
    assert longer[:10] == hashes
    more = full.alloc(6)
    late = dict(zip(range(12, 16), win.alloc(4)))
    cache.insert(longer, blocks + more, 1, late)
    assert cache.match_window(longer, 16) == (16, list(late.values()))
    assert cache.match_window(longer, 10) == (0, [])      # [6, 10) went
    # the cache's references on the older tails are gone (the writer's and
    # the adopter's stay)
    assert all(win.refcount(b) == 1 for b in early.values())
    assert all(win.refcount(b) == 2 for b in held.values())
    assert cache.stats()["window_entries"] == 4
    # fewer blocks than the tail: all of them must be there
    short = prefix_hashes(tokens(12, seed=2), BS)
    b3, w3 = full.alloc(3), win.alloc(3)
    cache.insert(short, b3, 1, dict(enumerate(w3)))
    assert cache.match_window(short, 3) == (3, w3)
    assert cache.match_window(short, 2) == (2, w3[:2])


def test_cached_window_blocks_are_bounded_reclaimed_and_flushed():
    full, win, cache = _cache_with_window(tail=2, cap=3)
    hashes = prefix_hashes(tokens(24, seed=3), BS)           # 6 blocks
    blocks, wblocks = full.alloc(6), win.alloc(6)
    cache.insert(hashes, blocks, 1, dict(enumerate(wblocks)))
    assert cache.stats()["window_entries"] == 3              # the LRU's bound
    win.release(wblocks)                    # the slot retires: the cache's
    assert win.used == 3                    # references keep the last three
    assert cache.reclaim_window(2) == 2 and win.used == 1
    assert cache.match(hashes, 1, 6) == blocks       # full entries stayed
    assert cache.match_window(hashes, 6) == (0, [])
    # an entry evicted from the full group takes its window block along
    full.release(blocks)
    assert cache.reclaim(6) == 6 and win.used == 0
    # a generation flip drops both
    cache.insert(hashes, full.alloc(6), 2, {5: win.alloc(1)[0]})
    cache.match(hashes, 3, 6)
    assert cache.stats()["window_entries"] == 0 and len(cache) == 0


# ------------------------------------------------------------- the batcher
def test_a_one_group_model_is_laid_out_as_before():
    """Pools of ``kv_blocks`` for every layer, ONE table array ``(slots,
    max_blocks)``, and ``signatures()`` with arrays where a grouped model has
    dictionaries (the lowered text is pinned in test_programs_unchanged)."""
    cb = batcher(dense(), capacity=64)
    try:
        assert cb._win is None and cb._programs.table_blocks == 16
        assert {a.shape[0] for pool in cb._programs.pools.values()
                for a in pool.values()} == {2 * 16 + 1}
        snap = cb.registry.current()
        sigs = cb._programs.signatures(cb._params_for(snap), snap.state)
        (decode,) = sigs["gen_decode_paged"]
        assert decode[4].shape == (2, 16) and decode[4].dtype == np.int32
        assert [ops[4].shape for ops in sigs["gen_prefill_chunk"]] \
            == [(1, 16)] * len(cb._chunk_buckets)
        assert set(cb.metrics.snapshot()["serve_kv_group_blocks_used"]
                   ["series"][0]["labels"].values()) == {FULL}
        assert "serve_kv_window_released_total" not in cb.metrics.snapshot()
        out = cb.generate(tokens(20), 12, temperature=0.0)
        np.testing.assert_array_equal(
            out, generate(cb.model, tokens(20)[None], 12, temperature=0.0)[0])
        assert "window_group" not in cb.kv_block_stats()
    finally:
        cb.shutdown()


def test_a_grouped_model_has_a_table_a_group_and_pools_of_the_rings_length():
    cb = batcher(laguna())
    try:
        win = cb._win
        assert (win.columns, win.tail, win.window) == (7, 4, W)
        # 2 slots x 7 columns + 2 cached tails of 4 + the trash block
        assert win.alloc.num_blocks == 2 * 7 + 2 * 4 + 1
        shapes = {lk: pool["k"].shape[0]
                  for lk, pool in cb._programs.pools.items()}
        assert shapes == {"layer_1": 65, "layer_5": 65, "layer_2": 23,
                          "layer_3": 23, "layer_4": 23}
        snap = cb.registry.current()
        (decode,) = cb._programs.signatures(cb._params_for(snap),
                                            snap.state)["gen_decode_paged"]
        assert {g: t.shape for g, t in decode[4].items()} \
            == {FULL: (2, 32), WINDOW: (2, 7)}
        # the gauges: one a group, and the unlabelled ones their sum
        bytes_a_block = BS * 2 * 2 * 8 * 4
        assert counter(cb, "serve_kv_token_bytes") == 5 * bytes_a_block // BS
    finally:
        cb.shutdown()


def test_window_blocks_are_released_and_both_allocators_drain():
    """Contexts several windows long on both slots: the window group never
    holds more than a ring a slot plus the cached tails while the full group
    grows with the context; everything returns when the slots retire and the
    prefix cache is flushed."""
    m = laguna()
    cb = batcher(m)
    try:
        prompts = [tokens(70, seed=1), tokens(45, seed=2), tokens(9, seed=3)]
        reqs = [cb.submit(p, 40, temperature=0.0) for p in prompts]
        peak_win = peak_full = 0
        while not all(r.event.is_set() for r in reqs):
            st = cb.kv_block_stats()
            peak_win = max(peak_win, st["window_group"]["blocks_used"])
            peak_full = max(peak_full, st["blocks_used"])
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(
                r.wait(), generate(m, p[None], 40, temperature=0.0)[0])
        assert 0 < peak_win <= 2 * 7 + 2 * 4
        assert peak_full > 2 * 7 + 2 * 4        # (70 + 40 + 45 + 40) / 4
        released = counter(cb, "serve_kv_window_released_total")
        allocated = counter(cb, "serve_kv_window_allocated_total")
        assert 0 < released < allocated
        assert counter(cb, "serve_kv_live_bytes") == (
            counter(cb, "serve_kv_group_live_bytes", group=FULL)
            + counter(cb, "serve_kv_group_live_bytes", group=WINDOW))
        st = cb.kv_block_stats()
        assert st["blocks_committed"] == 0
        assert st["window_group"]["blocks_committed"] == 0
        # what is still used is the prefix cache's, in both groups
        assert st["blocks_used"] == st["blocks_cached"] > 0
        assert st["window_group"]["blocks_used"] \
            == st["prefix_cache"]["window_entries"] > 0
        cb.flush_prefix_cache()
        st = cb.kv_block_stats()
        assert st["blocks_used"] == 0 == st["window_group"]["blocks_used"]
        assert cb._alloc.available == cb._alloc.usable
        assert cb._win.alloc.available == cb._win.alloc.usable
        assert (cb._tables_np == 0).all() and (cb._win.tables_np == 0).all()
    finally:
        cb.shutdown()


def test_a_shared_window_block_is_never_written_when_the_ring_laps():
    """A request adopts a cached prefix with its window tail and decodes far
    enough for its ring to lap several times (7 columns x 4 = 28 positions;
    60 tokens): the cached blocks' contents are bit for bit what they were,
    and a second request over the same prefix still reads them right."""
    m = laguna()
    cb = batcher(m)
    try:
        base = tokens(41, seed=4)
        cb.generate(base, 2, temperature=0.0)            # caches 10 blocks
        cached = sorted(cb._prefix._wruns.values())
        assert len(cached) == 4

        def contents():
            return {lk: {n: np.asarray(a[np.asarray(cached)])
                         for n, a in cb._programs.pools[lk].items()}
                    for lk in cb._win.layers}

        before = contents()
        # held here too, so that none is handed out again once the cache lets
        # go of it: whatever changes in them is a write through a shared block
        with cb._cond:
            cb._win.alloc.retain(cached)
        longer = np.concatenate([base, tokens(6, seed=5)])
        out = cb.generate(longer, 60, temperature=0.0)
        assert counter(cb, "serve_prefix_cache_hits_total") == 1
        np.testing.assert_array_equal(
            out, generate(m, longer[None], 60, temperature=0.0)[0])
        after = contents()
        for lk in before:
            for n in before[lk]:
                np.testing.assert_array_equal(before[lk][n], after[lk][n])
        # (the longer prompt's tail replaced the run's older one in the
        # cache, and the answer's tail that one when the request finished:
        # what the window group caches now is the tail behind the 26 whole
        # blocks of prompt and answer)
        with cb._cond:
            assert not set(cached) & set(cb._prefix._wruns.values())
            assert len(cb._prefix._wruns) == 4
            cb._win.alloc.release(cached)
        # the next turn reads prompt and answer out of the cache and is right
        again = np.concatenate([longer, out, tokens(3, seed=6)])
        np.testing.assert_array_equal(
            cb.generate(again, 8, temperature=0.0),
            generate(m, again[None], 8, temperature=0.0)[0])
        assert counter(cb, "serve_prefix_cache_hits_total") == 2
        assert counter(cb, "serve_prefix_hits_shortened_total") == 0
        assert counter(cb, "serve_prefill_tokens_saved_total") \
            == 40 + (47 + 60 - 1) // BS * BS
    finally:
        cb.shutdown()


def test_a_hit_whose_tail_is_gone_is_shortened_and_still_right():
    m = laguna()
    cb = batcher(m)
    try:
        base = tokens(41, seed=7)
        cb.generate(base, 2, temperature=0.0)
        # leaves the cached run after 24 tokens: blocks [2, 6) would be
        # needed and the ring had let go of them
        fork = np.concatenate([base[:24], tokens(9, seed=8)])
        np.testing.assert_array_equal(
            cb.generate(fork, 10, temperature=0.0),
            generate(m, fork[None], 10, temperature=0.0)[0])
        assert counter(cb, "serve_prefix_hits_shortened_total") == 1
        assert counter(cb, "serve_prefix_cache_hits_total") == 0
        assert counter(cb, "serve_prefill_tokens_saved_total") == 0
    finally:
        cb.shutdown()


def test_a_fork_shares_the_ring_and_copies_on_write_a_group():
    import time

    m = laguna()
    cb = batcher(m)
    try:
        real = cb._programs.decode

        def slow(*a, **k):             # or the parent finishes before a fork
            time.sleep(0.02)
            return real(*a, **k)

        cb._programs.decode = slow
        prompt = tokens(30, seed=9)
        parent = cb.submit(prompt, 40, temperature=0.0)
        while len(parent.out) < 4:
            time.sleep(0.005)
        # the worker makes the copy between two turns, a step or two on
        child = cb.fork(parent)
        at = len(parent.out)
        got_parent, got_child = parent.wait(), child.wait()
        want = generate(m, prompt[None], 40, temperature=0.0)[0]
        np.testing.assert_array_equal(got_parent, want)
        # the child resumes from the parent's state: the same greedy chain
        n = len(got_child)
        assert n >= 40 - at - 1
        np.testing.assert_array_equal(got_child, want[40 - n:][:n]
                                      if n <= 40 else want)
        # the fork's position, from the child's default budget; where it
        # falls on a block's edge only whole blocks are shared and nothing
        # is ever copied
        at_pos = 30 + (40 - n) - 1
        assert counter(cb, "serve_kv_cow_copies_total") \
            >= (2 if at_pos % BS else 0)                  # one a group
        cb.flush_prefix_cache()
        assert cb._win.alloc.used == 0 and cb._alloc.used == 0
        assert cb._win.committed == 0
    finally:
        cb.shutdown()


# ------------------------------------------- the answer's tail (ISSUE 35)
def test_the_ring_holds_the_tail_behind_a_run_at_every_position_of_a_lap():
    """At the published sizes (window 512, blocks of 16, chunks of 64: 37
    columns, a tail of 32 blocks): after the step that writes position
    ``pos``, a finishing request has ``n_end = (pos + 1) // 16`` whole
    blocks, and a hit on them needs blocks ``n_end - 32 .. n_end - 1``.
    ``release_behind`` keeps every block the query at ``pos`` can see, and
    block ``n_end - 32`` ends at most 495 positions behind it: the ring
    holds them all, at every position of more than a lap (37 x 16 = 592),
    behind a prompt on and off the chunk grid."""
    bs, window, chunk = 16, 512, 64
    columns = ring_blocks(window, chunk, bs)
    tail = -(-(window - 1) // bs)
    assert (columns, tail) == (37, 32)
    for tp in (1, 40, 700, 1000, 1017):
        alloc = BlockAllocator(2 * columns + 1)
        ring = RingPages(alloc, bs, window, columns)
        for off in range(0, tp, chunk):                 # the prompt's chunks
            ring.release_behind(off)
            ring.ensure(min(tp, off + chunk))
        for pos in range(tp, tp + 700):                 # one decode step each
            ring.release_behind(pos)
            ring.ensure(pos + 1)
            ring.row()                                  # no two in a column
            n_end = (pos + 1) // bs
            assert all(b in ring.blocks
                       for b in range(max(0, n_end - tail), n_end)), (tp, pos)


def test_the_answers_tail_is_held_when_a_request_finishes():
    """The same through the batcher at the small sizes (a ring of 7 columns
    of 4, a tail of 4): requests that end at every position of a lap; what
    ``_cache_answer`` hands the cache is the whole tail each time."""
    m = laguna()
    cb = batcher(m)
    seen = []
    real = cb._ring_tail

    def ring_tail(ring, n):
        held = real(ring, n)
        seen.append((n, sorted(held)))
        return held

    cb._ring_tail = ring_tail
    try:
        prompt = tokens(30, seed=11)
        for n_out in range(3, 33):                      # 28 positions a lap
            cb.flush_prefix_cache()
            del seen[:]
            cb.generate(prompt, n_out, temperature=0.0)
            (n_prompt, held_prompt), (n_end, held) = seen
            assert n_prompt == 30 // BS
            assert n_end == (30 + n_out - 1) // BS
            assert held == list(range(n_end - 4, n_end))
        assert counter(cb, "serve_prefix_answer_tokens_cached_total") > 0
    finally:
        cb.shutdown()


def test_the_next_turn_hits_whole_behind_the_answer_and_is_right():
    """Turn after turn a session sends its answer back: every hit covers
    prompt and answer (never shortened), the tokens are the uncached ones,
    and the window LRU holds ONE tail for the session whatever its length
    (a longer run's tail replaces the older one)."""
    m = laguna()
    cb = batcher(m)
    try:
        history = tokens(21, seed=12)
        saved = 0
        for turn in range(6):
            prompt = np.concatenate([history, tokens(3 + turn, seed=20 + turn)])
            n_out = 9 + turn
            out = cb.generate(prompt, n_out, temperature=0.0)
            np.testing.assert_array_equal(
                out, generate(m, prompt[None], n_out, temperature=0.0)[0])
            if turn:
                # everything the last turn fed: its prompt and answer but
                # the token it sampled last
                saved += (len(history) - 1) // BS * BS
            assert counter(cb, "serve_prefill_tokens_saved_total") == saved
            assert counter(cb, "serve_prefix_cache_hits_total") == turn
            assert counter(cb, "serve_prefix_hits_shortened_total") == 0
            st = cb.kv_block_stats()
            assert st["prefix_cache"]["window_entries"] == 4       # one tail
            assert st["blocks_cached"] == (len(prompt) + n_out - 1) // BS
            history = np.concatenate([prompt, out])
        assert len(history) > 100       # the ring lapped several times
        cb.flush_prefix_cache()
        assert cb._win.alloc.used == 0 and cb._alloc.used == 0
        assert cb._win.committed == 0
    finally:
        cb.shutdown()


def test_two_sessions_keep_a_tail_each():
    m = laguna()
    cb = batcher(m)
    try:
        hist = [tokens(26, seed=31), tokens(19, seed=32)]
        for turn in range(4):
            prompts = [np.concatenate([h, tokens(4, seed=40 + turn + 7 * i)])
                       for i, h in enumerate(hist)]
            reqs = [cb.submit(p, 10, temperature=0.0) for p in prompts]
            for i, (p, r) in enumerate(zip(prompts, reqs)):
                out = r.wait()
                np.testing.assert_array_equal(
                    out, generate(m, p[None], 10, temperature=0.0)[0])
                hist[i] = np.concatenate([p, out])
            assert cb.kv_block_stats()["prefix_cache"]["window_entries"] == 8
        assert counter(cb, "serve_prefix_cache_hits_total") == 6
        assert counter(cb, "serve_prefix_hits_shortened_total") == 0
    finally:
        cb.shutdown()


# ------------------------------------------------------- the state group
def sala(**kw):
    args = dict(seed=3, input_shape=(128,), num_layers=3, published_layers=3,
                mixer_types=["minicpm4", "lightning-attn", "lightning-attn"],
                d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
                lightning_heads=4, ffn_width=48, dim_model_base=8,
                sparse=dict(kernel_size=8, kernel_stride=BS, block_size=8,
                            topk=3, init_blocks=1, window_size=8,
                            dense_len=24), vocab=64)
    args.update(kw)
    m = models.MiniCpmSalaLM(**args).build()
    m.init()
    return m


def test_state_layers_form_a_third_group_of_slots_not_blocks():
    m = sala()
    groups = cache_groups(m)
    assert [(g.name, g.window, g.layers) for g in groups] == [
        (FULL, None, ("layer_1",)), (STATE, None, ("layer_2", "layer_3"))]
    parts = dict(cache_parts(m))
    assert parts["layer_2"].state == {"state": "float32"}
    assert parts["layer_1"].strides == {"kpool": BS}
    # blocks hold the per-token parts (k, v: 2 x 8 x 4 B a token) and one
    # pooled key a block; a state is no block's
    assert block_bytes(m, BS, "float32") == BS * 2 * 64 + 64
    assert block_bytes(m, BS, "float32", groups[0].layers) \
        == block_bytes(m, BS, "float32")
    assert state_slot_bytes(m) == 2 * 4 * 8 * 8 * 4
    pools = build_pools(m, 9, BS, "float32", state_rows=(2, 3))
    assert {n: a.shape for n, a in pools["layer_1"].items()} == {
        "k": (9, BS, 2, 8), "v": (9, BS, 2, 8), "kpool": (9, 2, 8)}
    assert {n: (a.shape, str(a.dtype)) for n, a in pools["layer_2"].items()} \
        == {"state": ((2, 4, 8, 8), "float32"),
            "state_snap": ((5, 4, 8, 8), "float32")}
    with pytest.raises(ValueError, match="state_rows"):
        build_pools(m, 9, BS, "float32")
    with pytest.raises(ValueError, match="block size must be 4"):
        build_pools(m, 9, 8, "float32", state_rows=(2, 3))
    # the models above have no such group, and are laid out as before
    assert [g.name for g in cache_groups(laguna())] == [FULL, WINDOW]
    assert [g.name for g in cache_groups(dense())] == [FULL]


def test_batcher_counts_the_state_group_and_serves_through_it():
    m = sala()
    cb = batcher(m, slots=2, capacity=96)
    try:
        assert isinstance(cb._state, StateGroup) and cb._win is None
        assert cb._state.snapshots == 4 and cb._state.slots == 2
        shapes = {n: a.shape for n, a in cb._programs.pools["layer_3"].items()}
        assert shapes == {"state": (2, 4, 8, 8), "state_snap": (6, 4, 8, 8)}
        prompts = np.stack([tokens(40, seed=s) for s in (1, 2)])
        want = generate(m, prompts, 12, temperature=0.0, capacity=64)
        assert np.array_equal(cb.generate(prompts, 12, temperature=0.0), want)
        stats = cb.kv_block_stats()
        # both requests left a snapshot at their prompt's end and one at
        # their answer's; the gauges count them in the state group
        assert stats["state_group"]["snapshots_used"] == 4
        assert counter(cb, "serve_kv_group_live_bytes", group=STATE) \
            == 4 * state_slot_bytes(m)
        assert counter(cb, "serve_kv_live_bytes") == stats["live_bytes"] \
            + 4 * state_slot_bytes(m)
        assert counter(cb, "serve_kv_token_bytes") == 2 * 64 + 16
    finally:
        cb.shutdown()
