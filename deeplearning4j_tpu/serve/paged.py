"""Paged KV-cache block management for the continuous batcher.

The dense layout gives every decode slot a private ``(1, capacity, ...)``
KV buffer, so HBM cost is ``O(slots x capacity)`` whether or not tokens are
live, and a request can never be longer than the buffer it was born with.
The paged layout (vLLM's PagedAttention scheme, adapted to the fixed-shape
XLA contract) carves KV memory into fixed-size **token blocks** in one
shared pool per attention layer:

- one pool for each part a layer's cache spec names (``build_pools``):
  ``k_pool`` / ``v_pool`` ``(num_blocks, block_size, Hkv, hd)`` for keys
  and values, ``latent_pool`` and ``rope_pool`` ``(num_blocks, block_size,
  width)`` for a latent-attention layer; device arrays, donated through
  every decode tick / prefill chunk (loop-carried, never copied);
- a per-slot **block table** ``(slots, max_blocks)`` int32 mapping logical
  block ``p // block_size`` to a physical block — a *traced operand* of
  the one compiled decode step, so growing/retiring sequences never
  changes a shape and never recompiles anything;
- physical **block 0 is reserved as the trash block**: unallocated table
  entries point at it, so the fixed-shape decode step can write every
  slot every tick (inactive slots scribble on trash) and right-padded
  prefill garbage lands there too. Nothing ever unmasked-reads block 0.

HBM cost becomes ``O(allocated blocks)`` — proportional to live tokens —
and per-request capacity is a *logical* limit (``max_blocks x
block_size``), decoupled from any dense buffer.

Sharing is first-class: every live block carries a **refcount**, so one
physical block can back the same prefix in many slots at once. The
:class:`PrefixCache` maps ``(params generation, rolling sha256 of
whole-block token runs)`` to physical blocks, holding one reference per
cached block; prefill adopts the longest cached run (refcount++) and
computes only the suffix. Only *whole* blocks are ever shared and decode
writes land in a slot's private tail block, so copy-on-write triggers
exactly when a slot must write into a block someone else still references
(a forked tail). All of it is pure host-side bookkeeping: integer free
lists and hash maps, no device work here — the batcher performs the one
CoW block copy on its own thread.

**Two block groups.** A layer may state a window for its cache
(``nn.generation.cache_parts``: a sliding-window attention layer never reads
further back). Layers group by it (:func:`cache_groups`): the ``full`` group
is everything above; the ``window`` group has pools, an allocator and tables
of its own, sized to the windows and not to the capacity. A slot's table row
there is a RING of ``R`` columns (:class:`RingPages`: logical block ``b`` in
column ``b % R``); blocks that lie wholly behind the window are released as
the sequence advances and their column zeroed, so the column's next block
is a newly allocated one and a block shared with the prefix cache or a fork
is never written. The prefix cache keeps window-group blocks under the same
rolling hashes, and a hit is usable only as far as the window's tail behind
it is still held (:meth:`PrefixCache.match_window`). A model whose layers
state no window has one group and everything here is as it was.

**The state group.** A layer may name a part with no position axis, a
recurrent state (``nn.generation.Parts.state``: a linear-attention layer).
Such layers form the ``state`` group (:class:`StateGroup`): SLOTS, not
blocks, so it has pools of its own and no allocator of token blocks. Beside
each state the pool keeps the state as it stood at the last block boundary the
slot passed (the programs refresh it), and a number of SNAPSHOTS: copies of
such boundary states that the prefix cache keeps under the hash of the run
they end (:meth:`PrefixCache.snapshot_row`), because a hit of ``n`` blocks is
usable by a state layer only where its state at exactly ``n x block_size``
tokens exists (:meth:`PrefixCache.match_state` shortens a hit to the longest
run that has one). A snapshot dies with its run's entry, or when a newer one
needs its row (LRU).

The device-side layout contract (how positions map into pools, the trash
block, append/read semantics, the ring) lives in ``nn/generation.py`` next
to ``cache_write`` / ``cache_gather``; this module only decides *which*
physical blocks a slot owns.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from .errors import CapacityError

TRASH_BLOCK = 0  # physical block 0 is never allocated; see module docstring
FULL, WINDOW = "full", "window"   # the block groups' names (metric labels)
STATE = "state"                   # the group of slots, not blocks
# snapshots the state group keeps under the prefix cache, a slot: a request
# leaves one at its prompt's last whole block and one at its answer's end
SNAPSHOTS_A_SLOT = 2


class BlockAllocator:
    """Refcounted free-list allocator over physical block ids
    ``1..num_blocks-1``.

    ``alloc`` hands out blocks at refcount 1; ``retain`` adds a reference
    (prefix adoption, forks); ``release`` drops one and returns the block
    to the free list when the count hits zero. LIFO reuse (a freed block
    is the next handed out) keeps the working set compact. Releasing a
    free block (double release) or the trash block stays a hard error —
    a refcount bug here is silent KV corruption, never something to limp
    past. Pure host-side and NOT thread-safe by itself — the batcher
    serializes calls under its own lock.
    """

    def __init__(self, num_blocks: int,
                 reclaimer: Optional[Callable[[int], int]] = None):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 usable + trash), "
                             f"got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # LIFO: low ids at the tail so fresh pools fill from block 1 up
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # last-ditch supply: asked to make `n` more blocks reclaimable
        # before alloc gives up (the prefix cache's LRU plugs in here, so
        # cached-but-unreferenced runs are reclaimed before anyone sheds)
        self._reclaimer = reclaimer

    def set_reclaimer(self, fn: Optional[Callable[[int], int]]) -> None:
        self._reclaimer = fn

    @property
    def usable(self) -> int:
        """Total allocatable blocks (excludes the trash block)."""
        return self.num_blocks - 1

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return len(self._refs)

    def refcount(self, block: int) -> int:
        """Current references on ``block`` (0 == free)."""
        return self._refs.get(int(block), 0)

    def alloc(self, n: int) -> List[int]:
        """Take ``n`` blocks at refcount 1 or raise :class:`CapacityError`
        (taking none).

        Callers gate admission on worst-case commitment, so exhaustion here
        means a bookkeeping bug — but it stays a *typed* failure either way.
        A registered reclaimer (prefix-cache LRU) is asked to free the
        shortfall first, so cached-but-idle blocks never starve live work.
        """
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) and self._reclaimer is not None:
            self._reclaimer(n - len(self._free))
        if n > len(self._free):
            raise CapacityError(
                f"KV block pool exhausted: need {n}, {len(self._free)} of "
                f"{self.usable} free")
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        return ids

    def retain(self, ids) -> None:
        """Add one reference to each live block (sharing a prefix/fork)."""
        for b in ids:
            b = int(b)
            if b == TRASH_BLOCK:
                raise ValueError("attempted to retain the trash block")
            if b not in self._refs:
                raise ValueError(f"retain of free block {b}")
            self._refs[b] += 1

    def release(self, ids) -> None:
        """Drop one reference per block; a block hitting zero goes back to
        the free list. Double release stays a hard error."""
        for b in ids:
            b = int(b)
            if b == TRASH_BLOCK:
                raise ValueError("attempted to release the trash block")
            c = self._refs.get(b)
            if c is None:
                raise ValueError(f"double free of block {b}")
            if c == 1:
                del self._refs[b]
                self._free.append(b)
            else:
                self._refs[b] = c - 1


class CacheGroup(NamedTuple):
    """The cached layers that share pools' length, an allocator and tables:
    ``full`` (no window stated) or ``window`` (the window they state)."""

    name: str
    window: Optional[int]
    layers: Tuple[str, ...]


def _is_state(lk: str, parts) -> bool:
    """Whether every part of the layer is a state (none has a position
    axis); a layer that mixed the two kinds would belong to two groups."""
    if not parts.state:
        return False
    if set(parts.state) != set(parts):
        raise ValueError(f"{lk} names state parts {sorted(parts.state)} "
                         f"beside per-token parts: one kind a layer")
    return True


def cache_groups(model) -> List[CacheGroup]:
    """``model``'s cached layers grouped by the cache window they state
    (``nn.generation.cache_parts``), the full group first; the layers whose
    cache is a state (no position axis) last, as the ``state`` group. One
    window value a model: two different ones would be two rings of
    different reach."""
    from ..nn.generation import cache_parts

    every = cache_parts(model)
    if not every:
        raise ValueError("model has no attention layers to page")
    states = tuple(lk for lk, p in every if _is_state(lk, p))
    spec = [(lk, p) for lk, p in every if lk not in states]
    windows = sorted({p.window for _, p in spec if p.window is not None})
    if len(windows) > 1:
        raise ValueError(f"cached layers state different windows {windows}: "
                         f"one window group a model")
    groups = [CacheGroup(FULL, None, tuple(
        lk for lk, p in spec if p.window is None))]
    if windows:
        groups.append(CacheGroup(WINDOW, windows[0], tuple(
            lk for lk, p in spec if p.window is not None)))
    groups.append(CacheGroup(STATE, None, states))
    return [g for g in groups if g.layers]


def build_pools(model, num_blocks, block_size: int, dtype,
                state_rows: Optional[Tuple[int, int]] = None) -> Dict:
    """Zero-filled block pools (device arrays) for every cached layer, one
    per part the layer's spec names (``nn.generation.cache_parts``):
    ``{layer_key: {part: (N, bs, *shape)}}`` — ``{"k": (N, bs, Hkv, hd),
    "v": ...}`` for KV-cached attention, ``{"latent": (N, bs, 512), "rope":
    (N, bs, 64)}`` for a layer that caches a latent and a rope key a token.
    ``num_blocks``: one number for every layer, or ``{group name: blocks}``
    (:func:`cache_groups`) where the groups' pools differ in length. A part
    held once a stride of tokens is ``(N, *shape)``: one entry a block, so
    ``block_size`` must be its stride. A state layer's parts are sized in
    slots, ``state_rows = (slots, snapshots)``: ``{part: (slots, *shape),
    part + "_snap": (slots + snapshots, *shape)}`` in the part's own dtype
    (row ``s`` of the second: slot ``s``'s state at its last block boundary;
    the rows behind them: the snapshots)."""
    import jax.numpy as jnp

    from ..nn.generation import cache_parts

    spec = cache_parts(model)
    if not spec:
        raise ValueError("model has no attention layers to page")

    def n_of(parts):
        if not isinstance(num_blocks, dict):
            return num_blocks
        return num_blocks[FULL if parts.window is None else WINDOW]

    def pools(lk, parts):
        if _is_state(lk, parts):
            if state_rows is None:
                raise ValueError(f"{lk} keeps a state: build_pools needs "
                                 f"state_rows=(slots, snapshots)")
            slots, snaps = state_rows
            out = {}
            for n, shape in parts.items():
                out[n] = jnp.zeros((slots,) + shape, parts.state[n])
                out[n + "_snap"] = jnp.zeros((slots + snaps,) + shape,
                                             parts.state[n])
            return out
        for n, stride in parts.strides.items():
            if stride != block_size:
                raise ValueError(
                    f"{lk} holds {n!r} once every {stride} tokens: the pool's "
                    f"block size must be {stride}, not {block_size}")
        return {n: jnp.zeros(
            (n_of(parts),) + (() if n in parts.strides else (block_size,))
            + shape, dtype) for n, shape in parts.items()}

    return {lk: pools(lk, parts) for lk, parts in spec}


def block_bytes(model, block_size: int, dtype,
                layers: Optional[Sequence[str]] = None) -> int:
    """Bytes one block holds across ALL cached layers (or those of
    ``layers``: one group's) and all the parts their specs name (k + v; a
    latent and its rope key; a part held once a stride of tokens counts
    ``block_size // stride`` entries) — the unit the live-KV-bytes gauge
    counts in, and ``block_size`` times what one token costs. A state is no
    block's: :func:`state_slot_bytes`."""
    from ..nn.generation import cache_parts

    itemsize = np.dtype(dtype).itemsize
    return sum(block_size // parts.strides.get(n, 1) * int(np.prod(shape))
               * itemsize
               for lk, parts in cache_parts(model)
               if layers is None or lk in layers
               for n, shape in parts.items() if n not in parts.state)


def state_slot_bytes(model) -> int:
    """Bytes of recurrent state ONE sequence holds across all state layers
    (each part in its own dtype): what a slot, and a snapshot, costs."""
    from ..nn.generation import cache_parts

    return sum(int(np.prod(shape)) * np.dtype(parts.state[n]).itemsize
               for _, parts in cache_parts(model)
               for n, shape in parts.items() if n in parts.state)


def blocks_needed(tokens: int, block_size: int) -> int:
    """Blocks covering ``tokens`` positions (ceil division)."""
    return -(-int(tokens) // int(block_size))


def prefix_hashes(tokens, block_size: int, state=None) -> List[bytes]:
    """Rolling sha256 over whole-block token runs.

    ``hashes[i]`` commits to tokens ``[0, (i+1)*block_size)`` — the entire
    run, not just block ``i`` — so two prompts share a cache entry only
    when every block before it matches too. Partial tail tokens are never
    hashed: only whole blocks are shareable.

    ``state``: a ``hashlib.sha256()`` to continue, advanced in place over
    the whole blocks of ``tokens`` and left after the last of them — so a
    run that grew (a prompt, then the answer decoded behind it) is hashed
    on from where its last whole block ended, never from its start.
    """
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    out: List[bytes] = []
    h = hashlib.sha256() if state is None else state
    for i in range(toks.shape[0] // int(block_size)):
        h.update(toks[i * block_size:(i + 1) * block_size].tobytes())
        out.append(h.digest())
    return out


class PrefixCache:
    """LRU of cached whole-block prefix runs, keyed on
    ``(params generation, rolling block-run sha256)``.

    The cache holds exactly ONE allocator reference per cached block, so a
    cached block survives its writer's retirement but is reclaimable the
    moment no slot references it. ``match`` finds the longest cached run
    for a prompt (pure lookup, no side effects — admission gates on the
    result before committing); ``adopt`` takes the references. A
    generation flip invalidates wholesale: stale-params KV can never be
    adopted, because every entry of the old generation is released before
    the first new-generation lookup returns.

    With a window group (``window_allocator``): a run's blocks of THAT
    group are cached under the same hashes, in an LRU of their own of at
    most ``window_max_blocks``, each entry one reference in the window
    allocator. Only a run's tail is ever there (the ``window_tail`` blocks a
    slot's ring still held behind the run's end when it was inserted; a
    longer run's tail replaces it), and a hit of ``n`` blocks
    is usable only if the ``window_tail`` blocks behind it are
    (:meth:`match_window`). An entry evicted from the full group takes its
    window block with it.

    Not thread-safe by itself — the batcher serializes calls under its
    own lock, same as :class:`BlockAllocator`.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int,
                 max_blocks: Optional[int] = None, *,
                 window_allocator: Optional[BlockAllocator] = None,
                 window_tail: int = 0,
                 window_max_blocks: Optional[int] = None,
                 state: Optional["StateGroup"] = None):
        self._alloc = allocator
        self.block_size = int(block_size)
        # hard size bound (entries == blocks); None = bounded only by the
        # pool via the allocator's reclaimer
        self.max_blocks = int(max_blocks) if max_blocks is not None else None
        self.generation: Optional[int] = None
        self._runs: "OrderedDict[bytes, int]" = OrderedDict()
        self.evictions = 0
        self.flushes = 0
        # the window group's blocks of cached runs, by the same hashes
        self._walloc = window_allocator
        self.window_tail = int(window_tail)
        self.window_max_blocks = window_max_blocks
        self._wruns: "OrderedDict[bytes, int]" = OrderedDict()
        # the state group's snapshots of cached runs, by the same hashes
        self._state = state

    def __len__(self) -> int:
        return len(self._runs)

    def blocks(self) -> List[int]:
        """Cached physical block ids (diagnostics/tests)."""
        return list(self._runs.values())

    def _ensure_generation(self, generation: int) -> None:
        if generation != self.generation:
            if self._runs:
                self.flush()
            self.generation = generation

    def flush(self) -> int:
        """Drop every entry, releasing the cache's references. Returns the
        number of entries released."""
        n = len(self._runs)
        if n:
            self._alloc.release(list(self._runs.values()))
            self._runs.clear()
            self.flushes += 1
        if self._wruns:
            self._walloc.release(list(self._wruns.values()))
            self._wruns.clear()
        if self._state is not None:
            self._state.clear()
        return n

    def match(self, hashes: Sequence[bytes], generation: int,
              limit: int) -> List[int]:
        """Longest cached run of full blocks from the start of the prompt
        (<= ``limit`` blocks), as physical ids. NO references are taken
        and no LRU state moves — call :meth:`adopt` once admission commits."""
        self._ensure_generation(generation)
        run: List[int] = []
        for h in hashes[:max(0, int(limit))]:
            b = self._runs.get(h)
            if b is None:
                break
            run.append(b)
        return run

    def match_window(self, hashes: Sequence[bytes],
                     n: int) -> Tuple[int, List[int]]:
        """The longest hit ``m <= n`` (in blocks) whose window tail — the
        ``window_tail`` blocks behind it, or all ``m`` if fewer — the window
        group still holds, and that tail's physical ids in logical order
        (logical blocks ``m - len(tail) .. m - 1``). ``(0, [])`` where no
        hit is usable. Pure lookup, like :meth:`match`."""
        held = 0          # consecutive blocks held, ending at block i
        best = 0
        for i, h in enumerate(hashes[:max(0, int(n))]):
            held = held + 1 if h in self._wruns else 0
            if held >= min(self.window_tail, i + 1):
                best = i + 1
        tail = hashes[max(0, best - self.window_tail):best]
        return best, [self._wruns[h] for h in tail]

    def match_state(self, hashes: Sequence[bytes],
                    n: int) -> Tuple[int, Optional[int]]:
        """The longest hit ``m <= n`` (in blocks) that ends where the state
        group holds a snapshot (a state layer can go on from a run only from
        its state at exactly the run's end), and that snapshot's row; ``(0,
        None)`` where none does. Pure lookup, like :meth:`match`."""
        for m in range(min(int(n), len(hashes)), 0, -1):
            row = self._state.row_of(hashes[m - 1])
            if row is not None:
                return m, row
        return 0, None

    def snapshot_row(self, hashes: Sequence[bytes], n: int) -> Optional[int]:
        """A row of the state group's snapshot pool for the state at the end
        of the cached run of ``n`` blocks, which the caller is about to copy
        there; None where the run is not cached (it was not inserted: a full
        cache), has its snapshot already, or no row can be had (every one
        pinned by an admission). Under the lock, after :meth:`insert`."""
        if not 0 < n <= len(hashes) or hashes[n - 1] not in self._runs:
            return None
        return self._state.take(hashes[n - 1])

    def adopt(self, hashes: Sequence[bytes], run: List[int],
              window_run: Sequence[int] = ()) -> None:
        """Take one reference per matched block and mark the run
        recently-used. ``run`` must be a fresh :meth:`match` result under
        the same lock (and ``window_run`` the tail :meth:`match_window`
        gave for it)."""
        if not run:
            return
        self._alloc.retain(run)
        for h in hashes[:len(run)]:
            self._runs.move_to_end(h)
        if window_run:
            self._walloc.retain(window_run)
            for h in hashes[len(run) - len(window_run):len(run)]:
                self._wruns.move_to_end(h)
        if self._state is not None:
            self._state.touch(hashes[len(run) - 1])

    def insert(self, hashes: Sequence[bytes], blocks: Sequence[int],
               generation: int,
               window_blocks: Optional[Dict[int, int]] = None) -> int:
        """Cache a slot's whole blocks: a prompt's when its prefill ends,
        and the run's as it stands — the blocks the decode steps filled
        included — when the request finishes (the cache takes its own
        reference per newly inserted block). Entries already present keep
        their existing physical block — the newcomer's copy stays private
        and retires with its slot. ``window_blocks`` ``{logical block:
        physical id}``: the run's TAIL in the window group (what the slot's
        ring still holds behind the run's end), cached beside the
        full-group entries that exist; what the window group held of this
        run before that tail is released at once: a later request that
        extends the run needs the new tail and no other (one that leaves the
        run earlier finds its hit shortened). Returns the number inserted
        (full group)."""
        self._ensure_generation(generation)
        ins = self._insert_full(hashes, blocks)
        if window_blocks:
            for h in hashes[:min(window_blocks)]:
                self._drop_window(h)
            for i, b in sorted(window_blocks.items()):
                if i < len(hashes) and hashes[i] in self._runs:
                    self._insert_window(hashes[i], b)
        return ins

    def _insert_window(self, h: bytes, block: int) -> None:
        if h in self._wruns:
            self._wruns.move_to_end(h)
            return
        while self.window_max_blocks is not None \
                and len(self._wruns) >= self.window_max_blocks:
            _, old = self._wruns.popitem(last=False)
            self._walloc.release([old])
        self._walloc.retain([block])
        self._wruns[h] = block

    def _drop_window(self, h: bytes) -> None:
        b = self._wruns.pop(h, None)
        if b is not None:
            self._walloc.release([b])

    def _drop_beside(self, h: bytes) -> None:
        """An entry left the full group: what the other groups keep under its
        hash goes with it (a window block, a state snapshot)."""
        self._drop_window(h)
        if self._state is not None:
            self._state.drop(h)

    def _insert_full(self, hashes: Sequence[bytes],
                     blocks: Sequence[int]) -> int:
        ins = 0
        for h, b in zip(hashes, blocks):
            if h in self._runs:
                self._runs.move_to_end(h)
                continue
            if self.max_blocks is not None \
                    and len(self._runs) >= self.max_blocks \
                    and not self._evict_lru():
                break
            self._alloc.retain([b])
            self._runs[h] = b
            ins += 1
        return ins

    def _evict_lru(self) -> bool:
        """Drop the least-recently-used entry (size bound), releasing the
        cache's reference — the block itself is freed only if no slot
        still references it."""
        if not self._runs:
            return False
        h, b = self._runs.popitem(last=False)
        self._alloc.release([b])
        self._drop_beside(h)
        self.evictions += 1
        return True

    def reclaim(self, need: int) -> int:
        """Capacity pressure: free up to ``need`` blocks by evicting LRU
        entries whose ONLY reference is the cache (those actually return
        to the free list). Entries still adopted by live slots are left
        alone — evicting them would free nothing. This is the allocator's
        reclaimer hook, so idle cached runs are always reclaimed before
        any request sheds."""
        freed = 0
        if need <= 0:
            return 0
        for h in list(self._runs.keys()):
            if freed >= need:
                break
            b = self._runs[h]
            if self._alloc.refcount(b) == 1:
                del self._runs[h]
                self._alloc.release([b])
                self._drop_beside(h)
                self.evictions += 1
                freed += 1
        return freed

    def reclaim_window(self, need: int) -> int:
        """:meth:`reclaim` for the window group's allocator: drop the
        least-recently-used cached window blocks nobody else holds. The
        full-group entries stay: a hit on them is shortened or missed."""
        freed = 0
        for h in list(self._wruns.keys()):
            if freed >= need:
                break
            if self._walloc.refcount(self._wruns[h]) == 1:
                self._drop_window(h)
                freed += 1
        return freed

    def stats(self) -> dict:
        out = {"entries": len(self._runs),
               "max_blocks": self.max_blocks,
               "evictions": self.evictions,
               "flushes": self.flushes,
               "generation": self.generation}
        if self._walloc is not None:
            out["window_entries"] = len(self._wruns)
        if self._state is not None:
            out["state_snapshots"] = self._state.used
        return out


class SlotPages:
    """One slot's view of the pool: its blocks, in logical order, plus
    which of them are *shared* (held via ``retain`` — adopted prefix runs
    or fork parents' blocks — rather than privately allocated).

    ``ensure(tokens)`` grows the mapping to cover ``tokens`` positions,
    allocating lazily — so the pool's *used* count tracks live tokens, not
    requested worst cases. The batcher writes the returned new block ids
    into its host block-table row. Releasing is uniform under refcounts:
    every block drops one reference, shared blocks simply survive in
    their other holders.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self._alloc = allocator
        self.block_size = int(block_size)
        self.blocks: List[int] = []
        self.shared: set = set()  # subset of blocks held by retain, not alloc

    def adopt(self, blocks: Sequence[int]) -> None:
        """Front-load already-retained shared blocks (prefix adoption).
        Must run before any private allocation."""
        if self.blocks:
            raise ValueError("adopt() must precede any allocation")
        self.blocks = [int(b) for b in blocks]
        self.shared.update(self.blocks)

    def ensure(self, tokens: int) -> List[int]:
        """Cover ``tokens`` positions; returns the NEWLY allocated ids."""
        need = blocks_needed(tokens, self.block_size) - len(self.blocks)
        if need <= 0:
            return []
        new = self._alloc.alloc(need)
        self.blocks.extend(new)
        return new

    def swap(self, idx: int, new_block: int) -> int:
        """Copy-on-write bookkeeping: replace the block at logical index
        ``idx`` with ``new_block`` (already allocated, private), dropping
        this slot's reference on the old one. Returns the old id — the
        caller has already copied its KV device-side."""
        old = self.blocks[idx]
        self.blocks[idx] = int(new_block)
        self.shared.discard(old)
        self._alloc.release([old])
        return old

    def release(self) -> None:
        """Copy-free retirement: drop one reference on every block; fully
        private blocks go straight back to the free list."""
        if self.blocks:
            self._alloc.release(self.blocks)
            self.blocks = []
            self.shared.clear()


class RingPages:
    """One slot's blocks in the WINDOW group: a ring of ``columns`` table
    columns over logical blocks, of which only those a query can still see
    are held: the contiguous range ``first .. next - 1``.

    ``release_behind(pos)`` drops the blocks that lie wholly behind the
    window of a query at ``pos`` (the first query of the step about to run);
    ``ensure(tokens)`` then maps the blocks up to ``tokens`` positions, each
    a newly allocated one — so a column is never re-used with the block it
    held a lap ago, and a block someone else still references (the prefix
    cache, a fork) is never written by the ring coming round. ``row()`` is
    the slot's table row: logical block ``b`` in column ``b % columns``,
    zero (the trash block) where nothing is held. The batcher calls release
    before ensure, every step; with ``columns = ring_blocks(window, largest
    chunk, block_size)`` the held blocks then never collide in a column.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int,
                 window: int, columns: int):
        self._alloc = allocator
        self.block_size = int(block_size)
        self.window = int(window)
        self.columns = int(columns)
        self.blocks: Dict[int, int] = {}   # logical block -> physical id
        self.shared: set = set()           # physical ids held by retain
        self.first = 0                     # lowest logical block held
        self.next = 0                      # first logical block never mapped
        self.committed = 0                 # blocks admission charged for it

    def adopt(self, first: int, blocks: Sequence[int]) -> None:
        """Hold already-retained shared blocks as logical blocks ``first,
        first + 1, ...`` (a prefix hit's window tail, a fork parent's
        ring); the next block to be allocated follows them."""
        if self.blocks:
            raise ValueError("adopt() must precede any allocation")
        self.blocks = {int(first) + i: int(b) for i, b in enumerate(blocks)}
        self.shared.update(self.blocks.values())
        self.first = int(first)
        self.next = self.first + len(self.blocks)

    def release_behind(self, pos: int) -> int:
        """Release every held block whose last position is more than
        ``window - 1`` behind ``pos``. Returns how many."""
        oldest = int(pos) - self.window + 1          # oldest visible position
        dead = 0
        while self.first < self.next \
                and (self.first + 1) * self.block_size <= oldest:
            phys = self.blocks.pop(self.first)
            self.shared.discard(phys)
            self._alloc.release([phys])
            self.first += 1
            dead += 1
        return dead

    def ensure(self, tokens: int) -> List[int]:
        """Map the blocks covering positions up to ``tokens`` (exclusive)
        that never were; returns the newly allocated ids."""
        need = blocks_needed(tokens, self.block_size) - self.next
        if need <= 0:
            return []
        new = self._alloc.alloc(need)
        for b in new:
            self.blocks[self.next] = b
            self.next += 1
        return new

    def swap(self, logical: int, new_block: int) -> int:
        """Copy-on-write bookkeeping, as :meth:`SlotPages.swap`."""
        old = self.blocks[logical]
        self.blocks[logical] = int(new_block)
        self.shared.discard(old)
        self._alloc.release([old])
        return old

    def row(self) -> np.ndarray:
        if self.next - self.first > self.columns:
            raise ValueError(
                f"ring of {self.columns} columns holds logical blocks "
                f"{self.first}..{self.next - 1}: two in one column")
        row = np.zeros(self.columns, np.int32)
        for b, phys in self.blocks.items():
            row[b % self.columns] = phys
        return row

    def release(self) -> None:
        if self.blocks:
            self._alloc.release(list(self.blocks.values()))
            self.blocks = {}
            self.shared.clear()
            self.first = self.next


class WindowGroup:
    """Host state of the window block group of one batcher: its allocator,
    the slots' ring tables ``(slots, columns)``, and the sizes they follow
    from. The pool holds every slot's ring (``slots x columns``: a slot
    never holds more, shared blocks included) plus ``cache_blocks`` for the
    window tails the prefix cache keeps, plus the trash block."""

    def __init__(self, group: CacheGroup, *, slots: int, block_size: int,
                 chunk: int, block_bytes: int, cached_tails: int):
        from ..nn.generation import ring_blocks

        self.window = int(group.window)
        self.layers = group.layers
        self.block_size = int(block_size)
        #: columns of a slot's ring table
        self.columns = ring_blocks(self.window, chunk, block_size)
        #: blocks behind a prefix hit that its first query still sees
        self.tail = blocks_needed(self.window - 1, block_size)
        self.cache_blocks = int(cached_tails) * self.tail
        self.alloc = BlockAllocator(
            slots * self.columns + self.cache_blocks + 1)
        self.tables_np = np.zeros((slots, self.columns), np.int32)
        self.block_bytes = int(block_bytes)
        self.committed = 0      # sum of the admitted requests' commitments

    def commitment(self, tokens: int) -> int:
        """Blocks a request of ``tokens`` positions can hold at once."""
        return min(self.columns, blocks_needed(tokens, self.block_size))

    def fits(self, tokens: int) -> bool:
        """Whether the pool can take one more request of ``tokens``."""
        return self.committed + self.commitment(tokens) <= self.alloc.usable

    def open(self, tokens: int) -> RingPages:
        """A new slot's ring, its commitment charged."""
        ring = RingPages(self.alloc, self.block_size, self.window,
                         self.columns)
        ring.committed = self.commitment(tokens)
        self.committed += ring.committed
        return ring

    def close(self, s: int, ring: RingPages) -> None:
        """Retire slot ``s``'s ring: drop its references, give back its
        commitment, zero its row."""
        ring.release()
        self.committed -= ring.committed
        ring.committed = 0
        self.tables_np[s] = 0


class StateGroup:
    """Host state of the state group of one batcher: which rows of the
    snapshot pool hold which cached run's state. The pool has ``slots +
    snapshots`` rows a state layer: row ``s < slots`` is slot ``s``'s own (its
    state at the last block boundary it passed; the programs write it), the
    rows behind them are snapshots, kept under the rolling hash of the run
    they end, least recently used first to go. A row an admission has matched
    is PINNED until the request's first chunk, which loads it, is enqueued:
    the device runs its queue in order, so a copy enqueued later may then
    overwrite it."""

    def __init__(self, group: CacheGroup, *, slots: int, snapshots: int,
                 slot_bytes: int):
        self.layers = group.layers
        self.slots = int(slots)
        self.snapshots = int(snapshots)
        self.slot_bytes = int(slot_bytes)
        self._free: List[int] = list(range(self.slots + self.snapshots - 1,
                                           self.slots - 1, -1))
        self._rows: "OrderedDict[bytes, int]" = OrderedDict()
        self._pins: Dict[int, int] = {}
        self._orphans: set = set()     # dropped while pinned: freed at unpin
        self.taken = 0
        self.evictions = 0

    @property
    def used(self) -> int:
        """Snapshots held."""
        return len(self._rows)

    def row_of(self, h: bytes) -> Optional[int]:
        return self._rows.get(h)

    def touch(self, h: bytes) -> None:
        if h in self._rows:
            self._rows.move_to_end(h)

    def take(self, h: bytes) -> Optional[int]:
        """A row for a new snapshot under ``h``: a free one, else the least
        recently used one nobody has pinned. None where ``h`` has one
        already (it is refreshed in the LRU) or none can be had."""
        if h in self._rows:
            self._rows.move_to_end(h)
            return None
        if not self._free:
            old = next((k for k, r in self._rows.items()
                        if r not in self._pins), None)
            if old is None:
                return None
            self._free.append(self._rows.pop(old))
            self.evictions += 1
        row = self._free.pop()
        self._rows[h] = row
        self.taken += 1
        return row

    def drop(self, h: bytes) -> None:
        row = self._rows.pop(h, None)
        if row is not None:
            self._release(row)

    def clear(self) -> None:
        rows, self._rows = list(self._rows.values()), OrderedDict()
        for row in rows:
            self._release(row)

    def _release(self, row: int) -> None:
        if row in self._pins:
            self._orphans.add(row)
        else:
            self._free.append(row)

    def pin(self, row: int) -> None:
        self._pins[row] = self._pins.get(row, 0) + 1

    def unpin(self, row: int) -> None:
        c = self._pins.get(row, 0)
        if c > 1:
            self._pins[row] = c - 1
            return
        self._pins.pop(row, None)
        if row in self._orphans:
            self._orphans.discard(row)
            self._free.append(row)
