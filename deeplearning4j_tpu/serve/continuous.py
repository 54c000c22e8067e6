"""Continuous batching for autoregressive generation.

``nn/generation.generate`` is whole-batch lockstep: every sequence in the
batch prefills together, decodes together, and finishes together — a short
sequence waits for the longest one, and a new request waits for the whole
batch. Serving wants the vLLM-style iteration-level schedule instead: a
fixed number of decode *slots*, each holding one in-flight sequence; every
engine tick decodes ALL slots one token; a sequence that finishes frees its
slot immediately and a queued prompt prefills into it, joining the
in-flight batch mid-stream.

Static shapes throughout (the TPU contract):

- the decode step is ONE executable for the life of the server: per-slot
  position/temperature/top-k/PRNG-key ride as *traced* vectors over the
  slot axis, so slot heterogeneity never changes a shape;
- prompts pad to a fixed set of ``prompt_buckets`` (and, chunked, to
  ``prefill chunk buckets``) before prefill, with the true length traced —
  compile count is ``<= |prompt_buckets| + 1``.

This module is the scheduler; the device programs it feeds (the sampler,
the prefill chunk, the decode step), their KV pools and their operand
lists are ``serve/programs.py``'s.

The KV cache is paged (layout contract in ``nn/generation.py``): one shared
block pool per attention layer (``serve/paged.py``); each slot owns an
``int32`` block-table row that maps logical block ``p // block_size`` to a
physical block. The table is a *traced operand* of the one decode
executable, so allocation, growth, and copy-free retirement (free the ids,
zero the row) never recompile anything. HBM cost is O(live tokens);
per-request ``capacity`` is a logical limit decoupled from any dense
buffer — rope models (no ``PositionalEmbedding`` table) can serve contexts
far past their training length. Admission commits worst-case blocks up
front (``ceil((prompt+max_new)/block_size)``), so a decode can never run
out of memory mid-flight; physical blocks are allocated lazily as tokens
materialize, which is what makes the live-KV-bytes gauge track live data.
Prefill is **chunked**: a long prompt advances ``prefill_chunk`` tokens per
step, interleaved with decode ticks under a priority-aware
:class:`~.engine.PrefillScheduler`, so a prompt burst cannot stall
in-flight decodes for its whole prefill.

KV is shared across requests (``prefix_cache=True``): whole blocks are
inserted into a :class:`~.paged.PrefixCache` keyed on ``(params
generation, rolling sha256 of block token runs)`` — a prompt's as its
prefill completes, and the whole run's, the blocks its decode steps filled
included, as the request finishes (so the next turn of a session that
sends the answer back prefills its fresh tokens and nothing else; only a
request served under one params generation from first chunk to last tick,
and never one that was aborted or shed) — and admission adopts the longest
cached run: refcount++ on the shared physical blocks, prefill computes only
the non-shared suffix, and the worst-case commitment charges only
non-shared blocks. Cached-but-idle runs
form an LRU the allocator reclaims under capacity pressure before anything
sheds; a registry generation flip invalidates the cache wholesale so
stale-params KV is never adopted. Decode writes always land in a slot's
private tail block, so copy-on-write triggers exactly when a slot must
write a block someone else still references (a forked tail): the batcher
copies that one block eagerly (host-side dispatch, never a new jit site),
swaps the table row, refcount--. :meth:`ContinuousBatcher.fork` clones a
decoding slot by duplicating its table row with refcount++ on every
block — one int32 row copy, never KV bytes. All sharing is host-side
bookkeeping: the decode step stays ONE executable for the server lifetime,
enforced by the committed compile-surface budget.

A model whose layers state a cache window (sliding-window attention:
``nn.generation.cache_parts``) gets a second block GROUP beside the one
above (``serve/paged.py``): pools sized to the windows, an allocator and a
ring table ``(slots, R)`` of its own. Admission commits in both groups; each
tick and each prefill chunk first releases the ring blocks that lie wholly
behind the window, then grows the ring; the prefix cache keeps window blocks
under the same hashes and a hit is shortened to what the window's tail
still covers; copy-on-write and forks work per group. Nothing selects this
but the model's cache spec, and a model with one group is served exactly as
before.

The worker runs one decode step AHEAD of what it has read (the comment
above ``_tick`` has the order of a turn and what it rests on): step n+1 is
prepared and enqueued while step n runs, its tokens, positions and keys
handed on inside the device (``serve/programs.py``), so the host's
milliseconds a tick hide behind the step instead of standing between two
steps. The next step is enqueued as late as the device allows (``_slack``),
so that a request arriving meanwhile still gets its prefill chunk in front
of it. Same seed and same requests give the same tokens as a loop that
reads every step before it enqueues the next.

The bit-exact baseline for all of it is whole-batch
``nn.generation.generate`` over its contiguous caches.

A model whose layers keep a recurrent STATE and name it as a cache part (a
linear-attention layer: ``nn.generation.Parts.state``) gets the state group
(``serve/paged.py``): one state a slot a layer, in pools the decode and chunk
programs update in place; a new request's first chunk starts its slot from
zeros or from a SNAPSHOT. Snapshots are what the prefix cache needs of such a
model: a hit of ``n`` blocks is usable only where the states at exactly ``n x
block_size`` tokens exist, so where a run enters the cache (a prompt's whole
blocks as its prefill ends, the answer's as the request finishes) the slot's
state at that block boundary — the programs keep it beside the current one —
is copied on the device into a snapshot row kept under the run's hash, and
admission shortens a hit to the longest run that has one
(``serve_prefix_hits_shortened_total{reason="state"}``). No state is ever
read back to the host; ``fork()`` copies the parent's.

Scope: embedding-front causal-attention stacks (the CausalLM family).
``RecurrentLayer`` carries are rejected — a right-padded prefill would run
the RNN carry over pad rows (a layer that names its state as a cache part and
masks the padding is served) — and non-causal attention cannot decode
incrementally at all; both families stay on whole-batch
``nn.generation.generate``.
"""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence

import numpy as np

from ..chaos import faults as _faults
from ..obs import flight as _flight
from ..obs import profile as _prof
from ..obs import trace as _trace
from .engine import PrefillScheduler
from .errors import (CapacityError, DeadlineExceededError, DrainTimeoutError,
                     ServeError, ServerClosingError, ShedError,
                     WorkerStallError)
from .paged import (FULL, SNAPSHOTS_A_SLOT, STATE, WINDOW, BlockAllocator,
                    PrefixCache, RingPages, SlotPages, StateGroup, WindowGroup,
                    block_bytes, blocks_needed, cache_groups, prefix_hashes,
                    state_slot_bytes)
from .programs import GenPrograms
from .registry import ModelRegistry


def _default_prompt_buckets(capacity: int) -> tuple:
    buckets, b = [], 8
    while b < capacity:
        buckets.append(b)
        b *= 2
    buckets.append(capacity)
    return tuple(sorted(set(buckets)))


# serve_gen_decode_seconds: buckets a tick's median can be read in (x 1.15 from
# 2 ms to 250 ms, so none is wider than 15% of its value), the default
# buckets below and above
TICK_BUCKETS = ((1e-4, 2.5e-4, 5e-4, 1e-3)
                + tuple(round(2e-3 * 1.15 ** i, 6) for i in range(35))
                + (0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))


# Constructor knobs a tuned config (aot/tuned.py) may set on the batcher.
# Unknown keys in a stored "gen" group are dropped, so configs written by a
# newer tuner (or an older one: "kv", when there were two layouts) never
# break this binary at boot.
GEN_KNOBS = frozenset({"slots", "capacity", "block_size", "kv_blocks",
                       "prefill_chunk", "prompt_buckets", "queue_limit",
                       "seed", "prefix_cache", "prefix_cache_blocks"})


def gen_opts_from_config(config: Optional[dict]) -> dict:
    """The ``gen`` group of a tuned config as ContinuousBatcher kwargs.

    The scheduler's ``decode_chunks``/``idle_chunks`` are stored as plain
    values (the config is JSON) and folded into a ``PrefillScheduler``
    here; everything else passes through filtered by :data:`GEN_KNOBS`.
    """
    group = dict((config or {}).get("gen") or {})
    decode_chunks = group.pop("decode_chunks", None)
    idle_chunks = group.pop("idle_chunks", None)
    opts = {k: v for k, v in group.items() if k in GEN_KNOBS}
    if decode_chunks is not None or idle_chunks is not None:
        opts["scheduler"] = PrefillScheduler(
            decode_chunks=int(1 if decode_chunks is None else decode_chunks),
            idle_chunks=int(4 if idle_chunks is None else idle_chunks))
    return opts


class _CachedRun(NamedTuple):
    """What a decoding request keeps of its prompt's place in the prefix
    cache, to cache the rest of its run when it finishes."""

    hashes: List[bytes]   # the prompt's whole blocks' rolling hashes
    state: object         # the rolling sha256 after the last of them
    generation: int       # the params generation every chunk ran under


class _GenRequest:
    """One queued/in-flight generation."""

    __slots__ = ("prompt", "max_new", "temperature", "top_k", "eos_id",
                 "deadline", "enq_t", "disp_t", "first_t", "event", "result",
                 "error", "out", "pushed_ns", "key", "slot", "ctx", "on_done",
                 "cancelled", "cached_run", "_cv")

    def __init__(self, prompt: np.ndarray, max_new: int, temperature: float,
                 top_k: Optional[int], eos_id: Optional[int],
                 deadline: Optional[float], ctx=None):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.eos_id = eos_id
        self.deadline = deadline
        self.enq_t = time.perf_counter()
        # perf_counter stamps beside enq_t: first prefill chunk dispatched,
        # first token pushed (serve_gen_queue_seconds / _first_token_seconds)
        self.disp_t: Optional[float] = None
        self.first_t: Optional[float] = None
        self.event = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[ServeError] = None
        self.out: List[int] = []
        # beside each token of ``out``, the worker's perf_counter_ns stamp of
        # its push (a tick's tokens share the tick's one publish stamp): what
        # the SSE writer's lag is measured from
        self.pushed_ns: List[int] = []
        self.key = None       # per-request PRNG key, set at admission
        self.slot: Optional[int] = None
        # request-trace context (obs/reqtrace); None whenever tracing is
        # uninstalled — every consumer guards on that
        self.ctx = ctx
        # completion hook (fleet SLO burn accounting); runs once, on the
        # thread that finished the request
        self.on_done = None
        # set by ContinuousBatcher.cancel(): the typed error the worker
        # finishes this request with at its next safe point
        self.cancelled: Optional[ServeError] = None
        # set when its prompt's blocks entered the prefix cache: the key to
        # cache the answer's under (None for a fork's child, whose tokens
        # before the fork are not its own to hash)
        self.cached_run: Optional[_CachedRun] = None
        self._cv = threading.Condition()

    # --- token-at-a-time surface (SSE streaming rides on this) ---
    def _push(self, tok: int, stamp_ns: int) -> None:
        with self._cv:
            self.pushed_ns.append(stamp_ns)     # first: whoever sees the
            self.out.append(tok)                # token finds its stamp
            self._cv.notify_all()

    def _finish(self, error: Optional[ServeError] = None) -> None:
        if self.event.is_set():
            # idempotent: a request shed by a crash-only restart (or forced
            # shutdown) must not be re-finished by a waking stale worker
            return
        if error is not None:
            self.error = error
        else:
            self.result = np.asarray(self.out, np.int32)
        if self.ctx is not None:
            # closes the decode stage; an error shed records its stage from
            # THIS thread (decode loop, watchdog, or shutdown caller), so
            # the thread that killed the request shows up in its flow
            self.ctx.finish_work(
                error=None if error is None else error.cause,
                tokens=len(self.out))
        self.event.set()
        with self._cv:
            self._cv.notify_all()
        cb = self.on_done
        if cb is not None:
            self.on_done = None
            try:
                cb(self)
            except Exception:  # an accounting hook must never kill the decode loop  # jaxlint: disable=broad-except
                pass

    def set_on_done(self, cb) -> None:
        """Attach the completion hook race-free: a request that already
        finished (tiny prompt, instant EOS) fires ``cb`` immediately."""
        with self._cv:
            if not self.event.is_set():
                self.on_done = cb
                return
        cb(self)

    def stream(self) -> Iterator[int]:
        """Yield tokens as they are decoded; returns when the request
        completes. A terminal error (deadline, shutdown, ...) raises AFTER
        every token decoded before it has been yielded — consumers see the
        partial output, then the typed failure."""
        i = 0
        while True:
            with self._cv:
                self._cv.wait_for(
                    lambda: len(self.out) > i or self.event.is_set())
                n = len(self.out)
                done = self.event.is_set() and n <= i
            while i < n:
                yield self.out[i]
                i += 1
            if done:
                if self.error is not None:
                    raise self.error
                return

    def wait(self) -> np.ndarray:
        self.event.wait()
        if self.error is not None:
            raise self.error
        return self.result


class _PrefillJob:
    """One prompt mid-prefill: its slot, block pages, and chunk cursor."""

    __slots__ = ("req", "slot", "pages", "chunks", "idx", "worst", "last",
                 "shared", "hashes", "hash_state", "gens", "ring", "load")

    def __init__(self, req: _GenRequest, slot: int, pages: SlotPages,
                 chunks: List[tuple], worst: int, shared: int = 0,
                 hashes: Optional[List[bytes]] = None, hash_state=None,
                 ring: Optional[RingPages] = None):
        self.req = req
        self.slot = slot
        self.pages = pages
        self.ring = ring        # its blocks in the window group, if any
        self.chunks = chunks    # [(offset, true_len, padded_bucket), ...]
        self.idx = 0
        self.worst = worst      # committed worst-case blocks (non-shared)
        self.last = None        # logits at the last REAL token so far
        self.shared = shared    # prefix blocks adopted from the cache
        self.hashes = hashes or []  # rolling block-run hashes of the prompt
        self.hash_state = hash_state  # the rolling sha256 behind the last
        self.gens: set = set()  # params generations its chunks ran under
        # what the slot's recurrent state starts from at the next chunk (a
        # model with a state group): a snapshot row (pinned until that chunk
        # is enqueued), -1 zeros, -2 the slot's own state
        self.load = -1

    @property
    def deadline(self):
        return self.req.deadline

    @property
    def enq_t(self):
        return self.req.enq_t


class _Mean:
    """The mean of the last ``STALL_GAPS`` samples (the window the worker's
    clock keeps its stall limit over), in whole nanoseconds."""

    __slots__ = ("_last", "_sum")

    def __init__(self):
        self._last = collections.deque(maxlen=_trace.STALL_GAPS)
        self._sum = 0

    def clear(self) -> None:
        self._last.clear()
        self._sum = 0

    def add(self, ns: int) -> None:
        if len(self._last) == self._last.maxlen:
            self._sum -= self._last[0]
        self._last.append(ns)
        self._sum += ns

    @property
    def n(self) -> int:
        return len(self._last)

    @property
    def mean(self) -> int:
        return self._sum // len(self._last) if self._last else 0


class _Step:
    """One decode step from its enqueue to its publish. What a tick books
    belongs to the step it reads, not to the turn that happens to read it,
    so it travels here: the rows the step was given (slot and the request
    that held it THEN), its lease and that lease's params generation, its
    own first dispatch stamp."""

    __slots__ = ("seq", "nxt", "rows", "snap", "lease", "t0", "start",
                 "chunks", "chunks_upto", "ahead")

    def __init__(self, seq: int, lease, snap):
        self.seq = seq
        self.lease, self.snap = lease, snap
        self.nxt = None         # its tokens, on the device
        self.rows: List[tuple] = []
        self.t0 = 0             # gen.tick.dispatch's first stamp
        # when the device began it, as the host can tell: its enqueue's
        # return, or the return of the readback of the step in front of it
        self.start = 0
        self.chunks = 0         # prefill chunks queued in front of it,
        #   behind the step before it
        self.chunks_upto = 0    # ... and all chunks enqueued before it
        self.ahead = False      # enqueued while the step before it ran

    def release(self) -> None:
        """Return the lease (idempotent: the registry's token is)."""
        self.lease.__exit__(None, None, None)


class _First(NamedTuple):
    """A first token the sampler left on the device, not read yet."""

    req: "_GenRequest"  # in its slot (req.slot) since the sampler's enqueue
    tok: object         # the device scalar
    before: int         # it is computed in front of the step of this seq
    chunks_upto: int    # chunks enqueued up to and including its own


class _RunAhead:
    """One worker's view of the device's queue: the decode step enqueued and
    not read yet, the first tokens not read yet, and what it has measured
    to decide how late the next step may be enqueued (all of it from the
    worker's own stamps: the step's length, the host's lead from the
    decision to the enqueue's return, a chunk's length)."""

    def __init__(self):
        self.step: Optional[_Step] = None
        self.seq = 0            # steps enqueued so far
        self.done = 0           # seq of the last step read back
        self.firsts: List[_First] = []
        self.chunks = 0         # chunks enqueued since the last step was
        self.step_ns, self.lead_ns, self.chunk_ns = _Mean(), _Mean(), _Mean()
        self.waited = False     # this turn slept in its slack

    def deadline(self) -> Optional[int]:
        """The stamp at which to begin the next step's prepare so that its
        enqueue returns ``AHEAD_MARGIN_NS`` before the device runs dry:
        the running step's expected end, the chunks queued in front of it
        and behind it included. None where there is nothing to go by (no
        step running, no measurement yet, a start nobody stamped): enqueue
        at once."""
        st = self.step
        if st is None or not self.step_ns.n or not self.lead_ns.n:
            return None
        if st.chunks and not st.ahead:
            # enqueued behind a chunk that was already running: when that
            # chunk began nobody stamped, so nothing says when this step will
            # end (guessing late idles the device for a chunk's length)
            return None
        return (st.start + self.step_ns.mean
                + (st.chunks + self.chunks) * self.chunk_ns.mean
                - self.lead_ns.mean - _trace.AHEAD_MARGIN_NS)


class _ForkCall:
    """One ``fork()`` on its way to the worker, which runs it between two
    turns with no step in flight."""

    __slots__ = ("req", "max_new", "temperature", "top_k", "key", "child",
                 "error", "done")

    def __init__(self, req, max_new, temperature, top_k, key):
        self.req, self.max_new = req, max_new
        self.temperature, self.top_k, self.key = temperature, top_k, key
        self.child: Optional[_GenRequest] = None
        self.error: Optional[Exception] = None
        self.done = threading.Event()


class ContinuousBatcher:
    """Fixed-slot continuous-batching decode loop over a model registry.

    ``slots``: concurrent in-flight sequences (the decode batch size).
    ``capacity``: max context per request (``len(prompt) + max_new_tokens
    <= capacity``). This is a *logical* bound backed by
    ``kv_blocks`` shared physical blocks of ``block_size`` tokens — a pool
    smaller than ``slots * capacity`` oversubscribes gracefully: requests
    queue while blocks are committed elsewhere and shed with a typed
    :class:`CapacityError` only when a request could never fit.
    ``prefill_chunk`` bounds how many prompt tokens one prefill step may
    process (``None`` = whole-prompt prefill); ``scheduler`` decides how
    prefill chunks interleave with decode ticks. Each decode tick leases
    the registry's current snapshot, so a hot-swap takes effect at the
    next token boundary — and, chunked, at the next *chunk* boundary
    during long prefills."""

    def __init__(self, model, registry: Optional[ModelRegistry] = None,
                 params=None, state=None, *, slots: int = 4,
                 capacity: int = 256,
                 block_size: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefix_cache_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = 64,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 queue_limit: int = 64, seed: int = 0, metrics=None,
                 scheduler: Optional[PrefillScheduler] = None,
                 aot_store=None, strict_aot: bool = False,
                 model_name: Optional[str] = None):
        import jax

        from ..nn.generation import check_decodes
        from ..obs.metrics import MetricsRegistry

        self.model = model
        # fleet serving: model=<name> on every batcher metric; None keeps
        # the historical single-model label sets (absent == empty label)
        self.model_name = model_name
        if registry is None:
            registry = ModelRegistry(
                params if params is not None else model.params,
                state if state is not None else model.state, metrics=metrics,
                model=model_name)
        self.registry = registry
        self.slots = int(slots)
        self.capacity = int(capacity)
        self.queue_limit = int(queue_limit)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.scheduler = scheduler if scheduler is not None \
            else PrefillScheduler()
        self.prompt_buckets = tuple(sorted(set(
            int(b) for b in (prompt_buckets
                             or _default_prompt_buckets(self.capacity))
            if b <= self.capacity))) or (self.capacity,)
        # model contract: embedding-front, causal, and every stateful layer
        # says how it decodes (serve/README.md)
        self.vocab = check_decodes(model, self.capacity, "cache capacity",
                                   served=True)
        # strict_aot: a store miss raises a typed AotTraceError instead of
        # tracing — and because _warm_for runs at construction, the FIRST
        # uncovered signature fails the boot itself, never a request
        self.strict_aot = bool(strict_aot)
        if self.strict_aot and aot_store is None:
            raise ValueError("strict_aot=True requires an aot_store — "
                             "a storeless batcher can only trace")

        S, C, V = self.slots, self.capacity, self.vocab
        self.block_size = int(block_size)
        self._maxb = blocks_needed(C, self.block_size)
        if kv_blocks is None:
            # dense-equivalent coverage + the reserved trash block
            kv_blocks = S * self._maxb + 1
        self.kv_blocks = int(kv_blocks)
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 or None")
        self.prefill_chunk = (int(prefill_chunk)
                              if prefill_chunk is not None else None)
        if self.prefill_chunk is not None:
            self._chunk_buckets = tuple(sorted(set(
                [b for b in self.prompt_buckets
                 if b <= self.prefill_chunk] + [self.prefill_chunk])))
        else:
            self._chunk_buckets = self.prompt_buckets
        self._alloc = BlockAllocator(self.kv_blocks)
        # block groups (serve/paged.py): everything above and below that
        # bears no group's name is the FULL group's; layers that state a
        # cache window share a second one, sized to the windows
        groups = {g.name: g for g in cache_groups(model)}
        if FULL not in groups:
            raise ValueError("every cached layer states a cache window: the "
                             "batcher needs a layer that keeps every position")
        self._block_bytes = block_bytes(model, self.block_size, model.dtype,
                                        groups[FULL].layers)
        self._win: Optional[WindowGroup] = None
        if WINDOW in groups:
            self._win = WindowGroup(
                groups[WINDOW], slots=S, block_size=self.block_size,
                chunk=self._chunk_buckets[-1],
                block_bytes=block_bytes(model, self.block_size, model.dtype,
                                        groups[WINDOW].layers),
                cached_tails=S if prefix_cache else 0)
        # the state group: slots, not blocks; its snapshots exist for the
        # prefix cache alone
        self._state: Optional[StateGroup] = None
        if STATE in groups:
            self._state = StateGroup(
                groups[STATE], slots=S, slot_bytes=state_slot_bytes(model),
                snapshots=SNAPSHOTS_A_SLOT * S if prefix_cache else 0)
        self._snap_pending: List[tuple] = []   # (slot, row) copies to make
        self._prefix: Optional[PrefixCache] = None
        if prefix_cache:
            win = self._win
            self._prefix = PrefixCache(
                self._alloc, self.block_size, prefix_cache_blocks,
                **({} if win is None else dict(
                    window_allocator=win.alloc, window_tail=win.tail,
                    window_max_blocks=win.cache_blocks)),
                **({} if self._state is None else dict(state=self._state)))
            # cached-but-idle runs are reclaimed before anyone sheds
            self._alloc.set_reclaimer(self._prefix.reclaim)
            if win is not None:
                win.alloc.set_reclaimer(self._prefix.reclaim_window)
        # distinct physical blocks slots hold via retain (adopted prefix
        # runs, fork rows) — these sit OUTSIDE every worst-case
        # commitment, so admission subtracts them from the pool
        self._shared_ledger: Dict[int, int] = {}
        self._cow_copies = 0
        self._forks = 0
        self._fork_salt = 0  # every attempt, successful or not
        self._px_hits = 0
        self._px_misses = 0
        self._tables_np = np.zeros((S, self._maxb), np.int32)
        self._slot_pages: List[Optional[SlotPages]] = [None] * S
        self._slot_worst = np.zeros(S, np.int64)
        self._committed = 0
        # the window group's twin of _slot_pages (a ring carries its own
        # commitment)
        self._slot_ring: List[Optional[RingPages]] = [None] * S

        self._base_key = jax.random.PRNGKey(seed)

        self._cond = threading.Condition()
        self._queue: List[_GenRequest] = []
        self._jobs: List[_PrefillJob] = []
        self._slot_req: List[Optional[_GenRequest]] = [None] * S
        self._slot_job: List[Optional[_PrefillJob]] = [None] * S
        self._closing = False
        # crash-only worker lifecycle (see ServeEngine): epoch stales a hung
        # worker, restart sheds its in-flight sequences with typed errors
        self._epoch = 0
        self._hb = time.monotonic()
        self._admitted = 0
        self._peak_active = 0
        # requests shed so far, all causes, and what that read at the last
        # published tick (a stall's record says how many fell inside it)
        self._shed_lock = threading.Lock()
        self._sheds = self._sheds_at_tick = 0
        # the flight recorder whose stack watchdog this worker armed
        self._watched: Optional[_flight.FlightRecorder] = None
        self._clock: Optional[_trace.PhaseClock] = None
        self._prefill_sigs = set()
        self._decode_sigs = set()

        # the host's view of each slot, as of the last step PUBLISHED:
        # the token the slot's next step consumes and its position. The
        # device is up to two steps further (the programs carry tokens,
        # positions and keys from step to step): these vectors, the keys
        # and the two sampling vectors are uploaded for the rows the host
        # sets, at an admission or a fork (_fresh), and not between
        self._next_tok = np.zeros(S, np.int32)
        self._pos = np.zeros(S, np.int32)
        self._temps = np.ones(S, np.float32)
        self._topks = np.full(S, V, np.int32)
        self._keys = np.zeros((S, 2), np.uint32)
        self._fresh = np.zeros(S, bool)
        # rows of the slot's request enqueued and not published yet, and the
        # tokens it has been given or promised (its first one, then a row
        # each): a request's last step is known by count before it is
        # enqueued, so no row is given beyond max_new
        self._unread = np.zeros(S, np.int32)
        self._made = np.zeros(S, np.int64)
        # a first token still on the device (the sampler's scalar), and the
        # key it left the slot (a device value too: reading it back would
        # wait for everything the device has queued): they reach the slot's
        # first decode step without visiting the host
        self._first_dev: List[Optional[object]] = [None] * S
        self._key_dev: List[Optional[object]] = [None] * S
        # prefill chunks enqueued / whose routing sums have been read
        self._chunks_enq = self._chunks_read = 0
        self._fork_calls: List[_ForkCall] = []

        m = self.metrics
        self._m_active = m.gauge("serve_gen_active_slots", self._lbl(),
                                 help="in-flight generation slots")
        self._m_qdepth = m.gauge("serve_gen_queue_depth", self._lbl(),
                                 help="generation requests waiting for a slot")
        self._m_admitted = m.counter("serve_gen_admitted_total", self._lbl(),
                                     help="generation requests prefilled")
        self._m_completed = m.counter("serve_gen_completed_total", self._lbl(),
                                      help="generation requests finished")
        self._m_tokens = m.counter("serve_gen_tokens_total", self._lbl(),
                                   help="tokens generated across all slots (each request's "
                                        "prefill-sampled first token included)")
        self._m_decode_s = m.histogram(
            "serve_gen_decode_seconds", self._lbl(), buckets=TICK_BUCKETS,
            help="one all-slots decode step: its own gen.tick.dispatch's "
                 "first stamp to the return of its own readback; the worker "
                 "enqueues the next step in between, so about a period and "
                 "a half where it runs ahead")
        self._m_ahead = m.counter(
            "serve_gen_ticks_ahead_total", self._lbl(),
            help="decode steps whose enqueue returned while the step before "
                 "them was still unread and not ready: the device had its "
                 "next step before it finished the last")
        self._m_discarded = m.counter(
            "serve_gen_rows_discarded_total", self._lbl(),
            help="rows of decode steps computed and thrown away: the step "
                 "was enqueued before the host saw its request end (eos_id, "
                 "a cancel)")
        self._m_prefill_s = m.histogram(
            "serve_gen_prefill_seconds", self._lbl(),
            help="the worker's time in one gen.prefill_chunk (per chunk when "
                 "chunked): table growth, uploads, the enqueue; unfenced, "
                 "not device time")
        self._m_queue_s = m.histogram(
            "serve_gen_queue_seconds", self._lbl(),
            help="enqueue to first prefill chunk dispatched, per request")
        self._m_first_s = m.histogram(
            "serve_gen_first_token_seconds", self._lbl(),
            help="enqueue to first token pushed, per request")
        self._m_occupancy = m.histogram(
            "serve_gen_slot_occupancy", self._lbl(),
            buckets=tuple((i + 1) / S for i in range(S)),
            help="active slots / total slots per decode tick")
        self._m_compiles = m.counter(
            "serve_compile_misses_total", self._lbl({"component": "generate"}),
            help="new (bucket, shape) signatures — each is an XLA compile")
        m.gauge("serve_kv_blocks_total", self._lbl(),
                help="allocatable KV blocks (excl. trash block)"
                ).set(self._alloc.usable)
        self._m_kv_used = m.gauge("serve_kv_blocks_used", self._lbl(),
                                  help="KV blocks currently allocated")
        self._m_kv_util = m.gauge(
            "serve_kv_block_utilization", self._lbl(),
            help="allocated / allocatable KV blocks")
        self._m_kv_bytes = m.gauge(
            "serve_kv_live_bytes", self._lbl(),
            help="bytes of KV pool backing live tokens (all layers, all "
                 "block groups)")
        m.gauge("serve_kv_token_bytes", self._lbl(),
                help="bytes of cache one token takes in the pools that were "
                     "built, all layers (of every block group) and all parts"
                ).set((self._block_bytes + (self._win.block_bytes
                                            if self._win else 0))
                      // self.block_size)
        self._m_group_used = {
            g: m.gauge("serve_kv_group_blocks_used", self._lbl({"group": g}),
                       help="KV blocks currently allocated, by block group")
            for g in groups}
        self._m_group_bytes = {
            g: m.gauge("serve_kv_group_live_bytes", self._lbl({"group": g}),
                       help="bytes of KV pool backing live tokens, by block "
                            "group (they add up to serve_kv_live_bytes)")
            for g in groups}
        if self._win is not None:
            self._m_win_released = m.counter(
                "serve_kv_window_released_total", self._lbl(),
                help="window-group blocks released because they lay wholly "
                     "behind their slot's window")
            self._m_win_alloc = m.counter(
                "serve_kv_window_allocated_total", self._lbl(),
                help="window-group blocks allocated to slots' rings")
            self._m_px_short = m.counter(
                "serve_prefix_hits_shortened_total", self._lbl(),
                help="admissions whose cached prefix run was cut short (or "
                     "to nothing) because the window group no longer held "
                     "the window's tail behind it")
        if self._state is not None:
            m.gauge("serve_state_slot_bytes", self._lbl(),
                    help="bytes of recurrent state one sequence holds, all "
                         "state layers (a slot's, and a snapshot's)"
                    ).set(self._state.slot_bytes)
            self._m_snaps = m.counter(
                "serve_state_snapshots_total", self._lbl(),
                help="state snapshots taken: a slot's state at a block "
                     "boundary copied under the hash of the cached run it "
                     "ends")
            self._m_snap_bytes = m.gauge(
                "serve_state_snapshot_bytes", self._lbl(),
                help="bytes of the snapshots the prefix cache holds now")
            self._m_px_short_state = {
                left: m.counter(
                    "serve_prefix_hits_shortened_total",
                    self._lbl({"reason": "state", "left": left}),
                    help="admissions whose cached prefix run was cut short "
                         "(left=some) or to nothing (left=none) because no "
                         "snapshot of the state layers existed at its end")
                for left in ("some", "none")}
        self._m_pf_depth = m.gauge(
            "serve_prefill_queue_depth", self._lbl(),
            help="prompts mid-prefill (chunked jobs in flight)")
        self._m_pf_chunks = m.counter(
            "serve_prefill_chunks_total", self._lbl(),
            help="prefill chunks executed")
        self._m_px_hits = m.counter(
            "serve_prefix_cache_hits_total", self._lbl(),
            help="admissions that adopted >= 1 cached prefix block")
        self._m_px_miss = m.counter(
            "serve_prefix_cache_misses_total", self._lbl(),
            help="admissions that found no cached prefix run")
        self._m_px_saved = m.counter(
            "serve_prefill_tokens_saved_total", self._lbl(),
            help="prompt tokens skipped by adopting cached prefix blocks")
        self._m_px_answer = m.counter(
            "serve_prefix_answer_tokens_cached_total", self._lbl(),
            help="tokens in the blocks finishing requests added to the "
                 "prefix cache (the blocks their decode steps filled)")
        self._m_px_shared = m.gauge(
            "serve_prefix_blocks_shared", self._lbl(),
            help="distinct KV blocks slots hold via sharing "
                 "(adopted prefix runs + fork rows)")
        self._m_cow = m.counter(
            "serve_kv_cow_copies_total", self._lbl(),
            help="copy-on-write block copies (a still-shared block "
                 "was about to be written)")
        self._m_forks = m.counter(
            "serve_gen_forks_total", self._lbl(),
            help="slots forked by block-table row copy")
        self._update_kv_gauges()
        # --- the parameters the compiled programs read. A model with a
        # compute_dtype gets, per params generation, ONE copy of the
        # registry's tree already cast to it (nn.generation.decode_params):
        # made by _warm_for at construction and in the registry's pre-flip
        # warmer, never inside a tick, so decode and prefill stream the
        # weights at compute width instead of re-casting them every step.
        # The registry's own tree (checkpoints, hot-swap, rollback) is
        # untouched; without a compute_dtype there is no copy ---
        self._casts = bool(model.config.compute_dtype)
        self._cast_lock = threading.Lock()
        # (registry tree, its copy, bytes), oldest first: the generation
        # being served, then candidates whose flip the worker has not seen
        self._copies: List[tuple] = []
        self._served: tuple = (None, None)  # (generation, tree to pass)
        self._m_casts = m.counter(
            "serve_params_cast_total", self._lbl(),
            help="compute-dtype copies of the served parameters made "
                 "(one per params generation, none inside a tick)")
        self._m_cast_bytes = m.gauge(
            "serve_params_compute_bytes", self._lbl(),
            help="bytes held by compute-dtype copies of the served "
                 "parameters (two generations coexist during a swap)")

        # --- the device programs and their pools (serve/programs.py). With
        # a persistent AOT store every generation executable loads from
        # disk before tracing, and is warmed eagerly so the decode loop
        # never traces in the request path after boot ---
        self._aot = aot_store
        snap0 = self.registry.current()
        win = self._win
        self._programs = GenPrograms(
            model, slots=S, vocab=V,
            table_blocks=self._maxb if win is None
            else {FULL: self._maxb, WINDOW: win.columns},
            kv_blocks=self.kv_blocks if win is None
            else {FULL: self.kv_blocks, WINDOW: win.alloc.num_blocks},
            block_size=self.block_size,
            chunk_buckets=self._chunk_buckets, metrics=m,
            compile_counter=self._m_compiles, store=aot_store,
            strict=self.strict_aot, snapshot=snap0,
            **({} if self._state is None
               else dict(state_snapshots=self._state.snapshots)))
        # what the layers' decode steps report of themselves (decode_sums)
        self._m_sums = [
            m.counter(f"serve_{f}_total", self._lbl(), help=what)
            for f, what in self._programs.sum_fields.items()]
        # what routing did, per program kind; nothing for a model without
        # experts
        if self._programs.routed:
            self._m_routing = {
                prog: [m.counter(f"serve_moe_{f}_total",
                                 self._lbl({"program": prog}), help=what)
                       for f, what in self._programs.routing_fields.items()]
                + [m.counter("serve_moe_layer_programs_total",
                             self._lbl({"program": prog}),
                             help="expert layers run: layers x decode steps "
                                  "and prefill chunks")]
                for prog in ("decode", "prefill")}
        t0 = time.perf_counter()
        self._warm_for(snap0.params, snap0.state)
        if self._aot is not None:
            m.gauge("serve_cold_start_seconds",
                    self._lbl({"component": "generate"}),
                    help="wall time to materialize the serving executables"
                    ).set(time.perf_counter() - t0)
        if self._aot is not None or self._casts:
            # before the flip, publish casts the candidate and warms the
            # full decode/prefill/sample executable set against the copy
            self.registry.add_warmer(self._warm_for)

        self._spawn_worker()

    @classmethod
    def from_tuned(cls, model, aot_store, workload_fingerprint: str, *,
                   registry=None, params=None, state=None, metrics=None,
                   model_name=None, **overrides) -> "ContinuousBatcher":
        """Boot with knobs resolved from the AOT store's tuned config for
        (current runtime fingerprint, ``workload_fingerprint``) — see
        ``aot/tuned.py``. Explicit ``overrides`` win; a miss boots the
        constructor defaults."""
        from ..aot.tuned import get_tuned

        config = get_tuned(aot_store, workload_fingerprint, metrics=metrics)
        opts = gen_opts_from_config(config)
        opts.update(overrides)
        return cls(model, registry=registry, params=params, state=state,
                   metrics=metrics, aot_store=aot_store,
                   model_name=model_name, **opts)

    def _spawn_worker(self) -> None:
        self._hb = time.monotonic()
        # a clock a worker; a restart's starts where the staled worker's
        # stopped being charged (obs/trace.py:PhaseClock)
        self._clock = _trace.PhaseClock(self.metrics, self._lbl(),
                                        after=self._clock)
        self._thread = threading.Thread(
            target=self._loop, args=(self._epoch, self._clock, _RunAhead()),
            daemon=True,
            name=f"serve-continuous-batcher-{self._epoch}")
        self._thread.start()

    # ---------------------------------------------------------------- warming
    def _warm_for(self, params, state) -> None:
        """Ready one params generation for the worker: make its
        compute-dtype copy, then (with a store) load-or-compile the
        programs' full static executable set against the COPY's dtypes.
        Runs at construction for the current generation and as a registry
        warmer for each publish candidate."""
        self._programs.warm(self._cast_params(params), state)

    # ------------------------------------------------- compute-dtype params
    def _make_copy(self, params) -> tuple:
        import jax

        from ..nn.generation import decode_params

        copy = decode_params(self.model, params)
        nbytes = sum(c.nbytes for c, a in zip(jax.tree.leaves(copy),
                                              jax.tree.leaves(params))
                     if c is not a)
        self._m_casts.inc()
        return params, copy, nbytes

    def _cast_params(self, params):
        """Make and keep the compute-dtype copy of one registry tree (the
        tree itself when the model has no compute_dtype). Callers hold the
        registry's publish lock (the warmer) or run before the worker
        exists (construction)."""
        if not self._casts:
            return params
        entry = self._make_copy(params)
        cur = self.registry.current().params
        with self._cast_lock:
            # publishes are serialized, so a copy that is neither the one
            # being served (first) nor the current snapshot's belongs to a
            # publish that failed after this warmer ran
            self._copies = [e for i, e in enumerate(self._copies)
                            if i == 0 or e[0] is cur] + [entry]
            self._m_cast_bytes.set(sum(e[2] for e in self._copies))
        return entry[1]

    def _params_for(self, snap):
        """The tree to hand the compiled programs for ``snap``. Worker
        thread only: its first call after a flip adopts the copy the
        pre-flip warmer made and drops the copies before it — this thread
        has returned every lease on them."""
        if not self._casts:
            return snap.params
        gen, tree = self._served
        if gen == snap.generation:
            return tree
        entry = None
        while True:
            with self._cast_lock:
                mine = [i for i, e in enumerate(self._copies)
                        if e[0] is snap.params]
                if mine:
                    del self._copies[:mine[-1]]
                elif entry is not None:
                    self._copies[:1] = [entry]
                if mine or entry is not None:
                    tree = self._copies[0][1]
                    self._m_cast_bytes.set(sum(e[2] for e in self._copies))
                    break
            # flipped without this batcher's warmer (a publish that raced
            # its construction): cast here, once, rather than serve f32
            entry = self._make_copy(snap.params)
        self._served = (snap.generation, tree)
        return tree

    # ------------------------------------------------------------------ admit
    def _lbl(self, labels: Optional[dict] = None) -> dict:
        out = dict(labels or {})
        if self.model_name is not None:
            out["model"] = self.model_name
        return out

    def _shed(self, cause: str) -> None:
        """Count one request shed (any thread)."""
        self.metrics.counter(
            "serve_shed_total", self._lbl({"cause": cause}),
            help="requests refused at admission, by cause").inc()
        with self._shed_lock:
            self._sheds += 1

    def queue_depth(self) -> int:
        """Generation requests waiting for a slot (Retry-After input)."""
        with self._cond:
            return len(self._queue)

    def submit(self, prompt, max_new_tokens: int, *, temperature: float = 1.0,
               top_k: Optional[int] = None, eos_id: Optional[int] = None,
               timeout_ms: Optional[float] = None, ctx=None) -> _GenRequest:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            raise ValueError("submit() takes one non-empty 1-D token prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.shape[0] + int(max_new_tokens) > self.capacity:
            raise CapacityError(
                f"prompt ({prompt.shape[0]}) + max_new_tokens "
                f"({max_new_tokens}) exceeds cache capacity {self.capacity}")
        worst = blocks_needed(prompt.shape[0] + int(max_new_tokens),
                              self.block_size)
        if worst > self._alloc.usable:
            # queueing can't help: this request can NEVER fit
            self._shed("over_capacity")
            raise CapacityError(
                f"request needs {worst} KV blocks but the pool only has "
                f"{self._alloc.usable} — raise kv_blocks or lower "
                f"max_new_tokens")
        deadline = (time.perf_counter() + timeout_ms / 1e3
                    if timeout_ms is not None else None)
        req = _GenRequest(prompt, max_new_tokens, temperature, top_k,
                          eos_id, deadline, ctx=ctx)
        with self._cond:
            if self._closing:
                self._shed("shutting_down")
                raise ServerClosingError("batcher is draining; not accepting "
                                         "new requests")
            if not self._thread.is_alive():
                # fail fast: a dead decode loop means this request would
                # queue forever — answer typed NOW; a watchdog (if running)
                # will restart the worker for later traffic
                self._shed("worker_dead")
                raise ServerClosingError(
                    "batcher worker thread is dead; request refused "
                    "(run a Watchdog for automatic crash-only restart)",
                    cause="worker_dead")
            if len(self._queue) >= self.queue_limit:
                self._shed("queue_full")
                raise ShedError(f"generation queue full "
                                f"({self.queue_limit}); shedding load")
            self._queue.append(req)
            self._m_qdepth.set(len(self._queue))
            self._cond.notify_all()
        return req

    def generate(self, prompt, max_new_tokens: int, *,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 ctx=None) -> np.ndarray:
        """Blocking generate. ``prompt``: (T,) ids -> returns (N,) ids;
        (B, T) -> (B, N), rows eos-padded to the longest. Mirrors
        ``nn.generation.generate`` (greedy chains match it exactly)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            return self.submit(prompt, max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               eos_id=eos_id, timeout_ms=timeout_ms,
                               ctx=ctx).wait()
        reqs = [self.submit(p, max_new_tokens, temperature=temperature,
                            top_k=top_k, eos_id=eos_id,
                            timeout_ms=timeout_ms) for p in prompt]
        outs = [r.wait() for r in reqs]
        width = max(o.shape[0] for o in outs)
        pad = eos_id if eos_id is not None else 0
        full = np.full((len(outs), width), pad, np.int32)
        for i, o in enumerate(outs):
            full[i, :o.shape[0]] = o
        return full

    def stream(self, prompt, max_new_tokens: int, *,
               temperature: float = 1.0, top_k: Optional[int] = None,
               eos_id: Optional[int] = None,
               timeout_ms: Optional[float] = None,
               ctx=None) -> Iterator[int]:
        """Submit and yield tokens one at a time as they are decoded."""
        return self.submit(np.asarray(prompt, np.int32), max_new_tokens,
                           temperature=temperature, top_k=top_k,
                           eos_id=eos_id, timeout_ms=timeout_ms,
                           ctx=ctx).stream()

    def cancel(self, req: _GenRequest, cause: str = "client_gone") -> bool:
        """Abandon one request whose consumer vanished (e.g. the SSE client
        dropped the socket mid-stream). A still-queued request is removed
        and finished immediately; an admitted one is flagged and retired by
        the worker at its next safe point (<= one decode tick), which
        releases its KV pages — cancellation never frees blocks a
        dispatched device call may still be writing. Counts
        ``serve_shed_total{cause=...}``. Idempotent; returns False when the
        request already finished."""
        err = ShedError(f"request abandoned by its consumer ({cause})",
                        cause=cause)
        queued = False
        with self._cond:
            if req.event.is_set() or req.cancelled is not None:
                return False
            if req in self._queue:
                self._queue.remove(req)
                self._m_qdepth.set(len(self._queue))
                queued = True
            else:
                req.cancelled = err
                self._cond.notify_all()
        self._shed(cause)
        if queued:
            req._finish(err)
        return True

    def fork(self, req: _GenRequest, *, max_new_tokens: Optional[int] = None,
             temperature: Optional[float] = None,
             top_k: Optional[int] = None) -> _GenRequest:
        """Clone a decoding request into a free slot by duplicating its
        block-table row with refcount++ on every block — one int32 row
        copy, never KV bytes. The primitive under best-of-n sampling and
        the speculative draft/verify follow-on.

        The child resumes from the parent's exact decode state (same
        pending token and position; its first decoded token lands at the
        same position as the parent's next one) and returns only tokens
        generated AFTER the fork point. It gets a fresh PRNG key, so
        sampled continuations diverge; at ``temperature=0`` both chains
        stay greedy and identical. Whole shared blocks are never written
        again; the partial tail block is copied on first write
        (copy-on-write), so forking is O(blocks) host work. Raises
        :class:`ShedError` when no free slot or insufficient block headroom
        exists, :class:`ServeError` when ``req`` is not currently decoding
        in a slot."""
        import jax

        with self._cond:
            self._fork_salt += 1
            salt = self._fork_salt
        # disjoint salt space from admission's fold_in(n): forks fold twice
        key = jax.random.fold_in(
            jax.random.fold_in(self._base_key, 0x666f726b), salt)
        call = _ForkCall(req, max_new_tokens, temperature, top_k,
                         np.asarray(key, np.uint32))
        # The copy is the WORKER's to make, between two turns and with no
        # step in flight: the worker runs a step or two ahead of what it has
        # published, and a copy of the published view (token, position,
        # table row, ring) taken under a step in flight would miss what that
        # step moves (a ring's blocks released behind its window, most of
        # all). A fork is rare; the worker drains its queue for it and the
        # copy is what it always was.
        with self._cond:
            self._fork_parent_locked(req)
            if threading.current_thread() is self._thread \
                    or not self._thread.is_alive() or self._closing:
                raise ServeError("fork() needs a running worker to make the "
                                 "copy, and cannot be called from it")
            self._fork_calls.append(call)
            self._cond.notify_all()
        call.done.wait()
        if call.error is not None:
            raise call.error
        return call.child

    def _fork_parent_locked(self, req: _GenRequest) -> int:
        s = req.slot
        if s is None or self._slot_req[s] is not req or req.event.is_set():
            raise ServeError("fork() needs a request currently decoding "
                             "in a slot (not queued, prefilling, or "
                             "finished)")
        return s

    def _run_forks_locked(self) -> None:
        """Under ``self._cond``, on the worker, nothing in flight: make the
        copies callers are waiting for."""
        calls, self._fork_calls = self._fork_calls, []
        for call in calls:
            try:
                call.child = self._fork_locked(call)
            except (ServeError, ValueError) as e:
                call.error = e
            call.done.set()

    def _fork_locked(self, call: _ForkCall) -> _GenRequest:
        req, max_new_tokens = call.req, call.max_new
        temperature, top_k = call.temperature, call.top_k
        s = self._fork_parent_locked(req)
        t = next((i for i in range(self.slots)
                  if self._slot_req[i] is None
                  and self._slot_job[i] is None), None)
        if t is None:
            self._shed("fork_no_slot")
            raise ShedError("fork(): no free decode slot")
        parent_pages = self._slot_pages[s]
        pos = int(self._pos[s])
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else max(1, req.max_new - len(req.out)))
        if max_new < 1:
            raise ValueError("fork max_new_tokens must be >= 1")
        if pos + max_new > self.capacity:
            raise CapacityError(
                f"fork at position {pos} + max_new_tokens {max_new} "
                f"exceeds cache capacity {self.capacity}")
        # charge only what the child can ever privately allocate: its
        # growth blocks plus one CoW copy of the partial tail; whole
        # shared blocks stay shared forever and ride the ledger instead
        worst = blocks_needed(pos + max_new, self.block_size) \
            - pos // self.block_size
        blocks = list(parent_pages.blocks)
        fresh = sum(1 for b in blocks if b not in self._shared_ledger)
        if self._committed + worst + len(self._shared_ledger) + fresh \
                > self._alloc.usable:
            self._shed("fork_capacity")
            raise ShedError(
                f"fork(): insufficient KV block headroom (need {worst} "
                f"committed + {fresh} shared)")
        if self._win is not None and not self._win.fits(pos + max_new):
            self._shed("fork_capacity")
            raise ShedError("fork(): insufficient KV block headroom in "
                            "the window group")
        child = _GenRequest(req.prompt, max_new,
                            float(temperature if temperature is not None
                                  else req.temperature),
                            top_k if top_k is not None else req.top_k,
                            req.eos_id, req.deadline)
        if self._win is not None:
            # the parent's ring, block for block: a held range of
            # logical blocks, shared until one of the two writes
            held = [b for _, b in sorted(self._slot_ring[s].blocks.items())]
            ring = self._win.open(pos + max_new)
            self._win.alloc.retain(held)
            ring.adopt(self._slot_ring[s].first, held)
            self._slot_ring[t] = ring
            self._win.tables_np[t] = self._win.tables_np[s]
        if self._state is not None:
            # the parent's state and its state at the last block boundary,
            # copied on the device (nothing is in flight: _run_forks_locked)
            with _trace.span(_trace.GEN_STATE_SNAPSHOT):
                self._programs.copy_states([(s, t)])
        self._alloc.retain(blocks)
        pages = SlotPages(self._alloc, self.block_size)
        pages.adopt(blocks)
        self._ledger_add(blocks)
        self._committed += worst
        self._slot_pages[t] = pages
        self._slot_worst[t] = worst
        self._slot_req[t] = child
        child.slot = t
        self._tables_np[t] = self._tables_np[s]
        # the five values the host sets (the same five an admission does)
        self._next_tok[t] = self._next_tok[s]
        self._pos[t] = pos
        self._temps[t] = child.temperature
        self._topks[t] = child.top_k if child.top_k else self.vocab
        self._keys[t] = call.key
        self._first_dev[t] = self._key_dev[t] = None
        self._fresh[t] = True
        self._unread[t] = self._made[t] = 0
        self._forks += 1
        self._m_forks.inc()
        self._m_admitted.inc()
        active = sum(1 for r in self._slot_req if r is not None)
        self._peak_active = max(self._peak_active, active)
        self._m_active.set(active)
        self._update_kv_gauges()
        return child

    # ---------------------------------------------------------------- serving
    def _bucket(self, t: int) -> int:
        for b in self.prompt_buckets:
            if b >= t:
                return b
        raise CapacityError(f"prompt length {t} exceeds largest prompt "
                            f"bucket {self.prompt_buckets[-1]}")

    def _chunk_bucket(self, t: int) -> int:
        for b in self._chunk_buckets:
            if b >= t:
                return b
        return self._chunk_buckets[-1]

    def _plan_chunks(self, tp: int, start: int = 0) -> List[tuple]:
        """Split a prompt into (offset, true_len, padded_bucket) chunks.
        ``start`` (block-aligned, < tp) skips the prefix already covered by
        adopted cache blocks. Full chunks run at exactly ``prefill_chunk``;
        the tail pads to the smallest chunk bucket that covers it.
        ``prefill_chunk=None`` is one whole-prompt chunk (the un-chunked
        baseline).

        A chunk's padding never reaches past ``capacity``: a learned
        position table has no row there, ``decode_forward``'s ``jnp.take``
        answers NaN for it, the NaN keys and values land in the trash
        block, and every slot with an unallocated table entry gathers that
        block (weight 0 x NaN): a run of token 0, cached for the prompts
        that share the prefix. Behind a prefix hit a tail starts off the
        chunk grid, so its bucket may not fit; it is then cut into whole
        buckets that do."""
        chunks, off = [], start
        while off < tp:
            take = tp - off
            if self.prefill_chunk is None:
                bucket = self._bucket(take)
            elif take > self.prefill_chunk:
                take = bucket = self.prefill_chunk
            else:
                bucket = self._chunk_bucket(take)
            if off + bucket > self.capacity:
                whole = [b for b in self._chunk_buckets if b <= take]
                if whole:   # else: narrower than the narrowest bucket
                    take = bucket = whole[-1]
            chunks.append((off, take, bucket))
            off += take
        return chunks

    def _update_kv_gauges(self) -> None:
        used = self._alloc.used
        live = used * self._block_bytes
        self._m_kv_used.set(used)
        self._m_kv_util.set(used / self._alloc.usable)
        self._m_group_used[FULL].set(used)
        self._m_group_bytes[FULL].set(live)
        if self._win is not None:
            wused = self._win.alloc.used
            self._m_group_used[WINDOW].set(wused)
            self._m_group_bytes[WINDOW].set(wused * self._win.block_bytes)
            live += wused * self._win.block_bytes
        if self._state is not None:
            st = self._state
            held = sum(1 for s in range(self.slots)
                       if self._slot_req[s] is not None
                       or self._slot_job[s] is not None)
            self._m_group_used[STATE].set(held + st.used)
            self._m_group_bytes[STATE].set((held + st.used) * st.slot_bytes)
            self._m_snap_bytes.set(st.used * st.slot_bytes)
            live += (held + st.used) * st.slot_bytes
        self._m_kv_bytes.set(live)
        self._m_px_shared.set(len(self._shared_ledger))

    # --- shared-block ledger: blocks held via retain (adoption/forks) sit
    # outside every commitment, so admission must subtract them from the
    # pool; counted per (block, holding slot) and sized by distinct block ---
    def _ledger_add(self, blocks) -> None:
        for b in blocks:
            self._shared_ledger[b] = self._shared_ledger.get(b, 0) + 1

    def _ledger_drop(self, blocks) -> None:
        for b in blocks:
            c = self._shared_ledger.get(b, 0)
            if c <= 1:
                self._shared_ledger.pop(b, None)
            else:
                self._shared_ledger[b] = c - 1

    def _release_pages(self, pages: SlotPages) -> None:
        """Retire a slot's pages, dropping its shared refs from the ledger
        first (refcounts make the release itself uniform)."""
        if pages.shared:
            self._ledger_drop(pages.shared)
        pages.release()

    def _write_table_row(self, s: int, blocks: List[int]) -> None:
        row = np.zeros(self._maxb, np.int32)
        row[:len(blocks)] = blocks
        self._tables_np[s] = row

    # --- the window group's half of a slot (no-ops without the group) ---
    def _ring_step(self, s: int, ring: RingPages, first_q: int,
                   upto: int) -> None:
        """Before a step whose first query sits at ``first_q`` and which
        writes positions below ``upto``: release the ring's blocks wholly
        behind that query's window, THEN map the blocks the step writes
        (newly allocated: a column never keeps the block of a lap ago),
        and write the slot's ring row."""
        released = ring.release_behind(first_q)
        new = ring.ensure(upto)
        if released:
            self._m_win_released.inc(released)
        if new:
            self._m_win_alloc.inc(len(new))
        if released or new:       # one step in block_size moves a decode's
            self._win.tables_np[s] = ring.row()

    def _release_ring(self, s: int, ring: Optional[RingPages]) -> None:
        if ring is not None:
            self._win.close(s, ring)

    def _table_rows(self, s: int):
        """Slot ``s``'s table row as the prefill program takes it: one
        ``(1, blocks)`` array, or one a group."""
        row = self._tables_np[s:s + 1].copy()
        if self._win is None:
            return row
        return {FULL: row, WINDOW: self._win.tables_np[s:s + 1].copy()}

    # --- admission: commit worst-case blocks, start a prefill job ---
    def _admit_locked(self, generation: int = 0) -> None:
        """Under ``self._cond``: hand free slots to queued requests as
        :class:`_PrefillJob` state machines (FIFO — a head request waiting
        on blocks holds the line, so big requests cannot be starved by a
        stream of small ones).

        Admission charges only NON-shared blocks: the longest cached
        prefix run is matched first (``generation`` is the registry
        generation read by the caller — a flip flushes the cache before
        any stale block can match), the gate subtracts both the charge and
        every shared block outside any commitment, and only then are the
        cached blocks adopted (refcount++) and the suffix planned."""
        for s in range(self.slots):
            if not self._queue:
                break
            if self._slot_req[s] is not None or self._slot_job[s] is not None:
                continue
            req = self._queue[0]
            tp = req.prompt.shape[0]
            hashes: List[bytes] = []
            hash_state = None
            run: List[int] = []
            if self._prefix is not None:
                hash_state = hashlib.sha256()
                hashes = prefix_hashes(req.prompt, self.block_size,
                                       hash_state)
                # never adopt the whole prompt: at least one real token
                # must prefill so the first sample has logits to read
                run = self._prefix.match(hashes, generation,
                                         (tp - 1) // self.block_size)
            win, ring, ring_run = self._win, None, []
            matched = len(run)
            if win is not None:
                if run:
                    # usable only as far as the window's tail behind the
                    # hit is still held in the window group
                    n, ring_run = self._prefix.match_window(hashes, matched)
                    run = run[:n]
                if not win.fits(tp + req.max_new):
                    break
            load, short_state = -1, None
            if self._state is not None and run:
                # usable only as far as a snapshot of the state layers
                # stands at the run's end
                n, row = self._prefix.match_state(hashes, len(run))
                if n < len(run):
                    if win is not None:
                        # the window group kept the tail behind the longer
                        # run's end and no other
                        n, row, ring_run = 0, None, []
                    short_state = "some" if n else "none"
                    run = run[:n]
                load = -1 if row is None else row
            shared = len(run)
            worst = blocks_needed(tp + req.max_new, self.block_size) - shared
            fresh = sum(1 for b in run if b not in self._shared_ledger)
            if self._committed + worst + len(self._shared_ledger) + fresh \
                    > self._alloc.usable:
                break  # wait for in-flight sequences to release blocks
            self._queue.pop(0)
            self._committed += worst
            pages = SlotPages(self._alloc, self.block_size)
            if win is not None:
                ring = win.open(tp + req.max_new)
                # the hit's tail: logical blocks shared - len .. shared - 1
                ring.adopt(shared - len(ring_run), ring_run)
                if shared < matched:
                    self._m_px_short.inc()
            if short_state is not None:     # counted once, not a pass it waits
                self._m_px_short_state[short_state].inc()
            if shared:
                self._prefix.adopt(hashes, run, ring_run)
                pages.adopt(run)
                self._ledger_add(run)
                self._px_hits += 1
                self._m_px_hits.inc()
                self._m_px_saved.inc(shared * self.block_size)
            elif self._prefix is not None:
                self._px_misses += 1
                self._m_px_miss.inc()
            job = _PrefillJob(
                req, s, pages,
                self._plan_chunks(tp, shared * self.block_size), worst,
                shared=shared, hashes=hashes, hash_state=hash_state,
                ring=ring)
            if load >= 0:
                job.load = load
                self._state.pin(load)
            self._slot_job[s] = job
            self._jobs.append(job)
        self._m_pf_depth.set(len(self._jobs))

    def _unpin_load(self, job: _PrefillJob) -> None:
        """The snapshot a job starts from is no longer needed: its first
        chunk is in the device's queue, or the job is gone."""
        if job.load >= 0:
            self._state.unpin(job.load)
        job.load = -2

    def _abort_job(self, job: _PrefillJob, err: ServeError) -> None:
        with self._cond:
            if job in self._jobs:
                self._jobs.remove(job)
            self._slot_job[job.slot] = None
            self._unpin_load(job)
            self._release_pages(job.pages)
            self._release_ring(job.slot, job.ring)
            self._committed -= job.worst
            self._write_table_row(job.slot, [])
            self._update_kv_gauges()
            self._m_pf_depth.set(len(self._jobs))
        job.req._finish(err)

    def _prefill_step(self, job: _PrefillJob, snap,
                      clock: _trace.PhaseClock, ra: _RunAhead) -> None:
        """Advance one chunk of one prompt: enqueued behind the decode step
        that runs, in front of the next."""
        with clock.span(_trace.GEN_PREFILL_CHUNK) as chunk:
            off, true_len, bucket = job.chunks[job.idx]
            with self._cond:
                if self._slot_job[job.slot] is not job:
                    return  # aborted (forced shutdown) since the tick was planned
                job.pages.ensure(off + true_len)
                self._write_table_row(job.slot, job.pages.blocks)
                if job.ring is not None:
                    self._ring_step(job.slot, job.ring, off, off + true_len)
                table_row = self._table_rows(job.slot)
                self._update_kv_gauges()
            if _prof.ACTIVE is not None:
                # live prompt tokens vs the chunk bucket they padded to
                _prof.ACTIVE.hint("generate", true_len, bucket)
            last = self._programs.prefill_chunk(
                self._params_for(snap), snap.state,
                job.req.prompt[off:off + true_len], bucket, table_row, off,
                **({} if self._state is None
                   else dict(slot=job.slot, load=job.load)))
            if self._state is not None:
                with self._cond:
                    self._unpin_load(job)
            req = job.req
            ctx = req.ctx
            if job.idx == 0:  # first chunk closes the queue wait (its offset
                # is nonzero when a cached prefix was adopted)
                self._queue_wait_over(req, chunk.t0 * 1e-9)
            if ctx is not None:
                ctx.add_stage("prefill_chunk", chunk.t0,
                              time.perf_counter_ns(), offset=off,
                              bucket=bucket)
            self._m_pf_chunks.inc()
            self._chunks_enq += 1
            ra.chunks += 1
            job.gens.add(snap.generation)
            job.last = last
            job.idx += 1
            with self._cond:
                sig = ("prefill", bucket)
                if sig not in self._prefill_sigs:
                    self._prefill_sigs.add(sig)
                    if self._aot is None:  # with a store, AotFunction counts real traces
                        self._m_compiles.inc()
        # serve_gen_prefill_seconds: the span's own two stamps
        self._m_prefill_s.observe(
            (chunk.t1 - chunk.t0) * 1e-9,
            trace_id=None if ctx is None else ctx.trace_id)
        if job.idx == len(job.chunks):
            with clock.span(_trace.GEN_FIRST_TOKEN):
                self._finish_prefill(job, ra)

    def _queue_wait_over(self, req: _GenRequest, t0: float) -> None:
        """Stamp the dispatch of a request's first prefill chunk: the one
        source of ``serve_gen_queue_seconds`` and of reqtrace's ``queue``
        stage."""
        req.disp_t = t0
        self._m_queue_s.observe(t0 - req.enq_t)
        if req.ctx is not None:
            req.ctx.add_stage("queue", int(req.enq_t * 1e9), int(t0 * 1e9))

    def _finish_prefill(self, job: _PrefillJob, ra: _RunAhead) -> None:
        """Last chunk done: enqueue the first token's sample, flip the slot
        from prefilling to decoding. The token is NOT read here (that would
        wait for the running step and the chunk, past the next step's
        deadline): it stays on the device, where the slot's first decode
        step takes it, and :meth:`_read_firsts` pushes it as soon as it is
        ready."""
        import jax

        req, s = job.req, job.slot
        gen_now = (self.registry.generation
                   if self._prefix is not None else None)
        with self._cond:
            if self._slot_job[s] is not job:
                return  # aborted (forced shutdown) mid-prefill
            self._admitted += 1
            n = self._admitted
            if self._prefix is not None and job.hashes \
                    and job.gens == {gen_now}:
                # cache this prompt's full blocks for the next request that
                # shares the prefix; skipped if a publish flipped the params
                # mid-prefill — that KV mixes generations and must retire
                # with its slot, never be adopted
                nfull = req.prompt.shape[0] // self.block_size
                self._prefix.insert(job.hashes[:nfull],
                                    job.pages.blocks[:nfull], gen_now,
                                    self._ring_tail(job.ring, nfull))
                # the slot's state stands at that boundary in its own row
                # of the snapshot pool (the last chunk left it there)
                self._snapshot_locked(s, job.hashes, nfull)
                # the answer's blocks follow under the same run's hashes
                # when the request finishes (_cache_answer)
                req.cached_run = _CachedRun(job.hashes, job.hash_state,
                                            gen_now)
                self._update_kv_gauges()
        self._flush_snapshots()
        if req.ctx is not None:
            # decode starts with the token-0 sample, not the first tick — a
            # request wedged before any tick completes still shows the stage
            req.ctx.decode_begin()
        key = jax.random.fold_in(self._base_key, n)
        key, sub = jax.random.split(key)
        tok0 = self._programs.sample(
            job.last[0], sub, req.temperature,
            req.top_k if req.top_k else self.vocab)
        with self._cond:
            if self._slot_job[s] is not job:
                return  # shed by a restart while the sampler was enqueued
            if job in self._jobs:
                self._jobs.remove(job)
            self._slot_job[s] = None
            self._slot_pages[s] = job.pages
            self._slot_worst[s] = job.worst
            self._slot_ring[s] = job.ring
            self._m_pf_depth.set(len(self._jobs))
            req.slot = s
            req.key = None
            self._slot_req[s] = req
            # the five values the host sets; the token and the key as
            # device values
            self._first_dev[s] = tok0
            self._key_dev[s] = key
            self._pos[s] = req.prompt.shape[0]
            self._temps[s] = req.temperature
            self._topks[s] = req.top_k if req.top_k else self.vocab
            self._fresh[s] = True
            self._unread[s] = 0
            self._made[s] = 1       # the first token is promised
            self._m_admitted.inc()
            active = sum(1 for r in self._slot_req if r is not None)
            self._peak_active = max(self._peak_active, active)
            self._m_active.set(active)
        ra.firsts.append(_First(req, tok0, ra.seq + 1, self._chunks_enq))

    def _read_firsts(self, ra: _RunAhead, block: bool = False) -> None:
        """Push the first tokens that can be read without waiting: those
        computed in front of a step already read back, and those the device
        says are ready. ``block``: also wait for those whose chunk the
        device is running NOW (nothing but the chunk in front of them: the
        step before it has been read back), which the caller does where the
        host has the time."""
        if not ra.firsts:
            return
        keep = []
        for f in ra.firsts:
            if f.before <= ra.done or f.tok.is_ready() \
                    or (block and f.before <= ra.done + 1):
                self._push_first(f, int(np.asarray(f.tok)))
            else:
                keep.append(f)
        ra.firsts = keep

    def _push_first(self, f: _First, tok0: int) -> None:
        """The prefill-sampled token: output like any other, and the end of
        the server's own time to first token."""
        req, s = f.req, f.req.slot
        if self._programs.routed:
            # the chunks in front of this token have run
            self._count_chunks_routing(f.chunks_upto)
        with self._cond:
            if self._slot_req[s] is not req:
                return      # shed since (a restart, a forced shutdown)
            self._first_dev[s] = None
            self._next_tok[s] = tok0    # where the slot's first step is
            #   still to be enqueued, it is a host value like a fork's
        stamp = time.perf_counter_ns()
        req._push(tok0, stamp)
        req.first_t = stamp * 1e-9
        self._m_first_s.observe(req.first_t - req.enq_t)
        self._m_tokens.inc()
        # a 1-token request finishes without ever decoding; an instant EOS
        # may have a row or two in flight, which their publish discards
        self._maybe_finish(s)

    def _ring_tail(self, ring: Optional[RingPages],
                   n: int) -> Optional[Dict[int, int]]:
        """What ``ring`` still holds of the window's tail behind a run of
        ``n`` whole blocks, ``{logical block: physical id}``: what a hit on
        that run will need (None without a window group)."""
        if ring is None:
            return None
        return {b: blk for b, blk in ring.blocks.items()
                if n - self._win.tail <= b < n}

    def _snapshot_locked(self, s: int, hashes: List[bytes], n: int) -> None:
        """Under ``self._cond``, a run of ``n`` blocks of slot ``s`` has just
        been inserted: keep the state layers' state at its end, which stands
        in the slot's own row of the snapshot pool, under the run's hash. The
        copy is made by :meth:`_flush_snapshots`, outside the lock and before
        anything else is enqueued."""
        if self._state is None:
            return
        row = self._prefix.snapshot_row(hashes, n)
        if row is not None:
            self._snap_pending.append((s, row))
            self._m_snaps.inc()

    def _flush_snapshots(self) -> None:
        """The worker, outside the lock: enqueue the snapshot copies the
        last insertions asked for (device to device; nothing is read)."""
        if self._snap_pending:
            pairs, self._snap_pending = self._snap_pending, []
            with _trace.span(_trace.GEN_STATE_SNAPSHOT, copies=len(pairs)):
                self._programs.copy_states(pairs, current=False)

    def _cache_answer(self, s: int, req: _GenRequest, generation,
                      in_flight: int = 0) -> None:
        """Under ``self._cond``, as slot ``s`` retires normally and before
        its pages go: cache the whole blocks of the request's run, the ones
        its decode steps filled included, so that a prompt which sends the
        answer back adopts them. The cache holds KV for ``prompt ++
        out[:-1]`` (the last sampled token was pushed, never fed). Only
        where every chunk, the tick that finished it (``generation``: its
        lease's) and the cache's entries are of one params generation;
        generations only grow, so the first and the last equal means all."""
        run, req.cached_run = req.cached_run, None
        if run is None \
                or not run.generation == generation == self._prefix.generation:
            return
        bs = self.block_size
        nfull = len(run.hashes)
        n_end = (req.prompt.shape[0] + len(req.out) - 1) // bs
        if n_end <= nfull:
            return
        # hashed on from the prompt's last whole block, not from its start
        hashes = run.hashes + prefix_hashes(
            np.concatenate([req.prompt[nfull * bs:],
                            np.asarray(req.out[:-1], np.int32)]),
            bs, run.state)
        added = self._prefix.insert(
            hashes, self._slot_pages[s].blocks[:n_end], generation,
            self._ring_tail(self._slot_ring[s], n_end))
        self._m_px_answer.inc(added * bs)
        # the slot's boundary state is the one at n_end blocks unless a row
        # still in flight for it (an eos_id hit, a cancel: thrown away at
        # its publish) has carried the slot over the next boundary
        fed = req.prompt.shape[0] + len(req.out) - 1
        if (fed + in_flight) // bs == n_end:
            self._snapshot_locked(s, hashes, n_end)

    def _maybe_finish(self, s: int, generation=None) -> bool:
        """Retire slot ``s`` if its request is done; True where it is still
        decoding. ``generation``: that of the lease of the decode tick that
        just ran (None before any has: there is then no block behind the
        prompt's to cache)."""
        with self._cond:
            req = self._slot_req[s]
            if req is None:
                return False
            done = (req.cancelled is not None
                    or len(req.out) >= req.max_new
                    or (req.eos_id is not None and req.out
                        and req.out[-1] == req.eos_id))
            if not done:
                return True
            self._slot_req[s] = None
            # rows of its still in flight are discarded at their publish
            # (the step holds the request, and the slot no longer does)
            in_flight = int(self._unread[s])
            self._unread[s] = self._made[s] = 0
            self._fresh[s] = False
            self._first_dev[s] = self._key_dev[s] = None
            if self._slot_pages[s] is not None:
                self._cache_answer(s, req, generation, in_flight)
                # copy-free retirement: blocks drop one reference (cached/
                # shared ones survive in their other holders) and the table
                # row zeroes (points at trash) — no device work
                self._release_pages(self._slot_pages[s])
                self._slot_pages[s] = None
                self._committed -= int(self._slot_worst[s])
                self._slot_worst[s] = 0
                self._write_table_row(s, [])
                self._release_ring(s, self._slot_ring[s])
                self._slot_ring[s] = None
                self._update_kv_gauges()
            self._m_completed.inc()
            self._m_active.set(sum(1 for r in self._slot_req if r is not None))
        self._flush_snapshots()
        req._finish(req.cancelled)
        return False

    # ------------------------------------------------------------- the tick
    # The worker runs ONE STEP AHEAD of what it has read: a turn enqueues
    # decode step n+1 and only then reads step n back, so the device goes
    # from one step to the next while the host publishes, admits and
    # prepares. What step n+1 needs of step n (tokens, positions, keys) the
    # programs carry on the device (serve/programs.py); its tables and
    # blocks depend on positions only, which the host knows ahead. What the
    # host learns one step late is what depends on a token's VALUE:
    #
    # - an ``eos_id`` hit (a cancel is handled like one). Step n+1 was
    #   enqueued with a row for the slot before step n's tokens said it had
    #   ended. That row is thrown away at its publish, never pushed
    #   (serve_gen_rows_discarded_total; the step keeps the REQUEST of each
    #   row, and the slot no longer holds it). What it wrote is harmless:
    #   it lands at the position of the last sampled token, one past
    #   everything _cache_answer caches (``prompt ++ out[:-1]``), in a block
    #   the slot still owned when the step was enqueued (prepare's
    #   copy-on-write made it private), and the device runs its queue in
    #   order: a block released at publish n and handed to another slot is
    #   written by its new owner, in a chunk or step enqueued later, after
    #   that row wrote it (tests/test_run_ahead.py).
    # - nothing else: ``max_new`` is a count (_made), so a request's last
    #   step is known before it is enqueued and no row is given beyond it.
    #
    # What is rare drains the queue first (_settle) and is as it always
    # was: a publish that flipped the params generation, a fork, the chaos
    # seam. A restart drops the bookkeeping of what was in flight (_epoch).
    def _wants_row_locked(self, s: int) -> bool:
        req = self._slot_req[s]
        return (req is not None and req.cancelled is None
                and self._made[s] < req.max_new)

    def _tick(self, epoch: int, clock: _trace.PhaseClock,
              ra: _RunAhead) -> None:
        """Enqueue the next decode step, then read back and publish the one
        before it."""
        # chaos seam, deliberately BEFORE any device dispatch or pool
        # mutation, and with nothing in flight: an injected error/hang here
        # simulates a wedged or dying decode step without ever corrupting
        # donated buffers (with the chaos plane installed the worker reads
        # every step back before it enqueues the next)
        if _faults.ACTIVE is not None:
            self._settle(epoch, clock, ra)
            _faults.ACTIVE.hit("serve.decode_step")
        # a consumer that vanished: its slot goes now, whatever is in
        # flight for it (handled like an eos_id hit)
        with self._cond:
            gone = [s for s, r in enumerate(self._slot_req)
                    if r is not None and r.cancelled is not None]
        for s in gone:
            self._maybe_finish(s)
        with clock.span(_trace.GEN_TICK) as tick:
            step = self._enqueue_step(epoch, clock, ra, tick)
            # (a drain inside the enqueue has read the step before already)
            prev, ra.step = ra.step, step
            self._read_firsts(ra)
            if prev is not None:
                self._retire_step(prev, epoch, clock, ra)
            if ra.step is None:
                # nothing runs behind them: read them now
                self._read_firsts(ra, block=True)

    def _enqueue_step(self, epoch: int, clock: _trace.PhaseClock,
                      ra: _RunAhead, tick) -> Optional[_Step]:
        """Prepare and dispatch one decode step over every slot that wants
        a row; None where none does."""
        t_decided = time.perf_counter_ns()
        lease = self.registry.lease(tag="gen_decode")
        snap = lease.__enter__()
        step = _Step(ra.seq + 1, lease, snap)
        try:
            prev = ra.step
            if prev is not None and prev.snap.generation != snap.generation:
                # a publish flipped the params: no step of the new
                # generation is enqueued behind one of the old (rare: drain)
                self._settle(epoch, clock, ra)
                prev = None
            with clock.span(_trace.GEN_TICK_PREPARE):
                with self._cond:
                    if self._epoch != epoch:
                        # staled by a crash-only restart; the new worker
                        # owns the slots
                        return None
                    active = [s for s in range(self.slots)
                              if self._wants_row_locked(s)]
                    if not active:
                        return None
                    # grow lazily to cover the token this step writes;
                    # the admission-time worst-case commitment guarantees
                    # success
                    cow: List[tuple] = []
                    for s in active:
                        pages = self._slot_pages[s]
                        wpos = int(self._pos[s]) + int(self._unread[s])
                        pages.ensure(wpos + 1)
                        wb = wpos // self.block_size
                        blk = pages.blocks[wb]
                        if self._alloc.refcount(blk) > 1:
                            # copy-on-write: someone else (a fork peer)
                            # still references the block this step writes
                            # — swap in a private copy first. Only ever
                            # the partial tail: whole shared blocks are
                            # never write targets.
                            new = self._alloc.alloc(1)[0]
                            if blk in pages.shared:
                                self._ledger_drop([blk])
                            pages.swap(wb, new)
                            cow.append((blk, new))
                            self._cow_copies += 1
                            self._m_cow.inc()
                        self._write_table_row(s, pages.blocks)
                    ring_cow = self._tick_rings(active, clock)
                    self._update_kv_gauges()
                    mask = np.zeros(self.slots, bool)
                    mask[active] = True
                    # inactive rows: zero tables (writes -> trash), which
                    # is also how the program knows them
                    tables = np.where(mask[:, None], self._tables_np, 0)
                    if self._win is not None:
                        tables = {FULL: tables, WINDOW: np.where(
                            mask[:, None], self._win.tables_np, 0)}
                    # the rows the host sets: admitted or forked since the
                    # last step was enqueued
                    fresh = None
                    if self._fresh[active].any():
                        sets = mask & self._fresh
                        fresh = (sets, np.array(self._next_tok),
                                 np.array(self._pos), np.array(self._keys),
                                 np.array(self._temps), np.array(self._topks),
                                 {int(s): (self._first_dev[s],
                                           self._key_dev[s])
                                  for s in np.flatnonzero(sets)
                                  if self._key_dev[s] is not None})
                        self._fresh[active] = False
                        for s in active:
                            self._key_dev[s] = None
                    step.rows = [(s, self._slot_req[s]) for s in active]
                    self._unread[active] += 1
                    self._made[active] += 1
                    step.chunks, ra.chunks = ra.chunks, 0
                    step.chunks_upto = self._chunks_enq
                    tick.set_metadata(active=len(active))
            if _prof.ACTIVE is not None:
                # live slots vs the fixed slot axis the decode step pads to
                _prof.ACTIVE.hint("generate", len(active), self.slots)
            with clock.span(_trace.GEN_TICK_DISPATCH) as dispatch:
                params = self._params_for(snap)
                if cow:
                    # device-side CoW copies, outside the lock (pools are
                    # only ever touched by this worker thread), before the
                    # decode dispatch
                    self._programs.copy_blocks(cow)
                if ring_cow:
                    self._programs.copy_blocks(ring_cow, WINDOW)
                step.nxt = self._programs.decode(params, snap.state, tables,
                                                 fresh)
                # one question a tick: did the device have this step before
                # it finished the last?
                step.ahead = prev is not None and not prev.nxt.is_ready()
            step.t0 = dispatch.t0
            step.start = dispatch.t1
            ra.seq = step.seq
            ra.lead_ns.add(dispatch.t1 - t_decided)
            if step.ahead:
                self._m_ahead.inc()
            elif ra.waited and not step.chunks:
                # the slack's wait outlasted the step (with a chunk in
                # front of this one the device is still busy: no verdict):
                # the length it went by is no longer the step's. Forget it;
                # the next steps are enqueued at once and measure it anew
                ra.step_ns.clear()
            lease = None
            return step
        finally:
            if lease is not None:
                lease.__exit__(None, None, None)

    def _retire_step(self, step: _Step, epoch: int,
                     clock: _trace.PhaseClock, ra: _RunAhead) -> None:
        """Read one enqueued step back and publish it: its tokens pushed,
        the clock's one stamp, its finishes."""
        nxt = ra.step
        try:
            with clock.span(_trace.GEN_TICK_READBACK) as readback:
                # with a step enqueued behind it, that enqueue asked; else
                # ask here (one question a tick either way)
                blocked = nxt.ahead if nxt is not None \
                    else not step.nxt.is_ready()
                nxt_np = np.asarray(step.nxt)
            ret = readback.t1
            ra.done = step.seq
            if nxt is not None and nxt.ahead:
                nxt.start = ret     # the device went straight on to it
            if blocked:
                # the host waited for the device, so the return is the
                # step's end: its length, or with one chunk in front of it,
                # the chunk's (more than one: a backlog from before any
                # step ran, of which nobody knows how much was left)
                took = ret - step.start
                if not step.chunks:
                    ra.step_ns.add(took)
                elif step.chunks == 1 and ra.step_ns.n:
                    ra.chunk_ns.add(max(0, took - ra.step_ns.mean))
            self._publish_step(step, nxt_np, ret, epoch, clock, ra)
        finally:
            step.release()

    def _publish_step(self, step: _Step, nxt_np: np.ndarray, ret: int,
                      epoch: int, clock: _trace.PhaseClock,
                      ra: _RunAhead) -> None:
        with clock.span(_trace.GEN_TICK_PUBLISH):
            # first tokens computed in front of this step come first: a
            # request's first token is pushed before its second
            self._read_firsts(ra)
            if self._programs.routed:
                self._count_routing(
                    "decode", self._programs.decode_routing(nxt_np))
                self._count_chunks_routing(step.chunks_upto)
            for counter, v in zip(self._m_sums,
                                  self._programs.decode_sums(nxt_np)):
                counter.inc(int(v))
            t0_ns, t1_ns = step.t0, ret
            pushes = []
            with self._cond:
                if self._epoch != epoch:
                    # restart raced the device call: drop the bookkeeping
                    return
                sig = ("decode", self.slots)
                if sig not in self._decode_sigs:
                    self._decode_sigs.add(sig)
                    if self._aot is None:  # with a store, AotFunction counts
                        self._m_compiles.inc()
                for s, req in step.rows:
                    if self._slot_req[s] is not req:
                        continue    # ended since the enqueue: discarded
                    self._unread[s] -= 1
                    if req.ctx is not None:
                        req.ctx.decode_tick(t0_ns, t1_ns)
                    tok = int(nxt_np[s])
                    self._next_tok[s] = tok
                    self._pos[s] = self._pos[s] + 1
                    pushes.append((s, req, tok))
            # serve_gen_decode_seconds: the step's own first dispatch stamp
            # to its own readback's return
            self._m_decode_s.observe((t1_ns - t0_ns) * 1e-9)
            self._m_occupancy.observe(len(step.rows) / self.slots)
            self._m_tokens.inc(len(pushes))
            if len(pushes) != len(step.rows):
                self._m_discarded.inc(len(step.rows) - len(pushes))
            # the tick's one publish stamp: every pushed token carries
            # it, and the clock measures the gap from the last one
            stamp = clock.tick()
            sheds, self._sheds_at_tick = self._sheds_at_tick, self._sheds
            if clock.stall is not None:
                self._record_stall(clock.stall, len(step.rows),
                                   self._sheds_at_tick - sheds)
            for _, req, tok in pushes:
                req._push(tok, stamp)
            for s, _, _ in pushes:
                self._maybe_finish(s, step.snap.generation)
            with self._cond:
                left = sum(1 for r in self._slot_req if r is not None)
            clock.decoding(left)
            self._watch_stacks(stamp if left else None)

    def _settle(self, epoch: int, clock: _trace.PhaseClock,
                ra: _RunAhead) -> None:
        """Drain the device's queue: read back and publish the step in
        flight, read every first token. After it the host's view of every
        slot is the device's."""
        step, ra.step = ra.step, None
        if step is not None:
            self._retire_step(step, epoch, clock, ra)
        self._read_firsts(ra, block=True)

    def _record_stall(self, stall: dict, active: int, sheds: int) -> None:
        """A stall the clock caught (counted there already) into the flight
        recorder, where one is installed, with what the scheduler held as it
        ended."""
        rec = _flight.ACTIVE
        if rec is None:
            return
        with self._cond:
            queue, jobs = len(self._queue), len(self._jobs)
        rec.record_event(
            "stall", "gen", f"{stall['gap_s']:.3f}s in {stall['phase']}",
            active_slots=active, queue_depth=queue, prefill_jobs=jobs,
            sheds=sheds, **self._lbl(), **stall)
        if stall["gap_s"] >= _flight.STACKS_AFTER_S:
            rec.note_stacks(
                f"stall of {stall['gap_s']:.3f}s in {stall['phase']} ended "
                f"time_ns={stall['time_ns']}: the dump above, if any, is its")

    def _watch_stacks(self, stamp_ns: Optional[int]) -> None:
        """Keep the installed flight recorder's stack watchdog armed while a
        slot decodes (``stamp_ns``: the tick's stamp) and cancelled while
        none does (None). With no recorder: two attribute loads."""
        rec = _flight.ACTIVE if stamp_ns is not None else None
        if rec is not None:
            rec.watch_stacks(stamp_ns)
        elif self._watched is not None:
            self._watched.unwatch_stacks()
        self._watched = rec

    def _tick_rings(self, active: List[int],
                    clock: _trace.PhaseClock) -> List[tuple]:
        """Under ``self._cond``, inside the tick's prepare phase: the window
        group's share of it. For every decoding slot, release the ring's
        blocks behind the window of the token this tick writes, map its
        block, and swap in a private copy where a fork peer still holds it
        (the prefix cache never does: a ring's column always gets a newly
        allocated block). Returns the ``(src, dst)`` copies to make."""
        cow: List[tuple] = []
        if self._win is None:
            return cow
        alloc = self._win.alloc
        with clock.span(_trace.GEN_KV_RELEASE):
            for s in active:
                ring = self._slot_ring[s]
                pos = int(self._pos[s]) + int(self._unread[s])
                self._ring_step(s, ring, pos, pos + 1)
                wb = pos // self.block_size
                blk = ring.blocks[wb]
                if alloc.refcount(blk) > 1:
                    new = alloc.alloc(1)[0]
                    ring.swap(wb, new)
                    cow.append((blk, new))
                    self._cow_copies += 1
                    self._m_cow.inc()
                    self._win.tables_np[s] = ring.row()
        return cow

    def _count_routing(self, program: str, sums) -> None:
        """One program's routing sums (ROUTING_FIELDS) into the counters."""
        *fields, programs = self._m_routing[program]
        for counter, v in zip(fields, sums):
            counter.inc(int(v))
        programs.inc(self._programs.routed)

    def _count_chunks_routing(self, upto: int) -> None:
        """The sums of the prefill chunks not counted yet among the first
        ``upto`` enqueued. Called only behind a readback of something the
        device computed after them (a step's tokens, a first token), so
        reading them waits for nothing; a chunk enqueued behind that value
        keeps its sums on the device until a later call."""
        n = upto - self._chunks_read
        if n > 0:
            self._chunks_read = upto
            for sums in self._programs.chunk_routing(n):
                self._count_routing("prefill", sums)

    def _loop(self, epoch: int, clock: _trace.PhaseClock,
              ra: _RunAhead) -> None:
        clock.bind()
        try:
            self._run_loop(epoch, clock, ra)
        except BaseException:
            # the decode loop is dying (injected fault, bug): a silent
            # death would hang every queued and in-flight caller — shed
            # everything with a typed error before the thread exits.
            # submit() fails fast afterwards; a watchdog restarts us.
            finish: List[_GenRequest] = []
            with self._cond:
                if self._epoch == epoch and not self._closing:
                    finish = self._shed_inflight_locked(include_queue=True)
            if finish:
                err = WorkerStallError(
                    "batcher worker died; generation shed, safe to retry")
                for req in finish:
                    self._shed("worker_stall")
                    req._finish(err)
            raise
        finally:
            if ra.step is not None:
                # exiting with a step in flight (staled, shut down, dying):
                # its bookkeeping is dropped, its lease returned
                ra.step.release()
            clock.close()
            self._watch_stacks(None)

    def _run_loop(self, epoch: int, clock: _trace.PhaseClock,
                  ra: _RunAhead) -> None:
        """One turn a pass. With a step in flight (``ra.step``, enqueued by
        the turn before and running now) a turn is: admit -> the turn's
        chunk, behind the running step -> the slack (wait for arrivals, as
        long as the device allows) -> enqueue the next step -> read the
        running step back -> publish it. With none it is the same without
        the waits: there is nothing to run ahead of."""
        while True:
            if self._fork_calls:
                self._settle(epoch, clock, ra)
            ra.waited = False
            plan, idle = self._admit(epoch, clock, ra)
            if plan is None:
                return
            if idle:
                # nothing to do: sleep outside the span (waiting for work is
                # not admission: the clock's gen.wait), after a second look
                # under the lock
                clock.idle()
                self._watch_stacks(None)
                with self._cond:
                    if not self._queue and not self._closing \
                            and not self._fork_calls \
                            and self._epoch == epoch:
                        self._cond.wait(0.05)
                continue
            # the chunks, the slack and the tick nest in gen.turn; its own
            # time is what lies between them, up to the loop's back edge:
            # the step's device arrays freed as _tick returns (which lends
            # the interpreter lock to the stream writers the tick just woke)
            with clock.span(_trace.GEN_TURN):
                self._prefill_steps(plan, clock, ra)
                if ra.step is not None:
                    self._slack(epoch, clock, ra, admit=not plan)
                self._tick(epoch, clock, ra)
            clock.turn_end()

    def _admit(self, epoch: int, clock: _trace.PhaseClock, ra: _RunAhead):
        """The top of a turn (and an arrival inside its slack): forks,
        admission, the plan of this turn's chunks. Returns ``(plan, idle)``;
        ``(None, _)`` where the worker is to exit."""
        with clock.span(_trace.GEN_ADMIT):
            # registry generation, read OUTSIDE self._cond (the registry
            # has its own lock): keys prefix-cache adoption, so a publish
            # flushes stale runs at the next admission
            cur = self.registry.current()
            gen = cur.generation if self._prefix is not None else 0
            if ra.step is None:
                # no lease is held here: an idle server, too, lets go of
                # the copy a publish retired
                self._params_for(cur)
            with self._cond:
                if self._epoch != epoch:
                    return None, False  # staled by a crash-only restart
                self._hb = time.monotonic()
                if self._fork_calls and ra.step is None and not ra.firsts:
                    self._run_forks_locked()
                has_active = any(r is not None for r in self._slot_req)
                idle = not self._queue and not has_active \
                    and not self._jobs and ra.step is None
                if idle and self._closing:
                    return None, True
                if idle:
                    return [], True
                self._admit_locked(gen)
                self._m_qdepth.set(len(self._queue))
                jobs = list(self._jobs)
                decoding = any(r is not None for r in self._slot_req)
            return self.scheduler.plan(jobs, decoding), False

    def _prefill_steps(self, plan: list, clock: _trace.PhaseClock,
                       ra: _RunAhead) -> None:
        """This turn's chunks, one a planned job."""
        now = time.perf_counter()
        for job in plan:
            if job.req.cancelled is not None:
                # consumer vanished mid-prefill: abort here, where
                # no device call holds the job's table row
                self._abort_job(job, job.req.cancelled)
                continue
            if job.idx == 0 and job.req.deadline is not None \
                    and now > job.req.deadline:
                self._abort_job(job, DeadlineExceededError(
                    "deadline exceeded waiting for a decode slot"))
                continue
            try:
                # one lease per chunk: hot-swap drains at chunk
                # granularity, not whole-prompt granularity
                with self.registry.lease(tag="gen_prefill") as snap:
                    self._prefill_step(job, snap, clock, ra)
            except ServeError as e:
                self._abort_job(job, e)
            except Exception as e:  # slot loop must outlive any bad request  # jaxlint: disable=broad-except
                self._abort_job(job,
                                ServeError(f"{type(e).__name__}: {e}"))

    def _slack(self, epoch: int, clock: _trace.PhaseClock, ra: _RunAhead,
               admit: bool) -> None:
        """A step runs and the next is not enqueued yet: enqueue it as LATE
        as the device allows, not as early as the host can. A request that
        arrives now still gets its chunk in front of the next step (first
        token after what is left of the running step + the chunk); once the
        next step is in the device's queue the chunk runs behind it, a
        whole step later. So the worker waits on ``self._cond``, which
        ``submit()`` notifies, until :meth:`_RunAhead.deadline`; it admits
        an arrival and enqueues its chunk at once (``admit``: the turn has
        not spent its chunks yet; one plan a turn), and reads a first token
        whose chunk the device runs meanwhile. Where the host's lead is
        longer than the step the deadline has passed before it is asked:
        no wait. The time is the wait for the running step, and is booked
        as such: gen.tick.readback."""
        with clock.span(_trace.GEN_TICK_READBACK):
            while True:
                deadline = ra.deadline()
                if deadline is None \
                        or time.perf_counter_ns() >= deadline:
                    return
                self._read_firsts(ra, block=True)
                with self._cond:
                    if self._epoch != epoch or self._closing \
                            or self._fork_calls:
                        return
                    if not any(self._wants_row_locked(s)
                               for s in range(self.slots)) \
                            and not self._jobs and not self._queue:
                        return      # no next step to hold back
                    if not (admit and self._queue):
                        left = deadline - time.perf_counter_ns()
                        if left > 0:
                            ra.waited = True
                            self._cond.wait(left * 1e-9)
                    arrived = admit and bool(self._queue)
                if arrived:
                    admit = False
                    plan, _ = self._admit(epoch, clock, ra)
                    if plan is None:
                        return
                    self._prefill_steps(plan, clock, ra)

    # ------------------------------------------------- watchdog + crash-only
    def heartbeat(self) -> float:
        """Monotonic timestamp of the decode loop's last liveness beat."""
        return self._hb

    def worker_alive(self) -> bool:
        return self._thread.is_alive()

    def _shed_inflight_locked(self, include_queue: bool
                              ) -> List[_GenRequest]:
        """Under ``self._cond``: strip every in-flight sequence (slots,
        prefill jobs — plus the queue when asked) out of the batcher state,
        releasing KV pages, and return the orphaned requests for the caller
        to finish OUTSIDE the lock."""
        finish: List[_GenRequest] = []
        if include_queue:
            finish.extend(self._queue)
            self._queue.clear()
        for job in list(self._jobs):
            self._unpin_load(job)
            self._release_pages(job.pages)
            self._release_ring(job.slot, job.ring)
            self._slot_job[job.slot] = None
            self._committed -= job.worst
            finish.append(job.req)
        self._jobs.clear()
        for s, req in enumerate(self._slot_req):
            if req is not None:
                finish.append(req)
                self._slot_req[s] = None
            if self._slot_pages[s] is not None:
                self._release_pages(self._slot_pages[s])
                self._slot_pages[s] = None
                self._committed -= int(self._slot_worst[s])
                self._slot_worst[s] = 0
            self._release_ring(s, self._slot_ring[s])
            self._slot_ring[s] = None
        self._tables_np[:] = 0
        # what was in flight for them is dropped at the epoch check; a fork
        # waiting for the worker has lost its parent
        self._unread[:] = 0
        self._made[:] = 0
        self._fresh[:] = False
        self._first_dev = [None] * self.slots
        self._key_dev = [None] * self.slots
        calls, self._fork_calls = self._fork_calls, []
        for call in calls:
            call.error = ServeError("fork(): the batcher shed its in-flight "
                                    "generations (restart or shutdown)")
            call.done.set()
        self._update_kv_gauges()
        self._m_pf_depth.set(0)
        self._m_qdepth.set(len(self._queue))
        self._m_active.set(0)
        return finish

    def restart_worker(self, reason: str = "watchdog") -> bool:
        """Crash-only decode-loop restart: stale the current worker by
        epoch, shed its in-flight sequences (slots + prefill jobs) with
        typed :class:`~.errors.WorkerStallError`, reclaim its registry
        leases, and spawn a fresh worker. Queued (not yet admitted)
        requests survive and are served by the new worker. Returns False
        if the batcher is shutting down."""
        with self._cond:
            if self._closing:
                return False
            old = self._thread
            self._epoch += 1
            finish = self._shed_inflight_locked(include_queue=False)
            self._spawn_worker()
            self._cond.notify_all()
        err = WorkerStallError(
            f"in-flight generation abandoned by batcher restart ({reason}); "
            f"safe to retry")
        for req in finish:
            self._shed("worker_stall")
            req._finish(err)
        self.registry.release_thread(old.ident if old is not None else None)
        return True

    def aot_functions(self) -> dict:
        """Tag -> :class:`~..aot.AotFunction` for every store-backed
        generation executable ({} without a store) — how a prebuild run
        gathers the concrete keys for the coverage record."""
        return self._programs.aot_functions()

    # -------------------------------------------------------------- lifecycle
    @property
    def compile_signatures(self) -> set:
        with self._cond:
            return self._prefill_sigs | self._decode_sigs

    @property
    def peak_active_slots(self) -> int:
        with self._cond:
            return self._peak_active

    def kv_block_stats(self) -> dict:
        """Allocator snapshot: totals, usage, live bytes, and the sharing
        picture (prefix cache + shared blocks + CoW/forks)."""
        with self._cond:
            used = self._alloc.used
            out = {"block_size": self.block_size,
                   "blocks_total": self._alloc.usable,
                   "blocks_used": used,
                   "blocks_committed": self._committed,
                   "live_bytes": used * self._block_bytes,
                   "blocks_shared": len(self._shared_ledger),
                   "cow_copies": self._cow_copies,
                   "forks": self._forks}
            if self._win is not None:
                w = self._win
                out["live_bytes"] += w.alloc.used * w.block_bytes
                out["window_group"] = {
                    "window": w.window, "ring_blocks": w.columns,
                    "blocks_total": w.alloc.usable,
                    "blocks_used": w.alloc.used,
                    "blocks_committed": w.committed,
                    "live_bytes": w.alloc.used * w.block_bytes}
            if self._state is not None:
                st = self._state
                out["state_group"] = {
                    "slot_bytes": st.slot_bytes, "snapshots": st.snapshots,
                    "snapshots_used": st.used, "snapshots_taken": st.taken,
                    "snapshots_evicted": st.evictions}
            if self._prefix is not None:
                px = self._prefix.stats()
                px["hits"] = self._px_hits
                px["misses"] = self._px_misses
                out["blocks_cached"] = px["entries"]
                out["prefix_cache"] = px
            return out

    def flush_prefix_cache(self) -> int:
        """Release every cached prefix run (admin/testing: proves cached
        blocks are the only thing keeping ``blocks_used`` nonzero after a
        drain). Returns the number of entries dropped."""
        if self._prefix is None:
            return 0
        with self._cond:
            n = self._prefix.flush()
            self._update_kv_gauges()
            return n

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> bool:
        """``drain=True`` finishes every queued and in-flight generation
        first; ``drain=False`` errors them out immediately.

        Returns True on a clean worker exit. If the worker is still alive
        when ``timeout`` expires (a hung in-flight request), it is
        abandoned crash-only style: all remaining work is answered with a
        typed :class:`~.errors.DrainTimeoutError`, its registry leases are
        reclaimed, and False is returned — shutdown never hangs."""
        finish = []
        with self._cond:
            self._closing = True
            if not drain:
                finish = self._shed_inflight_locked(include_queue=True)
            self._cond.notify_all()
        if finish:
            err = ServerClosingError("batcher shut down before dispatch")
            for req in finish:
                req._finish(err)
        self._thread.join(timeout)
        # no worker will read what a later publish would ready here, and
        # the copies' device memory goes with it, not with the last
        # reference to this object
        self.registry.remove_warmer(self._warm_for)
        with self._cast_lock:
            self._copies = []
            self._served = (None, None)
            self._m_cast_bytes.set(0)
        if not self._thread.is_alive():
            return True
        with self._cond:
            self._epoch += 1  # stale the wedged worker
            finish = self._shed_inflight_locked(include_queue=True)
            self._cond.notify_all()
        err = DrainTimeoutError(
            f"shutdown drain timed out after {timeout}s with generation "
            f"in flight")
        for req in finish:
            self._shed("drain_timeout")
            req._finish(err)
        self.registry.release_thread(self._thread.ident)
        return False
