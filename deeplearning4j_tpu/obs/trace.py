"""Span tracer — nested wall-clock spans exportable as Chrome-trace JSON
(loadable in Perfetto / chrome://tracing) — and the one seam through which
the package's hot paths put host spans into the JAX profiler's own trace.

Stdlib only at import. Spans use the monotonic ``time.perf_counter_ns`` clock
(never ``time.time`` — NTP steps would produce negative durations) and
per-thread span stacks, so concurrent threads (AsyncIterator prefetch, server
handler pools) each get a correctly nested track keyed by ``tid``.

Hot-path spans: :func:`span` opens a ``jax.profiler.TraceAnnotation`` (a
TraceMe) and nothing else. While a profiler session is live
(``jax.profiler.start_trace`` / ``ProfilerListener``) the profiler writes the
event into the same ``.xplane.pb``, on the ``/host:CPU`` plane, as the
device's ``XLA Ops``; with no session it costs one flag test. The session is
the only switch. :meth:`Tracer.span` opens the same annotation beside its own
record, so ``StepTelemetry``'s spans land on the host plane of a traced run
too. The names below are the table every site, test and trace reader uses
(``benchmark/harness/host_spans.py`` keeps a copy: the benchmark imports
nothing from the program).

The generation worker's clock: :class:`PhaseClock` hands out the context
managers for the worker's sites (``clock.span(name)``): the annotation exactly
as :func:`span` opens it, and a ``perf_counter_ns`` stamp beside it, session
or not. From those stamps it keeps the worker's exclusive time by innermost
phase for its whole life, and counts, times and splits every stall between two
published ticks (``obs/README.md``: the phase table and the suspects).

The trace format is the Chrome trace-event JSON flavor Perfetto ingests
natively: complete events (``ph: "X"``) with microsecond ``ts``/``dur``,
instant events (``ph: "i"``), and thread-name metadata (``ph: "M"``). See
``obs/README.md`` for how to open the output.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

# --- the span-name table (serve/continuous.py unless said) ----------------
GEN_ADMIT = "gen.admit"                  # _run_loop top: lock, admission, plan
GEN_PREFILL_CHUNK = "gen.prefill_chunk"  # one _prefill_step (dense: the prefill)
GEN_FIRST_TOKEN = "gen.first_token"      # first-token sample and its readback
GEN_TICK = "gen.tick"                    # one _tick; metadata active=<slots>
GEN_TICK_PREPARE = "gen.tick.prepare"    # locked: CoW, ensure, tables, copies
GEN_TICK_DISPATCH = "gen.tick.dispatch"  # CoW copies, uploads, decode enqueued
GEN_TICK_READBACK = "gen.tick.readback"  # tokens and keys back on the host
GEN_TICK_PUBLISH = "gen.tick.publish"    # metrics, bookkeeping, pushes, finishes
GEN_TURN = "gen.turn"                    # the worker's turn after admission: the
#   chunks and the tick nest in it; its own time is what lies between them
#   (leases, the step's arrays freed, the interpreter lock lent to writers)
GEN_KV_RELEASE = "gen.kv_release"        # inside gen.tick.prepare, only for a
#   model with a window block group: blocks behind the windows released, the
#   rings grown (not in SPAN_NAMES: a model without the group never opens it)
GEN_STATE_SNAPSHOT = "gen.state_snapshot"  # only for a model with a state
#   group: the device copies that keep a slot's state at a block boundary as
#   a snapshot under the prefix cache (and a fork's copy of a state). A plain
#   annotation inside gen.first_token or gen.tick.publish, never a phase of
#   the worker's clock (not in SPAN_NAMES: no other model opens it)
HTTP_STREAM_WRITE = "http.stream_write"  # serve/http.py: one SSE event written
GC_PAUSE = "gc.pause"                    # one collection; generation=0|1|2
SPAN_NAMES = (GEN_ADMIT, GEN_PREFILL_CHUNK, GEN_FIRST_TOKEN, GEN_TICK,
              GEN_TICK_PREPARE, GEN_TICK_DISPATCH, GEN_TICK_READBACK,
              GEN_TICK_PUBLISH, GEN_TURN, HTTP_STREAM_WRITE, GC_PAUSE)
GEN_WAIT = "gen.wait"    # a phase of the worker's PhaseClock and never a span:
#   the worker outside every span, which is its idle wait for work
GEN_STALL = "gen.stall"  # instant on the worker's line of a live session where
#   its clock caught a stall; gap_ms=<the gap>, phase=<where most of it fell>
# the phases of the worker's clock: the wait, and the spans the worker opens
WORKER_PHASES = (GEN_WAIT, GEN_ADMIT, GEN_TURN, GEN_PREFILL_CHUNK,
                 GEN_FIRST_TOKEN, GEN_TICK, GEN_TICK_PREPARE, GEN_KV_RELEASE,
                 GEN_TICK_DISPATCH, GEN_TICK_READBACK, GEN_TICK_PUBLISH)


class _NullSpan:
    """Shared no-op context manager for a disabled tracer or a process
    without JAX (stateless, so one instance is safely reentrant across
    threads)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()

_ANNOTATION = None   # jax.profiler.TraceAnnotation, once JAX is in the process


def _annotation():
    """``jax.profiler.TraceAnnotation``, looked up once. Never the reason JAX
    gets imported: a process that has not loaded it (a router, a scraper)
    has no profiler session either, and keeps getting None."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def span(name: str, **args):
    """Context manager for a hot-path site: a profiler ``TraceAnnotation``
    called ``name`` (``args`` become the event's stats). ``set_metadata``
    on the entered span adds stats known only later."""
    ann = _ANNOTATION or _annotation()
    return _NULL_SPAN if ann is None else ann(name, **args)


class _Span:
    """One live span; created by :meth:`Tracer.span`, records on ``__exit__``."""

    __slots__ = ("tracer", "name", "args", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._depth = 0
        self._ann = span(name, **args)   # the same span, in the profiler's trace

    def __enter__(self):
        tr = self.tracer
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        stack = tr._stack()
        self._depth = len(stack)
        if stack:
            self.args = dict(self.args, parent=stack[-1])
        stack.append(self.name)
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        tr = self.tracer
        stack = tr._stack()
        # Unwind to the depth recorded at __enter__: an exception thrown
        # between our __enter__ and a nested span's __exit__ leaves orphan
        # entries above us, so "pop only if stack[-1] == self.name" would
        # skip the pop and corrupt parent attribution for every later span
        # on this thread.
        if len(stack) > self._depth:
            del stack[self._depth:]
        tr._add({"name": self.name, "ph": "X", "cat": "obs",
                 "ts": (self._t0 - tr._epoch_ns) / 1e3,
                 "dur": (end - self._t0) / 1e3,
                 "pid": tr._pid, "tid": threading.get_ident(),
                 **({"args": self.args} if self.args else {})})
        return False


class Tracer:
    """Collects spans; exports ``{"traceEvents": [...]}`` Chrome-trace JSON.

    ``enabled=False`` makes :meth:`span`/:meth:`instant` strict no-ops (one
    shared null context manager, no allocation). ``max_events`` bounds host
    memory for long runs — past it, events are counted as dropped instead of
    appended, and the drop count rides along in the export's ``otherData``.
    """

    def __init__(self, enabled: bool = True, max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self.dropped = 0
        self._epoch_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._named_tids: set = set()

    def _stack(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _add(self, event: dict) -> None:
        tid = event.get("tid")
        with self._lock:
            # Name the track only when the event comes from its own thread:
            # async events may carry a foreign tid (a stage closed on behalf
            # of the thread that ran it) and must not steal its label.
            if (tid is not None and tid not in self._named_tids
                    and tid == threading.get_ident()):
                self._named_tids.add(tid)
                self._events.append(
                    {"name": "thread_name", "ph": "M", "pid": self._pid,
                     "tid": tid,
                     "args": {"name": threading.current_thread().name}})
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(event)

    # --- public API ---
    def span(self, name: str, **args):
        """Context manager timing a nested span: ``with tracer.span("x"):``"""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (compile events, epoch boundaries)."""
        if not self.enabled:
            return
        self._add({"name": name, "ph": "i", "s": "t", "cat": "obs",
                   "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
                   "pid": self._pid, "tid": threading.get_ident(),
                   **({"args": args} if args else {})})

    def async_event(self, name: str, id_: str, t0_ns: int, end_ns: int,
                    tid: Optional[int] = None, cat: str = "request",
                    **args) -> None:
        """Async begin/end pair (``ph: "b"/"e"``) keyed by ``id``.

        Perfetto stitches every async event sharing ``(cat, id)`` into one
        track regardless of which thread emitted it — this is how a request
        whose stages run on the HTTP handler, the batcher worker, and the
        watchdog becomes a single flow. Timestamps are explicit (the same
        ``perf_counter_ns`` clock as spans) so a stage can be recorded after
        the fact; ``tid`` may name the thread that actually *ran* the stage
        when the recording thread differs.
        """
        if not self.enabled:
            return
        tid = threading.get_ident() if tid is None else tid
        base = {"cat": cat, "id": id_, "pid": self._pid, "tid": tid}
        self._add({**base, "name": name, "ph": "b",
                   "ts": (t0_ns - self._epoch_ns) / 1e3,
                   **({"args": args} if args else {})})
        self._add({**base, "name": name, "ph": "e",
                   "ts": (end_ns - self._epoch_ns) / 1e3})

    @property
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object (Perfetto-loadable as-is)."""
        return {"traceEvents": self.events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: Optional[str] = None) -> str:
        s = json.dumps(self.to_chrome())
        if path:
            with open(path, "w") as f:
                f.write(s)
        return s

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._named_tids.clear()
            self.dropped = 0


class GcPauses:
    """One ``gc.callbacks`` hook: every collection is observed into
    ``process_gc_pause_seconds{generation}`` and shown as a ``gc.pause``
    span. A collection stops every Python thread (it runs under the GIL, on
    whichever thread tripped the threshold) and collections never nest, so
    one start time is enough. Two clock reads per collection; nothing per
    tick. ``install``/``remove`` add and drop exactly this one entry.
    ``seconds`` is the process's sum over every installed hook (the collector
    is the process's): a worker's :class:`PhaseClock` reads it once a tick to
    say how much of a stall was the collector's."""

    seconds = 0.0

    def __init__(self, metrics):
        self._hist = [metrics.histogram(
            "process_gc_pause_seconds", {"generation": str(g)},
            help="stop-the-world time of one garbage collection, by the "
                 "generation collected") for g in range(3)]
        self._t0 = 0.0
        self._span = _NULL_SPAN

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = span(GC_PAUSE, generation=info["generation"])
            self._span.__enter__()
            self._t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self._t0
            self._span.__exit__(None, None, None)
            GcPauses.seconds += dt
            self._hist[info["generation"]].observe(dt)

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)


# --- the generation worker's clock ------------------------------------------
# A gap between two published ticks is a stall when it is longer than both of
# these. Constants and not options: with a second reading of "stall" two runs'
# counts could not be compared.
STALL_FLOOR_NS = 100_000_000    # 100 ms: above the jitter of the shortest tick
#   any configuration has (12 ms), below the ~150 ms stalls of all streams
STALL_FACTOR = 4                # x the mean of the last STALL_GAPS gaps: a tick
#   that carries a prefill chunk is ~1.5 x a plain one and stays out, and so
#   do the 45 ms ticks of the slowest configuration
STALL_GAPS = 64
# The generation worker enqueues decode step n+1 while step n runs, as late as
# the device allows (serve/continuous.py: so that an arrival's prefill chunk
# still goes in front of it). It aims for the enqueue to return this long
# before the running step's expected end: above the jitter of the host's own
# lead (prepare + dispatch, 2-5 ms with a spread under a millisecond), far
# below a step. Late costs an idle gap (serve_gen_ticks_ahead_total misses
# one); early costs an arrival one step of its time to first token.
AHEAD_MARGIN_NS = 2_000_000
STALL_TURNS = 32                # turns kept for a stall's record
STALL_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
SCHEDSTAT = "/proc/thread-self/schedstat"   # ns running, ns RUNNABLE and not
#   running, slices: of the thread that opens it


class _Phase:
    """One name's site on a :class:`PhaseClock`'s thread, made once and
    entered many times (the worker nests no name in itself): the annotation
    :func:`span` opens, and the clock's stamps ``t0`` / ``t1`` of its last
    enter and exit."""

    __slots__ = ("name", "t0", "t1", "_clock", "_idx", "_ann")

    def __init__(self, clock: "PhaseClock", name: str, idx: int):
        self.name = name
        self.t0 = self.t1 = 0
        self._clock = clock
        self._idx = idx
        self._ann = _NULL_SPAN

    # enter and exit book the time since the clock's last stamp to the phase
    # open until now (PhaseClock._stamp, written out: sixteen of these a tick)
    def __enter__(self):
        self._ann = span(self.name)
        self._ann.__enter__()
        c = self._clock
        self.t0 = now = c._now()
        c._ns[c._cur] += now - c._last
        c._last = now
        c._stack.append(c._cur)
        c._cur = self._idx
        return self

    def __exit__(self, *exc):
        c = self._clock
        self.t1 = now = c._now()
        c._ns[c._cur] += now - c._last
        c._last = now
        c._cur = c._stack.pop()
        self._ann.__exit__(*exc)
        return False

    def set_metadata(self, **args) -> None:
        self._ann.set_metadata(**args)


class PhaseClock:
    """The generation worker's one clock, on for the worker's whole life,
    profiler session or not. One a worker thread; only that thread stamps it.

    *Phases.* ``span(name)`` is the context manager of the worker's sites: the
    ``TraceAnnotation`` as :func:`span` opens it, and a ``perf_counter_ns``
    stamp at enter and at exit. Time is kept EXCLUSIVE, by innermost open
    phase (the rule ``benchmark/harness/host_spans.py:innermost`` applies to a
    trace), in one fixed slot a phase; outside every span the worker is in
    ``gen.wait``. The accounting is closed: from ``t_start`` to any stamp the
    phases add up to the elapsed nanoseconds. ``turn_end`` (the loop's back
    edge) and ``idle`` move what accrued into
    ``serve_gen_phase_seconds_total{phase}`` and ``serve_gen_ticks_total``:
    once a tick, not once a span.

    *A restart.* ``PhaseClock(..., after=old)`` retires ``old`` (a retired
    clock charges the counters nothing more, whatever its thread still
    stamps) and starts at the stamp up to which ``old`` HAD charged them; what
    lies between that stamp and the new worker's first is booked to the phase
    the old worker was last seen in (its uncounted ticks come along). So the
    counters hold every nanosecond since the first worker started once, and
    none twice.

    *Stalls.* ``tick()`` is the tick's one publish stamp. While a slot stayed
    in decode since the previous one (``decoding(left)``; ``idle`` voids the
    gap) the time between two of them is a token gap of every stream, and one
    longer than ``max(STALL_FLOOR_NS, STALL_FACTOR x the mean of the last
    STALL_GAPS gaps)`` is a stall: counted, observed, and split from reads
    taken once a tick into its seconds by phase, the worker's own CPU seconds
    (``thread_time_ns``) and the whole process's (``process_time_ns``: beside
    an idle worker they say whether ANOTHER thread of the process ran, holding
    the interpreter lock, or nobody did), the seconds it was runnable and not
    running (``schedstat``'s second field, where the kernel keeps one: the
    file is the opening thread's own, so ``bind`` opens it on the worker's)
    and the collector's (:class:`GcPauses`). ``stall`` then holds the record, for the
    flight recorder, until the next tick."""

    def __init__(self, metrics, labels: Optional[Dict[str, str]] = None,
                 now: Callable[[], int] = time.perf_counter_ns,
                 after: Optional["PhaseClock"] = None):
        self._now = now
        self._metrics = metrics
        self._labels = lbl = dict(labels or {})
        names = WORKER_PHASES
        self._phases = {n: _Phase(self, n, i) for i, n in enumerate(names)}
        self._ns = [0] * len(names)         # exclusive ns by phase, so far
        self._flushed = [0] * len(names)    # ... as of the last flush
        self._mark_ns = [0] * len(names)    # ... as of the last tick()
        self._stack: List[int] = []
        # between flush (the worker) and retire (whoever restarts it)
        self._flush_lock = threading.Lock()
        self._retired = False
        self._ticks = self._ticks_flushed = 0
        if after is None:
            self._cur = 0                   # GEN_WAIT: no span open
            self.t_start = now()
        else:
            self.t_start, self._cur, self._ticks = after._retire()
        self._last = self._flushed_to = self.t_start
        self.t_end: Optional[int] = None    # close()'s stamp
        # the last tick(): its stamp, and the three reads taken beside it
        self._armed = False
        self._mark_t = self._mark_cpu = self._mark_pcpu = self._mark_rq = 0
        self._mark_gc = 0.0
        self._rq_fd = -1
        self._gaps = [0] * STALL_GAPS
        self._gaps_sum = self._gaps_n = self._gaps_i = 0
        # the ends of the last turns, a row each: [stamp, ns by phase so far]
        # (one more row than turns: a turn is the difference of two)
        self._turns = [[0] * (1 + len(names)) for _ in range(STALL_TURNS + 1)]
        self._turns[0][0] = self.t_start
        self._turn_i = self._turn_n = 1
        self.stall: Optional[dict] = None
        m = metrics
        self._m_phase = [m.counter(
            "serve_gen_phase_seconds_total", {**lbl, "phase": p},
            help="the generation worker's time by innermost phase (its spans "
                 "and gen.wait), exclusive: the phases add up to its wall "
                 "time") for p in names]
        self._m_ticks = m.counter(
            "serve_gen_ticks_total", lbl, help="decode ticks published")
        self._m_stalls = m.counter(
            "serve_gen_stalls_total", lbl,
            help="gaps between two published ticks, a slot decoding "
                 "throughout, longer than max(100 ms, 4 x the mean of the "
                 "last 64)")
        self._m_stall_s = m.histogram(
            "serve_gen_stall_seconds", lbl, buckets=STALL_BUCKETS,
            help="one stall of the generation worker: the whole gap")
        self._m_stall_phase = [m.counter(
            "serve_gen_stall_seconds_total", {**lbl, "phase": p},
            help="seconds of the worker's stalls, by the phase they fell in")
            for p in names]
        self._m_stall_cpu = m.counter(
            "serve_gen_stall_thread_cpu_seconds_total", lbl,
            help="the worker thread's own CPU seconds inside its stalls")
        self._m_stall_pcpu = m.counter(
            "serve_gen_stall_process_cpu_seconds_total", lbl,
            help="CPU seconds of the whole process, every thread, inside the "
                 "worker's stalls")
        self._m_stall_gc = m.counter(
            "serve_gen_stall_gc_seconds_total", lbl,
            help="the collector's stop-the-world seconds inside the "
                 "worker's stalls")
        self._m_stall_rq = None     # made by bind(), where the kernel keeps it

    # --- phases --------------------------------------------------------------
    def span(self, name: str) -> _Phase:
        return self._phases[name]

    def _stamp(self) -> int:
        """Now, the time since the last stamp booked to the open phase."""
        now = self._now()
        self._ns[self._cur] += now - self._last
        self._last = now
        return now

    def totals(self) -> Dict[str, int]:
        """Exclusive nanoseconds by phase from ``t_start`` to now (it takes
        a stamp: the worker's thread only)."""
        self._stamp()
        return dict(zip(WORKER_PHASES, self._ns))

    def _flush(self) -> None:
        with self._flush_lock:
            if self._retired:
                return
            ns, flushed = self._ns, self._flushed
            for i, counter in enumerate(self._m_phase):
                if ns[i] != flushed[i]:
                    counter.inc((ns[i] - flushed[i]) * 1e-9)
                    flushed[i] = ns[i]
            self._flushed_to = self._last
            if self._ticks != self._ticks_flushed:
                self._m_ticks.inc(self._ticks - self._ticks_flushed)
                self._ticks_flushed = self._ticks

    def _retire(self) -> Tuple[int, int, int]:
        """Any thread, once: no flush after this one returns charges the
        counters. Returns the stamp up to which they have been charged, the
        phase this clock's worker is in and the ticks it has published and
        not yet counted (``PhaseClock(after=...)`` takes all three over)."""
        with self._flush_lock:
            self._retired = True
            return (self._flushed_to, self._cur,
                    self._ticks - self._ticks_flushed)

    def turn_end(self) -> None:
        """The loop's back edge after a turn: what accrued goes into the
        counters, and the turn's end into the ring a stall's record is cut
        from."""
        row = self._turns[self._turn_i]
        row[0] = self._last
        row[1:] = self._ns
        self._turn_i = (self._turn_i + 1) % len(self._turns)
        self._turn_n = min(self._turn_n + 1, len(self._turns))
        self._flush()

    def idle(self) -> None:
        """The worker found nothing to do and is about to wait: a server
        waiting for work is not stalled, so the gap across the wait never
        counts."""
        self._armed = False
        self._flush()

    def bind(self) -> None:
        """On the worker's own thread, before its loop: what a retired clock
        left uncharged is booked (to the phase it named), and the thread's
        schedstat opened (``thread-self`` is whoever opens it)."""
        self._stamp()
        self._cur = 0
        try:
            self._rq_fd = os.open(SCHEDSTAT, os.O_RDONLY)
        except OSError:
            return      # no such kernel: the counter is never created
        self._m_stall_rq = self._metrics.counter(
            "serve_gen_stall_runqueue_seconds_total", self._labels,
            help="seconds inside its stalls that the worker thread was "
                 "runnable and not running (schedstat's run-queue wait)")

    def close(self) -> None:
        """The worker is exiting: the last stamp and flush, the descriptor."""
        self.t_end = self._stamp()
        self._flush()
        fd, self._rq_fd = self._rq_fd, -1
        if fd >= 0:
            os.close(fd)

    # --- stalls --------------------------------------------------------------
    def _runqueue_ns(self) -> int:
        if self._rq_fd < 0:
            return 0
        try:
            return int(os.pread(self._rq_fd, 64, 0).split()[1])
        except (OSError, IndexError, ValueError):
            return 0

    def tick(self) -> int:
        """The tick's one publish stamp, taken as its tokens are pushed;
        ``stall`` is the record of the stall this gap was, or None."""
        now = self._stamp()
        cpu = time.thread_time_ns()
        pcpu = time.process_time_ns()
        rq = self._runqueue_ns()
        gc_s = GcPauses.seconds
        self._ticks += 1
        self.stall = None
        if self._armed:
            gap = now - self._mark_t
            n = self._gaps_n
            limit = STALL_FLOOR_NS
            if n:
                limit = max(limit, STALL_FACTOR * self._gaps_sum // n)
            if gap > limit:
                self._stalled(now, gap, cpu, pcpu, rq, gc_s)
                # into the mean at the limit: one stall does not hide the
                # next, and a lasting change of pace is learned, not reported
                # for ever
                gap = limit
            i = self._gaps_i
            self._gaps_sum += gap - self._gaps[i]
            self._gaps[i] = gap
            self._gaps_i = (i + 1) % STALL_GAPS
            if n < STALL_GAPS:
                self._gaps_n = n + 1
        self._mark_t, self._mark_cpu, self._mark_pcpu = now, cpu, pcpu
        self._mark_rq, self._mark_gc = rq, gc_s
        self._mark_ns[:] = self._ns
        self._armed = True
        return now

    def decoding(self, left: int) -> None:
        """After the tick's finishes: ``left`` slots are still in decode.
        With none the next gap is no stream's token gap."""
        self._armed = left > 0

    def _stalled(self, now: int, gap: int, cpu: int, pcpu: int, rq: int,
                 gc_s: float) -> None:
        phases = {}
        for i, p in enumerate(WORKER_PHASES):
            if self._ns[i] != self._mark_ns[i]:
                phases[p] = (self._ns[i] - self._mark_ns[i]) * 1e-9
                self._m_stall_phase[i].inc(phases[p])
        gap_s = gap * 1e-9
        cpu_s = max(0, cpu - self._mark_cpu) * 1e-9
        pcpu_s = max(0, pcpu - self._mark_pcpu) * 1e-9
        gc_in = max(0.0, gc_s - self._mark_gc)
        self._m_stalls.inc()
        self._m_stall_s.observe(gap_s)
        self._m_stall_cpu.inc(cpu_s)
        self._m_stall_pcpu.inc(pcpu_s)
        self._m_stall_gc.inc(gc_in)
        rq_s = None
        if self._m_stall_rq is not None:
            rq_s = max(0, rq - self._mark_rq) * 1e-9
            self._m_stall_rq.inc(rq_s)
        top = max(phases, key=phases.get)
        ann = _ANNOTATION or _annotation()
        if ann is not None and ann.is_enabled():
            # a session is live: an instant on the worker's line, to read
            # the trace's longest gaps against the program's own verdict
            with ann(GEN_STALL, gap_ms=gap_s * 1e3, phase=top):
                pass
        self.stall = {
            "gap_s": gap_s, "phase": top, "phase_s": phases,
            "thread_cpu_s": cpu_s, "process_cpu_s": pcpu_s,
            "runqueue_s": rq_s, "gc_s": gc_in,
            "mean_gap_s": (self._gaps_sum / self._gaps_n * 1e-9
                           if self._gaps_n else None),
            "perf_counter_ns": now, "time_ns": time.time_ns(),
            "turns": self._last_turns()}

    def _last_turns(self) -> List[dict]:
        """The ring's turns, oldest first: each its end stamp and the
        milliseconds of it by phase."""
        n = len(self._turns)
        rows = [self._turns[(self._turn_i - self._turn_n + k) % n]
                for k in range(self._turn_n)]
        return [{"end_ns": b[0],
                 "ms": {p: (b[1 + i] - a[1 + i]) * 1e-6
                        for i, p in enumerate(WORKER_PHASES)
                        if b[1 + i] != a[1 + i]}}
                for a, b in zip(rows, rows[1:])]
