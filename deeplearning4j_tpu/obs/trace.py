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
from typing import Dict, List, Optional

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
HTTP_STREAM_WRITE = "http.stream_write"  # serve/http.py: one SSE event written
GC_PAUSE = "gc.pause"                    # one collection; generation=0|1|2
SPAN_NAMES = (GEN_ADMIT, GEN_PREFILL_CHUNK, GEN_FIRST_TOKEN, GEN_TICK,
              GEN_TICK_PREPARE, GEN_TICK_DISPATCH, GEN_TICK_READBACK,
              GEN_TICK_PUBLISH, GEN_TURN, HTTP_STREAM_WRITE, GC_PAUSE)


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
    tick. ``install``/``remove`` add and drop exactly this one entry."""

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
            self._hist[info["generation"]].observe(dt)

    def install(self) -> None:
        gc.callbacks.append(self)

    def remove(self) -> None:
        if self in gc.callbacks:
            gc.callbacks.remove(self)
