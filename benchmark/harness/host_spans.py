"""The program's own host spans, read from the profiler's trace, and the
device's idle time attributed to them.

The program opens ``jax.profiler.TraceAnnotation``s at every boundary of its
generation loop (``deeplearning4j_tpu/obs/trace.py``; the names below are a
copy of its table, because the benchmark imports nothing from the program's
``obs/``). During a profiler session they land on the ``/host:CPU`` plane of
the same ``.xplane.pb`` as the device's ``XLA Ops``. The batcher's worker
thread is the line of that plane that holds ``gen.tick`` events (the profiler
calls the line ``python``, not by the thread's name).

Attribution. The gaps are the complement, inside ``trace_reduce.window_ns``,
of the union of ``XLA Ops`` intervals on the busiest device: the gaps
``trace_reduce.idle_gaps`` sums. Every nanosecond of a gap takes the name of
the innermost worker-line span of the table that covers it, or
``unattributed`` (time inside ``gen.tick`` but between its children counts as
unattributed too; ``gen.turn``'s own time, between the chunks, the tick and
the loop's back edge, is a share of its own). A share is 100 x attributed ns
/ window ns, so on one chip the eight shares add up to ``device_idle_share``.

The two clocks. The profiler stamps device events with the device's clock
converted to the host's, and the conversion is off by a constant of a
millisecond or so in one trace (PERF.md, PR 23). ``clock_shift_ns`` bounds
the constant from both sides by causality: no program starts on the device
before the host enqueued it (host and device name one execution by the same
``run_id``), and no readback ends before the program it waits for has. The
first bound is tight to the tens of microseconds between an enqueue and the
start, so it is the shift ``attribute`` adds to the device's times; the second
is late by the readback's latency, about a millisecond a tick, all of which
would move from ``gen.tick.readback`` to ``gen.tick.dispatch``: the reader
logs the split at both, so that a move of either share can be told from a move
of the estimate. A trace without ``run_id`` pairs, or without a device plane
(the CPU rehearsal), reads as nothing.

    python3 benchmark/harness/host_spans.py <trace.xplane.pb | trace dir>

prints the shift and its bounds, the shares at both, how many decode programs
begin inside a ``gen.tick.dispatch`` or ``gen.tick.readback`` span, how much
of ``gen.turn``'s own time the stream writers fill, and the collector's
pauses beside the longest gaps.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

if __package__ in (None, ""):       # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from harness import env, trace_reduce
else:
    from . import env, trace_reduce

# --- copy of the program's table (deeplearning4j_tpu/obs/trace.py) ----------
GEN_ADMIT = "gen.admit"
GEN_PREFILL_CHUNK = "gen.prefill_chunk"
GEN_FIRST_TOKEN = "gen.first_token"
GEN_TICK = "gen.tick"
GEN_TICK_PREPARE = "gen.tick.prepare"
GEN_TICK_DISPATCH = "gen.tick.dispatch"
GEN_TICK_READBACK = "gen.tick.readback"
GEN_TICK_PUBLISH = "gen.tick.publish"
GEN_TURN = "gen.turn"
HTTP_STREAM_WRITE = "http.stream_write"
GC_PAUSE = "gc.pause"
WORKER_SPANS = (GEN_ADMIT, GEN_PREFILL_CHUNK, GEN_FIRST_TOKEN, GEN_TICK,
                GEN_TICK_PREPARE, GEN_TICK_DISPATCH, GEN_TICK_READBACK,
                GEN_TICK_PUBLISH, GEN_TURN)
UNATTRIBUTED = "unattributed"

HOST_PLANE = "/host:CPU"
DECODE_MODULE = "decode_paged"      # as decode_step_device_ms matches it
# XLA's own host events that carry the ``run_id`` of one execution, which the
# device's ``XLA Modules`` event carries too (TPU v5e, libtpu 0.0.34)
ENQUEUED = "DoEnqueueProgram"


class Span(NamedTuple):
    name: str
    start: int      # ns
    end: int        # ns
    stats: dict


class Host(NamedTuple):
    """The host plane of one trace: every thread's events of the table, the
    worker's line, XLA's enqueue events and the device's programs by
    ``run_id``."""

    lines: List[List[Span]]             # table spans, per thread, by start
    worker: Optional[int]               # index into ``lines``
    enqueued: Dict[int, int]            # run_id -> host ns the enqueue began
    module_runs: Dict[int, List[Tuple[Optional[int], int, int, str]]]
    # device number -> [(run_id or None, start ns, end ns, program)] of XLA Modules


def trace_path(cell_name: str) -> Optional[str]:
    """The run's trace file: where ``env.cache_dirs`` put the profile."""
    return trace_reduce.find_xplane(
        os.path.join(env.BENCH_DIR, ".cache", cell_name, "trace"))


@functools.lru_cache(maxsize=2)
def load(path: str) -> Host:
    """Parsed once per process: seven readers ask for the same file."""
    from jax.profiler import ProfileData

    table = set(WORKER_SPANS) | {HTTP_STREAM_WRITE, GC_PAUSE}
    lines: List[List[Span]] = []
    enqueued: Dict[int, int] = {}
    module_runs: Dict[int, List[Tuple[Optional[int], int, int, str]]] = {}
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        if dev:
            for line in plane.lines:
                if line.name != trace_reduce.MODULES_LINE:
                    continue
                runs = module_runs.setdefault(int(dev.group(2)), [])
                for e in line.events:
                    rid = dict(e.stats).get("run_id")
                    runs.append((None if rid is None else int(rid),
                                 int(e.start_ns),
                                 int(e.start_ns + e.duration_ns),
                                 trace_reduce.module_name(str(e.name))))
            continue
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                name = str(e.name)
                if name in table:
                    spans.append(Span(name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      dict(e.stats)))
                elif name == ENQUEUED:
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        enqueued[int(rid)] = int(e.start_ns)
            if spans:
                lines.append(sorted(spans, key=lambda s: (s.start, -s.end)))
    ticks = [sum(s.name == GEN_TICK for s in ln) for ln in lines]
    worker = int(np.argmax(ticks)) if ticks and max(ticks) > 0 else None
    return Host(lines, worker, enqueued, module_runs)


def clock_shift_ns(host: Host) -> dict:
    """``{"pairs", "lower_ns", "upper_ns"}``: the bounds on the nanoseconds to
    ADD to the device's times to put them on the host's clock (the module's
    docstring). ``lower_ns`` is the largest enqueue-minus-device-start over
    the executions both sides name by ``run_id`` (on the v5e all 94 prefill
    chunks of one trace read 1.94 to 2.06 ms: PERF.md, PR 23), None where the
    runtime wrote no pairs. ``upper_ns`` is the least
    readback-end-minus-decode-end, each ``gen.tick.readback`` paired with the
    decode program that ended nearest to it."""
    lows = np.array([host.enqueued[rid] - start
                     for runs in host.module_runs.values()
                     for rid, start, _, _ in runs if rid in host.enqueued],
                    np.int64)
    if len(lows):   # a pair a whole millisecond past the rest is a mismatch
        lows = lows[lows <= np.percentile(lows, 90) + 1_000_000]
    info = {"pairs": int(len(lows)),
            "lower_ns": int(lows.max()) if len(lows) else None,
            "upper_ns": None}
    ends = np.array(sorted(e for runs in host.module_runs.values()
                           for _, _, e, prog in runs if DECODE_MODULE in prog),
                    np.int64)
    backs = np.array([s.end for s in host.lines[host.worker]
                      if s.name == GEN_TICK_READBACK] if host.worker is not None
                     else [], np.int64)
    if len(ends) and len(backs):
        i = np.searchsorted(ends, backs)
        lo = ends[np.clip(i - 1, 0, len(ends) - 1)]
        hi = ends[np.clip(i, 0, len(ends) - 1)]
        diffs = backs - np.where(backs - lo <= hi - backs, lo, hi)
        # a readback at the trace's edge pairs with a neighbour's program, a
        # tick away: keep the pairs near the median
        diffs = diffs[np.abs(diffs - np.median(diffs)) < 2_000_000]
        info["upper_ns"] = int(diffs.min())
    return info


def innermost(spans: List[Span]) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Disjoint segments of one thread's properly nested spans, each labelled
    by the innermost span covering it: name -> (starts, ends), sorted."""
    marks = sorted([(s.start, 1, i) for i, s in enumerate(spans)]
                   + [(s.end, 0, i) for i, s in enumerate(spans)])
    out: Dict[str, Tuple[list, list]] = {}
    stack: List[int] = []
    cursor = 0
    for t, opening, i in marks:
        if stack and t > cursor:
            seg = out.setdefault(spans[stack[-1]].name, ([], []))
            seg[0].append(cursor)
            seg[1].append(t)
        cursor = t
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return {k: (np.array(a, np.int64), np.array(b, np.int64))
            for k, (a, b) in out.items()}


def gaps_ns(devices: Dict[int, trace_reduce.Device], shift: int
            ) -> Tuple[Tuple[np.ndarray, np.ndarray], Tuple[int, int]]:
    """Idle intervals of the busiest device inside the traced window, on the
    host's clock, and the window."""
    w0, w1 = trace_reduce.window_ns(devices)
    busy = trace_reduce.busy_s(devices)
    d = devices[max(busy, key=busy.get)]
    s, e = trace_reduce.union(d.ops.start, d.ops.end)
    starts = np.concatenate([[w0], e]) + shift
    ends = np.concatenate([s, [w1]]) + shift
    keep = ends > starts
    return (starts[keep], ends[keep]), (w0 + shift, w1 + shift)


def by_span_ns(host: Host, devices: Dict[int, trace_reduce.Device],
               shift: int) -> Tuple[Dict[str, int], int, int]:
    """Idle nanoseconds by innermost worker span with the device's times
    moved by ``shift``, all idle nanoseconds, and the window's length."""
    worker = [s for s in host.lines[host.worker] if s.name in WORKER_SPANS]
    gaps, (w0, w1) = gaps_ns(devices, shift)
    idle = int((gaps[1] - gaps[0]).sum())
    by_span = {name: trace_reduce.overlap(gaps, seg)
               for name, seg in innermost(worker).items()}
    by_span.pop(GEN_TICK, None)     # between a tick's children: unattributed
    by_span[UNATTRIBUTED] = idle - sum(by_span.values())
    return by_span, idle, int(w1 - w0)


def attribute(host: Host, devices: Dict[int, trace_reduce.Device]
              ) -> Optional[dict]:
    """``{"window_ns", "idle_ns", "shift", "by_span": {name: ns},
    "by_span_upper"}`` or None where there is nothing to read: no worker
    line (a program without the spans), no device plane, or no ``run_id``
    pairs to put the two on one clock. ``by_span`` is the reading, at the
    shift's lower bound; ``by_span_upper`` the same at its upper bound."""
    if host.worker is None or not devices:
        return None
    shift = clock_shift_ns(host)
    if shift["lower_ns"] is None:
        return None
    by_span, idle, window = by_span_ns(host, devices, shift["lower_ns"])
    upper = (by_span_ns(host, devices, shift["upper_ns"])[0]
             if shift["upper_ns"] is not None else None)
    return {"window_ns": window, "idle_ns": idle, "shift": shift,
            "by_span": by_span, "by_span_upper": upper}


_ATTRIBUTED: Dict[str, Optional[dict]] = {}    # by trace file, as ``load`` caches


def idle_share(run, *names: str) -> Optional[float]:
    """100 x the idle nanoseconds under the spans ``names`` over the traced
    window: what a ``layer_metrics/idle_*_share.py`` returns."""
    path = trace_path(run.cell.name)
    if path is None:
        return None
    if path not in _ATTRIBUTED:     # eight readers, one attribution
        got = _ATTRIBUTED[path] = attribute(load(path), run.trace or {})
        if got is not None and got["by_span_upper"] is not None:
            pct = {k: [100.0 * by.get(n, 0) / got["window_ns"]
                       for by in (got["by_span"], got["by_span_upper"])]
                   for k, n in (("dispatch", GEN_TICK_DISPATCH),
                                ("readback", GEN_TICK_READBACK))}
            env.log("host_spans: device clock + {lower_ns} ns ({pairs} run_id "
                    "pairs; upper bound {upper_ns}); at the lower / upper "
                    "bound idle_tick_dispatch_share {d[0]:.2f} / {d[1]:.2f}, "
                    "idle_tick_readback_share {r[0]:.2f} / {r[1]:.2f}".format(
                        d=pct["dispatch"], r=pct["readback"], **got["shift"]))
    got = _ATTRIBUTED[path]
    if got is None or got["window_ns"] <= 0:
        return None
    return 100.0 * sum(got["by_span"].get(n, 0) for n in names) / got["window_ns"]


# --- the kernel's events -----------------------------------------------------
def op_durations_ns(devices: Dict[int, trace_reduce.Device], pattern: str
                    ) -> List[int]:
    """Device durations of the operations whose trace name matches
    ``pattern``, all devices pooled. A Mosaic kernel is one ``XLA Ops`` event
    named after the ``pallas_call``'s ``name=`` (``%flash_fwd.1 = ...
    custom-call(...)``)."""
    rx = re.compile(pattern)
    return [int(e - s) for d in devices.values()
            for s, e, nm in zip(d.ops.start, d.ops.end, d.ops.names)
            if rx.search(nm)]


# --- by hand ------------------------------------------------------------------
def report(path: str) -> dict:
    """What PERF.md quotes from a trace: the shift and its bounds, the shares
    at both, the decode programs that begin inside a dispatch or readback
    span, the part of ``gen.turn``'s own time during which a handler thread
    is inside ``http.stream_write``, and the collector's pauses beside the
    longest gaps."""
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    host, devices = load(path), trace_reduce.load(path)
    got = attribute(host, devices)
    if got is None:
        return {"worker": host.worker, "devices": len(devices),
                "shift": clock_shift_ns(host) if host.worker is not None else None}
    shift = got["shift"]["lower_ns"]
    pct = lambda by: {k: 100.0 * v / got["window_ns"]      # noqa: E731
                      for k, v in sorted(by.items())}
    out = {"shift": got["shift"],
           "idle_share": 100.0 * got["idle_ns"] / got["window_ns"],
           "shares": pct(got["by_span"]),
           "shares_at_upper_bound": (pct(got["by_span_upper"])
                                     if got["by_span_upper"] else None)}
    worker = host.lines[host.worker]
    cover = innermost([s for s in worker if s.name in
                       (GEN_TICK_DISPATCH, GEN_TICK_READBACK)])
    for label, add in (("shifted", shift), ("unshifted", 0)):
        starts = [s + add for runs in host.module_runs.values()
                  for _, s, _, prog in runs if DECODE_MODULE in prog]
        inside = 0
        for a, b in cover.values():
            idx = np.searchsorted(a, starts, side="right") - 1
            inside += int(sum(i >= 0 and t < b[i] for i, t in zip(idx, starts)))
        out[f"decode_starts_inside_dispatch_or_readback_{label}"] = {
            "inside": inside, "of": len(starts)}
    # what runs while the worker stands between its spans: the writers
    own = innermost([s for s in worker if s.name in WORKER_SPANS]).get(GEN_TURN)
    if own is not None:
        writes = sorted((s.start, s.end) for i, ln in enumerate(host.lines)
                        if i != host.worker for s in ln
                        if s.name == HTTP_STREAM_WRITE)
        wu = trace_reduce.union(np.array([w[0] for w in writes], np.int64),
                                np.array([w[1] for w in writes], np.int64))
        own_ns = int((own[1] - own[0]).sum())
        out["turn_own_time"] = {
            "mean_us_a_turn": own_ns / 1e3 / max(
                1, sum(s.name == GEN_TURN for s in worker)),
            "under_stream_write_share": (
                100.0 * trace_reduce.overlap(own, wu) / own_ns if own_ns else None)}
    (gs, ge), _ = gaps_ns(devices, shift)
    pauses = [s for ln in host.lines for s in ln if s.name == GC_PAUSE]
    segments = innermost([s for s in worker if s.name in WORKER_SPANS])
    order = np.argsort(gs - ge)[:8]
    out["longest_gaps"] = [
        {"ms": float(ge[i] - gs[i]) / 1e6,
         # the worker's innermost span over the gap, and the writers at work
         "under_ms": {k: ns / 1e6 for k, seg in segments.items() for ns in
                      [trace_reduce.overlap((gs[i:i + 1], ge[i:i + 1]), seg)] if ns},
         "stream_writes": sum(1 for ln in host.lines for s in ln
                              if s.name == HTTP_STREAM_WRITE
                              and s.start < ge[i] and gs[i] < s.end),
         "gc_pause_ms": [{"generation": int(p.stats.get("generation", -1)),
                          "ms": (p.end - p.start) / 1e6} for p in pauses
                         if p.start < ge[i] and gs[i] < p.end]}
        for i in order]
    out["gc_pauses"] = {
        str(g): {"count": len(ms), "max_ms": max(ms, default=0.0)}
        for g in (0, 1, 2)
        for ms in [[(p.end - p.start) / 1e6 for p in pauses
                    if int(p.stats.get("generation", -1)) == g]]}
    return out


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
