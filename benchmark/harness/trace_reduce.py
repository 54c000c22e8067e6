"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX:
``jax.profiler.ProfileData`` reads planes, their lines, and events with a
start and a duration in nanoseconds.

What a TPU trace looks like (TPU v5e, jax 0.9.0): one plane per chip named
``/device:TPU:<n>``; on it the line ``XLA Ops`` holds one event per executed
HLO operation (nested: a ``while`` spans its body's operations) and the line
``XLA Modules`` one event per executed program (``jit_<function>(<id>)``),
and ``Async XLA Ops`` the spans of asynchronous operations from their start
to their done (copies, slices, and a mesh's collectives where XLA made them
asynchronous). Event names are the operation's HLO text, ``%name = ...``.
Host threads live on ``/host:CPU``. Every reduction below works per device
and reports the worst or the mean as the metric asks.

Definitions, so that every PR computes the same number the same way:

- busy: the union of the intervals of ``XLA Ops`` events (nesting collapses);
- window: first event start to last event end over all device planes;
- idle share: 1 - busy / window, per device;
- an operation's time: its SELF time, duration minus the events nested in it,
  so that a ``while`` does not count its body twice;
- collective time: the union of the intervals of events, on ``XLA Ops`` or
  ``Async XLA Ops``, whose name starts with, or whose HLO opcode is,
  all-reduce, all-gather, reduce-scatter, collective-permute or all-to-all
  (``-start``/``-done`` halves included);
- exposed collective time: the part of that union during which no other leaf
  operation runs on the same device.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
_COLLECTIVES = "(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
# by the operation's name, or by its opcode in the HLO text: a psum under
# shard_map is named after the primitive (``%psum_invariant.7 = ... all-reduce(``)
COLLECTIVE = re.compile(rf"^%?{_COLLECTIVES}|\s{_COLLECTIVES}(-start|-done)?\(")


class Line(NamedTuple):
    """Events of one trace line, sorted by start."""

    start: np.ndarray   # int64 ns
    end: np.ndarray     # int64 ns
    names: List[str]

    @classmethod
    def of(cls, events) -> "Line":
        rows = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                        str(e.name)) for e in events),
                      key=lambda r: (r[0], -r[1]))
        return cls(np.array([r[0] for r in rows], np.int64),
                   np.array([r[1] for r in rows], np.int64),
                   [r[2] for r in rows])

    def __len__(self) -> int:
        return len(self.names)


class Device(NamedTuple):
    ops: Line
    modules: Line
    async_ops: Line     # spans of asynchronous operations, start to done


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler.start_trace`` dir."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> Dict[int, Device]:
    """Device planes of a trace file, by device number."""
    from jax.profiler import ProfileData

    out: Dict[int, Device] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        out[int(m.group(2))] = Device(*(
            Line.of(lines[name].events if name in lines else [])
            for name in (OPS_LINE, MODULES_LINE, ASYNC_LINE)))
    return out


def union(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merge intervals (sorted by start) into disjoint ones."""
    if len(start) == 0:
        return start, end
    top = np.maximum.accumulate(end)
    new = np.concatenate([[True], start[1:] > top[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(start) - 1]])
    return start[first], top[last]


def overlap(a: Tuple[np.ndarray, np.ndarray],
            b: Tuple[np.ndarray, np.ndarray]) -> int:
    """Nanoseconds covered by both of two disjoint sorted interval sets."""
    total, j = 0, 0
    bs, be = b
    for s, e in zip(*a):
        while j < len(bs) and be[j] <= s:
            j += 1
        k = j
        while k < len(bs) and bs[k] < e:
            total += min(e, be[k]) - max(s, bs[k])
            k += 1
    return int(total)


def window_ns(devices: Dict[int, Device]) -> Tuple[int, int]:
    starts = [d.ops.start[0] for d in devices.values() if len(d.ops)]
    ends = [d.ops.end.max() for d in devices.values() if len(d.ops)]
    if not starts:
        return 0, 0
    return int(min(starts)), int(max(ends))


def busy_s(devices: Dict[int, Device]) -> Dict[int, float]:
    out = {}
    for n, d in devices.items():
        s, e = union(d.ops.start, d.ops.end)
        out[n] = float((e - s).sum()) / 1e9
    return out


def self_ns(line: Line) -> np.ndarray:
    """Each event's duration minus the events nested directly inside it."""
    dur = (line.end - line.start).astype(np.int64)
    out = dur.copy()
    stack: List[int] = []
    for i in range(len(line)):
        while stack and line.end[stack[-1]] <= line.start[i]:
            stack.pop()
        if stack:
            out[stack[-1]] -= dur[i]
        stack.append(i)
    return out


def is_leaf(line: Line) -> np.ndarray:
    """True where no other event starts inside the event."""
    nxt = np.concatenate([line.start[1:], [np.iinfo(np.int64).max]])
    return nxt >= line.end


def op_name(name: str) -> str:
    """``%fusion.123 = ...`` / ``fusion.123`` -> ``fusion.123``."""
    return name.split(" ")[0].lstrip("%")


def top_ops(devices: Dict[int, Device], n: int = 10) -> List[list]:
    """The operations with most self time, summed over one device (the
    busiest), by the trace's own names."""
    if not devices:
        return []
    busy = busy_s(devices)
    d = devices[max(busy, key=busy.get)]
    totals: Dict[str, int] = {}
    for name, ns in zip(d.ops.names, self_ns(d.ops)):
        key = op_name(name)
        totals[key] = totals.get(key, 0) + int(ns)
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def idle_gaps(devices: Dict[int, Device], n: int = 10,
              label: Optional[Callable[[int, int], str]] = None) -> List[list]:
    """Idle time of the busiest device, summed by label. Without
    annotations inside the program a gap can only be named by the program
    that ran before it (``after:<module>``) or by what the benchmark's own
    thread was doing (``label(start_ns, end_ns)``)."""
    if not devices:
        return []
    busy = busy_s(devices)
    d = devices[max(busy, key=busy.get)]
    s, e = union(d.ops.start, d.ops.end)
    totals: Dict[str, int] = {}
    for gs, ge in zip(e[:-1], s[1:]):
        name = label(int(gs), int(ge)) if label else None
        if name is None:
            i = int(np.searchsorted(d.modules.start, gs, side="right")) - 1
            name = ("after:" + module_name(d.modules.names[i]) if i >= 0
                    else "unattributed")
        totals[name] = totals.get(name, 0) + int(ge - gs)
    rows = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in rows]


def module_name(name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def module_busy_ms(devices: Dict[int, Device], pattern: str) -> List[float]:
    """For every execution of a program whose name matches ``pattern``: the
    device-busy milliseconds inside it (union of operation intervals clipped
    to the program's event). All devices pooled."""
    rx = re.compile(pattern)
    out: List[float] = []
    for d in devices.values():
        us, ue = union(d.ops.start, d.ops.end)
        for ms, me, name in zip(d.modules.start, d.modules.end,
                                d.modules.names):
            if not rx.search(name):
                continue
            one = (np.array([ms]), np.array([me]))
            lo = int(np.searchsorted(ue, ms, side="right"))
            hi = int(np.searchsorted(us, me, side="left"))
            out.append(overlap(one, (us[lo:hi], ue[lo:hi])) / 1e6)
    return out


def op_self_s(devices: Dict[int, Device], pattern: str) -> Dict[int, Tuple[float, int]]:
    """Per device: (self seconds, calls) of the operations matching
    ``pattern``."""
    rx = re.compile(pattern)
    out = {}
    for n, d in devices.items():
        hit = np.array([bool(rx.search(nm)) for nm in d.ops.names], bool)
        ns = self_ns(d.ops)
        out[n] = (float(ns[hit].sum()) / 1e9, int(hit.sum()))
    return out


def collectives(devices: Dict[int, Device]) -> Dict[int, dict]:
    """Per device: seconds inside collectives, the exposed part, busy and
    the window."""
    w0, w1 = window_ns(devices)
    out = {}
    for n, d in devices.items():
        coll = np.array([bool(COLLECTIVE.search(nm)) for nm in d.ops.names],
                        bool)
        acoll = np.array([bool(COLLECTIVE.search(nm))
                          for nm in d.async_ops.names], bool)
        leaf = is_leaf(d.ops)
        cs = np.concatenate([d.ops.start[coll], d.async_ops.start[acoll]])
        ce = np.concatenate([d.ops.end[coll], d.async_ops.end[acoll]])
        order = np.argsort(cs, kind="stable")
        cu = union(cs[order], ce[order])
        other = leaf & ~coll
        ou = union(d.ops.start[other], d.ops.end[other])
        c_ns = int((cu[1] - cu[0]).sum())
        bs, be = union(d.ops.start, d.ops.end)
        out[n] = {"collective_s": c_ns / 1e9,
                  "exposed_s": (c_ns - overlap(cu, ou)) / 1e9,
                  "busy_s": float((be - bs).sum()) / 1e9,
                  "window_s": (w1 - w0) / 1e9}
    return out


def summary(devices: Dict[int, Device]) -> dict:
    """``busy_s`` (mean over devices), ``window_s``, the worst device's idle
    share, and the breakdown the driver copies into the ledger."""
    w0, w1 = window_ns(devices)
    busy = busy_s(devices)
    window = (w1 - w0) / 1e9
    return {
        "busy_s": float(np.mean(list(busy.values()))) if busy else 0.0,
        "window_s": window,
        "idle_share_worst": (1.0 - min(busy.values()) / window
                             if busy and window > 0 else None),
    }
