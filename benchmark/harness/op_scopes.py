"""Device time by the program's ``jax.named_scope``s.

Where a scope lands in a TPU trace (v5e, libtpu 0.0.34; PERF.md, PR 23): not
in an event's name, which is the HLO instruction's text, and not in an
event's own stats, which is all ``jax.profiler.ProfileData`` shows. It is in
the plane's ``event_metadata``: every ``XLA Ops`` event points at an
``XEventMetadata`` whose stat ``tf_op`` holds the operation's JAX ``op_name``,
the path of scopes it was traced under with the transforms around them:

    jit(_decode_paged_fn)/attention/cache_read/gather:
    jit(step)/transpose(jvp(mlp))/dot_general:

``op_names`` reads just that table from the ``.xplane.pb`` with a decoder of
the protobuf wire format (lines, which hold the events, are skipped by their
length: 0.3 s for a 44 MB trace). Its key is the program's id (the stat
``program_id``, which is the number in the name of the ``XLA Modules`` event
an operation runs inside, ``jit__decode_paged_fn(7054026437570601022)``)
and the metadata's name, which is the event name ``trace_reduce`` already
has: two programs of one trace do hold instructions of the same text (the
head's weight cast of the decode step and of the prefill chunk, in one chip
trace of three), so the name alone would give one program's time to the
other.

    XSpace         { repeated XPlane planes = 1 }
    XPlane         { name = 2; map<int64, XEventMetadata> event_metadata = 4;
                     map<int64, XStatMetadata> stat_metadata = 5 }
    XEventMetadata { name = 2; repeated XStat stats = 5 }
    XStatMetadata  { name = 2 }
    XStat          { metadata_id = 1; uint64_value = 3; str_value = 5 }

A share is 100 x the self time of a program's operations under a scope over
the self time of all that program's operations, on the busiest device. A
program is told by the head of the path (``jit(_decode_paged_fn)``).

Why no per-layer metric reads this (PERF.md, PR 23). The metadata is only as
fresh as the executable. JAX's persistent compilation cache leaves metadata
out of its key (``jax_compilation_cache_include_metadata_in_key`` is off), so
a tree whose programs equal another tree's loads that tree's executable,
scopes and all: on the chip a tree that says ``weight_cast`` traced as
``cast``, the name an earlier tree had given the scope. Parent and change of
a benchmark check share one cache, so a metric read from scopes could vanish
from a line through no fault of the tree under test. This reader is the
operator's and the builder's, by hand:

    python3 benchmark/harness/op_scopes.py <trace.xplane.pb | trace dir> [scope ...]

prints, for every program of the trace, its device time by outermost scope,
and the share of each ``scope`` named (a nested one, such as ``cache_read``).
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, Iterator, Tuple

import numpy as np

if __package__ in (None, ""):       # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from harness import trace_reduce
else:
    from . import trace_reduce

OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"
MODULE_ID = re.compile(r"\((\d+)\)$")

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, value


def _entry(buf) -> dict:
    """A map entry or a flat message as {field: last value}."""
    return dict(_fields(buf))


Key = Tuple[int, str]      # (program_id, event name)


def op_names(path: str) -> Dict[int, Dict[Key, str]]:
    """Device number -> {(program_id, event name): JAX op_name} for the
    operations whose metadata carries both."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[int, Dict[Key, str]] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                events.append(_entry(value).get(2))
            elif pf == 5:
                e = _entry(value)
                stat_names[e.get(1)] = bytes(_entry(e[2]).get(2, b"")).decode()
        dev = trace_reduce.DEVICE_PLANE.match(name)
        if not dev:
            continue
        table = out.setdefault(int(dev.group(2)), {})
        for meta in events:
            ev_name, op, program = None, None, None
            for mf, value in _fields(meta):
                if mf == 2:
                    ev_name = bytes(value).decode()
                elif mf == 5:
                    stat = _entry(value)
                    kind = stat_names.get(stat.get(1))
                    if kind == OP_NAME_STAT and 5 in stat:
                        op = bytes(stat[5]).decode()
                    elif kind == PROGRAM_STAT and 3 in stat:
                        program = int(stat[3])
            if ev_name and op and program is not None:
                table[program, ev_name] = op
    return out


def scope_rx(scope: str) -> "re.Pattern[str]":
    """``scope`` as one component of an op_name's path, bare or inside a
    transform: ``/mlp/``, ``/jvp(mlp)/``, ``/transpose(jvp(mlp))/``."""
    return re.compile(rf"[/(]{re.escape(scope)}[/)]")


def self_ns_by_op(devices: Dict[int, trace_reduce.Device],
                  names: Dict[int, Dict[Key, str]]) -> Dict[str, int]:
    """On the busiest device: self nanoseconds by op_name. An operation's
    program is the ``XLA Modules`` event it starts inside."""
    if not devices:
        return {}
    busy = trace_reduce.busy_s(devices)
    n = max(busy, key=busy.get)
    d, table = devices[n], names.get(n, {})
    ids = [int(m.group(1)) if m else None
           for m in map(MODULE_ID.search, d.modules.names)]
    inside = np.searchsorted(d.modules.start, d.ops.start, side="right") - 1
    out: Dict[str, int] = {}
    for ev_name, i, start, ns in zip(d.ops.names, inside, d.ops.start,
                                     trace_reduce.self_ns(d.ops)):
        if i < 0 or start >= d.modules.end[i]:
            continue
        op = table.get((ids[i], ev_name))
        if op is not None:
            out[op] = out.get(op, 0) + int(ns)
    return out


def outermost(op: str) -> str:
    """The first component after the program that is not a ``jit(...)``
    wrapper, transforms peeled: the outermost scope, or the primitive of an
    operation under none."""
    for part in op.rstrip(":").split("/")[1:]:
        inner = re.sub(r"^(?:\w+\()+|\)+$", "", part)
        if not part.startswith(("jit(", "pjit(")) and inner:
            return inner
    return op


def report(path: str, scopes=()) -> list:
    """Per program (the head of the path): self milliseconds, shares by
    outermost scope (the 16 largest), and the share of every scope of
    ``scopes`` found in it."""
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    programs: Dict[str, Dict[str, int]] = {}
    for op, ns in self_ns_by_op(trace_reduce.load(path), op_names(path)).items():
        programs.setdefault(op.split("/")[0], {})[op] = ns
    out = []
    for prog, by in sorted(programs.items()):
        total = sum(by.values())
        if not total:
            continue
        outer: Dict[str, int] = {}
        for op, ns in by.items():
            outer[outermost(op)] = outer.get(outermost(op), 0) + ns
        named = {sc: sum(ns for op, ns in by.items() if scope_rx(sc).search(op))
                 for sc in scopes}
        out.append({"program": prog, "self_ms": total / 1e6,
                    "outermost": {k: 100.0 * v / total for k, v in
                                  sorted(outer.items(), key=lambda kv: -kv[1])[:16]},
                    "scopes": {k: 100.0 * v / total for k, v in named.items() if v}})
    return out


if __name__ == "__main__":
    for row in report(sys.argv[1], sys.argv[2:]):
        print(json.dumps(row))
