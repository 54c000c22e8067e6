"""Per-layer metrics, one small reader each, found by the metric's name:
``benchmark/layer_metrics/<name>.json`` (a declarative ``source``) or
``<name>.py`` (one function ``read(run) -> float | None``). A reader that
finds nothing to read returns None and the metric is left out of the line.

The declarative sources (``"source": {"type": ...}``):

- ``ratio``: ``scale * num / den``; each side is a *term* (below). A zero
  denominator reads as nothing.
- ``term``: ``scale *`` one term.
- ``histogram_quantile``: quantile ``q`` of the observations a program
  histogram ``name`` took inside the window (bucket counts at the window's
  end minus those at its start, interpolated inside the bucket), times
  ``scale``.
- ``trace_module``: over the executions, in the device trace, of the program
  whose name matches ``match``: ``reduce`` (``median``, ``mean``, ``sum``,
  ``count``) of the device-busy milliseconds inside one execution.
- ``trace_ops``: self time of the device operations matching ``match`` as a
  percentage of busy time, on the device where that share is largest.
- ``trace_idle``: 100 x (1 - busy / traced window) on the idlest device.
- ``trace_collectives``: ``field`` ``collective_share`` (time inside
  collectives over busy time) or ``exposed_share`` (the part of it during
  which nothing else runs on that device, over the traced window), in
  percent, on the device where it is largest.
- ``span_quantile``: quantile ``q`` of the durations of the program's spans
  called ``name`` (``StepTelemetry``'s tracer), the first ``skip`` left out,
  times ``scale`` (durations are microseconds).
- ``same_as``: what the per-layer metric ``metric`` reads: one reading
  under a second name, where another ``moves`` or list of cells wants it.

A term is ``{"counter": name, "field": "value"|"sum"|"count",
"at": "window"|"boot"|"end", "labels": {...}, "missing": x}`` (a program counter, gauge or
histogram family summed over its series; ``window`` is end minus start,
``boot`` its value when the server had booted, ``end`` its value at the
window's end), ``{"client": field}`` (a number the load generator reports),
``{"result": field}`` (a number of the runner's own result),
``{"sum": [term, ...]}``, or ``{"const": x}``.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, Optional

import numpy as np

from . import env, trace_reduce


class Run:
    """What a reader may look at. Counter snapshots are
    ``MetricsRegistry.snapshot()`` dicts of the program; ``trace`` is
    ``trace_reduce.load()``'s device planes or None in an untraced run."""

    def __init__(self, cell, device: dict, **kw):
        self.cell = cell
        self.device = device
        self.counters_boot: dict = kw.get("counters_boot") or {}
        self.counters_start: dict = kw.get("counters_start") or {}
        self.counters_end: dict = kw.get("counters_end") or {}
        self.client: dict = kw.get("client") or {}
        self.result: dict = kw.get("result") or {}
        self.trace: Optional[dict] = kw.get("trace")
        self.spans: list = kw.get("spans") or []

    @property
    def peak(self):
        from . import peaks

        return peaks.peak(self.device["kind"])


def family_total(snap: dict, name: str, field: str,
                  labels: Optional[dict]) -> Optional[float]:
    fam = snap.get(name)
    if fam is None:
        return None
    total = 0.0
    for s in fam.get("series", []):
        if labels and any(s["labels"].get(k) != v for k, v in labels.items()):
            continue
        total += float(s.get(field, 0.0) or 0.0)
    return total


def term(run: Run, t: Dict[str, Any]) -> Optional[float]:
    if "const" in t:
        return float(t["const"])
    if "sum" in t:
        parts = [term(run, x) for x in t["sum"]]
        return None if any(p is None for p in parts) else float(sum(parts))
    if "client" in t:
        v = run.client.get(t["client"])
        return None if v is None else float(v)
    if "result" in t:
        v = run.result.get(t["result"])
        return None if v is None else float(v)
    field, at = t.get("field", "value"), t.get("at", "window")
    if at == "boot":
        return family_total(run.counters_boot, t["counter"], field,
                             t.get("labels"))
    end = family_total(run.counters_end, t["counter"], field, t.get("labels"))
    if end is None and "missing" in t and run.counters_end:
        return float(t["missing"])   # a counter nobody has incremented yet
    if at == "end" or end is None:
        return end
    start = family_total(run.counters_start, t["counter"], field,
                          t.get("labels")) or 0.0
    return end - start


def histogram_quantile(start: dict, end: dict, name: str,
                       q: float) -> Optional[float]:
    """Quantile of what a histogram family observed between two snapshots,
    all series pooled; linear inside the bucket (the overflow bucket is cut
    at the largest value ever seen)."""
    fam = end.get(name)
    if fam is None:
        return None
    counts: Dict[float, float] = {}
    top = 0.0
    for snap, sign in ((end, 1.0), (start, -1.0)):
        for s in snap.get(name, {}).get("series", []):
            prev = 0.0
            for bound, cum in s.get("buckets", []):
                b = float("inf") if bound == "+Inf" else float(bound)
                counts[b] = counts.get(b, 0.0) + sign * (cum - prev)
                prev = cum
            top = max(top, float(s.get("max") or 0.0))
    total = sum(counts.values())
    if total <= 0:
        return None
    target, cum, lower = q * total, 0.0, 0.0
    for b in sorted(counts):
        c = counts[b]
        if c > 0 and cum + c >= target:
            upper = min(b, max(top, lower))
            return lower + (upper - lower) * (target - cum) / c
        cum += c
        lower = b if b != float("inf") else lower
    return top


def read_declared(run: Run, source: dict) -> Optional[float]:
    kind = source["type"]
    scale = float(source.get("scale", 1.0))
    if kind == "term":
        v = term(run, source["term"])
        return None if v is None else scale * v
    if kind == "ratio":
        num, den = term(run, source["num"]), term(run, source["den"])
        if num is None or not den:
            return None
        return scale * num / den
    if kind == "histogram_quantile":
        v = histogram_quantile(run.counters_start, run.counters_end,
                               source["name"], float(source["q"]))
        return None if v is None else scale * v
    if kind == "trace_module":
        if not run.trace:
            return None
        ms = trace_reduce.module_busy_ms(run.trace, source["match"])
        if not ms:
            return None
        how = source.get("reduce", "median")
        return scale * float({"median": np.median, "mean": np.mean,
                              "sum": np.sum, "count": len}[how](ms))
    if kind == "trace_ops":
        if not run.trace:
            return None
        busy = trace_reduce.busy_s(run.trace)
        shares = [100.0 * s / busy[n] for n, (s, calls)
                  in trace_reduce.op_self_s(run.trace, source["match"]).items()
                  if busy.get(n) and calls]
        return scale * max(shares) if shares else None
    if kind == "trace_idle":
        if not run.trace:
            return None
        worst = trace_reduce.summary(run.trace)["idle_share_worst"]
        return None if worst is None else scale * 100.0 * worst
    if kind == "trace_collectives":
        if not run.trace:
            return None
        per_dev = trace_reduce.collectives(run.trace).values()
        if source["field"] == "collective_share":
            shares = [d["collective_s"] / d["busy_s"] for d in per_dev
                      if d["busy_s"] > 0]
        else:
            shares = [d["exposed_s"] / d["window_s"] for d in per_dev
                      if d["window_s"] > 0]
        return scale * 100.0 * max(shares) if shares else None
    if kind == "span_quantile":
        durs = [e["dur"] for e in run.spans if e.get("name") == source["name"]
                and "dur" in e][int(source.get("skip", 0)):]
        if not durs:
            return None
        return scale * float(np.quantile(durs, float(source["q"])))
    if kind == "same_as":
        v = read(run, source["metric"])
        return None if v is None else scale * v
    raise ValueError(f"unknown layer-metric source type {kind!r}")


def read(run: Run, name: str) -> Optional[float]:
    """The metric ``name`` for this run, or None."""
    base = os.path.join(env.BENCH_DIR, "layer_metrics", name)
    if os.path.exists(base + ".json"):
        with open(base + ".json") as f:
            return read_declared(run, json.load(f)["source"])
    if os.path.exists(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            f"layer_metric_{name}", base + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(run)
    raise FileNotFoundError(
        f"per-layer metric {name!r} has no reader: add "
        f"benchmark/layer_metrics/{name}.json or {name}.py")


def read_all(run: Run) -> Dict[str, dict]:
    """Every per-layer metric of the run's cell, as the last line wants it."""
    out = {}
    for m in run.cell.metrics("per_layer"):
        v = read(run, m["name"])
        if v is not None and np.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
