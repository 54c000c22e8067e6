#!/usr/bin/env python
"""CI smoke train: 5 telemetry-instrumented steps on CPU, exporting the
Chrome-trace JSON and Prometheus scrape as build artifacts.

Asserts the ISSUE-2 acceptance surface — the scrape must contain the
``train_step_seconds`` histogram and ``compile_cache_misses_total`` counter
(``device_memory_bytes`` appears only on a backend that reports allocator
statistics, which the CPU does not), and the trace must be Perfetto-loadable
(valid JSON, ``traceEvents`` with complete events) — so a regression in the
telemetry path fails CI before it reaches a real TPU run.

Artifacts land in $CI_ARTIFACTS_DIR (default: ./ci-artifacts/):
smoke_trace.json (open at https://ui.perfetto.dev) and smoke_metrics.prom.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

from deeplearning4j_tpu.data import ArrayIterator
from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.nn.model import NetConfig, Sequential
from deeplearning4j_tpu.obs import StepTelemetry
from deeplearning4j_tpu.train import Trainer

STEPS = 5
BATCH = 16


def main() -> int:
    out_dir = os.environ.get("CI_ARTIFACTS_DIR", "ci-artifacts")
    os.makedirs(out_dir, exist_ok=True)

    rng = np.random.RandomState(0)
    x = rng.rand(STEPS * BATCH, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, STEPS * BATCH)]
    model = Sequential(
        NetConfig(updater={"type": "sgd", "learning_rate": 0.1}),
        [Dense(n_out=8, activation="relu"),
         Output(n_out=3, loss="mcxent", activation="softmax")], (5,))
    tel = StepTelemetry()
    Trainer(model).fit(ArrayIterator(x, y, batch_size=BATCH), epochs=1,
                       telemetry=tel)

    trace_path = os.path.join(out_dir, "smoke_trace.json")
    prom_path = os.path.join(out_dir, "smoke_metrics.prom")
    tel.export_trace(trace_path)
    prom = tel.to_prometheus()
    with open(prom_path, "w") as f:
        f.write(prom)

    with open(trace_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    assert any(e.get("ph") == "X" and e.get("name") == "train_step"
               for e in events), "no train_step span in trace"
    assert all({"name", "ph", "pid", "tid"} <= set(e) for e in events), \
        "malformed trace event"
    for needle in ("train_step_seconds_bucket", "compile_cache_misses_total"):
        assert needle in prom, f"missing {needle} in Prometheus scrape"
    snap = tel.snapshot()
    assert snap["steps"] == STEPS, f"expected {STEPS} steps, got {snap['steps']}"

    print(f"smoke_trace: {snap['steps']} steps, "
          f"{snap['steps_per_sec']:.1f} steps/sec, "
          f"{snap['compile_cache_misses']} compile(s), "
          f"{len(events)} trace events -> {trace_path}, {prom_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
