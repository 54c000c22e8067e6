"""JAX-aware training-step instrumentation — the instrument behind
``Trainer.fit(telemetry=...)`` and ``ParallelWrapper.fit(telemetry=...)``.

What it separates (the TensorFlow-timeline decomposition the reference never
had):

- **data-wait** — host time blocked on the iterator (``wrap_iterator``);
  with AsyncIterator prefetch this is the true input-pipeline stall, not the
  raw ETL cost.
- **dispatch** — time for the jitted step call to *return*: trace/compile on
  a cache miss, async-dispatch enqueue otherwise.
- **device-compute** — dispatch-return → ``jax.block_until_ready`` on the
  step outputs. Fencing every step serializes the host with the device, so
  enabling telemetry trades the deferred-readback pipelining for visibility
  — that is the deal, and it is why the default (``telemetry=None``) path
  must make zero obs calls.

Compile-cache misses are counted at the trainer's ``_batch_sig`` altitude:
a (structure, shape, dtype) signature never seen before means jax will
trace+compile — the first call and every shape change. Device memory is
gauged from ``device.memory_stats()`` where the backend provides it; a
backend that keeps no allocator statistics (CPU) gets no series.

Everything here is HOST-side: nothing is traced, nothing touches the jitted
step functions, so telemetry can never introduce a jaxlint host-sync finding
inside compiled code.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, Iterator, Optional, Set

from .metrics import MetricsRegistry
from .trace import Tracer


class StepTelemetry:
    """One instrument object per fit: registry + tracer + step phase timing.

    Pass to ``Trainer.fit(telemetry=StepTelemetry())`` (or attach a
    :class:`~deeplearning4j_tpu.obs.listener.TelemetryListener`, which fit
    auto-adopts). ``fence=False`` skips the per-step
    ``block_until_ready`` — dispatch/compute are no longer separable, but
    the deferred-readback pipelining is preserved.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None, fence: bool = True,
                 memory_every: int = 10):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.fence = fence
        self.memory_every = max(int(memory_every), 0)
        self._sigs: Dict[str, Set[Any]] = {}
        self._t0: Optional[float] = None
        self._steps = 0
        reg = self.registry
        self._step_hist = reg.histogram(
            "train_step_seconds",
            help="end-to-end train step wall time (dispatch + device compute)")
        self._dispatch_hist = reg.histogram(
            "train_dispatch_seconds",
            help="time for the jitted step call to return (enqueue, or "
                 "trace+compile on a cache miss)")
        self._device_hist = reg.histogram(
            "train_device_compute_seconds",
            help="dispatch return -> block_until_ready on the step outputs")
        self._data_hist = reg.histogram(
            "train_data_wait_seconds",
            help="host time blocked on the (possibly prefetching) iterator")
        self._compile_counter = reg.counter(
            "compile_cache_misses_total",
            help="first-call/shape-change step signatures (each one is an "
                 "XLA trace+compile)")
        self._steps_counter = reg.counter(
            "train_steps_total", help="train steps dispatched")
        self._samples_counter = reg.counter(
            "train_samples_total", help="training examples consumed")

    # --- fit-loop hooks ---
    def wrap_iterator(self, it: Iterable) -> Iterator:
        """Yield batches from ``it``, timing each ``next()`` as data-wait."""
        def gen():
            src = iter(it)
            while True:
                t0 = time.perf_counter()
                with self.tracer.span("data_wait"):
                    try:
                        ds = next(src)
                    except StopIteration:
                        return
                self._data_hist.observe(time.perf_counter() - t0)
                yield ds
        return gen()

    def step(self, thunk: Callable[[], Any], sig: Any = None,
             batch_size: int = 0, kind: str = "train"):
        """Run one dispatched train step through the phase clocks.

        ``thunk`` dispatches the (already-jitted) step and returns its device
        outputs; ``sig`` is the batch signature for compile-miss detection.
        """
        if self._t0 is None:
            self._t0 = time.perf_counter()
        if sig is not None:
            seen = self._sigs.setdefault(kind, set())
            if sig not in seen:
                seen.add(sig)
                self._compile_counter.inc()
                self.tracer.instant("compile_cache_miss", kind=kind)
        t0 = time.perf_counter()
        with self.tracer.span("train_step", kind=kind):
            with self.tracer.span("dispatch"):
                out = thunk()
            t1 = time.perf_counter()
            if self.fence:
                import jax

                with self.tracer.span("device_compute"):
                    jax.block_until_ready(out)
        t2 = time.perf_counter()
        self._step_hist.observe(t2 - t0)
        self._dispatch_hist.observe(t1 - t0)
        if self.fence:
            self._device_hist.observe(t2 - t1)
        self._steps += 1
        self._steps_counter.inc()
        if batch_size:
            self._samples_counter.inc(batch_size)
        if self.memory_every and self._steps % self.memory_every == 1:
            self.record_memory()
        return out

    def parallel_step(self, thunk: Callable[[], Any], batch_size: int = 0):
        """ParallelWrapper step: aggregate throughput + per-replica skew.

        After dispatch, each addressable shard of the loss is fenced in
        device order and its cumulative readiness time recorded as
        ``parallel_replica_step_seconds{replica=...}`` — the gauge of the
        SLOWEST replica is exact (it gates the step), earlier ones are upper
        bounds (fencing is sequential), so the max-min spread is a
        conservative skew signal.
        """
        reg = self.registry
        if self._t0 is None:
            self._t0 = time.perf_counter()
        t0 = time.perf_counter()
        with self.tracer.span("parallel_step"):
            with self.tracer.span("dispatch"):
                out = thunk()
            if self.fence:
                import jax

                with self.tracer.span("device_compute"):
                    for sh in getattr(out, "addressable_shards", []):
                        jax.block_until_ready(sh.data)
                        reg.gauge("parallel_replica_step_seconds",
                                  # bounded by the device count, not traffic
                                  # jaxlint: disable-next=metric-label-cardinality
                                  {"replica": str(sh.device.id)},
                                  help="cumulative time to this replica's "
                                       "loss shard readiness (skew gauge)"
                                  ).set(time.perf_counter() - t0)
                    jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        reg.histogram("parallel_step_seconds",
                      help="end-to-end multi-device step wall time"
                      ).observe(dt)
        if batch_size and dt > 0:
            reg.gauge("parallel_samples_per_second",
                      help="aggregate training throughput over all replicas"
                      ).set(batch_size / dt)
        self._steps += 1
        self._steps_counter.inc()
        if batch_size:
            self._samples_counter.inc(batch_size)
        if self.memory_every and self._steps % self.memory_every == 1:
            self.record_memory()
        return out

    def record_memory(self) -> None:
        """Device memory gauges from each device's allocator statistics.
        A backend that reports none (CPU) gets no series — host memory is
        never written under a device-memory name."""
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if key in stats:
                    self.registry.gauge(
                        "device_memory_bytes",
                        # bounded by the device count, not traffic
                        # jaxlint: disable-next=metric-label-cardinality
                        {"device": f"{d.platform}:{d.id}", "kind": key},
                        help="per-device allocator stats, where the "
                             "backend reports them").set(float(stats[key]))

    # --- export ---
    def snapshot(self) -> dict:
        """Summary dict: steps/sec, step-time quantiles, compile count."""
        elapsed = (time.perf_counter() - self._t0) if self._t0 else 0.0
        steps = self._steps
        pct = self._step_hist.percentiles()
        return {
            "steps": steps,
            "steps_per_sec": steps / elapsed if elapsed > 0 else 0.0,
            "samples_per_sec": (self._samples_counter.value / elapsed
                                if elapsed > 0 else 0.0),
            "mean_step_seconds": self._step_hist.mean,
            "p50_step_seconds": pct["p50"],
            "p95_step_seconds": pct["p95"],
            "p99_step_seconds": pct["p99"],
            "compile_cache_misses": int(self._compile_counter.value),
        }

    def export_trace(self, path: Optional[str] = None) -> str:
        return self.tracer.export(path)

    def to_prometheus(self) -> str:
        return self.registry.to_prometheus()
