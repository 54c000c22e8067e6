"""What a run needs from its surroundings: the manifest, the cell's cache
directories inside the checkout, the device as JAX reports it, and a count
of what compiled when."""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
from typing import Dict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files,
    found by name: ``configs[<config>].file`` and
    ``benchmark/traffic/<traffic>.json`` (beside the manifest's own
    directory for a test manifest)."""

    def __init__(self, manifest_path: str, workload: str):
        self.manifest_path = os.path.abspath(manifest_path)
        self.manifest = load_json(self.manifest_path)
        base = os.path.dirname(self.manifest_path)
        try:
            self.entry = next(w for w in self.manifest["workloads"]
                              if w["name"] == workload)
        except StopIteration:
            names = [w["name"] for w in self.manifest["workloads"]]
            raise SystemExit(f"no workload {workload!r} in "
                             f"{self.manifest_path}: {names}") from None
        cfg = next(c for c in self.manifest["configs"]
                   if c["name"] == self.entry["config"])
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = load_json(os.path.join(base, cfg["file"]))
        self.traffic_dir = os.path.join(
            base, os.path.dirname(os.path.dirname(cfg["file"])), "traffic")
        self.traffic_path = os.path.join(self.traffic_dir,
                                         self.entry["traffic"] + ".json")
        self.traffic = load_json(self.traffic_path)
        self.official = self.manifest_path == os.path.abspath(MANIFEST)

    def metrics(self, group: str) -> list:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def cache_dirs(cell_name: str) -> Dict[str, str]:
    """Fixed paths inside the checkout (the path is part of a compile
    cache's key). ``JAX_COMPILATION_CACHE_DIR`` wins where it is set."""
    base = os.path.join(BENCH_DIR, ".cache", cell_name)
    dirs = {"xla": os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(base, "xla"),
            "aot": os.path.join(base, "aot"),
            "trace": os.path.join(base, "trace"),
            "tmp": os.path.join(base, "tmp")}
    shutil.rmtree(dirs["trace"], ignore_errors=True)
    shutil.rmtree(dirs["tmp"], ignore_errors=True)
    for d in dirs.values():
        os.makedirs(d, exist_ok=True)
    return dirs


def use_compile_cache(xla_dir: str) -> None:
    """Every program, however small, goes to the persistent cache: a second
    run of a cell must find all of them there."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", xla_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileWatch:
    """Counts XLA compiles (persistent-cache misses) and every backend
    compile-or-load, so a run can say what happened inside its window."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.misses = 0      # compiled: not in the persistent cache
        self.builds = 0      # compiled or loaded from the cache
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.misses += 1

    def _on_secs(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.builds += 1


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n_used: int) -> int:
    """The allocator's peak on the fullest chip in use (0 where the backend
    keeps no statistics, as on the CPU)."""
    import jax

    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:n_used]]
    return max(peaks, default=0)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def log_compared(failed_checks: list, compared: dict) -> None:
    """A run's last lines on standard error: which checks failed, and each
    number compared beside its limit (the result's line carries the same
    under ``compared``, its last key)."""
    for name, (value, limit) in compared.items():
        log(f"compared {name} = {value:.6g} limit {limit:.6g}")
    log(f"checks failed: {failed_checks or 'none'}")


def trace_window(trace_dir: str, delay_s: float, length_s: float):
    """Profile ``length_s`` seconds starting ``delay_s`` from now, on a
    thread of its own; join the thread to wait for the trace file.

    The device is traced; the host's Python is not. With the profiler's
    default Python tracer on, a decode tick of the serving cells took two to
    three times as long inside the traced 5 s (the host turn is most of a
    tick), the device's idle share read 66-76% where the untraced tick says
    35-45%, and the trace file was 52 MB (PR 22, my chip runs 1 and 2)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1    # the program's own annotations, once it has any

    def work():
        time.sleep(delay_s)
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        time.sleep(length_s)
        jax.profiler.stop_trace()

    t = threading.Thread(target=work, name="bench-trace", daemon=True)
    t.start()
    return t
