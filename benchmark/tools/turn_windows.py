#!/usr/bin/env python3
"""One server, several consecutive windows: boot a serving cell once
(``serve_runner.boot``), run ``--windows`` untraced windows of its traffic
against it (``serve_runner.drive``, a seed each), and print for every window
what the client saw (``itl_p50_ms``, ``itl_max_ms``, ``late_p99_ms``) beside
what the program's own clock says of the same seconds: the per-tick
milliseconds by phase, the writers' lag and every stall
(``deeplearning4j_tpu/obs/trace.py:PhaseClock``; the readers are the
benchmark's, ``layer_metrics/turn_*_ms.json`` and their neighbours). What a
45 s run of a fresh boot cannot show: how the host's turn moves with the
server's age.

``--recorder on`` installs a ``FlightRecorder(out_dir=--out)`` around every
window, so that a stall lands in ``<out>/windows.jsonl`` with its record and a
worker that stands still for a second gets every thread's stack written to
``<out>/stall_stacks.txt``; ``pairs`` runs every seed twice, recorder on and
off (on, off, off, on, ...): the recorder's cost. ``--profile S`` opens a
``jax.profiler`` session (options as ``env.trace_window``'s) for ``S`` seconds
in the middle of every window and reads the clock's metrics over those seconds
alone (``traced``), over the part of the window before them (``before``),
over ``stop_trace()`` itself (``stopping``) and over what is left of the
window after it (``after``), beside the whole window's: which phases the
profiler's own milliseconds land in, and when.

    python3 benchmark/tools/turn_windows.py --workload laguna-mixedctx-decode \\
        --windows 8 --seed 2147497101 --out chiprun_out/drift-laguna

One JSON line a window on stdout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

CLOCK_METRICS = (
    "turn_prepare_ms", "turn_dispatch_ms", "turn_readback_ms",
    "turn_publish_ms", "turn_admit_ms", "turn_prefill_ms", "turn_self_ms",
    "turn_host_ms", "tick_mean_ms", "tick_p50_ms", "stall_count",
    "stall_share", "stall_max_ms", "stall_readback_share",
    "stall_offcpu_share", "stall_proc_cpu_share", "write_lag_p50_ms",
    "write_lag_p99_ms", "gc_pause_share")
PHASES = "serve_gen_phase_seconds_total"


def clock_metrics(layer_metrics, run) -> dict:
    return {name: v for name in CLOCK_METRICS
            for v in [layer_metrics.read(run, name)] if v is not None}


def main(argv=None) -> int:
    from harness import env, layer_metrics, serve_runner

    from deeplearning4j_tpu.obs import flight

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0,
                    help="the weights' and the first window's; +1 a window")
    ap.add_argument("--recorder", choices=("on", "off", "pairs"), default="off")
    ap.add_argument("--profile", type=float, default=0.0,
                    help="seconds of every window to run under the profiler")
    ap.add_argument("--out", default="chiprun_out/turn_windows")
    ap.add_argument("--manifest", default=env.MANIFEST)
    args = ap.parse_args(argv)
    args.trace = 0
    cell = env.Cell(args.manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    dirs = env.cache_dirs(cell.name)
    env.use_compile_cache(dirs["xla"])
    dev = env.device_info()
    if cell.official and dev["platform"] != "tpu":
        print(f"turn_windows: no TPU (backend {dev['platform']!r})",
              file=sys.stderr)
        return 2
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    plan = [(args.seed + i, args.recorder == "on") for i in range(args.windows)]
    if args.recorder == "pairs":    # on, off, off, on: neither side always first
        plan = [(args.seed + i, on) for i in range(args.windows)
                for on in ((True, False) if i % 2 == 0 else (False, True))]
    watch = env.CompileWatch()
    server, _mdl = serve_runner.boot(cell, args.seed, dirs, T_START)
    traced: dict = {}

    def profile():
        """On the driving thread, while the window is open: the counters
        just inside a profiler session of ``--profile`` seconds."""
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        time.sleep(0.4 * args.seconds)
        traced["before_end"] = server.metrics.snapshot()
        jax.profiler.start_trace(dirs["trace"], profiler_options=options)
        time.sleep(0.2)
        traced["start"], t0 = server.metrics.snapshot(), time.perf_counter()
        time.sleep(args.profile)
        traced["end"], t1 = server.metrics.snapshot(), time.perf_counter()
        traced["window_s"] = t1 - t0
        jax.profiler.stop_trace()
        traced["after_start"] = server.metrics.snapshot()
        traced["stop_s"] = time.perf_counter() - t1

    caught = 0
    try:
        with open(os.path.join(out_dir, "windows.jsonl"), "a") as log:
            for seed, on in plan:
                rec = flight.install(flight.FlightRecorder(
                    event_capacity=4096, out_dir=out_dir)) if on else None
                try:
                    got = serve_runner.drive(
                        cell, argparse.Namespace(**{**vars(args), "seed": seed}),
                        server, watch, dirs, T_START,
                        in_window=profile if args.profile else None)
                finally:
                    flight.uninstall()
                c = got["client"]
                run = layer_metrics.Run(
                    cell, dev, counters_start=got["counters_start"],
                    counters_end=got["counters_end"], client=c)
                row = {"workload": cell.name, "seed": seed, "recorder": on,
                       "server_age_s": time.perf_counter() - T_START,
                       "window_s": c["window_s"], "completed": c["completed"],
                       "failed": c["failed"],
                       "tokens_per_s": c["tokens_in_window"] / c["window_s"],
                       "itl_p50_ms": c["itl_p50_ms"],
                       "itl_max_ms": c["itl_max_ms"],
                       "ttft_p50_ms": c["ttft_p50_ms"],
                       "late_p99_ms": c["late_p99_ms"],
                       **clock_metrics(layer_metrics, run)}
                if args.profile:
                    row["stop_trace_s"] = traced["stop_s"]
                    for part, a, b in (
                            ("before", got["counters_start"], traced["before_end"]),
                            ("traced", traced["start"], traced["end"]),
                            ("stopping", traced["end"], traced["after_start"]),
                            ("after", traced["after_start"], got["counters_end"])):
                        row[part] = clock_metrics(layer_metrics, layer_metrics.Run(
                            cell, dev, counters_start=a, counters_end=b,
                            client={"window_s": traced["window_s"]}))
                # every phase's seconds over the window, gen.wait among them
                row["phase_s"] = {
                    s["labels"]["phase"]: s["value"] - (layer_metrics.family_total(
                        got["counters_start"], PHASES, "value", s["labels"]) or 0.0)
                    for s in got["counters_end"].get(PHASES, {}).get("series", [])}
                stalls = [e["data"] for e in (rec.events() if rec else [])
                          if e["kind"] == "stall"]
                caught += len(stalls)
                row["stalls"] = [{k: v for k, v in d.items() if k != "turns"}
                                 for d in stalls]
                print(json.dumps(row), flush=True)
                row["stalls"] = stalls
                row["tokens_by_second"] = c.get("tokens_by_second")
                log.write(json.dumps(row) + "\n")
                log.flush()
    finally:
        server.stop(drain=False)
    stacks = os.path.join(out_dir, flight.STACKS_FILE)
    if os.path.exists(stacks) and os.path.getsize(stacks):
        with open(stacks) as f:
            text = f.read()
        print(f"--- {stacks} ({len(text)} bytes; its end) ---\n{text[-12000:]}",
              flush=True)
    print(json.dumps({"windows": len(plan), "stalls_in_recorder": caught,
                      "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
