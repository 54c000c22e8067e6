#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell, once, by a sweep on the chip:
the highest mean rate at which a run sheds nothing and ends with no deeper
queue (mean over its last quarter) than at its midpoint (mean over its
second quarter, plus one request). All rates run in one process after one set-up,
each drained before the next; the cell's traffic file is used with only
``arrivals.mean_rate_rps`` replaced.

    python3 benchmark/tools/find_knee.py --workload sc1b-chat-burst \\
        --rates 4,6,8,10,12 --seconds 51 --out chiprun_out/knee.json

Prints one JSON line per rate and writes them all to ``--out``. The cell then
runs at 0.8 of the knee: write that number into the traffic file.
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


def mean_depth(depths, lo: float, hi: float) -> float:
    """Mean queue depth over the samples taken in [lo, hi) of the window:
    "at its midpoint" is the second quarter, "at its end" the last one, so
    that each holds at least one whole burst cycle."""
    xs = [d for f, d in depths if lo <= f < hi]
    return sum(xs) / len(xs) if xs else 0.0


def main(argv=None) -> int:
    from harness import env, serve_runner

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="mean rates, requests/s")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--manifest", default=env.MANIFEST)
    args = ap.parse_args(argv)
    args.trace = 0
    cell = env.Cell(args.manifest, args.workload)
    if cell.traffic["kind"] != "serve_open":
        raise SystemExit("the knee is a property of an open-loop cell")
    dirs = env.cache_dirs(cell.name)
    env.use_compile_cache(dirs["xla"])
    dev = env.device_info()
    if cell.official and dev["platform"] != "tpu":
        print(f"find_knee: no TPU (backend {dev['platform']!r})", file=sys.stderr)
        return 2
    watch = env.CompileWatch()
    server, _mdl = serve_runner.boot(cell, args.seed, dirs, T_START)
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            spec = json.loads(json.dumps(cell.traffic))
            spec["arrivals"]["mean_rate_rps"] = rate
            path = os.path.join(dirs["tmp"], f"knee-{rate:g}.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            depths = []   # (fraction of the window, waiting + mid-prefill)

            def in_window(depths=depths):
                """Sample the program's queue gauges four times a second."""
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < args.seconds - 0.3:
                    snap = server.metrics.snapshot()
                    depths.append((
                        (time.perf_counter() - t0) / args.seconds,
                        serve_runner.total(snap, "serve_gen_queue_depth")
                        + serve_runner.total(snap, "serve_prefill_queue_depth")))
                    time.sleep(0.25)

            rec = serve_runner.drive(cell, args, server, watch, dirs, T_START,
                                     traffic_path=path, in_window=in_window)
            c = rec["client"]
            shed = (serve_runner.total(rec["counters_final"], "serve_shed_total")
                    - serve_runner.total(rec["counters_start"], "serve_shed_total"))
            row = {"rate_rps": rate, "attempted": c["attempted"],
                   "failed": c["failed"], "shed": shed,
                   "queue_mid": mean_depth(depths, 0.25, 0.5),
                   "queue_end": mean_depth(depths, 0.75, 1.0),
                   "tokens_per_s": c["tokens_in_window"] / c["window_s"],
                   "ttft_p50_ms": c["ttft_p50_ms"], "ttft_p90_ms": c["ttft_p90_ms"],
                   "itl_p50_ms": c["itl_p50_ms"], "late_p99_ms": c["late_p99_ms"],
                   }
            row["sustained"] = bool(shed == 0 and c["failed"] == 0 and
                                    row["queue_end"] <= row["queue_mid"] + 1.0)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        server.stop(drain=False)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": dev, "workload": cell.name,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
