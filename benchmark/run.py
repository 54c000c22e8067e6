#!/usr/bin/env python3
"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration file (``benchmark/configs/``) and a traffic
file (``benchmark/traffic/``); the traffic file's ``kind`` picks the runner
(``serve_open``, ``serve_closed``, ``train``). The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``; then ``checks``,
``failed_checks`` (which of them failed, the agreement gate's numbers by name)
and, last, ``compared``: each number a check compared, beside its limit.
Everything else goes to stderr, whose last lines say the same.

No TPU, no number: a cell of the repository's manifest exits 2 on any other
backend, and with fewer chips than it asks for. ``--manifest`` points at
another manifest (the rehearsal cells under ``benchmark/tests/data``), whose
cells may run on the CPU and say so in ``device``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                     # the harness package
sys.path.insert(0, os.path.dirname(HERE))    # the program under test

RUNNERS = {"serve_open": "serve_runner", "serve_closed": "serve_runner",
           "train": "train_runner"}


def main(argv=None) -> int:
    from harness import env

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the measured window (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=env.MANIFEST)
    args = ap.parse_args(argv)
    cell = env.Cell(args.manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    kind = cell.traffic["kind"]
    if kind not in RUNNERS:
        raise SystemExit(f"traffic kind {kind!r} has no runner: {sorted(RUNNERS)}")

    dirs = env.cache_dirs(cell.name)
    env.use_compile_cache(dirs["xla"])
    dev = env.device_info()
    print(f"[bench] {cell.name}: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} cache={dirs['xla']}", file=sys.stderr, flush=True)
    if cell.official and dev["platform"] != "tpu":
        print(f"benchmark: no TPU: JAX's backend is {dev['platform']!r}. A cell "
              f"of BENCHMARK.json only runs on a TPU.", file=sys.stderr)
        return 2
    if dev["count"] < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX sees "
              f"{dev['count']}.", file=sys.stderr)
        return 2
    if dev["platform"] == "tpu":
        from harness import peaks

        peaks.peak(dev["kind"])   # an unknown device_kind is an error, now

    import importlib

    runner = importlib.import_module("harness." + RUNNERS[kind])
    out = runner.run(cell, args, T_START, env.CompileWatch(), dirs)
    # which checks failed, and each number compared beside its limit: the
    # last lines of stderr and (`compared`) the last key of the result
    out.setdefault("failed_checks",
                   sorted(k for k, v in out["checks"].items() if not v))
    out["compared"] = out.pop("compared", {})
    env.log_compared(out["failed_checks"], out["compared"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
