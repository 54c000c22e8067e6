#!/usr/bin/env python3
"""The agreement gate's readings for one cell and one seed, and its
lower-precision control at the cell's own size: what the bounds in a
configuration's ``agreement`` group are set from (``harness/agreement.py``
has the rule; PERF.md section 6, PR 44, the tables).

    python3 benchmark/tools/agreement_readings.py --workload olmoe-chat-decode --seed 7 --seconds 45 --control 1

One process: seeded weights, the program's server, the cell's traffic for
``--seconds`` (the checked requests are the schedule's, so a window long
enough to send them serves), then outside the window the gate as a run
computes it and, with ``--control 1``, the same positions with the reference
fed the weights rounded to 8 bits put in the program's place (the weights are
rounded in place: the process ends there). The last line of stdout is one
JSON record: ``agreement`` (the run's record), ``control`` (the shares the
8-bit computation reads) and ``control_fails`` (the bounds it breaks, which
must not be empty). ``--positions <file>`` keeps every judged position's gap
and routing margin.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(argv=None) -> int:
    from harness import agreement, env, serve_runner

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--positions", default=None)
    ap.add_argument("--manifest", default=env.MANIFEST)
    args = ap.parse_args(argv)
    args.trace = 0
    cell = env.Cell(args.manifest, args.workload)
    dirs = env.cache_dirs(cell.name)
    env.use_compile_cache(dirs["xla"])
    server, mdl = serve_runner.boot(cell, args.seed, dirs, T_START)
    try:
        rec = serve_runner.drive(cell, args, server, env.CompileWatch(), dirs,
                                 T_START)
    finally:
        server.stop(drain=False)
    del server
    client = rec["client"]
    agree, _ = agreement.check(cell, mdl.params, client["checked"],
                               args.positions)
    out = {"workload": cell.name, "seed": args.seed, "device": env.device_info(),
           "checked_missing": client["checked_missing"], "agreement": agree}
    if args.control:
        rule = agreement.rules(cell.config)
        ctl = agreement.control(
            cell, mdl.params, client["checked"],
            args.positions and args.positions + ".control.json")
        out["control"] = ctl
        out["control_fails"] = agreement.judge(
            ctl, {"flip_share": 1.0}, rule)["failed"]
    env.log(f"{out}")
    print(json.dumps(out), flush=True)
    return 0 if agree["ok"] and (not args.control or out["control_fails"]) else 1


if __name__ == "__main__":
    sys.exit(main())
