#!/usr/bin/env python3
"""Look at one trace by hand before trusting code against it: the planes of
an ``.xplane.pb``, their lines, how many events each holds and the names
that take most time.

    python3 benchmark/tools/trace_summary.py benchmark/.cache/<cell>/trace [regex]

With a regex: every device operation whose HLO text matches it, with its
calls and total time (how a kernel or a collective is named in this trace).
"""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    from harness import trace_reduce

    target = (argv or sys.argv[1:])[0]
    path = target if target.endswith(".pb") else trace_reduce.find_xplane(target)
    if not path:
        print(f"no .xplane.pb under {target}", file=sys.stderr)
        return 1
    print(f"{path} ({os.path.getsize(path)} bytes)")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            totals, n = {}, 0
            for e in line.events:
                n += 1
                totals[e.name] = totals.get(e.name, 0) + e.duration_ns
            print(f"  line {line.name!r}: {n} events")
            if plane.name.startswith("/device:"):
                for name, ns in sorted(totals.items(), key=lambda kv: -kv[1])[:12]:
                    print(f"    {ns / 1e6:10.3f} ms  {name[:110]}")
    devices = trace_reduce.load(path)
    args = argv or sys.argv[1:]
    if len(args) > 1:
        import re

        rx = re.compile(args[1])
        for n, d in devices.items():
            hits = {}
            for line in (d.ops, d.async_ops):
                for name, s, e in zip(line.names, line.start, line.end):
                    if rx.search(name):
                        c, t = hits.get(name[:160], (0, 0))
                        hits[name[:160]] = (c + 1, t + int(e - s))
            print(f"device {n}: {len(hits)} names match {args[1]!r}")
            for name, (c, t) in sorted(hits.items(), key=lambda kv: -kv[1][1])[:20]:
                print(f"  {t / 1e6:10.3f} ms {c:6d} calls  {name}")
    print("summary", trace_reduce.summary(devices))
    print("top ops (self time)", trace_reduce.top_ops(devices))
    print("idle gaps", trace_reduce.idle_gaps(devices))
    return 0


if __name__ == "__main__":
    sys.exit(main())
