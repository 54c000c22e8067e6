#!/usr/bin/env python3
"""Record the small two-chip trace that ``benchmark/tests`` keeps: a jitted
``shard_map`` of ``body`` (a bf16 matmul, then a psum over two chips; the trace
calls the program ``jit_body``) run four times under ``jax.profiler``. Needs
two TPU chips.

    python3 benchmark/tools/record_small_trace.py chiprun_out/two_chip_v5e.xplane.pb
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from harness import trace_reduce

    out = (argv or sys.argv[1:])[0]
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < 2:
        print(f"needs two TPU chips, found {len(devs)} x {devs[0].platform}",
              file=sys.stderr)
        return 2
    mesh = Mesh(np.array(devs[:2]), ("x",))

    def body(a, b):
        return jax.lax.psum(a @ b, "x")

    small_step = jax.jit(jax.shard_map(body, mesh=mesh,
                                       in_specs=(P(None, "x"), P("x", None)),
                                       out_specs=P()))
    a = jax.device_put(jnp.ones((512, 1024), jnp.bfloat16),
                       NamedSharding(mesh, P(None, "x")))
    b = jax.device_put(jnp.ones((1024, 512), jnp.bfloat16),
                       NamedSharding(mesh, P("x", None)))
    small_step(a, b).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(4):
            small_step(a, b).block_until_ready()
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(tmp)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out), "bytes",
          trace_reduce.summary(trace_reduce.load(out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
