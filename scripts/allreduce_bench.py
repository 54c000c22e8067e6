#!/usr/bin/env python
"""ParallelWrapper allreduce-bandwidth driver metric (BASELINE.md row 4,
ref ParallelWrapper.java:467 — the NCCL allreduce the reference times).

Written when one chip was all there was, so the metric decomposes into two
parts (a four-chip host can now time the collective itself — ROADMAP S0):

1. REAL CHIP — the GSPMD-fused cost on the compute side: step-time delta
   between a plain ResNet-50 train step and the identical step wrapped in
   the ParallelWrapper shared_gradients program on a 1-device mesh. On one
   device XLA elides the all-reduce, so the delta is the wrapper's whole
   residual overhead (sharding constraints, program structure) — the
   correct single-chip number, and it should be ~0.

2. VIRTUAL 8-DEVICE MESH (CPU) — the collective is real (ring all-reduce
   over shared memory): time psum of a ResNet-50-sized gradient pytree
   (25.6M f32) alone, giving the per-step collective cost floor the
   wrapper adds when the wire is infinitely fast, plus the wire model:
   ring all-reduce moves 2(n-1)/n * 4B/param; at v5e ICI 1.6 Tbps/link
   (2 links/axis duplex) the 25.6M-param reduce is sub-millisecond —
   overlap with the 15.9ms backward makes it free in steady state.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PARAMS_RESNET50 = 25_557_032  # our ResNet50 param count (matches ref zoo)


def wire_model(n, params=PARAMS_RESNET50, bytes_per=4,
               ici_GBps=200.0):
    """Ring all-reduce wire math at v5e ICI (1.6 Tbps/link duplex)."""
    mb = 2 * (n - 1) / n * params * bytes_per / 1e6
    return {"n": n, "MB_per_worker": round(mb, 1),
            "t_ms_at_ici": round(mb / 1e3 / ici_GBps * 1e3, 3)}


def real_chip():
    import time

    import jax
    import numpy as np

    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.train import Trainer

    rng = np.random.RandomState(0)
    x = rng.randn(128, 224, 224, 3).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.randint(0, 1000, 128)]

    def timed(fit_one, iters=10):
        # steps chain through trainer/wrapper state, so fencing the last
        # loss fences the whole loop
        jax.block_until_ready(fit_one())  # compile + warm
        t0 = time.perf_counter()
        loss = None
        for _ in range(iters):
            loss = fit_one()
        jax.block_until_ready(loss)
        return (time.perf_counter() - t0) / iters * 1e3

    m = ResNet50(num_classes=1000, seed=0).build()
    m.config.compute_dtype = "bfloat16"
    m.init()
    tr = Trainer(m)
    step = tr._make_step()
    key = jax.random.PRNGKey(0)

    def plain_one():
        nonlocal_state["p"], nonlocal_state["o"], nonlocal_state["s"], loss = \
            step(nonlocal_state["p"], nonlocal_state["o"],
                 nonlocal_state["s"], x, y, key, None, None)
        return loss

    nonlocal_state = {"p": tr.params, "o": tr.opt_state, "s": tr.state}
    t_plain = timed(plain_one)

    m2 = ResNet50(num_classes=1000, seed=0).build()
    m2.config.compute_dtype = "bfloat16"
    m2.init()
    pw = ParallelWrapper(m2, mode="shared_gradients")
    t_pw = timed(lambda: pw._fit_batch(x, y))
    return {"plain_step_ms": round(t_plain, 2),
            "pw_shared_gradients_step_ms": round(t_pw, 2),
            "wrapper_overhead_ms": round(t_pw - t_plain, 2)}


def virtual_mesh():
    """Run in a subprocess with an 8-device CPU mesh; time bare psum of a
    ResNet-50-sized gradient tree."""
    code = r"""
import time
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")  # this child is the CPU half
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
devs = np.array(jax.devices()[:8])
mesh = Mesh(devs, ("dp",))
N = 25_557_032
# the FULL gradient buffer replicated on every worker (in_specs P(None)):
# each device contributes all 25.6M f32 values, exactly the
# ParallelWrapper shared_gradients wire pattern
g = jnp.ones((N,), jnp.float32)

@jax.jit
def reduce_only(g):
    def f(g):
        return jax.lax.psum(g, "dp")
    return jax.shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None))(g)

jax.block_until_ready(reduce_only(g))
t0 = time.perf_counter()
for _ in range(5):
    jax.block_until_ready(reduce_only(g))
dt = (time.perf_counter() - t0) / 5
mb = 2 * 7 / 8 * N * 4 / 1e6  # ring all-reduce: 2(n-1)/n of the buffer
print(f"RESULT {dt*1e3:.2f} {mb:.0f}")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT"):
            ms, mb = line.split()[1:]
            return {"psum_ms_8dev_cpu": float(ms),
                    "ring_MB_per_worker": float(mb),
                    "note": "full 25.6M-param buffer replicated per worker; "
                            "CPU shared-memory ring; collective overhead "
                            "floor, not ICI wire"}
    return {"error": out.stderr[-300:]}


if __name__ == "__main__":
    # the CPU child runs first, before this process touches JAX: a parent
    # that holds the chip would starve a child that needed it (this child
    # does not — it is pinned to the CPU)
    res = {"wire_model": [wire_model(n) for n in (4, 8, 32)],
           "virtual_mesh": virtual_mesh()}
    if "--cpu-only" not in sys.argv:
        import jax

        if jax.devices()[0].platform != "tpu":
            raise SystemExit("allreduce_bench: no TPU for the real-chip part "
                             "(pass --cpu-only for the CPU half alone)")
        res["real_chip"] = real_chip()
    print(json.dumps(res, indent=1))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "allreduce_bench.json"), "w") as f:
        json.dump(res, f, indent=1)
