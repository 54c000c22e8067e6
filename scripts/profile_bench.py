#!/usr/bin/env python
"""Decompose ResNet-50 bench step time on the real chip.

Measures, each as a two-point slope (k2 - k1 steps between two fenced runs):
  1. dispatch:   trivial jitted chained op   (pure dispatch overhead)
  2. fwd:        forward pass only
  3. step_py:    full train step, python loop (what bench.py measures today)
  4. step_scan:  K train steps inside one jitted lax.scan (one dispatch)

Usage: python scripts/profile_bench.py [batch ...]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from model_benches import peak_bf16

from deeplearning4j_tpu.data import BenchmarkIterator
from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.train import Trainer

RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 4.09e9


def slope(fn, k1, k2):
    fn(3)  # warmup/compile
    t1 = fn(k1)
    t2 = fn(k2)
    return (t2 - t1) / (k2 - k1)


def main():
    batches = [int(b) for b in sys.argv[1:]] or [128, 256]
    dev = jax.devices()[0]
    print("device:", dev.device_kind)
    PEAK = peak_bf16(dev.device_kind)  # unknown device: an error, no default

    # 1. dispatch overhead: chained tiny op
    @jax.jit
    def tiny(x):
        return x + 1.0

    def run_tiny(k):
        x = jnp.zeros((8,))
        t0 = time.perf_counter()
        for _ in range(k):
            x = tiny(x)
        _ = float(x[0])
        return time.perf_counter() - t0

    dt = slope(run_tiny, 5, 40)
    print(f"dispatch per-call: {dt * 1e3:.2f} ms")

    for batch in batches:
        img = 224
        zm = ResNet50(num_classes=1000, seed=0, input_shape=(img, img, 3))
        model = zm.build()
        model.config.compute_dtype = "bfloat16"
        model.init()
        tr = Trainer(model)
        step = tr._make_step()
        it = BenchmarkIterator((img, img, 3), 1000, batch, 1)
        ds = next(iter(it))
        x = jax.device_put(np.asarray(ds.features))
        y = jax.device_put(np.asarray(ds.labels))
        rng = jax.random.PRNGKey(0)

        # forward only
        @jax.jit
        def fwd(params, state, x):
            ys, _ = model.forward(params, state, x, training=False)
            return ys[0]

        def run_fwd(k):
            t0 = time.perf_counter()
            o = None
            for _ in range(k):
                o = fwd(tr.params, tr.state, x)
            _ = float(o[0, 0])
            return time.perf_counter() - t0

        tf = slope(run_fwd, 3, 12)

        # full step, python loop
        params, opt_state, state = tr.params, tr.opt_state, tr.state

        def run_step(k):
            nonlocal params, opt_state, state
            t0 = time.perf_counter()
            for _ in range(k):
                params, opt_state, state, loss = step(params, opt_state, state, x, y, rng)
            _ = float(loss)
            return time.perf_counter() - t0

        tp = slope(run_step, 3, 12)

        # K steps in one scan
        model.init()  # fresh params (prior ones were donated by step)
        tr2 = Trainer(model)
        tx = tr2.tx

        def one(carry, _):
            p, o, s = carry
            def loss_fn(pp):
                l, ns = model.score(pp, s, x, y, training=True, rng=rng)
                return l, ns
            (l, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            import optax
            up, o = tx.update(g, o, p)
            p = optax.apply_updates(p, up)
            return (p, o, ns), l

        def mk(k):
            def f(carry):
                return jax.lax.scan(one, carry, None, length=k)
            return jax.jit(f)

        f3, f12 = mk(3), mk(12)
        p0, o0, s0 = tr2.params, tr2.opt_state, tr2.state
        # warmup both
        r3 = f3((p0, o0, s0)); _ = float(r3[1][-1])
        r12 = f12((p0, o0, s0)); _ = float(r12[1][-1])
        t0 = time.perf_counter(); r3 = f3((p0, o0, s0)); _ = float(r3[1][-1])
        t3 = time.perf_counter() - t0
        t0 = time.perf_counter(); r12 = f12((p0, o0, s0)); _ = float(r12[1][-1])
        t12 = time.perf_counter() - t0
        ts = (t12 - t3) / 9

        for name, t in [("fwd", tf), ("step_py", tp), ("step_scan", ts)]:
            ips = batch / t
            mfu = ips * RESNET50_TRAIN_FLOPS_PER_IMAGE / PEAK if "step" in name else \
                  ips * 4.09e9 / PEAK
            print(f"b={batch} {name:10s}: {t * 1e3:7.2f} ms/step  {ips:8.1f} img/s  mfu={mfu:.3f}")


if __name__ == "__main__":
    main()
