#!/usr/bin/env python
"""Microbench: BN-backward-style reductions — XLA fusion vs Pallas kernel.

The ResNet-50 step spends ~10.6ms in multiply_reduce fusions (sum(dy),
sum(dy*x) + dx elementwise over (B,H,W,C)). This measures, on a
stage-1-sized tensor, whether a hand-written Pallas kernel beats XLA's
fusion throughput enough to justify a custom BN VJP.

Timing: iterations are chained (dx feeds the next dy) inside one jitted
fori_loop, so device time per iteration is (t(K2)-t(K1))/(K2-K1) with a
single data-dependent readback per run.
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

M, C = 128 * 56 * 56, 256  # stage-1 shape flattened


def bn_bwd_xla(x, dy, a):
    s_dy = jnp.sum(dy, axis=0, dtype=jnp.float32)
    s_dyx = jnp.sum((dy * x).astype(jnp.float32), axis=0)
    dx = dy * a + (s_dy * (1.0 / M)).astype(x.dtype) + x * (s_dyx * (2.0 / M)).astype(x.dtype)
    return dx


def bn_bwd_pallas(x, dy, a):
    from jax.experimental import pallas as pl

    TM = 8192
    grid = M // TM

    def sum_kernel(x_ref, dy_ref, sdy_ref, sdyx_ref):
        i = pl.program_id(0)
        xv = x_ref[...].astype(jnp.float32)
        dyv = dy_ref[...].astype(jnp.float32)

        @pl.when(i == 0)
        def _():
            sdy_ref[...] = jnp.zeros_like(sdy_ref)
            sdyx_ref[...] = jnp.zeros_like(sdyx_ref)

        sdy_ref[...] += jnp.sum(dyv, axis=0, keepdims=True)
        sdyx_ref[...] += jnp.sum(dyv * xv, axis=0, keepdims=True)

    s_dy, s_dyx = pl.pallas_call(
        sum_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((TM, C), lambda i: (i, 0)),
                  pl.BlockSpec((TM, C), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, C), lambda i: (0, 0)),
                   pl.BlockSpec((1, C), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, C), jnp.float32),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)],
    )(x, dy)

    c1 = (s_dy * (1.0 / M)).astype(x.dtype)
    c2 = (s_dyx * (2.0 / M)).astype(x.dtype)

    def dx_kernel(x_ref, dy_ref, a_ref, c1_ref, c2_ref, dx_ref):
        dx_ref[...] = dy_ref[...] * a_ref[...] + c1_ref[...] + x_ref[...] * c2_ref[...]

    dx = pl.pallas_call(
        dx_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((TM, C), lambda i: (i, 0)),
                  pl.BlockSpec((TM, C), lambda i: (i, 0)),
                  pl.BlockSpec((1, C), lambda i: (0, 0)),
                  pl.BlockSpec((1, C), lambda i: (0, 0)),
                  pl.BlockSpec((1, C), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((TM, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, C), x.dtype),
    )(x, dy, a.reshape(1, C), c1, c2)
    return dx


def make_loop(fn, k):
    @jax.jit
    def loop(x, dy, a):
        def body(_, dyc):
            return fn(x, dyc, a)

        return jax.lax.fori_loop(0, k, body, dy)

    return loop


def measure(fn, x, dy, a, k1=4, k2=24):
    l1, l2 = make_loop(fn, k1), make_loop(fn, k2)
    float(jnp.sum(l1(x, dy, a)[0]))  # compile+warm
    float(jnp.sum(l2(x, dy, a)[0]))
    t0 = time.perf_counter()
    float(jnp.sum(l1(x, dy, a)[0]))
    t1 = time.perf_counter()
    float(jnp.sum(l2(x, dy, a)[0]))
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / (k2 - k1)


def main():
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(M, C).astype(np.float32).astype(jnp.bfloat16))
    dy = jax.device_put(rng.randn(M, C).astype(np.float32).astype(jnp.bfloat16))
    a = jax.device_put(rng.randn(C).astype(np.float32).astype(jnp.bfloat16))

    bytes_moved = (2 * M * C * 2) * 2 + M * C * 2  # read x,dy twice + write dx
    t = measure(bn_bwd_xla, x, dy, a)
    print(f"xla    {t * 1e3:7.3f} ms   {bytes_moved / t / 1e9:7.1f} GB/s effective")

    try:
        r0 = bn_bwd_xla(x, dy, a)
        r1 = bn_bwd_pallas(x, dy, a)
        np.testing.assert_allclose(np.asarray(r0, np.float32), np.asarray(r1, np.float32),
                                   rtol=5e-2, atol=5e-1)
        t = measure(bn_bwd_pallas, x, dy, a)
        print(f"pallas {t * 1e3:7.3f} ms   {bytes_moved / t / 1e9:7.1f} GB/s effective")
    except Exception as e:
        print(f"pallas failed: {type(e).__name__}: {str(e)[:400]}")


if __name__ == "__main__":
    main()
