#!/usr/bin/env python
"""Bisect the Mosaic flash-backward wrong-gradients bug on chip.

Stage A: single grid block (nq=nk=1), non-causal — isolates one kernel
invocation (no scratch accumulation, no masking).
Stage B: a copy kernel that loads a (1, bq, 1) block and broadcasts it to
(bq, D) — isolates the 1-lane load path the backward uses for lse/delta.
Stage C: multi-block non-causal, then causal — isolates accumulation and
the mask/reachability specialization.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import deeplearning4j_tpu.ops.flash_attention as fa

print("devices:", jax.devices())
rng = np.random.RandomState(0)


def grads(backend, q, k, v, causal, bq, bk):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=causal, backward=backend,
            block_q=bq, block_k=bk) ** 2)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def cmp(tag, B, T, H, D, causal, bq, bk):
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    gx = grads("xla", q, k, v, causal, bq, bk)
    gp = grads("pallas", q, k, v, causal, bq, bk)
    for name, a, b in zip("qkv", gx, gp):
        err = float(jnp.max(jnp.abs(a - b)) /
                    (jnp.max(jnp.abs(a)) + 1e-30))
        print(f"{tag} d{name}: rel-max-err {err:.2e}", flush=True)


# Stage B first (cheapest signal): 1-lane block load + broadcast
def copy_kernel(x_ref, o_ref):
    o_ref[0] = jnp.broadcast_to(x_ref[0], o_ref.shape[1:])

bq, D = 256, 128
x = jnp.asarray(rng.randn(1, 512, 1), jnp.float32)
y = pl.pallas_call(
    copy_kernel,
    grid=(1, 2),
    in_specs=[pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))],
    out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
    out_shape=jax.ShapeDtypeStruct((1, 512, D), jnp.float32),
)(x)
err = float(jnp.max(jnp.abs(y - jnp.broadcast_to(x, y.shape))))
print(f"stageB 1-lane load+broadcast: max-abs-err {err:.2e}", flush=True)

# Stage A: single block, non-causal
cmp("stageA single-block noncausal", 1, 256, 1, 128, False, 256, 256)
# Stage C1: multi-block non-causal (accumulation across k blocks)
cmp("stageC1 4-block noncausal", 1, 1024, 1, 128, False, 256, 256)
# Stage C2: multi-block causal (mask + reachability specialization)
cmp("stageC2 4-block causal", 1, 1024, 1, 128, True, 256, 256)
# Stage C3: the shape that first failed on the chip (2026-07)
cmp("stageC3 orig", 2, 1024, 4, 64, True, 512, 512)
print("DONE", flush=True)
