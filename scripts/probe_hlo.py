#!/usr/bin/env python
"""Probe 2: HLO dtype audit + batch-256 throughput.

Checks the compiled train step for f32 convolutions (mixed-precision leaks)
and measures throughput at BENCH_BATCH (default 256).
"""

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np
from model_benches import peak_bf16

from deeplearning4j_tpu.data import BenchmarkIterator
from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.train import Trainer

dev = jax.devices()[0]
on_tpu = dev.platform != "cpu"
batch = int(os.environ.get("BENCH_BATCH", 256 if on_tpu else 4))
img = int(os.environ.get("BENCH_IMG", 224 if on_tpu else 32))

zm = ResNet50(num_classes=1000, seed=0, input_shape=(img, img, 3))
model = zm.build()
if on_tpu:
    model.config.compute_dtype = "bfloat16"
model.init()

tr = Trainer(model)
step = tr._make_step()
it = BenchmarkIterator((img, img, 3), 1000, batch, 1)
ds = next(iter(it))
x = jax.device_put(np.asarray(ds.features))
y = jax.device_put(np.asarray(ds.labels))
rng = jax.random.PRNGKey(0)
params, opt_state, state = tr.params, tr.opt_state, tr.state

lowered = step.lower(params, opt_state, state, x, y, rng)
hlo = lowered.as_text()
convs = re.findall(r"(\S+) = (\S+) convolution\(", hlo)
from collections import Counter

dtypes = Counter(re.match(r"([a-z0-9]+)\[", t).group(1) for _, t in convs if re.match(r"([a-z0-9]+)\[", t))
print(f"convolutions by output dtype: {dict(dtypes)}  (total {len(convs)})")
dots = re.findall(r" = (\S+) dot\(", hlo)
ddt = Counter(re.match(r"([a-z0-9]+)\[", t).group(1) for t in dots if re.match(r"([a-z0-9]+)\[", t))
print(f"dots by output dtype: {dict(ddt)}")
# f32 convolution operand check: find conv lines with f32 operands
f32conv = [line for line in hlo.splitlines() if "convolution(" in line and "f32[" in line.split("convolution(")[0]]
print(f"conv defs with f32 output: {len(f32conv)}")
for line in f32conv[:6]:
    print("  ", line.strip()[:160])

compiled = lowered.compile()
ca = compiled.cost_analysis()
if isinstance(ca, list):
    ca = ca[0]
print(f"flops/step @batch{batch}: {ca.get('flops', 0):.3e} ({ca.get('flops', 0)/batch:.3e}/img)")

def run(k, params, opt_state, state):
    t0 = time.perf_counter()
    for _ in range(k):
        params, opt_state, state, loss = step(params, opt_state, state, x, y, rng)
    lf = float(loss)
    return time.perf_counter() - t0, params, opt_state, state

_, params, opt_state, state = run(3, params, opt_state, state)
t1, params, opt_state, state = run(5, params, opt_state, state)
t2, params, opt_state, state = run(15, params, opt_state, state)
per_step = (t2 - t1) / 10
ips = batch / per_step
mfu = ips * 3 * 8.18e9 * (img / 224.0) ** 2 / peak_bf16(dev.device_kind)
print(f"batch {batch}: {per_step*1e3:.2f} ms/step, {ips:.1f} img/s, MFU(2/MAC)={mfu:.3f}")
