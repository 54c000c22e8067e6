#!/usr/bin/env python
"""Ablation profiler for the ResNet-50 bench: where does the step time go?

Times variants of the ResNet-50 train step on the real chip as a two-point
slope (k2 - k1 steps between two fenced runs):
  full      : the exact bench train step
  fwd_loss  : forward + loss, no backward, no optimizer
  fwd_infer : inference forward (training=False, running stats)
  sgd       : train step with plain SGD (isolates adam cost)
  nobn      : train step on a BN-free ResNet-50 (BN folded away)
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from deeplearning4j_tpu.models import ResNet50
from deeplearning4j_tpu.models.cnn import _net_config
from deeplearning4j_tpu.nn.model import GraphBuilder
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn import vertices as V
from deeplearning4j_tpu.train import Trainer

BATCH = 128
IMG = 224


def resnet50_nobn(seed=0):
    g = GraphBuilder(_net_config(seed)).add_input("in", (IMG, IMG, 3))

    def conv(name, inp, n_out, k, stride=1, act="relu"):
        g.add_layer(name, L.Conv2D(n_out=n_out, kernel=(k, k), stride=(stride, stride),
                                   padding="same", use_bias=True, activation=act), inp)
        return name

    def bottleneck(name, inp, mid, out, stride=1, project=False):
        a = conv(f"{name}_a", inp, mid, 1, stride)
        b = conv(f"{name}_b", a, mid, 3)
        c = conv(f"{name}_cc", inp=b, n_out=out, k=1, act="identity")
        sc = conv(f"{name}_proj", inp, out, 1, stride, act="identity") if project else inp
        g.add_vertex(f"{name}_add", V.ElementWise(op="add"), c, sc)
        g.add_layer(name, L.ActivationLayer(activation="relu"), f"{name}_add")
        return name

    x = conv("stem", "in", 64, 7, stride=2)
    g.add_layer("pool1", L.Subsampling2D(kernel=(3, 3), stride=(2, 2), padding="same"), x)
    x = "pool1"
    for si, (blocks, mid, out, stride) in enumerate(
            [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2), (3, 512, 2048, 2)]):
        for bi in range(blocks):
            x = bottleneck(f"s{si}b{bi}", x, mid, out,
                           stride=stride if bi == 0 else 1, project=bi == 0)
    g.add_layer("gap", L.GlobalPooling(mode="avg"), x)
    g.add_layer("out", L.Output(n_out=1000, activation="softmax", loss="mcxent"), "gap")
    return g.set_outputs("out").build()


def timeit(fn, *args, steps=16):
    """Two-point slope timing; fn must return device values; we chain by
    re-feeding nothing (args fixed) and syncing via one readback at the end."""
    outs = fn(*args)
    jax.block_until_ready(outs)

    def run(k):
        t0 = time.perf_counter()
        o = None
        for _ in range(k):
            o = fn(*args)
        jax.block_until_ready(o)
        return time.perf_counter() - t0

    t1 = run(max(steps // 4, 1))
    t2 = run(steps)
    return (t2 - t1) / (steps - max(steps // 4, 1))


def timeit_step(step, params, opt_state, state, x, y, rng, steps=16):
    p, o, s, loss = step(params, opt_state, state, x, y, rng)
    float(loss)

    def run(k, p, o, s):
        t0 = time.perf_counter()
        for _ in range(k):
            p, o, s, loss = step(p, o, s, x, y, rng)
        float(loss)
        return time.perf_counter() - t0, p, o, s

    k1, k2 = max(steps // 4, 1), steps
    t1, p, o, s = run(k1, p, o, s)
    t2, p, o, s = run(k2, p, o, s)
    return (t2 - t1) / (k2 - k1)


def build(model_ctor, updater=None):
    zm = model_ctor(num_classes=1000, seed=0, input_shape=(IMG, IMG, 3))
    model = zm.build()
    model.config.compute_dtype = "bfloat16"
    if updater:
        model.config.updater = updater
    model.init()
    tr = Trainer(model)
    return model, tr


def main():
    x = np.random.RandomState(0).rand(BATCH, IMG, IMG, 3).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[np.random.RandomState(1).randint(0, 1000, BATCH)]
    x, y = jax.device_put(x), jax.device_put(y)
    rng = jax.random.PRNGKey(0)
    results = {}

    model, tr = build(ResNet50)

    @jax.jit
    def fwd_loss(params, state, x, y, rng):
        loss, _ = model.score(params, state, x, y, training=True, rng=rng)
        return loss

    results["fwd_loss"] = timeit(fwd_loss, tr.params, tr.state, x, y, rng)

    @jax.jit
    def fwd_infer(params, state, x):
        ys, _ = model.forward(params, state, x, training=False)
        return ys[0]

    results["fwd_infer"] = timeit(fwd_infer, tr.params, tr.state, x)

    # the donating step goes LAST for this trainer: it deletes tr.params
    step = tr._make_step()
    results["full"] = timeit_step(step, tr.params, tr.opt_state, tr.state, x, y, rng)

    model_sgd, tr_sgd = build(ResNet50, updater={"type": "sgd", "learning_rate": 1e-2})
    step_sgd = tr_sgd._make_step()
    results["sgd"] = timeit_step(step_sgd, tr_sgd.params, tr_sgd.opt_state, tr_sgd.state, x, y, rng)

    nob = resnet50_nobn()
    nob.config.compute_dtype = "bfloat16"
    nob.init()
    tr_nob = Trainer(nob)
    step_nob = tr_nob._make_step()
    results["nobn"] = timeit_step(step_nob, tr_nob.params, tr_nob.opt_state, tr_nob.state, x, y, rng)

    for k, v in results.items():
        print(f"{k:10s} {v * 1e3:8.2f} ms/step   {BATCH / v:9.1f} img/s")


if __name__ == "__main__":
    main()
