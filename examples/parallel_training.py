"""Data-parallel training over a device mesh — BASELINE.json config #4
(ParallelWrapper multi-device; here on a virtual 8-CPU mesh so the example
runs anywhere; on a TPU slice the same code uses the real chips)."""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from examples._common import setup

setup(min_devices=2)  # needs a mesh (JAX_PLATFORMS=cpu gives 8 virtual devices)

import numpy as np

from deeplearning4j_tpu.data.datasets import load_mnist
from deeplearning4j_tpu.data.iterators import ArrayIterator
from deeplearning4j_tpu.nn import NetConfig, SequentialBuilder
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.parallel import ParallelWrapper


def main(epochs=1, n=1024):
    x, y = load_mnist(train=True, num_examples=n)
    net = (SequentialBuilder(NetConfig(seed=0, updater={"type": "adam",
                                                        "learning_rate": 1e-3}))
           .input_shape(28, 28, 1)
           .layer(L.Conv2D(n_out=8, kernel=(3, 3), activation="relu"))
           .layer(L.Subsampling2D(kernel=(2, 2), stride=(2, 2)))
           .layer(L.Flatten())
           .layer(L.Dense(n_out=64, activation="relu"))
           .layer(L.Output(n_out=10, activation="softmax", loss="mcxent"))
           .build())
    # one global batch per step, sharded over the mesh; GSPMD inserts the
    # gradient all-reduce (the reference's SHARED_GRADIENTS mode)
    pw = ParallelWrapper(net, mode="shared_gradients")
    pw.fit(ArrayIterator(x, y, 128, shuffle=True), epochs=epochs)
    ev = pw.evaluate(ArrayIterator(x[:512], y[:512], 128))
    print(f"devices: {pw.n_dev}, train-set accuracy: {ev.accuracy():.3f}")
    return ev.accuracy()


if __name__ == "__main__":
    main()
