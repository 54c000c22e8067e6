"""Mixture-of-Experts + pipeline-parallel causal LM — the scaling-axes demo
(ep + pp; dp/tp/sp are shown in parallel_training.py and the transformer
sharding rules). Needs four devices: a TPU slice, or JAX_PLATFORMS=cpu for
an 8-device virtual CPU mesh.

1. Trains a Switch-style MoE causal LM with the standard Trainer (the MoE
   load-balancing aux loss flows through Sequential.score automatically).
2. Runs the same transformer blocks pipeline-parallel over a 4-stage GPipe
   schedule inside one jitted train step.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from examples._common import setup

setup(min_devices=4)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.data import ArrayIterator
from deeplearning4j_tpu.nn import NetConfig, SequentialBuilder
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.parallel import (PIPE_AXIS, from_microbatches,
                                         make_mesh, pipeline_apply,
                                         stack_stage_params, to_microbatches)
from deeplearning4j_tpu.train import Trainer


def main(epochs=20, V=40, T=16):
    rng = np.random.RandomState(0)
    x = rng.randint(0, V, (64, T)).astype(np.int32)
    y = ((x + 3) % V).astype(np.int32)  # learnable successor task

    # --- 1) MoE LM through the standard Trainer ---
    net = (SequentialBuilder(NetConfig(seed=0, updater={"type": "adamw",
                                                        "learning_rate": 5e-3}))
           .input_shape(T)
           .layer(L.EmbeddingSequence(n_in=V, n_out=32))
           .layer(L.MoETransformerBlock(num_heads=4, num_experts=4, top_k=2,
                                        causal=True))
           .layer(L.RnnOutput(n_out=V, activation="softmax", loss="mcxent"))
           .build())
    tr = Trainer(net)
    it = ArrayIterator(x, y, 16)
    before = tr.score_iterator(it)
    tr.fit(it, epochs=epochs)
    after = tr.score_iterator(it)
    aux = float(tr.state["layer_1"]["aux_loss"])
    print(f"MoE LM: loss {before:.3f} -> {after:.3f}  (balance aux {aux:.4f})")

    # --- 2) pipeline-parallel blocks (GPipe over a 4-stage mesh) ---
    S, M, d = 4, 4, 32
    mesh = make_mesh({PIPE_AXIS: S}, jax.devices()[:S])
    blk = L.TransformerEncoderBlock(num_heads=4, causal=True)
    emb = L.EmbeddingSequence(n_in=V, n_out=d)
    head = L.RnnOutput(n_out=V, activation="softmax", loss="mcxent")
    ks = jax.random.split(jax.random.PRNGKey(0), S + 2)
    params = {"emb": emb.init(ks[0], (T,))[0],
              "blocks": stack_stage_params([blk.init(k, (T, d))[0]
                                            for k in ks[1:S + 1]]),
              "head": head.init(ks[S + 1], (T, d))[0]}

    def stage_fn(p, h):
        out, _, _ = blk.apply(p, {}, h, training=False)
        return out

    def loss_fn(p):
        h, _, _ = emb.apply(p["emb"], {}, x[:32])
        h = from_microbatches(pipeline_apply(stage_fn, p["blocks"],
                                             to_microbatches(h, M), mesh))
        return head.score(p["head"], {}, h, y[:32])

    tx = optax.adamw(5e-3)
    opt = tx.init(params)

    @jax.jit
    def step(p, o):
        l, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, l

    first = None
    for _ in range(3 * epochs):
        params, opt, l = step(params, opt)
        first = first if first is not None else float(l)
    print(f"pipelined LM ({S} stages, {M} microbatches): "
          f"loss {first:.3f} -> {float(l):.3f}")
    return after, float(l)


if __name__ == "__main__":
    main()
