#!/usr/bin/env python
"""Transformer training MFU on the real chip — the matmul-bound counterpart
to the ResNet-50 bench (PERF.md): a GPT-style causal LM train step, flash vs
dense attention, sparse-label LM loss, MFU from 6*N*tokens + attention FLOPs.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np
from model_benches import peak_bf16

B = int(os.environ.get("TB_BATCH", 8))
T = int(os.environ.get("TB_SEQ", 2048))
L = int(os.environ.get("TB_LAYERS", 12))
DM = int(os.environ.get("TB_DMODEL", 768))
V = int(os.environ.get("TB_VOCAB", 32000))


def measure(flash):
    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.train import Trainer

    zm = CausalLM(seed=0, input_shape=(T,), num_layers=L, d_model=DM,
                  num_heads=DM // 64, vocab=V, flash=flash)
    m = zm.build()
    m.config.compute_dtype = "bfloat16"
    m.init()
    tr = Trainer(m)
    step = tr._make_step()
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randint(0, V, (B, T)).astype(np.int32))
    y = jax.device_put(rng.randint(0, V, (B, T)).astype(np.int32))
    r = jax.random.PRNGKey(0)
    p, o, s = tr.params, tr.opt_state, tr.state
    p, o, s, loss = step(p, o, s, x, y, r)
    lf = float(loss)

    def run(k, p, o, s):
        t0 = time.perf_counter()
        for _ in range(k):
            p, o, s, loss = step(p, o, s, x, y, r)
        float(loss)
        return time.perf_counter() - t0, p, o, s

    t1, p, o, s = run(3, p, o, s)
    t2, p, o, s = run(12, p, o, s)
    dt = (t2 - t1) / 9
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tr.params))
    # 6ND counts only MATMUL parameters: token/positional embedding tables
    # are gathers (their fwd is O(B*T*D) lookups, not 2*N*B*T flops) — the
    # LM head matmul is real and stays. Counting embeddings inflates MFU
    # ~19% at V=32k d=768.
    from deeplearning4j_tpu.nn.layers import EmbeddingSequence, PositionalEmbedding
    from deeplearning4j_tpu.nn.model import _layer_key

    n_embed = sum(
        int(np.prod(a.shape))
        for i, layer in enumerate(m.layers)
        if isinstance(layer, (EmbeddingSequence, PositionalEmbedding))
        for a in jax.tree.leaves(tr.params.get(_layer_key(i, layer), {})))
    n_matmul = n_params - n_embed
    # + causal attention: 12*B*T^2*DM*L/2 (fwd+bwd, halved for causality)
    flops = 6 * n_matmul * B * T + 12 * B * T * T * DM * L // 2
    peak = peak_bf16(jax.devices()[0].device_kind)
    return dt, flops / dt / peak, lf, n_params, n_matmul


def main():
    for flash in (False, True):
        dt, mfu, loss, n, nm = measure(flash)
        print(f"flash={flash}: {dt * 1e3:8.2f} ms/step  MFU {mfu:.3f}  "
              f"loss {loss:.3f}  params {n / 1e6:.1f}M "
              f"(matmul {nm / 1e6:.1f}M)  tokens/s {B * T / dt:,.0f}")


if __name__ == "__main__":
    main()
