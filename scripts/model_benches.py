#!/usr/bin/env python
"""Bench breadth — BASELINE.md configs 1-3 alongside ResNet-50:
LeNet-MNIST, GravesLSTM char-RNN, VGG16 step-time + MFU on one chip, timed
around ``block_until_ready`` after a warm-up. FLOPs per step come from XLA's
own cost model (``compiled.cost_analysis()``) so every model family is
counted consistently (fwd+bwd+optimizer, exactly what executes).

Usage (needs a TPU; exits non-zero without one):
    python scripts/model_benches.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

# Peak dense bf16 FLOP/s per chip, keyed by ``device_kind`` (Google Cloud TPU
# documentation, system architecture pages). The one table bench.py and this
# file divide by; a device that is not in it is an error, not a default.
PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,       # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e (Trillium)
}


def peak_bf16(device_kind) -> float:
    kind = str(device_kind)
    if kind not in PEAK_BF16:
        raise KeyError(f"no peak FLOP/s recorded for device_kind {kind!r}; "
                       f"add it to PEAK_BF16 with its source "
                       f"(known: {sorted(PEAK_BF16)})")
    return PEAK_BF16[kind]


def _timed_steps(run_k, steps):
    """Seconds per step: ``run_k(k)`` dispatches k steps and returns the
    last output; warm-up first, then ``steps`` steps fenced with
    ``block_until_ready``."""
    import jax

    jax.block_until_ready(run_k(max(steps // 4, 1)))
    t0 = time.perf_counter()
    jax.block_until_ready(run_k(steps))
    return (time.perf_counter() - t0) / steps


def bench_model(name, build_fn, batch, in_shape, n_classes, *, seq=False,
                steps=20, bf16=True, token_vocab=None, spe=1, micro=1):
    """``spe`` > 1 measures the ``steps_per_execution`` megastep path
    (Trainer._make_multi_step): spe train steps scanned inside one compiled
    program, amortizing per-step dispatch — the number that matters for
    small models whose single step is ~1-3 ms (dispatch-bound).
    ``micro`` > 1 measures the grad_accum path: micro microbatches of size
    ``batch`` per optimizer update (amortizes updater HBM traffic for
    100M+ param models). step_ms/flops are per (micro)batch step either
    way. spe and micro are mutually exclusive."""
    import jax

    from deeplearning4j_tpu.train import Trainer

    assert not (spe > 1 and micro > 1)
    model = build_fn()
    if bf16:
        model.config.compute_dtype = "bfloat16"
    model.init()
    tr = Trainer(model, grad_accum=micro)
    step = tr._make_step()
    rng = np.random.RandomState(0)
    x = rng.randn(batch, *in_shape).astype(np.float32)
    if token_vocab:  # (B, T) int token ids (BERT fine-tune shape)
        x = rng.randint(0, token_vocab, (batch, *in_shape)).astype(np.int32)
        y = np.eye(n_classes, dtype=np.float32)[rng.randint(0, n_classes, batch)]
    elif seq:  # (B, T, V) one-hot inputs + (B, T, V) targets (char-RNN)
        T, V = in_shape
        ids = rng.randint(0, V, (batch, T))
        x = np.eye(V, dtype=np.float32)[ids]
        y = np.eye(V, dtype=np.float32)[rng.randint(0, V, (batch, T))]
    else:
        y = np.eye(n_classes, dtype=np.float32)[rng.randint(0, n_classes, batch)]
    xd, yd = jax.device_put(x), jax.device_put(y)
    r = jax.random.PRNGKey(0)

    lowered = step.lower(tr.params, tr.opt_state, tr.state, xd, yd, r, None, None)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    flops = float((ca or {}).get("flops", 0.0))

    p, o, s = tr.params, tr.opt_state, tr.state
    p, o, s, loss = step(p, o, s, xd, yd, r, None, None)
    jax.block_until_ready(loss)  # (also settles net_state for the megastep)

    if spe > 1 or micro > 1:
        k = max(spe, micro)
        many = tr._make_multi_step() if spe > 1 else tr._make_accum_step()
        xs, ys = jnp_stack_k(xd, k), jnp_stack_k(yd, k)
        rs = jax.random.split(jax.random.PRNGKey(1), k)
        fn, args = many, (xs, ys, rs, None, None)
    else:
        fn, args = step, (xd, yd, r, None, None)

    def run_k(k):
        nonlocal p, o, s
        for _ in range(k):
            p, o, s, out = fn(p, o, s, *args)
        return out

    dt = _timed_steps(run_k, steps)
    dt /= spe * micro  # per (micro)batch train step either way
    dev = jax.devices()[0]
    peak = peak_bf16(dev.device_kind)
    row = {"model": name, "batch": batch, "step_ms": round(dt * 1e3, 2),
           "samples_per_sec": round(batch / dt, 1),
           "flops_per_step": flops,
           "mfu": round(flops / dt / peak, 4) if flops else None}
    if spe > 1:
        row["steps_per_execution"] = spe
    if micro > 1:
        row["grad_accum"] = micro
    return row


def jnp_stack_k(a, k):
    """(k, ...) broadcast-stack of one device array (D2D, no host trip)."""
    import jax.numpy as jnp

    return jnp.broadcast_to(a[None], (k,) + tuple(a.shape)).copy() \
        if hasattr(a, "shape") else a


def bench_transformer(*, num_layers=12, d_model=1536, batch=8, seq=1024,
                      vocab=32000, flash=True, steps=15, micro=1,
                      remat=False, pos="learned", window=None):
    """The matmul-dominated envelope case (PERF.md: 440M CausalLM + flash
    kernel at MFU 0.45 in the 2026-07 capture, where exact-BN ResNet-50
    caps ~0.36-0.40).
    Sparse integer labels — no (B, T, V) one-hot. ``micro=N`` measures the
    grad_accum path: N microbatches of size ``batch`` per optimizer update
    (one compiled program) — amortizes the AdamW HBM pass, the dominant
    non-matmul cost at 500M+ params. step_ms/tokens are per MICROBATCH so
    rows stay comparable."""
    import jax

    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.train import Trainer

    zm = CausalLM(seed=0, input_shape=(seq,), num_layers=num_layers,
                  d_model=d_model, num_heads=max(d_model // 64, 1),
                  vocab=vocab, flash=flash, remat=remat, pos=pos,
                  window=window)
    model = zm.build()
    model.config.compute_dtype = "bfloat16"
    model.init()
    tr = Trainer(model, grad_accum=micro)
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randint(0, vocab, (micro * batch, seq)).astype(np.int32))
    y = jax.device_put(rng.randint(0, vocab, (micro * batch, seq)).astype(np.int32))
    r = jax.random.PRNGKey(0)
    if micro > 1:
        import jax.numpy as jnp

        step = tr._make_accum_step()
        xs = x.reshape(micro, batch, seq)
        ys = y.reshape(micro, batch, seq)
        rs = jax.random.split(r, micro)
        args = (xs, ys, rs, None, None)
    else:
        step = tr._make_step()
        args = (x, y, r, None, None)
    compiled = step.lower(tr.params, tr.opt_state, tr.state, *args).compile()
    flops = float((compiled.cost_analysis() or {}).get("flops", 0.0)) / micro
    p, o, s = tr.params, tr.opt_state, tr.state

    def run_k(k):
        nonlocal p, o, s
        for _ in range(k):
            p, o, s, loss = step(p, o, s, *args)
        return loss

    dt = _timed_steps(run_k, steps) / micro
    dev = jax.devices()[0]
    peak = peak_bf16(dev.device_kind)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tr.params))
    row = {"model": f"causal_lm_{n_params/1e6:.0f}M_{'flash' if flash else 'dense'}",
           "batch": batch, "seq": seq, "step_ms": round(dt * 1e3, 2),
           "tokens_per_sec": round(batch * seq / dt, 1),
           "flops_per_step": flops,
           "mfu": round(flops / dt / peak, 4) if flops else None}
    if micro > 1:
        row["grad_accum"] = micro
    return row


def main():
    import jax

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"model_benches: no TPU: JAX's backend is "
                         f"{dev.platform!r}; these are device numbers")
    from deeplearning4j_tpu.models import (BertBase, LeNet, ResNet50, VGG16,
                                           GravesLSTMCharRNN)

    jobs = [
        ("lenet_mnist",
         lambda: LeNet(num_classes=10, seed=0, input_shape=(28, 28, 1)).build(),
         dict(batch=1024, in_shape=(28, 28, 1), n_classes=10)),
        ("graves_lstm_char_rnn",
         lambda: GravesLSTMCharRNN(seed=0, tbptt=0).build(),
         dict(batch=128, in_shape=(64, 98), n_classes=98, seq=True)),
        ("vgg16",
         lambda: VGG16(num_classes=1000, seed=0,
                       input_shape=(224, 224, 3)).build(),
         dict(batch=64, in_shape=(224, 224, 3), n_classes=1000)),
        ("resnet50",
         lambda: ResNet50(num_classes=1000, seed=0,
                          input_shape=(224, 224, 3)).build(),
         dict(batch=128, in_shape=(224, 224, 3), n_classes=1000)),
        # BASELINE config 5 (stretch): BERT-base fine-tune shape — the
        # architecture the Keras/HF import path targets (models/transformer.py
        # BertBase; keras_import golden tests cover the weight path).
        ("bert_base_t128",
         lambda: BertBase(num_classes=2, seed=0, input_shape=(128,),
                          flash=False).build(),
         dict(batch=64, in_shape=(128,), n_classes=2, token_vocab=30522)),
    ]
    for name, build, kw in jobs:
        print(json.dumps(bench_model(name, build, **kw)), flush=True)
    print(json.dumps(bench_transformer()), flush=True)


if __name__ == "__main__":
    main()
