"""Normalization layers: BatchNorm, LRN, LayerNorm, RMSNorm.

Reference parity: ``nn/conf/layers/BatchNormalization.java`` (running stats as
mutable state, gamma/beta params, lockGammaBeta option) and
``LocalResponseNormalization.java``. LayerNorm/RMSNorm are TPU-first additions
required by the transformer/long-context model families (absent from DL4J 0.9,
which predates attention).

BatchNorm state follows the functional-state convention: running mean/var live
in the ``state`` pytree; ``apply`` in training mode returns the EMA-updated
state (the caller threads it), replacing DL4J's in-place helper mutation
(CudnnBatchNormalizationHelper — SURVEY.md §2.3).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...ops import activations
from ..api import Array, Layer, Shape, register_layer


@register_layer
@dataclass(frozen=True)
class BatchNorm(Layer):
    """BatchNormalization.java — normalizes over all axes but the last (feature)."""

    decay: float = 0.9  # EMA decay for running stats (DL4J `decay`)
    eps: float = 1e-5
    lock_gamma_beta: bool = False  # DL4J lockGammaBeta: fixed gamma=1, beta=0
    activation: str = "identity"

    def init(self, key, input_shape, dtype=jnp.float32):
        n = input_shape[-1]
        params = {}
        if not self.lock_gamma_beta:
            params = {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}
        state = {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}
        return params, state

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))
        if training:
            # Single-pass statistics: mean and E[x^2] are SIBLING reductions
            # over the same operand, so XLA fuses them into ONE read of the
            # activation (jnp.var's (x - mean)^2 form chains two dependent
            # reductions = two full HBM passes — measured 39% of the ResNet-50
            # step going to BatchNorm before this). Accumulate in f32 even
            # under bf16 compute: batch moments are precision-sensitive.
            mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
            msq = jnp.mean(lax.square(x.astype(jnp.float32)), axis=axes)
            var = jnp.maximum(msq - lax.square(mean), 0.0)
            sdt = state["mean"].dtype
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean.astype(sdt),
                "var": self.decay * state["var"] + (1 - self.decay) * var.astype(sdt),
            }
        else:
            mean = state["mean"].astype(jnp.float32)
            var = state["var"].astype(jnp.float32)
            new_state = state
        # Fold (mean, var, gamma, beta) into ONE per-channel affine y = x*a + b
        # (channel-vector math is free; the elementwise pass over x is one op
        # that fuses with the following activation / residual add).
        inv = lax.rsqrt(var + self.eps)
        if not self.lock_gamma_beta:
            a = inv * params["gamma"].astype(jnp.float32)
            b = params["beta"].astype(jnp.float32) - mean * a
        else:
            a = inv
            b = -mean * inv
        y = x * a.astype(x.dtype) + b.astype(x.dtype)
        return activations.get(self.activation)(y), new_state, mask


@register_layer
@dataclass(frozen=True)
class LRN(Layer):
    """LocalResponseNormalization.java — cross-channel (AlexNet-era).

    y = x / (k + alpha/n * sum_{j in window} x_j^2)^beta over the channel axis.
    Implemented as a reduce_window over channels; XLA fuses the whole thing.
    """

    n: int = 5
    k: float = 2.0
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        half = self.n // 2
        sq = jnp.square(x)
        window = (1,) * (x.ndim - 1) + (self.n,)
        pad = [(0, 0)] * (x.ndim - 1) + [(half, self.n - 1 - half)]
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1,) * x.ndim, pad)
        denom = jnp.power(self.k + (self.alpha / self.n) * ssum, self.beta)
        return x / denom, state, mask


@register_layer
@dataclass(frozen=True)
class LayerNorm(Layer):
    """Per-example normalization over the feature axis (transformer standard)."""

    eps: float = 1e-6
    use_bias: bool = True

    def init(self, key, input_shape, dtype=jnp.float32):
        n = input_shape[-1]
        params = {"gamma": jnp.ones((n,), dtype)}
        if self.use_bias:
            params["beta"] = jnp.zeros((n,), dtype)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps) * params["gamma"]
        if self.use_bias:
            y = y + params["beta"]
        return y, state, mask


def rms_norm(x, gamma, eps: float):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis. The mean
    square and the scaling run in f32 for anything narrower (a bf16 mean over
    2048 columns keeps three digits), and the result is rounded to
    ``x.dtype`` once. On f32 input this is the plain formula."""
    wide = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(wide)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * gamma.astype(wide)).astype(x.dtype)


@register_layer
@dataclass(frozen=True)
class RMSNorm(Layer):
    """RMS normalization (LLaMA-style) — cheaper than LayerNorm on the VPU."""

    eps: float = 1e-6

    def init(self, key, input_shape, dtype=jnp.float32):
        return {"gamma": jnp.ones((input_shape[-1],), dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], self.eps), state, mask
