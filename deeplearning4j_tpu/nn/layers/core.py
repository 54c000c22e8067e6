"""Core feed-forward layers: Dense, Activation, Dropout, Embedding, Output/Loss.

Reference parity: ``nn/conf/layers/DenseLayer.java``, ``ActivationLayer``,
``DropoutLayer``, ``EmbeddingLayer``, ``OutputLayer``, ``LossLayer``,
``CenterLossOutputLayer``, ``ElementWiseMultiplicationLayer``, ``PReLULayer``.

TPU notes: Dense is a single MXU matmul; DL4J's separate bias-add / activation
kernels fuse into it under XLA. Embedding lookups compile to dynamic-gather —
one-hot matmul is used for tiny vocab sizes where gather underutilizes the MXU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from ...ops import activations, initializers, losses
from ..api import (Array, Layer, Params, Shape, State, apply_input_dropout,
                   register_layer, split_rng)


@register_layer
@dataclass(frozen=True)
class Dense(Layer):
    """Fully-connected layer (DenseLayer.java). y = act(x @ W + b)."""

    n_out: int = 0
    activation: str = "identity"
    use_bias: bool = True

    def output_shape(self, input_shape: Shape) -> Shape:
        return input_shape[:-1] + (self.n_out,)

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = input_shape[-1]
        wk, bk = jax.random.split(key)
        w = initializers.init_param(wk, self.weight_init or "xavier", (n_in, self.n_out), dtype=dtype)
        params = {"w": w}
        if self.use_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = apply_input_dropout(self, x, rng, training)
        y = x @ params["w"]
        if self.use_bias:
            y = y + params["b"]
        return activations.get(self.activation)(y), state, mask


@register_layer
@dataclass(frozen=True)
class ActivationLayer(Layer):
    """Standalone activation (ActivationLayer.java) — fuses to a no-op boundary under XLA."""

    activation: str = "relu"

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return activations.get(self.activation)(x), state, mask


@register_layer
@dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout layer (DropoutLayer.java). ``rate`` is drop prob."""

    rate: float = 0.5

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        from ...ops.regularization import dropout

        if training and rng is None:
            raise ValueError("DropoutLayer needs rng in training mode")
        y = dropout(rng, x, self.rate, training) if training else x
        return y, state, mask


@register_layer
@dataclass(frozen=True)
class GaussianNoise(Layer):
    """Additive zero-mean Gaussian noise during training
    (conf/dropout/GaussianNoise.java; Keras GaussianNoise parity)."""

    stddev: float = 0.1

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if training:
            if rng is None:
                raise ValueError("GaussianNoise needs rng in training mode")
            x = x + self.stddev * jax.random.normal(rng, x.shape, x.dtype)
        return x, state, mask


def _check_rate(layer_name: str, rate: float):
    if not 0.0 <= rate < 1.0:
        raise ValueError(f"{layer_name} rate must be in [0, 1), got {rate}")


@register_layer
@dataclass(frozen=True)
class GaussianDropout(Layer):
    """Multiplicative 1-mean Gaussian noise with stddev sqrt(rate/(1-rate))
    (conf/dropout/GaussianDropout.java; Keras GaussianDropout parity)."""

    rate: float = 0.5

    def __post_init__(self):
        _check_rate("GaussianDropout", self.rate)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if training and self.rate > 0.0:
            if rng is None:
                raise ValueError("GaussianDropout needs rng in training mode")
            std = (self.rate / (1.0 - self.rate)) ** 0.5
            x = x * (1.0 + std * jax.random.normal(rng, x.shape, x.dtype))
        return x, state, mask


@register_layer
@dataclass(frozen=True)
class AlphaDropout(Layer):
    """SELU-preserving dropout (conf/dropout/AlphaDropout.java; Keras
    AlphaDropout parity): dropped units are set to alpha' and the output is
    affinely rescaled so self-normalizing activations keep mean/variance."""

    rate: float = 0.5

    def __post_init__(self):
        _check_rate("AlphaDropout", self.rate)

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if training and self.rate > 0.0:
            if rng is None:
                raise ValueError("AlphaDropout needs rng in training mode")
            alpha_p = -1.7580993408473766  # -alpha*lambda of SELU
            q = 1.0 - self.rate
            a = float((q + alpha_p ** 2 * q * self.rate) ** -0.5)
            b = float(-a * alpha_p * self.rate)
            keep = jax.random.bernoulli(rng, q, x.shape)
            x = a * jnp.where(keep, x, jnp.asarray(alpha_p, x.dtype)) + b
        return x, state, mask


@register_layer
@dataclass(frozen=True)
class Embedding(Layer):
    """EmbeddingLayer.java: integer ids -> embedding vectors.

    Input: (B,) or (B, 1) int ids; output (B, n_out). For sequences see
    EmbeddingSequence. ``one_hot_matmul`` routes tiny-vocab lookups through the
    MXU instead of gather.
    """

    n_in: int = 0  # vocab size
    n_out: int = 0
    use_bias: bool = False
    activation: str = "identity"
    one_hot_matmul: bool = False

    def output_shape(self, input_shape: Shape) -> Shape:
        return (self.n_out,)

    def init(self, key, input_shape, dtype=jnp.float32):
        w = initializers.init_param(key, self.weight_init or "xavier", (self.n_in, self.n_out), dtype=dtype)
        params = {"w": w}
        if self.use_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        ids = x.astype(jnp.int32)
        if ids.ndim >= 2 and ids.shape[-1] == 1:
            ids = ids[..., 0]
        if self.one_hot_matmul:
            y = jax.nn.one_hot(ids, self.n_in, dtype=params["w"].dtype) @ params["w"]
        else:
            y = jnp.take(params["w"], ids, axis=0)
        if self.use_bias:
            y = y + params["b"]
        return activations.get(self.activation)(y), state, mask


@register_layer
@dataclass(frozen=True)
class EmbeddingSequence(Layer):
    """EmbeddingSequenceLayer: (B, T) int ids -> (B, T, n_out).

    ``mask_zero=True`` emits a (B, T) padding mask (ids != 0) downstream —
    Keras Embedding(mask_zero=True) parity for model import.
    """

    n_in: int = 0
    n_out: int = 0
    mask_zero: bool = False

    def output_shape(self, input_shape: Shape) -> Shape:
        return input_shape + (self.n_out,)

    def init(self, key, input_shape, dtype=jnp.float32):
        w = initializers.init_param(key, self.weight_init or "xavier", (self.n_in, self.n_out), dtype=dtype)
        return {"w": w}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        ids = x.astype(jnp.int32)
        if self.mask_zero and mask is None:
            mask = (ids != 0).astype(jnp.float32)
        return jnp.take(params["w"], ids, axis=0), state, mask


@register_layer
@dataclass(frozen=True)
class ElementWiseMultiplication(Layer):
    """ElementWiseMultiplicationLayer: y = act(x * w + b), learned per-feature scale."""

    activation: str = "identity"

    def init(self, key, input_shape, dtype=jnp.float32):
        n = input_shape[-1]
        return {"w": jnp.ones((n,), dtype), "b": jnp.zeros((n,), dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return activations.get(self.activation)(x * params["w"] + params["b"]), state, mask


@register_layer
@dataclass(frozen=True)
class PReLU(Layer):
    """PReLULayer: ReLU with learned negative slope per feature."""

    def init(self, key, input_shape, dtype=jnp.float32):
        return {"alpha": jnp.zeros((input_shape[-1],), dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return jnp.where(x >= 0, x, x * params["alpha"]), state, mask


class _LossMixin:
    """Shared scoring for output layers — DL4J BaseOutputLayer.computeScore.

    ``use_logits``: when the (activation, loss) pair is softmax+MCXENT or
    sigmoid+XENT, score fuses them via the stable *_logits losses; ``apply``
    still emits probabilities for inference parity.
    """

    def _loss_fn_and_preact(self):
        act = getattr(self, "activation", "identity")
        loss = str(getattr(self, "loss", "mse")).lower()
        if act == "softmax" and loss in ("mcxent", "negativeloglikelihood"):
            return losses.get("mcxent_logits"), True
        if act == "sigmoid" and loss == "xent":
            return losses.get("xent_logits"), True
        return losses.get(loss), False

    def score_from_preactivation(self, preact: Array, labels: Array, mask=None):
        fn, fused = self._loss_fn_and_preact()
        with jax.named_scope("loss"):
            if fused:
                return fn(preact, labels, mask=mask)
            return fn(activations.get(getattr(self, "activation", "identity"))(preact), labels, mask=mask)


@register_layer
@dataclass(frozen=True)
class Output(Layer, _LossMixin):
    """OutputLayer.java: Dense + loss. ``score()`` computes the training loss."""

    n_out: int = 0
    activation: str = "softmax"
    loss: str = "mcxent"
    use_bias: bool = True

    def output_shape(self, input_shape: Shape) -> Shape:
        return input_shape[:-1] + (self.n_out,)

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = input_shape[-1]
        w = initializers.init_param(key, self.weight_init or "xavier", (n_in, self.n_out), dtype=dtype)
        params = {"w": w}
        if self.use_bias:
            params["b"] = jnp.zeros((self.n_out,), dtype)
        return params, {}

    def preactivation(self, params, x):
        with jax.named_scope("head"):
            y = x @ params["w"]
            if self.use_bias:
                y = y + params["b"]
            return y

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        x = apply_input_dropout(self, x, rng, training)
        return activations.get(self.activation)(self.preactivation(params, x)), state, mask

    def score(self, params, state, x, labels, *, mask=None):
        return self.score_from_preactivation(self.preactivation(params, x), labels, mask)


@register_layer
@dataclass(frozen=True)
class LossLayer(Layer, _LossMixin):
    """LossLayer.java: loss without params (input must already be n_out wide)."""

    activation: str = "identity"
    loss: str = "mse"

    def has_params(self):
        return False

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        return activations.get(self.activation)(x), state, mask

    def score(self, params, state, x, labels, *, mask=None):
        return self.score_from_preactivation(x, labels, mask)


@register_layer
@dataclass(frozen=True)
class RnnOutput(Output):
    """RnnOutputLayer.java: per-timestep Output over (B, T, F) with time masking."""

    def score(self, params, state, x, labels, *, mask=None):
        return self.score_from_preactivation(self.preactivation(params, x), labels, mask)


@register_layer
@dataclass(frozen=True)
class CnnLossLayer(LossLayer):
    """CnnLossLayer.java: per-pixel loss over (B, H, W, C) (e.g. segmentation)."""


@register_layer
@dataclass(frozen=True)
class RnnLossLayer(LossLayer):
    """RnnLossLayer.java: per-timestep loss over (B, T, F) with time masking
    (the param-free counterpart of RnnOutput; input must already be n_out
    wide — e.g. fed by a recurrent layer with matching hidden size)."""


@register_layer
@dataclass(frozen=True)
class CenterLossOutput(Output):
    """CenterLossOutputLayer.java: softmax CE + center loss on the input features."""

    alpha: float = 0.05
    lambda_: float = 2e-4

    def init(self, key, input_shape, dtype=jnp.float32):
        params, _ = super().init(key, input_shape, dtype)
        state = {"centers": jnp.zeros((self.n_out, input_shape[-1]), dtype)}
        return params, state

    def score(self, params, state, x, labels, *, mask=None):
        ce = self.score_from_preactivation(self.preactivation(params, x), labels, mask)
        label_idx = jnp.argmax(labels, axis=-1)
        cl, _ = losses.center_loss(x, label_idx, state["centers"], self.alpha)
        return ce + self.lambda_ * cl

    def update_centers(self, state, x, labels):
        label_idx = jnp.argmax(labels, axis=-1)
        _, new_centers = losses.center_loss(x, label_idx, state["centers"], self.alpha)
        return {**state, "centers": new_centers}
