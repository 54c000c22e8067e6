"""Weight initialization schemes — parity with DL4J ``WeightInit`` (21 schemes).

Reference: ``nn/weights/WeightInit.java:68-72`` lists ZERO, ONES, SIGMOID_UNIFORM,
NORMAL, LECUN_NORMAL, UNIFORM, XAVIER, XAVIER_UNIFORM, XAVIER_FAN_IN,
XAVIER_LEGACY, RELU, RELU_UNIFORM, IDENTITY, LECUN_UNIFORM, VAR_SCALING_*
(6 variants), DISTRIBUTION.

Each scheme is a function ``(key, shape, fan_in, fan_out, dtype) -> Array``.
fan_in/fan_out are passed explicitly because DL4J computes them from layer
semantics (e.g. convs use kernel receptive field), not raw shape.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array

_REGISTRY: Dict[str, Callable] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name.lower()] = fn
        return fn

    return deco


def get(name_or_fn):
    if callable(name_or_fn):
        return name_or_fn
    key = str(name_or_fn).lower()
    if key not in _REGISTRY:
        raise ValueError(f"Unknown weight init '{name_or_fn}'. Known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def names():
    return sorted(_REGISTRY)


def compute_fans(shape: Sequence[int], kind: str = "dense"):
    """fan_in/fan_out following DL4J conventions.

    dense:  (in, out) -> fan_in=in, fan_out=out
    conv:   (kh, kw, in, out) [HWIO] -> fan_in=kh*kw*in, fan_out=kh*kw*out
    """
    shape = tuple(int(s) for s in shape)  # static dims, host-side  # jaxlint: disable=host-sync
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = math.prod(shape[:-2])
    return receptive * shape[-2], receptive * shape[-1]


register("zero")(lambda key, shape, fan_in, fan_out, dtype=jnp.float32: jnp.zeros(shape, dtype))
register("zeros")(lambda key, shape, fan_in, fan_out, dtype=jnp.float32: jnp.zeros(shape, dtype))
register("ones")(lambda key, shape, fan_in, fan_out, dtype=jnp.float32: jnp.ones(shape, dtype))


@register("normal")
def normal(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # DL4J NORMAL: N(0, 1/sqrt(fan_in)) — note std not variance.
    return jax.random.normal(key, shape, dtype) / jnp.sqrt(jnp.asarray(fan_in, dtype))


@register("normal_0.02")
def normal_002(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # N(0, 0.02) whatever the fans: the GPT-2 / OLMo ``initializer_range``
    return 0.02 * jax.random.normal(key, shape, dtype)


@register("uniform")
def uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # DL4J UNIFORM: U(-a, a), a = sqrt(3/fan_in)
    a = math.sqrt(3.0 / fan_in)
    return jax.random.uniform(key, shape, dtype, -a, a)


@register("xavier")
def xavier(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # Glorot normal: N(0, 2/(fan_in+fan_out)) variance.
    std = math.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype) * std


@register("xavier_uniform")
def xavier_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    a = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -a, a)


@register("xavier_fan_in")
def xavier_fan_in(key, shape, fan_in, fan_out, dtype=jnp.float32):
    std = math.sqrt(1.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


@register("xavier_legacy")
def xavier_legacy(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # DL4J's historical variant: variance 1/(fan_in+fan_out).
    std = math.sqrt(1.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype) * std


@register("relu")
def relu_init(key, shape, fan_in, fan_out, dtype=jnp.float32):
    # He normal: N(0, 2/fan_in) variance.
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


@register("relu_uniform")
def relu_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    a = math.sqrt(6.0 / fan_in)
    return jax.random.uniform(key, shape, dtype, -a, a)


@register("lecun_normal")
def lecun_normal(key, shape, fan_in, fan_out, dtype=jnp.float32):
    std = math.sqrt(1.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std


@register("lecun_uniform")
def lecun_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    a = math.sqrt(3.0 / fan_in)
    return jax.random.uniform(key, shape, dtype, -a, a)


@register("sigmoid_uniform")
def sigmoid_uniform(key, shape, fan_in, fan_out, dtype=jnp.float32):
    a = 4.0 * math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -a, a)


@register("identity")
def identity_init(key, shape, fan_in, fan_out, dtype=jnp.float32):
    if len(shape) == 2 and shape[0] == shape[1]:
        return jnp.eye(shape[0], dtype=dtype)
    # Conv identity: delta kernel at spatial center.
    if len(shape) >= 3 and shape[-2] == shape[-1]:
        w = jnp.zeros(shape, dtype)
        center = tuple(s // 2 for s in shape[:-2])
        eye = jnp.eye(shape[-1], dtype=dtype)
        return w.at[center].set(eye)
    raise ValueError(f"IDENTITY init requires square weights, got {shape}")


def _var_scaling(key, shape, scale_mode, distribution, fan_in, fan_out, dtype):
    if scale_mode == "fan_in":
        n = fan_in
    elif scale_mode == "fan_out":
        n = fan_out
    else:
        n = (fan_in + fan_out) / 2.0
    if distribution == "normal":
        return jax.random.normal(key, shape, dtype) * math.sqrt(1.0 / n)
    a = math.sqrt(3.0 / n)
    return jax.random.uniform(key, shape, dtype, -a, a)


for _mode in ("fan_in", "fan_out", "fan_avg"):
    for _dist in ("normal", "uniform"):
        _name = f"var_scaling_{_mode}_{_dist}"

        def _make(mode=_mode, dist=_dist):
            def fn(key, shape, fan_in, fan_out, dtype=jnp.float32):
                return _var_scaling(key, shape, mode, dist, fan_in, fan_out, dtype)

            return fn

        register(_name)(_make())


def distribution(dist_name: str, **kwargs):
    """WeightInit.DISTRIBUTION — arbitrary parameterized distribution.

    Supported: normal(mean,std), uniform(lower,upper), truncated_normal(mean,std),
    constant(value), orthogonal(gain), binomial(p) — parity with nn/conf/distribution/.
    """
    dist_name = dist_name.lower()

    def fn(key, shape, fan_in, fan_out, dtype=jnp.float32):
        if dist_name == "normal" or dist_name == "gaussian":
            return kwargs.get("mean", 0.0) + jax.random.normal(key, shape, dtype) * kwargs.get("std", 1.0)
        if dist_name == "uniform":
            return jax.random.uniform(key, shape, dtype, kwargs.get("lower", -1.0), kwargs.get("upper", 1.0))
        if dist_name == "truncated_normal":
            return kwargs.get("mean", 0.0) + jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) * kwargs.get("std", 1.0)
        if dist_name == "constant":
            return jnp.full(shape, kwargs.get("value", 0.0), dtype)
        if dist_name == "orthogonal":
            return jax.nn.initializers.orthogonal(scale=kwargs.get("gain", 1.0))(key, shape, dtype)
        if dist_name == "binomial":
            return jax.random.bernoulli(key, kwargs.get("p", 0.5), shape).astype(dtype)
        raise ValueError(f"Unknown distribution '{dist_name}'")

    return fn


def init_param(key, scheme, shape, kind: str = "dense", dtype=jnp.float32,
               fan_in: Optional[int] = None, fan_out: Optional[int] = None) -> Array:
    """Initialize one parameter tensor using a named scheme."""
    fi, fo = compute_fans(shape, kind)
    fn = get(scheme)
    return fn(key, tuple(shape), fan_in or fi, fan_out or fo, dtype)
