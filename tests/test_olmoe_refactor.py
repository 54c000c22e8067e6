"""PR 31 moved what follows an expert layer's routing choice (one weight an
expert, the routing sums, the experts' three matmuls) out of
``layers/olmoe.py`` into ``layers/experts.py``, which the GLM block shares.
``OlmoeBlock`` must compute what it computed: here the parent commit's
``_ffn`` is written out as it stood (31e153b, ``olmoe.py:156-190``), and the
block's feed-forward, routing sums, full forward and cached decode on seeded
weights equal it BIT FOR BIT, in f32 and in bf16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.generation import decode_forward, init_caches
from deeplearning4j_tpu.nn.layers.norm import rms_norm


def parent_ffn(self, params, x, live):
    p = params["moe"]
    shape = x.shape
    h = rms_norm(x, params["ln2_g"], self.eps).reshape(-1, shape[-1])
    logits = jnp.dot(h, p["w_router"], preferred_element_type=jnp.float32)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), self.top_k)
    chosen = jax.nn.one_hot(idx, self.num_experts, dtype=jnp.int32)
    weight = jnp.sum(gate[:, :, None] * chosen, axis=1)
    routing = None
    if live is not None:
        rows = jnp.broadcast_to(live, shape[:-1]).reshape(-1)
        load = jnp.sum(chosen * rows[:, None, None], axis=(0, 1))
        routing = jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                             jnp.max(load)]).astype(jnp.int32)
    f32 = jnp.float32
    g = jnp.einsum("nd,edf->nef", h, p["w_gate"], preferred_element_type=f32)
    u = jnp.einsum("nd,edf->nef", h, p["w_up"], preferred_element_type=f32)
    a = (jax.nn.silu(g) * u * weight[:, :, None]).astype(h.dtype)
    y = jnp.einsum("nef,efd->nd", a, p["w_down"],
                   preferred_element_type=f32).astype(h.dtype)
    return y.reshape(shape), routing


def build(dtype):
    m = models.OlmoeLM(seed=3, input_shape=(96,), num_layers=2, d_model=64,
                       num_heads=4, num_experts=8, top_k=2, expert_width=32,
                       vocab=128, dtype=dtype).build()
    m.init()
    return m


def run(m, ids):
    """The full forward's hidden states, the cached decode's logits, and one
    layer's feed-forward with its routing sums."""
    pre, _ = m.forward(m.params, m.state, jnp.asarray(ids[None]),
                       up_to=len(m.layers) - 1)
    caches = init_caches(m, 1, 48, m.dtype)
    decoded = []
    for lo, hi in ((0, 16), (16, 29), (29, 30)):
        lg, caches = decode_forward(m, m.params, m.state,
                                    jnp.asarray(ids[None, lo:hi]), caches,
                                    jnp.int32(lo))
        decoded.append(np.asarray(lg))
    x = jnp.take(m.params["layer_0"]["w"], jnp.asarray(ids[None]), axis=0)
    live = jnp.asarray(np.arange(30)[None] < 23)
    y, routing = m.layers[1]._ffn(m.params["layer_1"], x, live)
    return [np.asarray(pre, np.float32), np.concatenate(decoded, axis=1),
            np.asarray(y, np.float32), np.asarray(routing)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_olmoe_computes_what_the_parent_computed(dtype, monkeypatch):
    m = build(dtype)
    ids = np.random.default_rng(2).integers(1, 128, 30).astype(np.int32)
    got = run(m, ids)
    monkeypatch.setattr(L.OlmoeBlock, "_ffn", parent_ffn)
    want = run(m, ids)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert got[3].tolist()[0] == 23 * 2      # live rows x top_k pairs
