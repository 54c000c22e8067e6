"""The OLMoE decoder block: RMSNorm, q/k RMSNorm over the whole projection,
bias-free projections, RoPE, and a DROPLESS top-k sparse SwiGLU expert layer.

Published as ``allenai/OLMoE-1B-7B`` (Muennighoff et al. 2024). For hidden
``x`` of width d, H heads of hd, E experts of width f, k experts a token::

    h  = rms(x) * g1                             rms(v) = v / sqrt(mean(v^2) + eps)
    q  = rms(h Wq) * gq ;  k_ = rms(h Wk) * gk ;  v = h Wv    (norms over ALL columns, before the heads)
    q, k_ = rope(q), rope(k_)
    x  = x + softmax(q k_^T / sqrt(hd), causal) v Wo
    h  = rms(x) * g2
    p  = softmax(h Wr) over all E, in f32 ;  S = the k largest p ;  no renormalisation
    x  = x + sum over e in S of  p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e

Every token goes to its k experts whatever the others chose: there is no
capacity and nothing is dropped (``moe.MoE`` is the GShard capacity layer, a
different thing). The router is this block's; what follows the choice (one
weight an expert, the routing sums, the experts' three matmuls) is
``layers/experts.py``'s, shared with the other dropless block.

The block is the first to say itself how it decodes (ROADMAP D1):
``cache_spec`` and ``decode`` are what ``nn.generation`` and the batcher ask a
layer for before their own ``isinstance`` ladders. The full forward
(``apply``) and ``decode`` call the same functions for the norms, the
projections, the router and the experts; only the attention's use of the
cache differs. Norm statistics, softmaxes and the router are f32 whatever the
parameters' dtype.

Not here, and listed as departures where a configuration uses the block: the
load-balancing and router-z auxiliary losses of the publication's training.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ...ops import initializers
from ..api import Layer, Shape, register_layer
from .attention import dot_product_attention, rope_rotate
from .experts import assign, swiglu_experts
from .norm import rms_norm

INIT = "normal_0.02"   # ``initializer_range`` 0.02


@register_layer
@dataclass(frozen=True)
class OlmoeBlock(Layer):
    """One OLMoE decoder layer: (B, T, D) -> (B, T, D), causal."""

    num_heads: int = 16
    num_kv_heads: Optional[int] = None   # None: one KV head a query head
    num_experts: int = 64
    top_k: int = 8
    expert_width: int = 1024
    eps: float = 1e-5
    rope_base: float = 10000.0

    @property
    def kv_heads(self) -> int:
        h = self.num_kv_heads or self.num_heads
        if self.num_heads % h:
            raise ValueError(f"num_heads={self.num_heads} must be divisible "
                             f"by num_kv_heads={h}")
        return h

    def init(self, key, input_shape, dtype=jnp.float32):
        d = input_shape[-1]
        d_kv = d // self.num_heads * self.kv_heads
        e, f = self.num_experts, self.expert_width
        if not 1 <= self.top_k <= e:
            raise ValueError(f"top_k={self.top_k} of {e} experts")
        ks = jax.random.split(key, 6)

        def w(k, *shape):
            return initializers.init_param(k, self.weight_init or INIT, shape,
                                           dtype=dtype)

        return {
            "ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
            "attn": {"w_qkv": w(ks[0], d, d + 2 * d_kv),
                     "q_g": jnp.ones((d,), dtype),
                     "k_g": jnp.ones((d_kv,), dtype),
                     "w_o": w(ks[1], d, d)},
            "moe": {"w_router": w(ks[2], d, e),
                    "w_gate": w(ks[3], e, d, f), "w_up": w(ks[4], e, d, f),
                    "w_down": w(ks[5], e, f, d)},
        }, {}

    # --- the hooks nn.generation and the batcher ask for -----------------
    def cache_spec(self, input_shape: Shape):
        """(kv_heads, head_dim) of the KV cache this layer decodes against."""
        return self.kv_heads, input_shape[-1] // self.num_heads

    def decode(self, params, x, cache, pos):
        """One chunk ``x`` (B, Tq, D) at absolute offset ``pos`` (scalar or
        (B,)) against ``cache`` in either layout of ``nn.generation``.
        A caller that wants to know what routing did puts ``"live"``
        ((B, Tq) bool, broadcastable: the rows that are real tokens) into
        the cache entry and finds ``"routing"`` in the one returned."""
        from ..generation import attend_cached

        Tq = x.shape[1]
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None] + jnp.arange(Tq)[None]
        else:
            positions = pos + jnp.arange(Tq)
        with jax.named_scope("attention"):
            q, k, v = self._qkv(params, x, positions)
            a, new = attend_cached(q, k, v, cache, pos)
            x = x + a @ params["attn"]["w_o"]
        m, routing = self._ffn(params, x, cache.get("live"))
        if routing is not None:
            new = {**new, "routing": routing}
        return x + m, new

    # --- the full forward -------------------------------------------------
    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        B, T, D = x.shape
        H, Hkv = self.num_heads, self.kv_heads
        with jax.named_scope("attention"):
            q, k, v = self._qkv(params, x, jnp.arange(T))
            if Hkv != H:
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
            see = jnp.tril(jnp.ones((T, T), jnp.bool_))[None, None]
            if mask is not None:     # (B, T) padding: never a key
                see = see & mask[:, None, None, :].astype(jnp.bool_)
            a = dot_product_attention(q, k, v, mask=see).reshape(B, T, D)
            x = x + a @ params["attn"]["w_o"]
        m, _ = self._ffn(params, x, None)
        return x + m, state, mask

    # --- shared by both paths ---------------------------------------------
    def _qkv(self, params, x, positions):
        """Norm, project, q/k-norm over the whole projection, split into
        heads, rotate: q (B, T, H, hd), k and v (B, T, Hkv, hd)."""
        p = params["attn"]
        B, T, D = x.shape
        H, Hkv = self.num_heads, self.kv_heads
        hd = D // H
        h = rms_norm(x, params["ln1_g"], self.eps)
        q, k, v = jnp.split(h @ p["w_qkv"], [D, D + Hkv * hd], axis=-1)
        q = rms_norm(q, p["q_g"], self.eps).reshape(B, T, H, hd)
        k = rms_norm(k, p["k_g"], self.eps).reshape(B, T, Hkv, hd)
        return (rope_rotate(q, positions, self.rope_base),
                rope_rotate(k, positions, self.rope_base),
                v.reshape(B, T, Hkv, hd))

    def _ffn(self, params, x, live):
        """The expert layer on (B, T, D), and what routing did to the rows
        ``live`` marks (None: nobody asked)."""
        p = params["moe"]
        shape = x.shape
        h = rms_norm(x, params["ln2_g"], self.eps).reshape(-1, shape[-1])
        with jax.named_scope("moe_router"):
            # accumulated and compared in f32: routing is a step function
            logits = jnp.dot(h, p["w_router"],
                             preferred_element_type=jnp.float32)
            gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                      self.top_k)            # (N, k)
            weight, routing = assign(gate, idx, self.num_experts, live,
                                     shape[:-1])
        with jax.named_scope("moe_experts"):
            y = swiglu_experts(h, p["w_gate"], p["w_up"], p["w_down"], weight)
        return y.reshape(shape), routing
