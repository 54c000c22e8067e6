"""The GLM-4.7-Flash decoder block (``model_type`` ``glm4_moe_lite``: the
DeepSeek-V3 block at other sizes): multi-head LATENT attention, and either a
dense SwiGLU (the leading layer) or 64 sigmoid-routed experts beside a shared
one.

For hidden ``x`` of width d, H heads, ``rms(v) = v / sqrt(mean(v^2) + eps) * g``::

    h       = rms(x)
    c_q     = rms(h W_qa)                       q = c_q W_qb -> (H, nope + rope) = (q_nope, q_rope)
    [c | r] = h W_kva          (kv_rank + rope)   c = rms(c) ;  k_rope = rope(r)   ONE rope key, shared by all heads
    [k_nope | v] = c W_kvb -> (H, nope + v)
    q_rope  = rope(q_rope)
    s       = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope) ; causal softmax ; o = sum w v
    x       = x + concat(o) W_o                 no bias anywhere
    h       = rms(x)
    dense layer:   x = x + W_d (silu(W_g h) * (W_u h))
    expert layer:  p = sigmoid(h W_r) (E) ;  S = the k largest of p + b   (b selects, and only selects)
                   w_e = scale * p_e / (sum over S of p + 1e-20)
                   x = x + sum over e in S of w_e * expert_e(h) + shared(h)        all SwiGLU

**What is cached is the latent.** The full forward (``apply``) expands
``c`` to per-head keys and values as written above. ``decode`` never does:
the cache holds ``c`` and ``k_rope``, ``kv_rank + rope`` values a token and
no heads (``cache_spec``: ``{"latent": (512,), "rope": (64,)}``; two arrays
because a TPU lays an array whose rows are 576 wide, not a multiple of its
128 lanes, block-index-minor, and then copies the whole pool on either side
of every scatter and gather), and the per-head matrices are absorbed into
the query and the output::

    q~ = q_nope W_kvb[k_nope part]^T  (H, kv_rank) ;  s = (q~ . c + q_rope . k_rope) / sqrt(nope + rope)
    o  = (sum w c) W_kvb[v part]

the same numbers by other algebra. Decode steps and prefill chunks alike take
this one path against the cache.

**Widths.** Parameters and the cache are held in the model's dtype (bf16
when served); the residual stream and every activation between two matmuls
are f32, and a product of an f32 activation with something held narrower is
exact in f32 (``experts.wide_einsum``). The reason is the router: it is a
step function, the chosen gates are renormalised to sum to ``scale``, so a
token whose k-th and (k+1)-th scores tie swaps a whole expert at weight
~``scale``/k when the scores move by a rounding error, and at 32-64 rows a
step is bound by streaming weights whatever the activations' width. A model
held in f32 multiplies as any f32 model does.

The block says itself how it decodes (``nn.generation.says_how_it_decodes``).
Not here: the multi-token-prediction layer of the publication (training and
self-drafting only), and its routing-bias update rule (training only).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import initializers
from ..api import Layer, Shape, register_layer
from .attention import rope_rotate
from .experts import assign, swiglu_experts, wide_einsum
from .norm import rms_norm

INIT = "normal_0.02"   # ``initializer_range`` 0.02


def _swiglu(h, p):
    """One dense SwiGLU on rows ``h`` (N, d); in ``h``'s dtype."""
    g = wide_einsum("nd,df->nf", h, p["w_gate"])
    u = wide_einsum("nd,df->nf", h, p["w_up"])
    a = (jax.nn.silu(g) * u).astype(h.dtype)
    return wide_einsum("nf,fd->nd", a, p["w_down"]).astype(h.dtype)


@register_layer
@dataclass(frozen=True)
class Glm4MoeLiteBlock(Layer):
    """One GLM-4.7-Flash decoder layer: (B, T, D) -> (B, T, D) in f32,
    causal. ``num_experts=0`` is the dense layer (one SwiGLU of
    ``ffn_width``); otherwise ``ffn_width`` is one expert's, and the shared
    experts are one SwiGLU of ``shared_experts * ffn_width``."""

    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    num_experts: int = 64
    top_k: int = 4
    ffn_width: int = 1536
    shared_experts: int = 1
    routed_scale: float = 1.8
    eps: float = 1e-5
    rope_base: float = 1e6

    def init(self, key, input_shape, dtype=jnp.float32):
        d, H, e, f = (input_shape[-1], self.num_heads, self.num_experts,
                      self.ffn_width)
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        nope, rope, v = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim)
        if e and not 1 <= self.top_k <= e:
            raise ValueError(f"top_k={self.top_k} of {e} experts")
        ks = iter(jax.random.split(key, 12))

        def w(*shape):
            return initializers.init_param(next(ks), self.weight_init or INIT,
                                           shape, dtype=dtype)

        def swiglu(width, *lead):
            return {"w_gate": w(*lead, d, width), "w_up": w(*lead, d, width),
                    "w_down": w(*lead, width, d)}

        params = {
            "ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
            "attn": {"w_qa": w(d, rq), "q_g": jnp.ones((rq,), dtype),
                     "w_qb": w(rq, H, nope + rope),
                     "w_kva": w(d, rkv + rope),
                     "kv_g": jnp.ones((rkv,), dtype),
                     "w_kvb": w(rkv, H, nope + v),
                     "w_o": w(H * v, d)},
        }
        if not e:
            params["mlp"] = swiglu(f)
        else:
            params["moe"] = {
                "w_router": w(d, e),
                # selects and only selects; the publication starts it at 0
                "e_score_correction_bias": jnp.zeros((e,), dtype),
                **swiglu(f, e),
                "shared": swiglu(self.shared_experts * f)}
        return params, {}

    # --- the hooks nn.generation and the batcher ask for -----------------
    def cache_spec(self, input_shape: Shape):
        """The normed latent ``c`` and the one rotated rope key: together
        one vector of ``kv_lora_rank + qk_rope_head_dim`` a token."""
        return {"latent": (self.kv_lora_rank,),
                "rope": (self.qk_rope_head_dim,)}

    def decode(self, params, x, cache, pos):
        """One chunk ``x`` (B, Tq, D) at absolute offset ``pos`` (scalar or
        (B,)) against ``cache`` in either layout of ``nn.generation``: the
        absorbed form. A caller that wants to know what routing did puts
        ``"live"`` ((B, Tq) bool, broadcastable: the rows that are real
        tokens) into the cache entry and finds ``"routing"`` in the one
        returned."""
        from ..generation import cache_gather, cache_write, causal_valid

        B, Tq, _ = x.shape
        nope = self.qk_nope_head_dim
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None] + jnp.arange(Tq)[None]
        else:
            positions = pos + jnp.arange(Tq)
        x = _wide(x)
        with jax.named_scope("attention"):
            p = params["attn"]
            q_nope, q_rope, c, k_rope = self._project(params, x, positions)
            with jax.named_scope("mla_q"):
                q_lat = wide_einsum("bqhe,che->bqhc", q_nope,
                                    p["w_kvb"][:, :, :nope])
            new = cache_write(cache, {"latent": c, "rope": k_rope}, pos)
            lat, rot = cache_gather(new, ("latent", "rope"))   # (B, L, ...)
            with jax.named_scope("mla_attend"):
                s = (wide_einsum("bqhc,bkc->bhqk", q_lat, lat)
                     + wide_einsum("bqhe,bke->bhqk", q_rope, rot)) \
                    * self._scale
                valid = causal_valid(pos, Tq, lat.shape[1])
                valid = valid[None, None] if valid.ndim == 2 \
                    else valid[:, None]
                w = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
                o = wide_einsum("bhqk,bkc->bqhc", w, lat)
                o = wide_einsum("bqhc,chv->bqhv", o, p["w_kvb"][:, :, nope:])
            x = x + wide_einsum("bqe,ed->bqd", o.reshape(B, Tq, -1), p["w_o"])
        m, routing = self._ffn(params, x, cache.get("live"))
        if routing is not None:
            new = {**new, "routing": routing}
        return x + m, new

    # --- the full forward: the expanded form --------------------------------
    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        B, T, _ = x.shape
        nope = self.qk_nope_head_dim
        x = _wide(x)
        # products of two activations: exact where the stream is wider than
        # what the model is held in (the module's note on widths)
        exact = None if params["ln1_g"].dtype == x.dtype \
            else jax.lax.Precision.HIGHEST
        with jax.named_scope("attention"):
            p = params["attn"]
            q_nope, q_rope, c, k_rope = self._project(params, x,
                                                      jnp.arange(T))
            with jax.named_scope("mla_kv"):
                kv = wide_einsum("bkc,che->bkhe", c, p["w_kvb"])
                k_nope, v = kv[..., :nope], kv[..., nope:]
            with jax.named_scope("mla_attend"):
                s = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, k_nope,
                                precision=exact)
                     + jnp.einsum("bqhe,bke->bhqk", q_rope, k_rope,
                                  precision=exact)) * self._scale
                see = jnp.tril(jnp.ones((T, T), jnp.bool_))[None, None]
                if mask is not None:     # (B, T) padding: never a key
                    see = see & mask[:, None, None, :].astype(jnp.bool_)
                w = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
                o = jnp.einsum("bhqk,bkhv->bqhv", w, v, precision=exact)
            x = x + wide_einsum("bqe,ed->bqd", o.reshape(B, T, -1), p["w_o"])
        m, _ = self._ffn(params, x, None)
        return x + m, state, mask

    # --- shared by both paths ---------------------------------------------
    @property
    def _scale(self) -> float:
        return 1.0 / np.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def _project(self, params, x, positions):
        """Norm and the low-rank projections: q_nope (B, T, H, nope), q_rope
        (B, T, H, rope) rotated, the normed latent c (B, T, kv_rank) and the
        one rotated rope key k_rope (B, T, rope)."""
        p = params["attn"]
        nope, rkv = self.qk_nope_head_dim, self.kv_lora_rank
        h = rms_norm(x, params["ln1_g"], self.eps)
        with jax.named_scope("mla_q"):
            c_q = rms_norm(wide_einsum("btd,dr->btr", h, p["w_qa"]),
                           p["q_g"], self.eps)
            q = wide_einsum("btr,rhe->bthe", c_q, p["w_qb"])
            q_rope = rope_rotate(q[..., nope:], positions, self.rope_base)
        with jax.named_scope("mla_kv"):
            kv = wide_einsum("btd,dr->btr", h, p["w_kva"])
            c = rms_norm(kv[..., :rkv], p["kv_g"], self.eps)
            k_rope = rope_rotate(kv[:, :, None, rkv:], positions,
                                 self.rope_base)[:, :, 0]
        return q[..., :nope], q_rope, c, k_rope

    def _ffn(self, params, x, live):
        """The feed-forward on (B, T, D), and what routing did to the rows
        ``live`` marks (None: nobody asked, or a dense layer)."""
        shape = x.shape
        h = rms_norm(x, params["ln2_g"], self.eps).reshape(-1, shape[-1])
        if not self.num_experts:
            with jax.named_scope("mlp"):
                return _swiglu(h, params["mlp"]).reshape(shape), None
        p = params["moe"]
        with jax.named_scope("moe_router"):
            # the product, the sigmoid, the bias and the choice in f32:
            # routing is a step function
            prob = jax.nn.sigmoid(wide_einsum("nd,de->ne", h, p["w_router"]))
            _, idx = jax.lax.top_k(
                prob + p["e_score_correction_bias"].astype(jnp.float32),
                self.top_k)                                   # (N, k)
            gate = jnp.take_along_axis(prob, idx, axis=-1)
            gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) \
                * self.routed_scale
            weight, routing = assign(gate, idx, self.num_experts, live,
                                     shape[:-1])
        with jax.named_scope("moe_experts"):
            y = swiglu_experts(h, p["w_gate"], p["w_up"], p["w_down"], weight)
        with jax.named_scope("moe_shared"):
            y = y + _swiglu(h, p["shared"])
        return y.reshape(shape), routing


def _wide(x):
    """The stream: f32 for anything narrower."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))
