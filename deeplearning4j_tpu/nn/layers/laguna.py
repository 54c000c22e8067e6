"""The Laguna decoder block (``model_type`` ``laguna``, ``poolside/
Laguna-S-2.1``): grouped-query attention whose layers come in two kinds, with
a head-wise output gate, and either a dense SwiGLU (the leading layer) or
softmax-routed experts beside a shared one.

For layer ``l`` of kind full or sliding, ``H_l`` query heads (they differ by
kind: 48 full, 72 sliding), ``Hkv`` key/value heads of ``hd``, hidden ``x``
of width d, ``rms(v) = v / sqrt(mean(v^2) + eps) * g``::

    h   = rms(x)
    q   = h Wq -> (H_l, hd) ;  k = h Wk -> (Hkv, hd) ;  v = h Wv -> (Hkv, hd)     no bias
    q,k = rope(q, pos), rope(k, pos)
            sliding: plain rope over the whole head
            full:    YaRN on the first ``rotary_dim`` dimensions of each head
                     (``attention.yarn_inv_freq``), cos and sin times
                     ``attention_factor``; the other dimensions unrotated
    a   = softmax(q k^T / sqrt(hd) + mask) v      causal; sliding: key j seen iff 0 <= i - j < window
    a_h = a_h * squash(h Wgate)_h                 Wgate (d, H_l): ONE gate a head a token
    x   = x + concat_h(a_h) Wo
    h   = rms(x)
    dense layer:   x = x + (silu(h Wg) * (h Wu)) Wd
    expert layer:  s = score(h Wr) over all E, f32 ;  S = the k largest s
                   w_e = scale * s_e / sum over S of s
                   x = x + sum over e in S, e HELD, of w_e * expert_e(h) + shared(h)   all SwiGLU

**Two fields the published config leaves unsaid** (the benchmark's
configuration file lists them under ``assumed``): ``router_score``
(``softmax``, or ``sigmoid``) and ``gate_act`` (``sigmoid``, or
``softplus``). No q/k norm and no gate on the shared expert: the config names
neither mechanism, so neither is built.

**A share of the experts.** ``experts_held = (first, count)``: the layer's
``w_gate`` / ``w_up`` / ``w_down`` are ``(count, d, f)``, the experts ``first
.. first + count - 1`` of the ``num_experts`` the router scores, ranks and
renormalises over; a chosen expert that lives elsewhere contributes nothing
HERE (its chip adds it in an expert-parallel deployment: the shares of all
chips plus the shared expert once are the uncut layer). ``None`` holds all.

**The cache.** ``k`` (rotated) and ``v``, ``(Hkv, hd)`` each a token. A
sliding layer states ``cache_window = window``: the dense layout keeps it at
capacity and masks the band, the paged layout hands it a ring
(``nn/generation.py``, the layout contract). ``apply`` (the full forward) and
``decode`` share every function but the cache.

**Widths** as ``glm4_moe_lite.py``: parameters and cache in the model's dtype
(bf16 when served), the stream and every activation between two matmuls f32,
multiplied exactly against what is held narrower (``experts.wide_einsum``):
the router is a step function.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import initializers
from ..api import Layer, Shape, register_layer
from .attention import rope_inv_freq, rope_rotate_freqs, yarn_inv_freq
from .experts import assign, swiglu_experts, wide_einsum
from .glm4_moe_lite import INIT, _swiglu, _wide
from .norm import rms_norm

ROUTER_SCORES = {"softmax": lambda z: jax.nn.softmax(z, axis=-1),
                 "sigmoid": jax.nn.sigmoid}
GATE_ACTS = {"sigmoid": jax.nn.sigmoid, "softplus": jax.nn.softplus}


@register_layer
@dataclass(frozen=True)
class LagunaBlock(Layer):
    """One Laguna decoder layer: (B, T, D) -> (B, T, D) in f32, causal.
    ``window=None`` is a full-attention layer; ``yarn_factor=0`` a plain
    rope. ``num_experts=0`` is the dense layer (one SwiGLU of
    ``ffn_width``); otherwise ``ffn_width`` is one routed expert's and
    ``shared_width`` the shared expert's."""

    num_heads: int = 48
    num_kv_heads: int = 8
    head_dim: int = 128
    window: Optional[int] = None
    rope_base: float = 5e5
    rotary_dim: Optional[int] = None      # None: the whole head
    yarn_factor: float = 0.0
    yarn_original: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0
    num_experts: int = 256
    top_k: int = 10
    ffn_width: int = 1024
    shared_width: int = 1024
    routed_scale: float = 2.5
    experts_held: Optional[Tuple[int, int]] = None
    router_score: str = "softmax"
    gate_act: str = "sigmoid"
    eps: float = 1e-6

    # --- sizes ------------------------------------------------------------
    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """(first, count) of the experts this layer holds, None for all."""
        if self.experts_held is None:
            return None
        first, count = (int(v) for v in self.experts_held)
        if not (0 <= first and 1 <= count and first + count <= self.num_experts):
            raise ValueError(f"experts_held={self.experts_held} of "
                             f"{self.num_experts} experts")
        return None if count == self.num_experts else (first, count)

    @property
    def cache_window(self) -> Optional[int]:
        """What the cache of this layer need reach back (``nn.generation``:
        a ring when paged); None for a full-attention layer."""
        return self.window

    def init(self, key, input_shape, dtype=jnp.float32):
        d, H, Hkv, hd = (input_shape[-1], self.num_heads, self.num_kv_heads,
                         self.head_dim)
        e, f = self.num_experts, self.ffn_width
        if H % Hkv:
            raise ValueError(f"num_heads={H} must be divisible by "
                             f"num_kv_heads={Hkv}")
        if e and not 1 <= self.top_k <= e:
            raise ValueError(f"top_k={self.top_k} of {e} experts")
        if self.router_score not in ROUTER_SCORES \
                or self.gate_act not in GATE_ACTS:
            raise ValueError(f"router_score={self.router_score!r} "
                             f"gate_act={self.gate_act!r}")
        ks = iter(jax.random.split(key, 12))

        def w(*shape):
            return initializers.init_param(next(ks), self.weight_init or INIT,
                                           shape, dtype=dtype)

        def swiglu(width, *lead):
            return {"w_gate": w(*lead, d, width), "w_up": w(*lead, d, width),
                    "w_down": w(*lead, width, d)}

        params = {
            "ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
            "attn": {"w_q": w(d, H, hd), "w_k": w(d, Hkv, hd),
                     "w_v": w(d, Hkv, hd), "w_head_gate": w(d, H),
                     "w_o": w(H * hd, d)},
        }
        if not e:
            params["mlp"] = swiglu(f)
        else:
            count = self.held[1] if self.held else e
            params["moe"] = {"w_router": w(d, e), **swiglu(f, count),
                             "shared": swiglu(self.shared_width)}
        return params, {}

    # --- the hooks nn.generation and the batcher ask for -----------------
    def cache_spec(self, input_shape: Shape):
        """Rotated keys and values, ``(kv_heads, head_dim)`` each a token;
        how far back they are needed is :attr:`cache_window`."""
        kv = (self.num_kv_heads, self.head_dim)
        return {"k": kv, "v": kv}

    def decode(self, params, x, cache, pos):
        """One chunk ``x`` (B, Tq, D) at absolute offset ``pos`` (scalar or
        (B,)) against ``cache`` in either layout of ``nn.generation``; a
        sliding layer's paged cache is a ring. A caller that wants to know
        what routing did puts ``"live"`` ((B, Tq) bool, broadcastable: the
        rows that are real tokens) into the cache entry and finds
        ``"routing"`` in the one returned."""
        from ..generation import (attend_in_place, cache_gather, cache_write,
                                  causal_valid, reads_in_place,
                                  ring_positions)

        Tq = x.shape[1]
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None] + jnp.arange(Tq)[None]
        else:
            positions = pos + jnp.arange(Tq)
        x = _wide(x)
        ring = self.window is not None and "tables" in cache
        with jax.named_scope("attention"):
            h, q, k, v = self._qkv(params, x, positions)
            new = cache_write(cache, {"k": k, "v": v}, pos, ring=ring)
            if reads_in_place(new, q, self.window):   # a full layer's step
                a = attend_in_place(q, new, pos)
            else:
                ck, cv = cache_gather(new, ("k", "v"))      # (B, L, Hkv, hd)
                kpos = ring_positions(new["tables"], new["k_pool"].shape[1],
                                      pos, Tq) if ring else None
                valid = causal_valid(pos, Tq, ck.shape[1], self.window, kpos)
                valid = valid[None, None, None] if valid.ndim == 2 \
                    else valid[:, None, None]
                a = self._attend(h, q, ck, cv, valid)
            x = x + self._gate_project(params, h, a)
        m, routing = self._ffn(params, x, cache.get("live"))
        if routing is not None:
            new = {**new, "routing": routing}
        return x + m, new

    # --- the full forward -------------------------------------------------
    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        T = x.shape[1]
        x = _wide(x)
        with jax.named_scope("attention"):
            h, q, k, v = self._qkv(params, x, jnp.arange(T))
            i = jnp.arange(T)
            see = i[None, :] <= i[:, None]
            if self.window is not None:
                see = see & (i[:, None] - i[None, :] < self.window)
            see = see[None, None, None]
            if mask is not None:     # (B, T) padding: never a key
                see = see & mask[:, None, None, None, :].astype(jnp.bool_)
            x = x + self._gate_project(params, h,
                                       self._attend(h, q, k, v, see))
        m, _ = self._ffn(params, x, None)
        return x + m, state, mask

    # --- shared by both paths ---------------------------------------------
    def _rope(self, x, positions):
        dim = self.rotary_dim or self.head_dim
        if not self.yarn_factor:
            return rope_rotate_freqs(x, positions,
                                     rope_inv_freq(dim, self.rope_base),
                                     self.attention_factor)
        with jax.named_scope("rope_yarn"):
            inv = yarn_inv_freq(dim, self.rope_base, self.yarn_factor,
                                self.yarn_original, self.yarn_beta_fast,
                                self.yarn_beta_slow)
            return rope_rotate_freqs(x, positions, inv, self.attention_factor)

    def _qkv(self, params, x, positions):
        """Norm, project, rotate: the normed rows h (B, T, D), q (B, T, H,
        hd) in the stream's dtype, k and v (B, T, Hkv, hd)."""
        p = params["attn"]
        h = rms_norm(x, params["ln1_g"], self.eps)
        q = wide_einsum("btd,dhe->bthe", h, p["w_q"]).astype(h.dtype)
        k = wide_einsum("btd,dhe->bthe", h, p["w_k"]).astype(h.dtype)
        v = wide_einsum("btd,dhe->bthe", h, p["w_v"]).astype(h.dtype)
        return h, self._rope(q, positions), self._rope(k, positions), v

    def _attend(self, h, q, k, v, see):
        """Grouped-query attention of ``q`` (B, Tq, H, hd) over keys and
        values (B, L, Hkv, hd) — the chunk's own in the full forward, the
        cache's gathered copy in ``decode`` — under ``see`` (broadcastable
        to (B, Hkv, G, Tq, L)): (B, Tq, H, hd) in f32."""
        B, Tq, H, hd = q.shape
        Hkv = k.shape[2]
        qg = q.reshape(B, Tq, Hkv, H // Hkv, hd)
        s = wide_einsum("bqhgd,bkhd->bhgqk", qg, k) / np.sqrt(hd)
        w = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1).astype(h.dtype)
        return wide_einsum("bhgqk,bkhd->bqhgd", w, v).reshape(B, Tq, H, hd)

    def _gate_project(self, params, h, a):
        """The attention's tail: ``a`` (B, Tq, H, hd) gated a head from the
        normed rows ``h`` and projected: (B, Tq, D)."""
        p = params["attn"]
        B, Tq, H, hd = a.shape
        with jax.named_scope("attn_gate"):
            gate = GATE_ACTS[self.gate_act](
                wide_einsum("btd,dh->bth", h, p["w_head_gate"]))
            a = (a * gate[..., None]).astype(h.dtype)
        return wide_einsum("bqe,ed->bqd", a.reshape(B, Tq, H * hd),
                           p["w_o"]).astype(h.dtype)

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
            # the product, the scores and the choice in f32: routing is a
            # step function. Scored, ranked and renormalised over ALL the
            # experts, whichever of them are held here
            score = ROUTER_SCORES[self.router_score](
                wide_einsum("nd,de->ne", h, p["w_router"]))
            gate, idx = jax.lax.top_k(score, self.top_k)          # (N, k)
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True) \
                * self.routed_scale
            weight, routing = assign(gate, idx, self.num_experts, live,
                                     shape[:-1], self.held)
        with jax.named_scope("moe_experts"):
            y = swiglu_experts(h, p["w_gate"], p["w_up"], p["w_down"], weight)
        with jax.named_scope("moe_shared"):
            y = y + _swiglu(h, p["shared"])
        return y.reshape(shape), routing
