"""Attention layers — the modern sequence stack (BERT-import target + long-context).

DL4J 0.9.x predates attention entirely (SURVEY.md §5: "no attention layers at
all"); the driver's stretch config is a Keras-imported BERT-base, and
long-context support is first-class in this framework. These layers are
designed TPU-first:

- one fused QKV projection (a single MXU matmul),
- scores computed in fp32 regardless of input dtype (bf16-safe softmax),
- optional blockwise computation compatible with ring attention over a
  sequence-parallel mesh axis (parallel/ring_attention.py wires the
  collective-permute loop around ``attend_blockwise``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import activations, initializers
from ..api import Array, Layer, Shape, apply_input_dropout, register_layer


def dot_product_attention(q, k, v, *, mask=None, scale=None,
                          dropout_rate: float = 0.0, dropout_rng=None):
    """(B, T, Hd, D) attention with fp32 accumulation. mask: (B, 1|H, Tq, Tk)
    additive or bool. dropout_rate > 0 with an rng applies inverted dropout
    to the attention weights (training-time attention dropout)."""
    *_, D = q.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(D).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def rope_rotate(x, positions, base: float = 10000.0):
    """Rotary position embedding (RoFormer) on (B, T, H, Dh) at absolute
    ``positions`` — (T,) shared across the batch, or (B, T) per-row (the
    continuous-batching decode path, where every slot sits at its own
    offset). The long-context position scheme: no learned table
    (a T=64k learned table is 100M params at d=1536), relative-distance
    attention by construction, and extrapolates past the training length.
    Rotation computed in f32 (bf16 angles at position ~64k lose the
    low-order bits that carry relative phase), cast back to x.dtype."""
    Dh = x.shape[-1]
    if Dh % 2:
        raise ValueError(f"rope needs an even head dim, got {Dh}")
    half = Dh // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., T, half)
    if ang.ndim == 2:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:  # per-row positions: (B, T, half) -> broadcast over heads only
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def rope_inv_freq(dim: int, base: float):
    """The ``dim // 2`` inverse frequencies ``base ** (-2i / dim)`` of a
    plain rope over ``dim`` rotated dimensions (numpy, float32)."""
    return (base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """The ``dim // 2`` inverse frequencies of a YaRN-scaled rope over
    ``dim`` rotated dimensions (numpy, float32): each plain frequency
    ``base ** (-2i / dim)`` blended with itself divided by ``factor``, by
    a linear ramp over the correction range of (``beta_fast``,
    ``beta_slow``): dimensions that turn more than ``beta_fast`` times
    within the ``original`` context keep their frequency, those that turn
    less than ``beta_slow`` times are interpolated, the ramp between."""
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * np.log(original / (rotations * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(np.floor(correction_dim(beta_fast)), 0)
    high = min(np.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (freq / factor * ramp + freq * (1.0 - ramp)).astype(np.float32)


def rope_rotate_freqs(x, positions, inv_freq, scale: float = 1.0):
    """:func:`rope_rotate` at given inverse frequencies, on the FIRST
    ``2 * len(inv_freq)`` dimensions of each head (pairs ``i`` with ``i +
    len(inv_freq)``); the dimensions after them pass unrotated (a partial
    rotary factor). ``scale`` multiplies cos and sin (YaRN's
    ``attention_factor``). f32 angles, the result in ``x``'s dtype."""
    half = len(inv_freq)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    lead = None if ang.ndim == 2 else slice(None)
    cos = (jnp.cos(ang) * scale)[lead, :, None, :]
    sin = (jnp.sin(ang) * scale)[lead, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:2 * half].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., 2 * half:]], axis=-1)


def _flash_attend(q, k, v, *, causal, lengths, key_mask, window):
    """The flash kernel, placed per device when the step is being traced
    under a multi-device mesh (Trainer/ParallelWrapper ``mesh=``).

    A ``pallas_call`` is opaque to GSPMD: under a multi-device ``jit`` XLA
    cannot partition it, so the kernel is wrapped in ``shard_map`` over the
    ambient mesh — batch over the data axis, heads over the model axis
    (attention is independent per example and per head, so no collective is
    needed). An axis that does not divide its dim, or any other mesh axis,
    leaves that dim replicated: every device along it runs the same kernel
    on the same block."""
    from ...ops.flash_attention import flash_attention
    from ..api import ACTIVE_MESH

    mesh = ACTIVE_MESH.get()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, lengths=lengths,
                               key_mask=key_mask, window=window)
    from jax.sharding import PartitionSpec as P

    from ...parallel.mesh import DATA_AXIS, MODEL_AXIS

    B, _, H, _ = q.shape
    dp, tp = mesh.shape.get(DATA_AXIS, 1), mesh.shape.get(MODEL_AXIS, 1)
    b_ax = DATA_AXIS if dp > 1 and B % dp == 0 else None
    h_ax = MODEL_AXIS if tp > 1 and H % tp == 0 else None
    qkv = P(b_ax, None, h_ax, None)
    operands, specs = [q, k, v], [qkv] * 3
    if lengths is not None:  # mutually exclusive with key_mask
        operands.append(lengths)
        specs.append(P(b_ax))
    elif key_mask is not None:
        operands.append(key_mask)
        specs.append(P(b_ax, None))

    def local(q, k, v, *m):
        return flash_attention(
            q, k, v, causal=causal, window=window,
            lengths=m[0] if lengths is not None else None,
            key_mask=m[0] if key_mask is not None else None)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=qkv, check_vma=False)(*operands)


@register_layer
@dataclass(frozen=True)
class MultiHeadAttention(Layer):
    """Fused-QKV multi-head self-attention. Input (B, T, D) -> (B, T, D).

    ``flash=True`` routes the score/softmax/weighted-sum through the Pallas
    flash kernel (ops/flash_attention.py): O(T·block) memory instead of a
    (T, T) score tensor — the long-context fast path. Used when the mask is
    absent, pure-causal, or a (B, T) key mask (the kernel's exact
    ``key_mask`` path — any mask pattern, no right-padding assumption, but
    every key block pays the masked-path cost); attention dropout falls
    back to the dense path.

    ``ragged=True`` declares that any (B, T) mask handed to this layer is
    RIGHT-PADDED (BERT-style: ones then zeros). The flash path then
    converts it to per-example ``lengths`` and rides the kernel's ragged
    path, which specializes interior blocks and skips key blocks beyond
    the length entirely — strictly faster than the exact key_mask path.
    The conversion is ``lengths = mask.sum(-1)``, so a mask that is NOT
    right-padded silently attends differently from the dense oracle;
    leave ragged=False (the default) for gappy/left-padded masks.

    ``ring=True`` routes through sequence-parallel ring attention
    (parallel/ring_attention.py) whenever the step is being traced under a
    mesh with a ``seq`` axis (Trainer/ParallelWrapper/MultiHostTrainer with
    ``mesh=``/``rules=`` install the ambient mesh): Q/K/V shard over the
    sequence axis, K/V blocks rotate via ppermute, O(T/n) memory per device.
    Outside a seq-parallel trace it falls back to flash/dense, so the same
    model config runs anywhere.
    """

    num_heads: int = 8
    causal: bool = False
    attn_dropout: float = 0.0
    flash: bool = False
    ring: bool = False
    rope: bool = False       # rotary positions on q/k (no learned table)
    rope_base: float = 10000.0
    num_kv_heads: Optional[int] = None  # GQA: < num_heads shrinks the KV
    # projection and decode cache by num_heads/num_kv_heads (MQA at 1);
    # None = standard MHA (one KV head per query head). NOTE: on the
    # flash/dense TRAINING paths KV is repeated to full H before attention
    # (full-width (B,T,H,hd) transients) — the savings are in params,
    # projection FLOPs, and the decode cache, not in attention compute; a
    # num_kv_heads-aware kernel variant is future work.
    ragged: bool = False  # (B, T) masks are right-padded: flash path uses
    # the faster per-example lengths kernel path (see class docstring)
    window: Optional[int] = None  # sliding-window attention (causal only):
    # query t attends keys [t-window+1, t]; O(T*window) attention cost

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
        k1, k2 = jax.random.split(key)
        wqkv = initializers.init_param(k1, self.weight_init or "xavier",
                                       (d, d + 2 * d_kv), dtype=dtype)
        wo = initializers.init_param(k2, self.weight_init or "xavier", (d, d), dtype=dtype)
        return {"w_qkv": wqkv, "b_qkv": jnp.zeros((d + 2 * d_kv,), dtype),
                "w_o": wo, "b_o": jnp.zeros((d,), dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if self.window is not None:
            # validate ONCE at the layer so both paths agree: the dense
            # fallback would otherwise silently ignore a non-causal window
            # while the flash path raises at trace time
            if not self.causal:
                raise ValueError("window= requires causal=True "
                                 "(sliding-window attention is a causal-LM "
                                 "construct)")
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
        B, T, D = x.shape
        H = self.num_heads
        Hkv = self.kv_heads
        hd = D // H
        qkv = x @ params["w_qkv"] + params["b_qkv"]
        q, k, v = jnp.split(qkv, [D, D + Hkv * hd], axis=-1)
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, Hkv, hd)
        v = v.reshape(B, T, Hkv, hd)
        if self.rope:
            # T here is the global length even under sequence parallelism
            # (shard_map splitting happens inside ring_attention), so
            # absolute positions are just arange(T). k rotates at Hkv heads
            # BEFORE any GQA repeat — rope depends only on position and
            # head_dim, so rotate-then-repeat == repeat-then-rotate at
            # H/Hkv times less work.
            pos = jnp.arange(T)
            q = rope_rotate(q, pos, self.rope_base)
            k = rope_rotate(k, pos, self.rope_base)
        if Hkv != H:
            # broadcast KV groups up to the query heads; the parameter and
            # decode-cache savings are upstream of this repeat
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        drop = self.attn_dropout if (training and rng is not None) else 0.0
        ring_mesh = dp = tp = None
        if self.ring and self.window is not None:
            import warnings

            warnings.warn(
                "ring=True is disabled because window= is set: ring "
                "attention computes full causal attention, so the window "
                "routes through flash/dense instead — per-device memory is "
                "O(T), not ring's O(T/n). Drop window= to keep sequence "
                "parallelism, or drop ring= to silence this.",
                stacklevel=2)
        if self.ring and mask is None and drop == 0.0 and self.window is None:
            # (ring attention computes full causal attention; a window
            # routes through flash/dense so the band is actually honored)
            from ..api import ACTIVE_MESH
            from ...parallel.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS

            m = ACTIVE_MESH.get()
            shape = dict(m.shape) if m is not None else {}
            if shape.get(SEQ_AXIS, 1) > 1 and T % shape[SEQ_AXIS] == 0:
                ring_mesh = m
                dp, tp = shape.get(DATA_AXIS, 1), shape.get(MODEL_AXIS, 1)
        if ring_mesh is not None:
            from ...parallel.ring_attention import ring_attention

            y = ring_attention(
                q, k, v, ring_mesh, causal=self.causal,
                batch_axis=DATA_AXIS if dp > 1 and B % dp == 0 else None,
                head_axis=MODEL_AXIS if tp > 1 and H % tp == 0 else None)
        elif self.flash and drop == 0.0 and (
                mask is None or (hasattr(mask, "ndim") and mask.ndim == 2)):
            # flash kernel handles no-mask / pure-causal directly; a (B, T)
            # key mask rides the kernel's EXACT key_mask path (no
            # right-padding assumption — left-padded or gappy masks are
            # honored bit-for-bit like the dense path), unless ragged=True
            # declared right-padding, in which case the faster per-example
            # lengths path (interior-block specialization + tail-block
            # skipping) is used. Attention dropout (weights never
            # materialized) falls back to dense.
            lengths = key_mask = None
            if mask is not None and self.ragged:
                lengths = mask.astype(jnp.int32).sum(axis=-1)
            else:
                key_mask = mask
            y = _flash_attend(q, k, v, causal=self.causal,
                                  lengths=lengths, key_mask=key_mask,
                                  window=self.window)
        else:
            attn_mask = None
            if self.causal:
                causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
                if self.window is not None:
                    band = (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
                            < self.window)
                    causal = causal & band
                attn_mask = causal[None, None]
            if mask is not None:
                key_mask = mask[:, None, None, :].astype(jnp.bool_)  # (B,1,1,Tk)
                attn_mask = key_mask if attn_mask is None else (attn_mask & key_mask)
            y = dot_product_attention(q, k, v, mask=attn_mask,
                                      dropout_rate=drop, dropout_rng=rng)
        y = y.reshape(B, T, D) @ params["w_o"] + params["b_o"]
        return y, state, mask


@register_layer
@dataclass(frozen=True)
class TransformerEncoderBlock(Layer):
    """Pre-LN transformer block: LN -> MHA -> +res -> LN -> MLP -> +res."""

    num_heads: int = 8
    mlp_ratio: int = 4
    activation: str = "gelu"
    causal: bool = False
    dropout_rate: float = 0.0
    flash: bool = False  # route self-attention through the Pallas kernel
    ring: bool = False   # route self-attention through seq-parallel ring
    # attention when traced under a mesh with a seq axis (falls back
    # flash/dense otherwise — same config runs anywhere)
    remat: bool = False  # gradient checkpointing: recompute this block's
    # internals in the backward pass instead of storing them — saved
    # activation memory shrinks to ~one residual-stream tensor per block
    # (jax.checkpoint per block; deep stacks / long context)
    rope: bool = False   # rotary positions on q/k inside the attention
    rope_base: float = 10000.0
    num_kv_heads: Optional[int] = None  # GQA (see MultiHeadAttention)
    window: Optional[int] = None  # sliding-window attention (causal only)
    ragged: bool = False  # (B, T) masks are right-padded -> flash lengths
    # path (see MultiHeadAttention.ragged)

    def init(self, key, input_shape, dtype=jnp.float32):
        d = input_shape[-1]
        k1, k2, k3 = jax.random.split(key, 3)
        mha = MultiHeadAttention(num_heads=self.num_heads, causal=self.causal,
                                 num_kv_heads=self.num_kv_heads)
        attn_params, _ = mha.init(k1, input_shape, dtype)
        h = d * self.mlp_ratio
        return {
            "ln1_g": jnp.ones((d,), dtype), "ln1_b": jnp.zeros((d,), dtype),
            "ln2_g": jnp.ones((d,), dtype), "ln2_b": jnp.zeros((d,), dtype),
            "attn": attn_params,
            "w_up": initializers.init_param(k2, "xavier", (d, h), dtype=dtype),
            "b_up": jnp.zeros((h,), dtype),
            "w_down": initializers.init_param(k3, "xavier", (h, d), dtype=dtype),
            "b_down": jnp.zeros((d,), dtype),
        }, {}

    @staticmethod
    def _ln(x, g, b, eps=1e-6):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * g + b

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        if self.remat:
            import functools

            body = functools.partial(self._body, training=training)
            y = jax.checkpoint(body)(params, x, rng, mask)
        else:
            y = self._body(params, x, rng, mask, training=training)
        return y, state, mask

    def _body(self, params, x, rng, mask, *, training=False):
        mha = MultiHeadAttention(num_heads=self.num_heads, causal=self.causal,
                                 flash=self.flash, ring=self.ring,
                                 rope=self.rope, rope_base=self.rope_base,
                                 num_kv_heads=self.num_kv_heads,
                                 window=self.window, ragged=self.ragged)
        # named scopes are HLO metadata only: they name these operations in
        # a profiler trace (obs/README.md, "Hot-path spans")
        with jax.named_scope("attention"):
            h = self._ln(x, params["ln1_g"], params["ln1_b"])
            a, _, _ = mha.apply(params["attn"], {}, h, training=training, rng=rng, mask=mask)
        x = x + a
        with jax.named_scope("mlp"):
            h = self._ln(x, params["ln2_g"], params["ln2_b"])
            act = activations.get(self.activation)
            m = act(h @ params["w_up"] + params["b_up"]) @ params["w_down"] + params["b_down"]
        if training and self.dropout_rate > 0 and rng is not None:
            from ...ops.regularization import dropout as do

            m = do(rng, m, self.dropout_rate, True)
        return x + m


@register_layer
@dataclass(frozen=True)
class PositionalEmbedding(Layer):
    """Learned positional embedding added to (B, T, D) inputs."""

    max_len: int = 512

    def init(self, key, input_shape, dtype=jnp.float32):
        d = input_shape[-1]
        return {"pos": 0.02 * jax.random.normal(key, (self.max_len, d), dtype)}, {}

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        T = x.shape[1]
        return x + params["pos"][:T], state, mask
