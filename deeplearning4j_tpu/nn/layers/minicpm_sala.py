"""The MiniCPM-SALA decoder block (``model_type`` ``minicpm_sala``,
``openbmb/MiniCPM-SALA``): one block class whose ``mixer`` is either
``minicpm4``, softmax attention over few KV heads that turns block-sparse past
``dense_len``, or ``lightning-attn``, linear attention with a fixed decay a
head; a dense SwiGLU behind both, and the family's scaled residual stream.

``x`` is the layer's input after its RMSNorm, ``rms_hd`` an RMSNorm over one
head's ``head_dim`` values, ``c = residual_scale``::

    h <- h + c * mixer(rms(h)) ;  h <- h + c * (silu(x Wg) * (x Wu)) Wd       (the first block: h <- embed_scale * h before)

    lightning-attn, head a, position t:
        q_t = rope(rms_hd(x_t Wq)) ;  k_t = rope(rms_hd(x_t Wk)) ;  v_t = x_t Wv
        S_t = lam_a S_{t-1} + k_t^T v_t   (hd x hd, S_{-1} = 0) ;  o_t = (q_t S_t) / sqrt(hd)
        y_t = (rms_hd(o_t) * sigmoid(x_t Wgate)) Wo
        lam_a = exp(-s_a f_l) ;  s_a = 2^(-8a/H), a = 1..H ;  f_l = 1 - decay_layer / (decay_depth - 1) + 1e-5

    minicpm4, KV head g with its H/Hkv query heads, query t, n = t + 1 positions visible:
        q = rms_hd(x Wq) ;  k = rms_hd(x Wk) ;  v = x Wv          no positions at all
        n <= dense_len: causal softmax attention over all n; else
          1. pooled keys kp_j = mean(k_{st j} .. k_{st j + ks - 1}), every j with st j + ks <= n
          2. p_{h,j} = softmax_j(q_{t,h} . kp_j / sqrt(hd)) ;  r_{g,j} = sum over h in g of p_{h,j}
          3. R_{g,b} = max r_{g,j} over the kernels that overlap the block b of block_size tokens
          4. visible: the first init_blocks blocks, those that overlap the last window_size positions
             (score +inf), and the best others until topk in all (``forced_in_topk``; False: topk beyond them)
          5. causal softmax attention over the visible blocks' positions
        y = (o * sigmoid(x Wgate)) Wo

**The caches** (``nn/generation.py``, the layout contract). A ``minicpm4``
layer keeps ``k`` and ``v`` a token and ``kpool``, ONE pooled key a
``kernel_stride`` tokens (``cache_strides``): a paged pool's block size must
equal the stride, so it is one a block. ``kp_j`` spans the blocks ``j .. j +
ks/st - 1``; it is stored in the LAST of them, the block that holds its last
position: a block shared through the prefix cache or a fork may have two
different successors but has one chain of predecessors, so whoever reaches that
block reaches every key under the mean. It is (re)written whenever that last
position is written, from the keys AS STORED, and read only by queries that
see that position. A decode step (one query a row) gathers the row's pooled
keys, selects, and gathers ONLY the selected blocks' keys and values; a chunk
of several queries gathers its rows' caches whole and masks them by each
query's selection (the same mathematics; a prefill chunk has one row). A
``lightning-attn`` layer keeps ``state``, ``(H, hd, hd)`` a SEQUENCE and no
position axis (``cache_state``), in ``state_dtype``; paged, the batcher adds
``state_snap``, the state as it stood at the last block boundary the
sequence passed and the snapshots the prefix cache keeps of such states.

**The chunked linear form** (``_lightning``): inside a chunk the masked
quadratic product with the decay matrix, across chunks the state; every decay
is ``exp`` of a non-positive number (it never divides by ``lam^i``: over 512
tokens the fastest head's is under e^-200). Tokens that ``live`` does not mark
(a chunk's right padding, a row that is not decoding) leave the state as it
was.

**Widths** as ``glm4_moe_lite.py`` and ``laguna.py``: parameters and ``k``,
``v``, ``kpool`` in the model's dtype (bf16 when served), the stream and every
activation between two matmuls f32, multiplied exactly against what is held
narrower (``experts.wide_einsum``) and against each other where both are f32
(``_exact``): the selection is a step function, and its scores are computed in
f32 from what the cache stores.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import initializers
from ..api import Layer, Shape, register_layer
from .attention import rope_rotate
from .experts import wide_einsum
from .glm4_moe_lite import INIT, _swiglu, _wide
from .norm import RMSNorm, rms_norm

MIXERS = ("minicpm4", "lightning-attn")
# what a sparse layer's ``decode`` reports under "sums" for a decode step
# (``decode_sums``): two int32 sums over the rows marked live and the KV heads
# (the batcher's serve_<name>_total counters)
DECODE_SUMS = {
    "sparse_kv_positions_read": "cached positions the sparse layers' decode "
                                "steps gathered and attended, a KV head",
    "sparse_kv_positions_live": "cached positions live in those rows, a KV "
                                "head",
}
# bytes of f32 scores a chunk's attention holds at once (its queries are taken
# in sub-blocks to stay under it)
SCORE_BYTES = 1 << 29


def _exact(spec: str, a, b):
    """``einsum`` of two f32 operands with f32 products (a TPU's default
    matmul would round both to bf16)."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def decay_rates(heads: int, layer: int, depth: int) -> np.ndarray:
    """``s_a f_l`` for a = 1..heads (float32): ``lam_a = exp(-rate_a)``."""
    slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads)
    return (slopes * (1.0 - layer / (depth - 1) + 1e-5)).astype(np.float32)


@register_layer
@dataclass(frozen=True)
class ScaledRMSNorm(RMSNorm):
    """The family's final norm: ``rms(x) / divide`` (``hidden_size /
    dim_model_base``) is what the head multiplies. Token-local, as the norm
    it extends."""

    divide: float = 1.0

    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        y, state, mask = super().apply(params, state, x, training=training,
                                       rng=rng, mask=mask)
        return y / self.divide, state, mask


@register_layer
@dataclass(frozen=True)
class MiniCpmSalaBlock(Layer):
    """One MiniCPM-SALA decoder layer: (B, T, D) -> (B, T, D) in f32, causal.
    ``num_kv_heads`` is the mixer's own (a linear layer has as many as query
    heads). The defaults are the published sizes and the family's
    ``sparse_config``."""

    mixer: str = "minicpm4"
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    ffn_width: int = 16384
    residual_scale: float = 1.4 / 32 ** 0.5
    embed_scale: float = 1.0            # scale_emb on the stack's first block
    rope_base: float = 10000.0          # lightning-attn only
    decay_layer: int = 0                # the layer's PUBLISHED index
    decay_depth: int = 32               # ... of the published depth
    state_dtype: str = "float32"
    dense_len: int = 8192               # minicpm4 only, from here on
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    forced_in_topk: bool = True
    eps: float = 1e-6

    # --- sizes ------------------------------------------------------------
    @property
    def linear(self) -> bool:
        return self.mixer == "lightning-attn"

    @property
    def cache_strides(self) -> Dict[str, int]:
        """Parts held once a stride of tokens (``nn.generation.Parts``)."""
        return {} if self.linear else {"kpool": self.kernel_stride}

    @property
    def cache_state(self) -> Dict[str, str]:
        """Parts with no position axis, one a sequence, and their dtype."""
        return {"state": self.state_dtype} if self.linear else {}

    #: the programs hand this layer ``live`` in its cache entry (the rows, or
    #: a chunk's tokens, that are real)
    reads_live = True

    @property
    def decode_sums(self) -> Dict[str, str]:
        """What a decode step of this layer leaves under ``"sums"`` in the
        cache it returns, in order (``{counter name: help}``)."""
        return {} if self.linear else dict(DECODE_SUMS)

    @property
    def _forced_most(self) -> int:
        return self.init_blocks + -(-self.window_size // self.block_size) + 1

    def init(self, key, input_shape, dtype=jnp.float32):
        d, H, Hkv, hd = (input_shape[-1], self.num_heads, self.num_kv_heads,
                         self.head_dim)
        if self.mixer not in MIXERS:
            raise ValueError(f"mixer={self.mixer!r}: one of {MIXERS}")
        if H % Hkv or (self.linear and H != Hkv):
            raise ValueError(f"num_heads={H}, num_kv_heads={Hkv} "
                             f"({self.mixer})")
        if not self.linear and (self.kernel_size % self.kernel_stride
                                or self.block_size % self.kernel_stride
                                or self.kernel_size > self.block_size
                                + self.kernel_stride):
            raise ValueError(
                f"kernel_size={self.kernel_size} and block_size="
                f"{self.block_size} must be multiples of kernel_stride="
                f"{self.kernel_stride}, a kernel no longer than a block and a "
                f"stride")
        ks = iter(jax.random.split(key, 8))

        def w(*shape):
            return initializers.init_param(next(ks), self.weight_init or INIT,
                                           shape, dtype=dtype)

        mix = {"w_q": w(d, H, hd), "w_k": w(d, Hkv, hd), "w_v": w(d, Hkv, hd),
               "w_gate": w(d, H * hd), "w_o": w(H * hd, d),
               "q_norm_g": jnp.ones((hd,), dtype),
               "k_norm_g": jnp.ones((hd,), dtype)}
        if self.linear:
            mix["o_norm_g"] = jnp.ones((hd,), dtype)
        f = self.ffn_width
        return {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
                "mix": mix,
                "mlp": {"w_gate": w(d, f), "w_up": w(d, f),
                        "w_down": w(f, d)}}, {}

    # --- the hooks nn.generation and the batcher ask for -----------------
    def cache_spec(self, input_shape: Shape):
        """A sparse layer: keys, values and the indexer's pooled keys, each
        ``(kv_heads, head_dim)`` (the last once a ``kernel_stride`` tokens:
        :attr:`cache_strides`); a linear layer: its state (no position axis:
        :attr:`cache_state`)."""
        if self.linear:
            return {"state": (self.num_heads, self.head_dim, self.head_dim)}
        kv = (self.num_kv_heads, self.head_dim)
        return {"k": kv, "v": kv, "kpool": kv}

    def decode(self, params, x, cache, pos):
        """One chunk ``x`` (B, Tq, D) at absolute offset ``pos`` (scalar or
        (B,)) against ``cache`` in either layout of ``nn.generation``.
        ``cache["live"]`` ((B, Tq) bool, broadcastable; absent: everything
        is) marks the real tokens: a row none of whose tokens is real is not
        decoding. A sparse layer that ran one query a row leaves
        :attr:`decode_sums` under ``"sums"`` in the cache it returns."""
        B, Tq = x.shape[:2]
        live = cache.get("live")
        live = jnp.ones((B, Tq), bool) if live is None \
            else jnp.broadcast_to(live, (B, Tq))
        pv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        x = _wide(x)
        if self.embed_scale != 1.0:
            x = x * self.embed_scale
        with jax.named_scope("attention"):
            h = rms_norm(x, params["ln1_g"], self.eps)
            if self.linear:
                a, new = self._linear_cached(params, h, cache, pv, live)
            else:
                a, new = self._sparse_cached(params, h, cache, pos, pv, live)
            x = x + self.residual_scale * self._out(params, h, a)
        return x + self.residual_scale * self._ffn(params, x), new

    # --- the full forward -------------------------------------------------
    def apply(self, params, state, x, *, training=False, rng=None, mask=None):
        """The whole sequence as ONE chunk at offset 0 against a cache made
        for it (in the stream's dtype: nothing is rounded), through the same
        functions as ``decode``."""
        B, T, _ = x.shape
        if self.linear:
            cache = {"state": jnp.zeros(
                (B, self.num_heads, self.head_dim, self.head_dim),
                self.state_dtype)}
        else:
            cap = -(-T // self.block_size) * self.block_size
            kv = (self.num_kv_heads, self.head_dim)
            cache = {"k": jnp.zeros((B, cap) + kv, jnp.float32),
                     "v": jnp.zeros((B, cap) + kv, jnp.float32),
                     "kpool": jnp.zeros((B, cap // self.kernel_stride) + kv,
                                        jnp.float32)}
        if mask is not None:
            cache["live"] = mask.astype(jnp.bool_)
        y, _ = self.decode(params, x, cache, 0)
        return y, state, mask

    # --- shared by both paths ---------------------------------------------
    def _qkv(self, params, h, positions):
        """Project, norm a head, rotate (a linear layer): q (B, T, H, hd), k
        and v (B, T, Hkv, hd), in the stream's dtype."""
        p = params["mix"]
        q = rms_norm(wide_einsum("btd,dhe->bthe", h, p["w_q"]).astype(h.dtype),
                     p["q_norm_g"], self.eps)
        k = rms_norm(wide_einsum("btd,dhe->bthe", h, p["w_k"]).astype(h.dtype),
                     p["k_norm_g"], self.eps)
        v = wide_einsum("btd,dhe->bthe", h, p["w_v"]).astype(h.dtype)
        if self.linear:
            q = rope_rotate(q, positions, self.rope_base)
            k = rope_rotate(k, positions, self.rope_base)
        return q, k, v

    def _out(self, params, h, a):
        """Gate the mixer's output ``a`` (B, T, H, hd) and project it."""
        p = params["mix"]
        B, T = a.shape[:2]
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(wide_einsum("btd,de->bte", h, p["w_gate"]))
            a = (a.reshape(B, T, -1) * gate).astype(h.dtype)
        return wide_einsum("bte,ed->btd", a, p["w_o"]).astype(h.dtype)

    def _ffn(self, params, x):
        shape = x.shape
        h = rms_norm(x, params["ln2_g"], self.eps).reshape(-1, shape[-1])
        with jax.named_scope("mlp"):
            return _swiglu(h, params["mlp"]).reshape(shape)

    # ------------------------------------------------------ lightning-attn
    def _linear_cached(self, params, h, cache, pv, live):
        """The linear mixer against its state, dense (``state`` (B, H, hd,
        hd)) or paged (``state_pool`` (slots, ...): row b is slot b, or the
        ONE row is slot ``cache["slot"]``; ``state_snap_pool``: a slot's row
        is its state at the last block boundary, blocks of ``cache["every"]``
        tokens; ``cache["load"]`` (1,): the row starts from that snapshot
        row, from zeros (-1), or goes on from its own state (-2))."""
        B, T = h.shape[:2]
        positions = pv[:, None] + jnp.arange(T)[None]
        q, k, v = self._qkv(params, h, positions)
        paged = "state_pool" in cache
        with jax.named_scope("linear_state"):
            pool = cache["state_pool" if paged else "state"]
            snaps = cache.get("state_snap_pool")
            slot = cache.get("slot") if paged else None
            if slot is None:
                S_in = pool
            else:
                load = cache["load"]
                S_in = jnp.where(
                    load >= 0, snaps[jnp.maximum(load, 0)],
                    jnp.where(load == -1, 0.0, pool[slot]))
            S_in = S_in.astype(jnp.float32)
            n = jnp.sum(live, axis=1).astype(jnp.int32)
            o, S_out = self._lightning(q, k, v, S_in, live, n)
            S_out = S_out.astype(pool.dtype)
            new = dict(cache)
            if not paged:
                new["state"] = S_out
                return self._o_norm(params, o), new
            new["state_pool"] = S_out if slot is None \
                else pool.at[slot].set(S_out)
            if snaps is not None:
                # the state as it stood when the row last passed a block
                # boundary: inside this chunk, after its first n_b tokens
                every = int(cache["every"])
                n_b = (pv + n) // every * every - pv
                rows = jnp.arange(B) if slot is None else slot
                if T == 1:      # a decode step: S_out, for the rows that hit
                    hit, S_b = (n_b == 1), S_out
                else:
                    hit = (n_b >= 0) & (n_b <= n)
                    S_b = self._state_after(k, v, S_in, live,
                                            jnp.clip(n_b, 0, n))
                new["state_snap_pool"] = snaps.at[
                    jnp.where(hit, rows, snaps.shape[0])].set(
                        S_b.astype(snaps.dtype), mode="drop")
        return self._o_norm(params, o), new

    def _o_norm(self, params, o):
        return rms_norm(o, params["mix"]["o_norm_g"], self.eps)

    def _rates(self):
        return jnp.asarray(decay_rates(self.num_heads, self.decay_layer,
                                       self.decay_depth))

    def _state_after(self, k, v, S_in, live, m):
        """The state after each row's first ``m`` (B,) real tokens:
        ``lam^m S_in + sum over s < m of lam^(m-1-s) k_s^T v_s``."""
        rate = self._rates()                                    # (H,)
        s = jnp.arange(k.shape[1])
        left = (m[:, None] - 1 - s[None]).astype(jnp.float32)   # (B, T)
        w = jnp.where(((left >= 0) & live)[:, None, :],
                      jnp.exp(-rate[None, :, None]
                              * jnp.maximum(left, 0.0)[:, None, :]), 0.0)
        carry = jnp.exp(-rate[None] * m[:, None].astype(jnp.float32))
        kw = k * jnp.moveaxis(w, 1, 2)[..., None]               # (B, T, H, hd)
        return carry[:, :, None, None] * S_in \
            + _exact("bshd,bshe->bhde", kw, v)

    def _lightning(self, q, k, v, S_in, live, n):
        """One chunk of the recurrence. ``q``, ``k``, ``v`` (B, T, H, hd)
        f32, ``S_in`` (B, H, hd, hd), ``live`` (B, T) the real tokens (a
        prefix of each row), ``n`` (B,) their count. Returns the outputs (B,
        T, H, hd) over sqrt(hd) and the state after the real tokens."""
        T = q.shape[1]
        rate = self._rates()
        t = jnp.arange(T)
        dt = (t[:, None] - t[None, :]).astype(jnp.float32)      # t - s
        decay = jnp.where(dt >= 0, jnp.exp(
            -rate[:, None, None] * jnp.maximum(dt, 0.0)[None]), 0.0)
        a = _exact("bthd,bshd->bhts", q, k) * decay[None] \
            * live[:, None, None, :]
        o = _exact("bhts,bshd->bthd", a, v)
        reach = jnp.exp(-rate[None, :] * (t[:, None] + 1.0))    # (T, H)
        o = o + _exact("bthd,bhde->bthe", q, S_in) * reach[None, :, :, None]
        return o / np.sqrt(self.head_dim), \
            self._state_after(k, v, S_in, live, n)

    # ------------------------------------------------------------ minicpm4
    def _sparse_cached(self, params, h, cache, pos, pv, live):
        """The sparse mixer against its cache: write the chunk's keys and
        values and the pooled keys they complete, then attend."""
        from ..generation import cache_write

        B, T = h.shape[:2]
        q, k, v = self._qkv(params, h, None)
        new = cache_write(cache, {"k": k, "v": v}, pos)
        new = {**cache, **new}
        view = self._paged_view(new)
        with jax.named_scope("sparse_pool"):
            view = self._write_pooled(view, pv, T)
        row_live = jnp.any(live, axis=1)
        if T == 1:
            a, counts = self._attend_selected(q[:, 0], view, pv, row_live)
            a = a[:, None]
            if "live" in cache:     # whoever marked the rows reads the sums
                new["sums"] = counts
        else:
            a = self._attend_masked(q, view, pv)
        if "tables" in cache:
            new["kpool_pool"] = view["kpool_pool"]
        else:
            new["kpool"] = view["kpool_pool"].reshape(cache["kpool"].shape)
        return a, new

    def _paged_view(self, cache):
        """Pools and tables of ``cache``; a dense cache ``(B, C, ...)`` seen
        as blocks of ``kernel_stride`` tokens under tables that count them."""
        st = self.kernel_stride
        if "tables" in cache:
            if cache["k_pool"].shape[1] != st:
                raise ValueError(
                    f"a paged pool's block size ({cache['k_pool'].shape[1]}) "
                    f"must be the indexer's kernel_stride ({st}): one pooled "
                    f"key a block")
            return {n: cache[n] for n in
                    ("k_pool", "v_pool", "kpool_pool", "tables")}
        B, C = cache["k"].shape[:2]
        if C % st:
            raise ValueError(f"cache capacity {C} must be a multiple of "
                             f"kernel_stride {st}")
        nb = C // st
        return {"k_pool": cache["k"].reshape((B * nb, st) + cache["k"].shape[2:]),
                "v_pool": cache["v"].reshape((B * nb, st) + cache["v"].shape[2:]),
                "kpool_pool": cache["kpool"].reshape(
                    (B * nb,) + cache["kpool"].shape[2:]),
                "tables": jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)}

    def _write_pooled(self, view, pv, T: int):
        """(Re)write the pooled key of every block whose LAST position the
        chunk ``pv .. pv + T - 1`` wrote: the mean, in f32, of the
        ``kernel_size`` keys that end there, as the cache stores them."""
        st, span = self.kernel_stride, self.kernel_size // self.kernel_stride
        tables, k_pool, kp_pool = (view["tables"], view["k_pool"],
                                   view["kpool_pool"])
        B, maxb = tables.shape
        n_cand = (T + st - 2) // st + 1
        blk = pv[:, None] // st + jnp.arange(n_cand)[None]          # (B, c)
        done = ((blk + 1) * st <= pv[:, None] + T) & (blk >= span - 1) \
            & (blk < maxb)
        under = blk[:, :, None] - (span - 1) + jnp.arange(span)[None, None]
        rows = jnp.arange(B)[:, None, None]
        phys = tables[rows, jnp.clip(under, 0, maxb - 1)]           # (B, c, span)
        keys = k_pool[phys].astype(jnp.float32)      # (B, c, span, st, Hkv, hd)
        mean = jnp.mean(keys, axis=(2, 3))
        at = tables[jnp.arange(B)[:, None], jnp.clip(blk, 0, maxb - 1)]
        at = jnp.where(done, at, kp_pool.shape[0])                  # dropped
        return {**view, "kpool_pool": kp_pool.at[at].set(
            mean.astype(kp_pool.dtype), mode="drop")}

    def _block_scores(self, q, kp, n):
        """Steps 2 and 3. ``q`` (B, Tq, H, hd) f32; ``kp`` (B, Nb, Hkv, hd)
        the row's pooled keys BY THE BLOCK THAT STORES THEM (block b holds
        the kernel that ends with it); ``n`` (B, Tq) positions visible.
        Returns R (B, Tq, Hkv, NB): each block's largest kernel score, -1
        where no kernel that overlaps it exists yet."""
        B, Tq, H, hd = q.shape
        Nb, G = kp.shape[1], kp.shape[2]
        st = self.kernel_stride
        per, back = self.block_size // st, self.kernel_size // st - 1
        qg = q.reshape(B, Tq, G, H // G, hd)
        s = wide_einsum("bqgke,bjge->bqgkj", qg, kp) / np.sqrt(hd)
        j = jnp.arange(Nb)
        valid = ((j >= back) & ((j + 1) * st <= n[..., None]))[:, :, None, None]
        s = jnp.where(valid, s, -jnp.inf)
        prob = jnp.where(valid, jax.nn.softmax(s, axis=-1), 0.0)
        r = jnp.where(valid[:, :, :, 0], jnp.sum(prob, axis=3), -1.0)
        # the kernels that overlap block b end in the stride-blocks
        # per * b .. per * b + per - 1 + back
        NB = -(-Nb // per)
        r = jnp.pad(r, ((0, 0),) * 3 + ((0, NB * per + back - Nb),),
                    constant_values=-1.0)
        return jax.lax.reduce_window(r, -jnp.inf, jax.lax.max,
                                     (1, 1, 1, per + back), (1, 1, 1, per),
                                     "VALID")

    def _select(self, R, n, K: int):
        """Step 4 over block scores ``R`` (B, Tq, G, NB) for queries that see
        ``n`` (B, Tq) positions: the ``K`` best blocks' indices (B, Tq, G, K)
        and which of them are taken. A query at or under ``dense_len`` takes
        every block it can see."""
        NB = R.shape[-1]
        b = jnp.arange(NB)
        n = n[..., None, None]
        last = (n - 1) // self.block_size
        first_w = jnp.maximum(n - self.window_size, 0) // self.block_size
        forced = ((b < self.init_blocks) | (b >= first_w)
                  | (n <= self.dense_len)) & (b <= last)
        score = jnp.where(forced, jnp.inf, jnp.where(b <= last, R, -jnp.inf))
        n_forced = jnp.sum(forced, axis=-1)                     # (B, Tq, 1)
        take = jnp.where(
            n[..., 0] <= self.dense_len, n_forced,
            self.topk + (0 if self.forced_in_topk else n_forced))
        vals, idx = jax.lax.top_k(score, min(K, NB))
        taken = (jnp.arange(vals.shape[-1]) < take[..., None]) \
            & (vals > -jnp.inf)
        return idx, taken

    def _widths(self):
        """Blocks a query past ``dense_len`` can take, and a query under it."""
        sparse = self.topk + (0 if self.forced_in_topk else self._forced_most)
        return sparse, max(sparse, -(-self.dense_len // self.block_size))

    def _attend_selected(self, q, view, pv, row_live):
        """One query a row, ``q`` (B, H, hd) at positions ``pv`` (B,): score
        the row's pooled keys, select, gather the selected blocks' keys and
        values and nothing else, attend. Returns (B, H, hd) and
        DECODE_SUMS over the rows ``row_live`` marks."""
        B, H, hd = q.shape
        tables = view["tables"]
        n = (pv + 1)[:, None]
        with jax.named_scope("sparse_select"):
            kp = view["kpool_pool"][tables]                 # (B, maxb, G, hd)
            R = self._block_scores(q[:, None], kp, n)
        K_sparse, K_any = self._widths()

        def attend(K):
            def run(_):
                with jax.named_scope("sparse_select"):
                    idx, taken = self._select(R, n, K)
                return self._gathered(q, view, pv, idx[:, 0], taken[:, 0])
            return run

        if K_any == K_sparse:
            out, read = attend(K_sparse)(None)
        else:
            # a row at or under dense_len reads every block it has: the wider
            # gather runs only in a step that has such a row
            short = jnp.any(row_live & (pv + 1 <= self.dense_len))
            out, read = jax.lax.cond(short, attend(K_any), attend(K_sparse),
                                     None)
        G = view["k_pool"].shape[2]
        counts = jnp.stack([
            jnp.sum(jnp.where(row_live, read, 0)),
            jnp.sum(jnp.where(row_live, (pv + 1) * G, 0))]).astype(jnp.int32)
        return out, counts

    def _gathered(self, q, view, pv, idx, taken):
        """Step 5 for one query a row over the blocks ``idx`` (B, G, K) of
        which ``taken`` are visible. Returns (B, H, hd) and the positions
        each row read (B,), summed over its KV heads."""
        B, H, hd = q.shape
        st, per = self.kernel_stride, self.block_size // self.kernel_stride
        tables = view["tables"]
        maxb = tables.shape[1]
        G = idx.shape[1]
        sub = idx[..., None] * per + jnp.arange(per)        # (B, G, K, per)
        sub = sub.reshape(B, G, -1)                         # stride-blocks
        ok = jnp.repeat(taken, per, axis=-1) & (sub < maxb)
        with jax.named_scope("sparse_gather"):
            phys = jnp.where(ok, tables[jnp.arange(B)[:, None, None],
                                        jnp.clip(sub, 0, maxb - 1)], 0)
            kpos = (sub[..., None] * st + jnp.arange(st)).reshape(B, G, -1)
            see = jnp.repeat(ok, st, axis=-1) & (kpos <= pv[:, None, None])
            ks, vs = [], []
            for g in range(G):      # a KV head gathers its own blocks
                ks.append(view["k_pool"][phys[:, g]][:, :, :, g])
                vs.append(view["v_pool"][phys[:, g]][:, :, :, g])
            kg = jnp.stack(ks, 1).reshape(B, G, -1, hd)     # (B, G, P, hd)
            vg = jnp.stack(vs, 1).reshape(B, G, -1, hd)
        with jax.named_scope("sparse_attend"):
            qg = q.reshape(B, G, H // G, hd)
            s = wide_einsum("bgke,bgpe->bgkp", qg, kg) / np.sqrt(hd)
            s = jnp.where(see[:, :, None], s, -1e30)
            w = jax.nn.softmax(s, axis=-1)
            out = wide_einsum("bgkp,bgpe->bgke", w, vg).reshape(B, H, hd)
        return out.astype(q.dtype), jnp.sum(see, axis=(1, 2))

    def _attend_masked(self, q, view, pv):
        """A chunk of queries ``q`` (B, Tq, H, hd) at ``pv .. pv + Tq - 1``:
        gather the rows' caches whole and mask each query's row of keys by
        its own selection; the queries a sub-block at a time."""
        B, Tq, H, hd = q.shape
        tables = view["tables"]
        maxb, st = tables.shape[1], self.kernel_stride
        L = maxb * st
        G = view["k_pool"].shape[2]
        from ..generation import cache_gather

        ck, cv = cache_gather(view, ("k", "v"))             # (B, L, G, hd)
        kp = view["kpool_pool"][tables]                     # (B, maxb, G, hd)
        _, K_any = self._widths()
        NB = -(-maxb // (self.block_size // st))
        key_pos = jnp.arange(L)
        sub = max(1, min(Tq, SCORE_BYTES // (4 * B * H * L)))
        sub = 1 << (sub.bit_length() - 1)                   # a power of two
        pad = (-Tq) % sub

        def attend(args):
            q_s, off = args                                 # (B, sub, H, hd)
            q_pos = pv[:, None] + off + jnp.arange(sub)[None]   # (B, sub)
            n = q_pos + 1
            see = key_pos[None, None, :] <= q_pos[:, :, None]   # (B, sub, L)
            see = jnp.broadcast_to(see[:, :, None], (B, sub, G, L))
            if L > self.dense_len:
                with jax.named_scope("sparse_select"):
                    R = self._block_scores(q_s, kp, n)
                    idx, taken = self._select(R, n, min(K_any, NB))
                    vis = jnp.zeros((B, sub, G, NB), bool)
                    vis = jnp.put_along_axis(vis, idx, taken, axis=-1,
                                             inplace=False)
                    see = see & jnp.repeat(vis, self.block_size,
                                           axis=-1)[..., :L]
            with jax.named_scope("sparse_attend"):
                qg = q_s.reshape(B, sub, G, H // G, hd)
                s = wide_einsum("bqgke,bpge->bgkqp", qg, ck) / np.sqrt(hd)
                s = jnp.where(jnp.moveaxis(see, 2, 1)[:, :, None], s, -1e30)
                w = jax.nn.softmax(s, axis=-1)
                return wide_einsum("bgkqp,bpge->bqgke", w, cv).reshape(
                    B, sub, H, hd).astype(q.dtype)

        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        n_sub = (Tq + pad) // sub
        qs = jnp.moveaxis(qp.reshape(B, n_sub, sub, H, hd), 1, 0)
        out = jax.lax.map(attend, (qs, jnp.arange(n_sub) * sub))
        return jnp.moveaxis(out, 0, 1).reshape(B, Tq + pad, H, hd)[:, :Tq]
