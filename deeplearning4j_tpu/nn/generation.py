"""Autoregressive generation: KV-cache decode + sampling for Sequential models.

Reference parity: DL4J generates text by stepping a stateful net one token at
a time — ``MultiLayerNetwork.rnnTimeStep`` (``MultiLayerNetwork.java:2800``)
drives the char-by-char sampling loop behind ``TextGenerationLSTM``
(``zoo/model/TextGenerationLSTM.java``), re-dispatching every op per token.

TPU design: the whole generate loop is ONE jit-compiled program — prefill
processes the prompt as a single chunk, then ``lax.scan`` emits tokens with
static shapes throughout. Attention layers decode against fixed-capacity KV
caches written in place with ``lax.dynamic_update_slice``; validity is a mask
computed from the traced absolute position (no dynamic shapes, no per-token
Python dispatch, no recompilation between steps). Recurrent layers thread
their ``rnnTimeStep`` carries through the same scan. Works for any Sequential
whose layers are token-local (embedding/norm/dense/output), recurrent, or
causal attention — i.e. the CausalLM / TextGenerationLSTM / GravesLSTMCharRNN
families — without the model opting in.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import activations as _act
from .layers import (ActivationLayer, AlphaDropout, Dense, DropoutLayer,
                     ElementWiseMultiplication, Embedding, EmbeddingSequence,
                     GaussianDropout, GaussianNoise, LayerNorm,
                     MultiHeadAttention, Output, PositionalEmbedding, PReLU,
                     RMSNorm, TransformerEncoderBlock)
from .layers.recurrent import RecurrentLayer
from .model import DTYPES, Sequential, _cast_floats, _layer_key

# Layers that act on each position independently — safe to run on a decode
# chunk with their ordinary eval-time apply(). Anything outside this set,
# the attention/positional/recurrent special cases, and the final Output is
# rejected by generate() up front: silently decoding a sequence-global layer
# (GlobalPooling, Bidirectional, convolution over time, ...) one token at a
# time would return numbers that disagree with the full forward pass.
_TOKEN_LOCAL = (ActivationLayer, AlphaDropout, Dense, DropoutLayer,
                ElementWiseMultiplication, Embedding, EmbeddingSequence,
                GaussianDropout, GaussianNoise, LayerNorm, PReLU, RMSNorm)


# --------------------------------------------------------------------------
# Cache layout contract
#
# A cached layer names the PARTS of its cache and the trailing shape one
# token takes in each (``cache_parts``): a KV-cached attention layer says
# ``{"k": (Hkv, hd), "v": (Hkv, hd)}``, a latent-attention layer
# ``{"latent": (512,), "rope": (64,)}``: 576 values a token, no heads.
# Every part lives in one of two layouts, both plain pytrees so they
# trace/vmap/donate like any other operand:
#
# dense  {name: (B, C, *shape), ...}
#     Position p of row b lives at [b, p]. C is the fixed capacity; HBM
#     cost is O(B * C) regardless of live tokens.
#
# paged  {name + "_pool": (N, bs, *shape), ..., "tables": (B, maxb) int32}
#     Position p of row b lives at pool[tables[b, p // bs], p % bs].
#     The pools are shared across rows; ``tables`` maps each row's logical
#     blocks to physical blocks, so HBM cost is O(allocated blocks) — the
#     allocator (serve/paged.py) hands blocks out on demand. Physical
#     block 0 is the TRASH block: unallocated table entries point at it,
#     so writes past a row's live region land there harmlessly and reads
#     of it are always causally masked. Appends whose logical block index
#     falls past the table (right-padding overflow) are also routed to
#     block 0. (``as_paged`` / ``paged_parts`` go between a layer's pools
#     and this dictionary; a KV layer's comes out as ``k_pool``/``v_pool``.)
#
# A layer may also state a WINDOW for its cache (``cache_window`` on the
# layer; ``cache_parts`` carries it as ``parts.window``): no query ever
# reads a position more than ``window - 1`` behind it. The dense layout
# keeps such a layer at full capacity and masks the band; the paged layout
# gives it a RING: its ``tables`` are ``(B, R)`` with ``R = ring_blocks(
# window, largest chunk, bs)`` columns, logical block ``b`` lives in column
# ``b % R``, and the pool behind it is sized to the rings, not to the
# capacity. Writes go through ``cache_write(..., ring=True)``, the gather
# is the same gather (``R * bs`` positions a row), and ``ring_positions``
# says which absolute position each gathered column holds, from which
# ``causal_valid(..., kpos=)`` masks: written before read, not older than
# the window, not from the ring's previous lap. The slack in ``R`` is what
# a right-padded chunk's garbage lands in: never on a position a real query
# of that chunk can see. Which physical block a column points at is the
# allocator's business (serve/paged.py: a block behind every live window
# is released and its column zeroed, so the column's next block is a newly
# allocated one and a block shared with another holder is never written).
#
# Two kinds of part have no row a token. A part a layer holds ONCE A STRIDE
# of tokens (``parts.strides``: ``{name: stride}``; a sparse-attention
# indexer's pooled keys) is ``(B, C // stride, *shape)`` dense and ``(N,
# *shape)`` paged: one entry a block, under the same tables, and the pool's
# block size must be the stride. A STATE part (``parts.state``: ``{name:
# dtype}``; a linear-attention layer's recurrent state) has no position axis
# at all: ``(B, *shape)`` dense, ``(slots, *shape)`` paged, row ``s`` the
# sequence in slot ``s``, whatever its length; it belongs to no block group
# (``serve/paged.py``: the ``state`` group, slots and not blocks), and a
# layer that names one masks a chunk's right padding out of it (the tokens
# its cache entry's ``live`` does not mark leave the state as it was). Both
# kinds are written and read by the layer that names them
# (``layers/minicpm_sala.py``).
#
# ``cache_write`` / ``cache_gather`` are the only two operations either
# layout supports on a per-token part (``cache_append`` / ``cache_read`` are their ``k``/``v``
# face); everything above them (masking, rope, GQA, a latent's absorbed
# projections) is layout-agnostic. One read goes AROUND the gather: a DECODE
# step (one query a row) over a paged ``k``/``v`` cache kept whole reads the
# two pools in place, each row's ``pos // bs + 1`` live blocks and nothing
# past them (``reads_in_place`` / ``attend_in_place``: the Pallas kernel of
# ``ops/paged_attention.py``, MQA, MHA and GQA alike). It leans on the same
# contract: a table's entries past the live blocks are never followed, and
# what the last live block holds past ``pos`` is masked inside the kernel.
# Prefill chunks, a ring, a latent pool, a sparse layer's selected blocks and
# the dense layout gather as before. ``pos`` may be a scalar (whole batch at
# one offset — prefill, lockstep decode) or a (B,) vector (per-row offsets —
# continuous-batching decode, where every slot sits at its own position).
#
# Invariant both layouts share: position p is WRITTEN before it is ever
# unmasked-READ (prefill writes 0..T-1 then reads causally; decode writes
# p then attends with mask <= p), so stale garbage beyond the live length
# is never observable.
# --------------------------------------------------------------------------


def _pos_vec(pos):
    """None if ``pos`` is a scalar offset, else the (B,) per-row vector."""
    return pos if getattr(pos, "ndim", 0) == 1 else None


def as_paged(pools, tables):
    """One layer's paged cache dictionary from its pools ``{name: (N, bs,
    *shape)}`` and the block tables."""
    return {**{f"{n}_pool": a for n, a in pools.items()}, "tables": tables}


def paged_parts(cache, names):
    """The pools ``{name: ...}`` back out of a paged cache dictionary."""
    return {n: cache[f"{n}_pool"] for n in names}


def ring_blocks(window: int, chunk: int, block_size: int) -> int:
    """Columns of a window layer's ring table: the blocks that hold the
    ``window - 1`` positions behind a chunk's first query and the chunk
    itself (``chunk``: the widest a program writes at once, padding
    included), plus one because neither end is block-aligned."""
    return -(-(int(window) - 1 + int(chunk)) // int(block_size)) + 1


def ring_positions(tables, bs: int, pos, Tq: int):
    """The absolute position each column of a RING cache's gather holds,
    (B, R * bs) int32, for ring ``tables`` (B, R) over blocks of ``bs``
    and a chunk of ``Tq`` queries at offset ``pos`` (scalar or (B,)):
    column ``c`` holds the newest logical block ``b <= (pos + Tq - 1) //
    bs`` with ``b % R == c``. Negative where the ring has not come round
    to the column yet."""
    B, R = tables.shape
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    newest = (p + (Tq - 1)) // bs                                   # (B,)
    cols = jnp.arange(R, dtype=jnp.int32)[None]
    blk = newest[:, None] - (newest[:, None] - cols) % R            # (B, R)
    kpos = blk[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    return kpos.reshape(B, R * bs)


def cache_write(cache, parts, pos, *, ring: bool = False):
    """Write a chunk's ``parts`` ``{name: (B, Tq, *shape)}`` at absolute
    offset ``pos`` (scalar or (B,) vector). Returns the updated cache (same
    layout, same shapes — never shape-changing, so writes inside jit never
    trigger a recompile), holding the parts written and, paged, the tables.
    ``ring``: a paged cache's tables are a window layer's ring (logical
    block ``b`` in column ``b % R``); a dense cache takes no notice."""
    B, Tq = next(iter(parts.values())).shape[:2]
    pv = _pos_vec(pos)
    if "tables" in cache:  # paged
        tables = cache["tables"]
        bs = cache[f"{next(iter(parts))}_pool"].shape[1]
        maxb = tables.shape[1]
        p = pv if pv is not None else jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32), (B,))
        wpos = p[:, None] + jnp.arange(Tq, dtype=jnp.int32)[None]  # (B, Tq)
        blk, off = wpos // bs, wpos % bs
        rows = jnp.arange(B, dtype=jnp.int32)[:, None]
        if ring:
            phys = tables[rows, blk % maxb]
        else:
            # logical blocks past the table (right-padded garbage) -> trash 0
            phys = jnp.where(blk < maxb,
                             tables[rows, jnp.minimum(blk, maxb - 1)], 0)
        out = {}
        for n, a in parts.items():
            pool = cache[f"{n}_pool"]
            out[f"{n}_pool"] = pool.at[phys, off].set(a.astype(pool.dtype))
        return {**out, "tables": tables}
    if pv is None:
        return {n: lax.dynamic_update_slice(
                    cache[n], a.astype(cache[n].dtype),
                    (0, pos) + (0,) * (a.ndim - 2))
                for n, a in parts.items()}
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    wpos = pv[:, None] + jnp.arange(Tq, dtype=jnp.int32)[None]
    return {n: cache[n].at[rows, wpos].set(a.astype(cache[n].dtype))
            for n, a in parts.items()}


def cache_gather(cache, names):
    """Materialize the parts ``names`` of the cache, each (B, L, *shape) in
    logical position order. Dense: the buffers themselves (L = C, no copy).
    Paged: a block-table gather (L = maxb * bs); entries past a row's live
    length are garbage the caller MUST mask causally (cache_write's
    invariant guarantees every position <= the current offset holds real
    data). A ring table gathers the same way, ``R * bs`` positions a row in
    COLUMN order: :func:`ring_positions` says what each is."""
    if "tables" not in cache:
        return tuple(cache[n] for n in names)
    tables = cache["tables"]
    B, maxb = tables.shape

    def gather(pool):
        return pool[tables].reshape(
            (B, maxb * pool.shape[1]) + pool.shape[2:])

    with jax.named_scope("cache_read"):  # the gather's name in a trace
        return tuple(gather(cache[f"{n}_pool"]) for n in names)


def cache_append(cache, k, v, pos):
    """:func:`cache_write` of a KV layer's two parts: ``k``/``v``
    (B, Tq, Hkv, hd)."""
    return cache_write(cache, {"k": k, "v": v}, pos)


def cache_read(cache):
    """:func:`cache_gather` of a KV layer's two parts: (K, V), each
    (B, L, Hkv, hd)."""
    return cache_gather(cache, ("k", "v"))


def _mha_decode(num_heads: int, params, x, cache, pos, *, rope=False,
                rope_base=10000.0, num_kv_heads=None, window=None):
    """Decode a query chunk ``x`` (B, Tq, D) at absolute offset ``pos``
    (scalar, or (B,) per-row) against a KV cache in either layout (see the
    layout contract above). Returns (y, new_cache).
    Attention is causal by construction — the ``valid`` mask lets token t
    see cache slots 0..pos+t; generate() rejects non-causal attention
    layers up front (they cannot be decoded incrementally). With ``rope``,
    the chunk's q/k rotate at their ABSOLUTE positions (pos..pos+Tq-1)
    before k enters the cache — cached keys were rotated at their own
    positions when written, so cached entries are never re-rotated. With
    GQA (num_kv_heads < num_heads) the cache holds only Hkv heads — the
    serving memory win — and broadcasts to H at score time."""
    from .layers.attention import rope_rotate

    B, Tq, D = x.shape
    H = num_heads
    Hkv = num_kv_heads or H
    hd = D // H
    qkv = x @ params["w_qkv"] + params["b_qkv"]
    q, k, v = jnp.split(qkv, [D, D + Hkv * hd], axis=-1)
    q = q.reshape(B, Tq, H, hd)
    k = k.reshape(B, Tq, Hkv, hd)
    v = v.reshape(B, Tq, Hkv, hd)
    if rope:
        pv = _pos_vec(pos)
        if pv is None:
            abs_pos = pos + jnp.arange(Tq)
        else:
            abs_pos = pv[:, None] + jnp.arange(Tq)[None]  # (B, Tq)
        q = rope_rotate(q, abs_pos, rope_base)
        k = rope_rotate(k, abs_pos, rope_base)
    y, cache = attend_cached(q, k, v, cache, pos, window=window)
    y = y @ params["w_o"] + params["b_o"]
    return y, cache


def causal_valid(pos, Tq: int, C: int, window=None, kpos=None):
    """Which of a cache's ``C`` slots each of a chunk's ``Tq`` queries at
    offset ``pos`` may see: slots 0..pos+t, and with ``window`` only the
    last ``window`` of them (the band mask of a sliding-window layer whose
    cache is kept at capacity: the dense layout, or a paged layer that
    states no ``cache_window``). (Tq, C) for a scalar ``pos``, (B, Tq, C)
    for a (B,) vector.

    ``kpos`` (B, C): the slots are a ring's gathered columns and hold these
    absolute positions (:func:`ring_positions`); a query sees a slot whose
    position is not after its own, not older than ``window``, and not
    negative (a column the ring has not reached). Always (B, Tq, C)."""
    pv = _pos_vec(pos)
    if kpos is not None:
        p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), kpos.shape[:1])
        qpos = p[:, None, None] + jnp.arange(Tq)[None, :, None]  # (B,Tq,1)
        k = kpos[:, None, :]
        return (k <= qpos) & (k >= 0) & (qpos - k < window)
    if pv is None:
        qpos = pos + jnp.arange(Tq)[:, None]
        valid = jnp.arange(C)[None, :] <= qpos  # (Tq, C)
        if window is not None:
            valid = valid & (qpos - jnp.arange(C)[None, :] < window)
        return valid
    qpos = pv[:, None, None] + jnp.arange(Tq)[None, :, None]  # (B,Tq,1)
    valid = jnp.arange(C)[None, None, :] <= qpos  # (B, Tq, C)
    if window is not None:
        valid = valid & (qpos - jnp.arange(C)[None, None, :] < window)
    return valid


def reads_in_place(cache, q, window=None) -> bool:
    """Whether a chunk of queries ``q`` (B, Tq, H, hd) is a DECODE step over
    a paged k/v cache kept whole, which :func:`attend_in_place` serves without
    a gather: ``tables`` there, one query a row, no window (a ring's table
    and a band over a cache at capacity stay on ``cache_gather``), and dtypes
    the kernel multiplies as exactly as the einsums."""
    from ..ops import paged_attention

    return ("tables" in cache and q.shape[1] == 1 and window is None
            and paged_attention.supports(q.dtype, cache["k_pool"].dtype))


def attend_in_place(q, cache, pos):
    """Attend a decode step's ``q`` (B, 1, H, hd) at ``pos`` (scalar or (B,))
    over a paged cache that already holds this step's keys and values:
    (B, 1, H, hd) in the wider of ``q``'s and the pools' dtype, as the
    einsums answer. The k/v pools are read where they lie,
    each row's live blocks only (``ops/paged_attention.py``): no gathered
    copy, no mask over the capacity."""
    from ..ops.paged_attention import paged_attention_decode

    B = q.shape[0]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    return paged_attention_decode(q[:, 0], cache["k_pool"], cache["v_pool"],
                                  cache["tables"], p)[:, None]


def attend_cached(q, k, v, cache, pos, *, window=None):
    """The layout-agnostic half of a cached attention: append the chunk's
    ``k``/``v`` (B, Tq, Hkv, hd; already rotated) at ``pos``, read the cache
    back, and attend ``q`` (B, Tq, H, hd) causally over slots 0..pos+t.
    Returns ((B, Tq, H*hd), new_cache). A layer with projections of its own
    (``layers/olmoe.py``) calls this from its ``decode``. A decode step over
    a paged cache reads the pools in place (:func:`reads_in_place`); every
    other call gathers."""
    B, Tq, H, hd = q.shape
    Hkv = k.shape[2]
    D = H * hd
    pv = _pos_vec(pos)
    cache = cache_append(cache, k, v, pos)
    if reads_in_place(cache, q, window):
        return attend_in_place(q, cache, pos).reshape(B, Tq, D), cache
    ck, cv = cache_read(cache)
    C = ck.shape[1]
    scale = 1.0 / np.sqrt(hd)
    valid = causal_valid(pos, Tq, C, window)
    if pv is None:
        vmask, vmask_g = valid[None, None], valid[None, None, None]
    else:
        vmask, vmask_g = valid[:, None], valid[:, None, None]
    if Hkv != H:
        # grouped einsum: query heads fold into (Hkv, G) so the cache is
        # consumed at Hkv heads directly — repeating it to H would
        # materialize a full-size (B, C, H, hd) transient every decode
        # step and forfeit the GQA serving-memory win at peak
        G = H // Hkv
        qg = q.reshape(B, Tq, Hkv, G, hd)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(vmask_g, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
        y = jnp.einsum("bhgqk,bkhd->bqhgd", w, cv).reshape(B, Tq, D)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(vmask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
        y = jnp.einsum("bhqk,bkhd->bqhd", w, cv).reshape(B, Tq, D)
    return y, cache


class Parts(dict):
    """``{part: trailing shape}`` of one layer's cache, and the ``window``
    the layer states for it (None: every position is kept). ``strides``:
    the parts held once a stride of tokens, ``{part: stride}``; ``state``:
    the parts with no position axis, one a sequence, ``{part: dtype}``
    (the layout contract above). Every other part is held a token."""

    window: Optional[int] = None
    strides: Dict[str, int] = {}
    state: Dict[str, str] = {}

    def dense_shape(self, name: str, batch: int, capacity: int) -> tuple:
        """The dense layout's shape of part ``name``."""
        if name in self.state:
            return (batch,) + self[name]
        return (batch, capacity // self.strides.get(name, 1)) + self[name]


def cache_parts(model: Sequential):
    """The cached layers of ``model`` as ``[(layer_key, {part: shape}), ...]``:
    for each the named parts of its cache and the trailing shape ONE token
    takes in each — ``{"k": (kv_heads, head_dim), "v": (kv_heads,
    head_dim)}`` for KV-cached attention, ``{"latent": (512,), "rope":
    (64,)}`` for a layer that caches a latent vector and one rope key a token
    and no heads. Each is a :class:`Parts`: ``parts.window`` is the cache
    LENGTH a sliding-window layer states (512: the paged layout gives it a
    ring of that reach and pools to match, ``serve/paged.py`` groups layers
    by it), None for a layer that keeps every position. It is everything a
    cache builder (serve/paged.py block pools, external runtimes) needs
    without walking layer internals. A layer that says how it decodes may
    name a recurrent STATE as a part (``parts.state``: one array a sequence,
    no position axis: ``{"state": (32, 128, 128)}`` for a linear-attention
    layer) and parts held once a stride of tokens (``parts.strides``: a
    sparse layer's pooled keys). The carries of the ``RecurrentLayer``
    family are NOT listed: they are opaque layer-owned state with no
    contract a batcher could keep (no mask for a chunk's padding, no
    snapshot)."""
    spec = []
    for i, layer in enumerate(model.layers):
        parts = _layer_parts(layer, model._shapes[i])
        if parts is not None:
            spec.append((_layer_key(i, layer), parts))
    return spec


def cache_spec(model: Sequential):
    """:func:`cache_parts` for a model whose cached layers all keep keys and
    values: ``[(layer_key, kv_heads, head_dim), ...]``. A layer that names
    other parts (a latent) has no such triple, and asking for one is an
    error: build from :func:`cache_parts`."""
    spec = []
    for lk, parts in cache_parts(model):
        if set(parts) != {"k", "v"} or parts["k"] != parts["v"]:
            raise ValueError(
                f"{lk} caches {parts}, not k and v of one (kv_heads, "
                f"head_dim): build its cache from cache_parts(model)")
        spec.append((lk,) + parts["k"])
    return spec


def says_how_it_decodes(layer) -> bool:
    """The one hook a stateful layer is reached through (ROADMAP D1): a
    layer that has ``decode(params, x, cache, pos) -> (y, cache)`` and
    ``cache_spec(input_shape)`` is asked before any ``isinstance`` ladder
    here or in the batcher is walked. ``cache_spec`` answers either
    ``(kv_heads, head_dim)`` — keys and values, parts ``k`` and ``v`` of
    that shape — or ``{part: trailing shape}`` for a cache of other parts
    (``{"latent": (512,), "rope": (64,)}``); ``decode`` finds the cache it
    is handed in the layout contract above under those names. A layer with
    an attribute ``cache_window`` (an int) states that its cache need not
    reach further back, and is handed a ring when paged; ``cache_strides``
    (``{part: stride}``) and ``cache_state`` (``{part: dtype}``) name the
    parts that are held once a stride of tokens and once a sequence. A layer
    with ``reads_live`` true finds ``cache["live"]`` in a served program (the
    rows, or a chunk's tokens, that are real); one with ``decode_sums``
    (``{counter name: help}``) leaves that many int32 sums under ``"sums"``
    in the cache a decode step returns, and the batcher counts them
    (``serve_<name>_total``). What is left on the ladders is the dense block
    and the bare attention layer."""
    return hasattr(layer, "decode") and hasattr(layer, "cache_spec")


def _layer_parts(layer, input_shape):
    """``{part: trailing shape}`` (:class:`Parts`) of the cache ``layer``
    decodes against, or None for a layer that keeps none."""
    kv = None
    if says_how_it_decodes(layer):
        kv = layer.cache_spec(input_shape)
    elif isinstance(layer, (TransformerEncoderBlock, MultiHeadAttention)):
        hd = input_shape[-1] // layer.num_heads
        kv = (layer.num_kv_heads or layer.num_heads), hd  # GQA: smaller
    if kv is None:
        return None
    if isinstance(kv, dict):
        parts = Parts((n, tuple(shape)) for n, shape in kv.items())
    else:
        parts = Parts(k=tuple(kv), v=tuple(kv))
    # only a layer that says how it decodes can state a cache window: the
    # ``window=`` of the ladder's attention layers is a band mask over a
    # cache kept at capacity
    parts.window = getattr(layer, "cache_window", None)
    parts.strides = dict(getattr(layer, "cache_strides", None) or {})
    parts.state = dict(getattr(layer, "cache_state", None) or {})
    return parts


def init_caches(model: Sequential, batch: int, capacity: int, dtype):
    """Dense-layout caches for every cached layer (+ recurrent carries).
    For the paged layout, build pools from :func:`cache_parts` instead."""
    caches: Dict[str, Any] = {}
    for i, layer in enumerate(model.layers):
        k = _layer_key(i, layer)
        parts = _layer_parts(layer, model._shapes[i])
        if parts is not None:
            caches[k] = {n: jnp.zeros(parts.dense_shape(n, batch, capacity),
                                      parts.state.get(n, dtype))
                         for n in parts}
        elif isinstance(layer, RecurrentLayer):
            caches[k] = layer.init_carry(batch, model._shapes[i], dtype)
    return caches


def decode_params(model: Sequential, params):
    """``params`` as :func:`decode_forward` reads them: float leaves in the
    model's ``compute_dtype``, on the device; the tree itself when the model
    computes in its parameters' dtype. A caller whose parameters stay fixed
    over many calls (a server between two publishes) casts ONCE with this
    and passes the copy: ``decode_forward`` casts whatever it is given, and
    on leaves that already have the compute dtype that traces to nothing, so
    the compiled step then reads the weights at compute width and holds no
    convert. Both ways every matmul sees the same operands."""
    if not model.config.compute_dtype:
        return params
    return _cast_floats(jax.device_put(params),
                        DTYPES[model.config.compute_dtype])


def decode_forward(model: Sequential, params, state, x, caches, pos):
    """Run one decode chunk through the stack. ``x``: (B, Tq) int ids or
    (B, Tq, F) features at absolute offset ``pos`` — a scalar, or a (B,)
    vector when every row sits at its own offset (continuous batching);
    returns (logits (B, Tq, V), new_caches). ``caches`` entries may be
    dense or paged (see the layout contract above). The final Output layer
    contributes its PRE-activation (logits) — sampling applies temperature
    in logit space."""
    cdt = DTYPES[model.config.compute_dtype] if model.config.compute_dtype else None
    if cdt is not None and jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(cdt)
    new = dict(caches)
    mask = None
    for i, layer in enumerate(model.layers):
        k = _layer_key(i, layer)
        p = params.get(k, {})
        if cdt is not None:
            # named scopes are HLO metadata only: a device trace carries
            # them with every operation (obs/README.md, "Hot-path spans")
            with jax.named_scope("weight_cast"):
                p = _cast_floats(p, cdt)
        if says_how_it_decodes(layer):
            x, new[k] = layer.decode(p, x, new[k], pos)
        elif isinstance(layer, TransformerEncoderBlock):
            with jax.named_scope("attention"):
                h = layer._ln(x, p["ln1_g"], p["ln1_b"])
                a, new[k] = _mha_decode(layer.num_heads, p["attn"], h, new[k],
                                        pos, rope=layer.rope,
                                        rope_base=layer.rope_base,
                                        num_kv_heads=layer.num_kv_heads,
                                        window=layer.window)
            x = x + a
            with jax.named_scope("mlp"):
                h = layer._ln(x, p["ln2_g"], p["ln2_b"])
                m = (_act.get(layer.activation)(h @ p["w_up"] + p["b_up"])
                     @ p["w_down"] + p["b_down"])
            x = x + m
        elif isinstance(layer, MultiHeadAttention):
            with jax.named_scope("attention"):
                x, new[k] = _mha_decode(layer.num_heads, p, x, new[k], pos,
                                        rope=layer.rope,
                                        rope_base=layer.rope_base,
                                        num_kv_heads=layer.num_kv_heads,
                                        window=layer.window)
        elif isinstance(layer, PositionalEmbedding):
            Tq = x.shape[1]
            pv = _pos_vec(pos)
            if pv is None:
                x = x + lax.dynamic_slice(p["pos"], (pos, 0),
                                          (Tq, p["pos"].shape[1]))
            else:  # per-row offsets; take() clips garbage positions past
                # max_len (they are causally masked / discarded anyway)
                idx = pv[:, None] + jnp.arange(Tq, dtype=jnp.int32)[None]
                x = x + jnp.take(p["pos"], idx, axis=0)
        elif isinstance(layer, RecurrentLayer):
            x, new[k] = layer.apply_sequence(p, x, new[k])
        elif isinstance(layer, Output):  # incl. RnnOutput/CenterLossOutput
            x = layer.preactivation(p, x)
        else:  # token-local layers: embedding, norms, dense, dropout(eval)...
            x, _, mask = layer.apply(p, state.get(k, {}), x,
                                     training=False, mask=mask)
    if cdt is not None or x.dtype == jnp.bfloat16:
        # logits leave in f32 whether the width came from a compute_dtype or
        # from parameters held in bf16: samplers have one signature
        x = x.astype(jnp.float32)
    return x, new


def check_decodes(model: Sequential, context: int, what: str, *,
                  served: bool = False) -> int:
    """The model contract of decoding one chunk at a time against a cache,
    for ``context`` positions (``what`` names them in the error). Every
    layer is token-local, recurrent, causal attention, a positional table
    at least ``context`` long, says how it decodes itself
    (:func:`says_how_it_decodes`), or the final Output. ``served`` adds the
    continuous batcher's terms, which right-pads token prompts into slots:
    an embedding front, no ``RecurrentLayer`` carry, an Output last (a layer
    that says how it decodes and names a state part in its cache spec IS
    served: it masks the padding out of its state). Returns the vocabulary
    size."""
    if served and not isinstance(model.layers[0],
                                 (Embedding, EmbeddingSequence)):
        raise ValueError(
            "continuous batching requires an embedding-front token model "
            "(CausalLM family); one-hot char models stay on "
            "nn.generation.generate")
    last = len(model.layers) - 1
    for i, layer in enumerate(model.layers):
        if says_how_it_decodes(layer):
            continue   # the layer's own decode() and cache_spec()
        if isinstance(layer, RecurrentLayer):
            if served:
                raise ValueError(
                    f"layer {i} {type(layer).__name__}: recurrent carries "
                    f"cannot survive a right-padded prefill (a layer that "
                    f"names its state as a cache part and masks the padding "
                    f"can: nn.generation.says_how_it_decodes) — use "
                    f"whole-batch nn.generation.generate for RNN models")
        elif isinstance(layer, PositionalEmbedding):
            # a learned positional TABLE bounds context; rope models have no
            # such layer, so paged capacity is free to exceed training length
            if layer.max_len < context:
                raise ValueError(
                    f"PositionalEmbedding(max_len={layer.max_len}) is shorter "
                    f"than {what} {context}")
        elif isinstance(layer, (TransformerEncoderBlock, MultiHeadAttention)):
            if not layer.causal:
                raise ValueError(
                    f"layer {i} {type(layer).__name__}(causal=False) cannot "
                    f"be decoded autoregressively — generation needs causal "
                    f"attention")
        elif not (isinstance(layer, _TOKEN_LOCAL)
                  or (isinstance(layer, Output) and i == last)):
            raise ValueError(
                f"layer {i} {type(layer).__name__} does not say how it "
                f"decodes: it is not token-local, and has no "
                f"decode(params, x, cache, pos) -> (y, cache) with "
                f"cache_spec(input_shape) -> (kv_heads, head_dim) or "
                f"{{part: shape}} (nn.generation.says_how_it_decodes). "
                f"Decoding it one token at a time without a cache would disagree with "
                f"its full forward pass")
    out_layer = model.layers[last]
    if served and not isinstance(out_layer, Output):
        raise ValueError("model must end in an Output layer")
    return int(getattr(out_layer, "n_out", 0) or model._shapes[-1][-1])


def top_k_threshold(scaled, top_k):
    """Per-row ``kth`` (rows,) such that ``scaled >= kth[:, None]`` keeps
    exactly each row's ``top_k`` largest values, ties at the k-th included:
    the VALUE ``sort(scaled)[V - top_k]``, found exactly and without
    sorting the vocabulary, at one cost whatever ``top_k`` is.

    ``scaled`` (rows, V) floats, ``top_k`` (rows,) traced, clipped to 1..V
    (``V`` = no restriction: the row's minimum, a mask that is all true).

    A search on the threshold that counts. Floats are compared through
    their order-preserving image in the unsigned integers (sign bit set on
    a positive, every bit flipped on a negative; a NaN made the positive
    quiet one first, so that it ranks last, where a sort puts it), and the
    answer is built from the top bit down, one pass over the row a bit: the
    largest ``t`` with at least ``k`` values ``>= t`` IS the k-th largest
    value, whatever ties it has."""
    k = jnp.clip(top_k, 1, scaled.shape[-1])
    bits = 8 * scaled.dtype.itemsize
    uint = jnp.dtype(f"uint{bits}")
    top = uint.type(1 << (bits - 1))
    b = lax.bitcast_convert_type(
        jnp.where(jnp.isnan(scaled), jnp.nan, scaled), uint)
    u = jnp.where(b >= top, ~b, b | top)

    def refine(i, t):
        cand = t | (top >> i.astype(uint))
        enough = jnp.sum(u >= cand[:, None], axis=-1) >= k
        return jnp.where(enough, cand, t)

    t = lax.fori_loop(0, bits, refine, jnp.zeros(scaled.shape[:1], uint))
    return lax.bitcast_convert_type(jnp.where(t >= top, t ^ top, ~t),
                                    scaled.dtype)


def sample_logits(logits, rng, temperature: float = 1.0,
                  top_k: Optional[int] = None):
    """Sample token ids (B,) from (B, V) logits. ``temperature=0`` = greedy;
    ``top_k`` restricts sampling to the k most likely tokens."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None and top_k > 0 and top_k < logits.shape[-1]:
        rows = logits.shape[:1]
        kth = top_k_threshold(logits, jnp.full(rows, top_k, jnp.int32))
        logits = jnp.where(logits >= kth[:, None], logits, -1e30)
    return jax.random.categorical(rng, logits, axis=-1)


def generate(model: Sequential, prompt, max_new_tokens: int, *,
             params=None, state=None, temperature: float = 1.0,
             top_k: Optional[int] = None, rng=None, seed: int = 0,
             capacity: Optional[int] = None) -> np.ndarray:
    """Autoregressively continue ``prompt`` for ``max_new_tokens`` tokens.

    ``prompt``: (B, Tp) int token ids (embedding-front models, e.g. CausalLM)
    or (B, Tp, V) one-hot rows (char models, e.g. TextGenerationLSTM /
    GravesLSTMCharRNN — the sampled id is re-fed as a one-hot row exactly
    like the reference's sampling loop). Returns the generated ids (B, N).

    One compiled program: prompt prefill + a ``lax.scan`` over decode steps.
    ``capacity`` (default Tp + max_new_tokens) sizes the KV caches.
    """
    params = params if params is not None else model.params
    state = state if state is not None else model.state
    assert params is not None, "call init() first"
    prompt = jnp.asarray(prompt)
    onehot = prompt.ndim == 3
    B, Tp = prompt.shape[:2]
    total = Tp + max_new_tokens
    capacity = capacity or total
    if capacity < total:
        raise ValueError(f"capacity {capacity} < prompt+new tokens {total}")
    V = check_decodes(model, total, "prompt+new tokens")
    # rng convention: pass an explicit key for streamed/nested sampling; with
    # rng=None each call derives its stream from ``seed`` (deterministic,
    # caller-controlled — never a library-internal constant key)
    rng = rng if rng is not None else jax.random.PRNGKey(seed)
    caches = init_caches(model, B, capacity, model.dtype)

    def embed(tok):  # (B,) int -> next input chunk
        if onehot:
            return jax.nn.one_hot(tok, V, dtype=prompt.dtype)[:, None, :]
        return tok[:, None].astype(prompt.dtype)

    def run(params, state, prompt, rng):
        logits, c = decode_forward(model, params, state, prompt, caches, 0)
        last = logits[:, -1]

        def body(carry, i):
            c, last, rng = carry
            rng, k1 = jax.random.split(rng)
            tok = sample_logits(last, k1, temperature, top_k)
            lg, c = decode_forward(model, params, state, embed(tok), c,
                                    Tp + i)
            return (c, lg[:, -1], rng), tok

        (_, _, _), toks = lax.scan(body, (c, last, rng),
                                   jnp.arange(max_new_tokens))
        return toks.T  # (B, N)

    # one compiled program per (shape/sampling) signature, cached ON the
    # model so repeated generate() calls (the interactive use) don't
    # recompile; the cache dies with the model object
    key = (B, Tp, max_new_tokens, capacity, onehot, float(temperature),
           top_k, str(prompt.dtype), str(model.config.compute_dtype))
    jit_cache = model.__dict__.setdefault("_generate_jit_cache", {})
    if key not in jit_cache:
        jit_cache[key] = jax.jit(run)
    return np.asarray(jit_cache[key](params, state, prompt, rng))
