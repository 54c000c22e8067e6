"""Plain reference of the MiniCPM-SALA decoder (``openbmb/MiniCPM-SALA``,
``model_type`` ``minicpm_sala``): token embedding times ``scale_emb``,
``num_hidden_layers`` blocks whose mixer is ``mixer_types[first_layer + i]``
(``minicpm4``: softmax attention over 2 KV heads that turns block-sparse past
``dense_len``; ``lightning-attn``: linear attention with a fixed decay a head),
final RMSNorm, the head fed the norm divided by ``hidden_size /
dim_model_base``. ``x`` is a layer's input after its RMSNorm (eps
``rms_norm_eps``), ``rms_hd`` an RMSNorm over one head's ``head_dim`` values
with a gain of that width::

    every layer   h <- h + c * mixer(rms(h)) ;  h <- h + c * mlp(rms(h))
                  c = scale_depth / sqrt(PUBLISHED num_hidden_layers) ;  mlp(x) = Wd (silu(Wg x) * Wu x)
    front, head   h0 = scale_emb * E[token] ;  logits = W_head (rms(h) / (hidden_size / dim_model_base))

    lightning-attn, head a of lightning_nh, position t:
        q_t = rope(rms_hd(Wq x_t)) ;  k_t = rope(rms_hd(Wk x_t)) ;  v_t = Wv x_t
              rope theta ``rope_theta`` over the whole head, pairs (i, i + hd/2)
        S_t = lam_a S_{t-1} + k_t^T v_t   (hd x hd, S_{-1} = 0) ;  o_t = (q_t S_t) / sqrt(hd)
        y_t = Wo (rms_hd(o_t) * sigmoid(Wgate x_t))
        lam_a = exp(-s_a f_l) ;  s_a = 2^(-8a/nh), a = 1..nh ;  f_l = 1 - l/(L-1) + 1e-5
              l the layer's PUBLISHED index, L the published depth (``lightning_decay``: assumed)

    minicpm4, KV head g of num_key_value_heads with its H/Hkv query heads, query t, n = t + 1 visible:
        q = rms_hd(Wq x) ;  k = rms_hd(Wk x) ;  v = Wv x          no rope (``attn_use_rope`` false)
        n <= dense_len:  causal softmax attention over all n, scale 1/sqrt(hd)
        else, with ``sparse_config`` (kernel_size ks, kernel_stride st, block_size bs, topk, init_blocks, window_size):
          1. pooled keys  kp_j = mean(k_{st j} .. k_{st j + ks - 1})   for every j with st j + ks <= n
          2. p_{h,j} = softmax_j(q_{t,h} . kp_j / sqrt(hd)) ;  r_{g,j} = sum over h in g of p_{h,j}
          3. block b of bs tokens scores  R_{g,b} = max r_{g,j} over the kernels that overlap it
          4. visible: blocks 0 .. init_blocks - 1, the blocks that overlap the last window_size positions
             (their score set to +inf), and the best others until topk blocks in all
             (``forced_in_topk`` true; false: topk blocks BEYOND the forced ones)
          5. causal softmax attention of the group's heads over the visible blocks' positions
        y = Wo (o * sigmoid(Wgate x))

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the linear layers are the
recurrence itself, one ``lax.scan`` step a token; the sparse layers score
every pooled key, rank every block and mask a full row of keys for every
query; no cache, no state pool, no chunked form, no kernel, nothing imported
from the program. Queries (``Q_BLOCK``) and the feed-forward's rows
(``ROW_BLOCK``) are taken a block at a time so that a sequence of tens of
thousands of tokens fits a chip: a block is a set of rows, no sum is split,
so blocking changes no sum's precision.

It reads the program's parameter tree by its leaf names only (``layer_0/w``
embedding; blocks ``layer_1 ..`` with ``ln1_g``, ``ln2_g``, ``mix/{w_q (d, H,
hd), w_k, w_v (d, Hkv, hd), w_gate (d, H * hd), w_o (H * hd, d), q_norm_g,
k_norm_g (hd,)}``, a linear layer's ``mix/o_norm_g (hd,)`` besides, and
``mlp/{w_gate, w_up, w_down}``; then ``gamma``; then the head's ``w``) and
casts every leaf to float32 where it is used.

**What is stored is given; what is computed is compared**
(``glm4_moe_lite_block.py`` has the argument in full). Where the
configuration states ``cache_dtype``, the reference rounds what a
deployment's cache stores of a sparse layer to that width once, where it is
produced: the keys, the values, and the pooled keys (the mean of the STORED
keys, taken in float32). Everything is computed from them in float32. The
linear layers' state is held in float32 and nothing of it is rounded.

**The selection is a discrete choice, like a router's.**
``hidden_and_margin`` returns each position's smallest selection margin over
the sparse layers: the score of the last block taken less that of the first
left out (infinite where a query sees every block: under ``dense_len``).

Departures from the publication, which the program under test shares: seeded
N(0, 0.02) weights, not a checkpoint; the stream in float32; step 2's
normaliser is computed exactly over all pooled keys (the published kernels
approximate it in two stages); no feature map on q and k of the linear
layers (the config names none); the sizes of ``sparse_config`` and the decay
slopes are the family's convention, not the catalog's (``assumed`` in the
configuration file).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 128      # queries of a sparse layer scored and attended at once
ROW_BLOCK = 4096   # rows of the feed-forward at once


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, pos, theta: float):
    """x: (T, H, hd) at positions ``pos`` (T,): the whole head rotated in
    pairs (i, i + hd/2). The positions are an argument of the jitted layer,
    not a constant inside it (a folded cosine is the host's)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(
        inv, jnp.float32)[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _stored(x, dtype):
    """``x`` as a cache of ``dtype`` would hand it back, in float32
    (``reduce_precision``: a cast down and up again is folded away by the
    TPU's compiler)."""
    if dtype is None:
        return x
    info = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def decay_rates(heads: int, layer: int, depth: int) -> np.ndarray:
    """``s_a f_l`` for a = 1..heads: ``lam_a = exp(-rate_a)``."""
    slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads)
    return (slopes * (1.0 - layer / (depth - 1) + 1e-5)).astype(np.float32)


def _blocked(fn, rows, block: int):
    """``fn`` over ``rows`` (T, ...) a block of rows at a time."""
    T = rows.shape[0]
    block = min(block, T)
    pad = (-T) % block
    rp = jnp.pad(rows, ((0, pad),) + ((0, 0),) * (rows.ndim - 1))
    out = jax.lax.map(fn, rp.reshape((-1, block) + rows.shape[1:]))
    return out.reshape((T + pad,) + out.shape[2:])[:T]


def _mlp(p, x, c: float, eps: float):
    def rows(xb):
        h = _rms(xb, p["ln2_g"], eps)
        m = p["mlp"]
        return (jax.nn.silu(h @ _f32(m["w_gate"])) * (h @ _f32(m["w_up"]))) \
            @ _f32(m["w_down"])

    return x + c * _blocked(rows, x, ROW_BLOCK)


@functools.partial(jax.jit, static_argnames=("c", "eps", "theta"))
def lightning_layer(p, x, pos, rates, *, c: float, eps: float, theta: float):
    """One ``lightning-attn`` block on x: (T, d) at positions ``pos`` (T,),
    the heads' decay rates ``rates`` (H,): the recurrence, a step a token."""
    with jax.default_matmul_precision("highest"):
        mx = p["mix"]
        h = _rms(x, p["ln1_g"], eps)
        q = _rope(_rms(jnp.einsum("td,dhe->the", h, _f32(mx["w_q"])),
                       mx["q_norm_g"], eps), pos, theta)
        k = _rope(_rms(jnp.einsum("td,dhe->the", h, _f32(mx["w_k"])),
                       mx["k_norm_g"], eps), pos, theta)
        v = jnp.einsum("td,dhe->the", h, _f32(mx["w_v"]))
        hd = q.shape[-1]
        lam = jnp.exp(-rates)[:, None, None]

        def step(S, qkv):
            q_t, k_t, v_t = qkv
            S = lam * S + k_t[:, :, None] * v_t[:, None, :]
            return S, jnp.einsum("hd,hde->he", q_t, S) / np.sqrt(hd)

        _, o = jax.lax.scan(step, jnp.zeros((q.shape[1], hd, hd), jnp.float32),
                            (q, k, v))
        o = _rms(o, mx["o_norm_g"], eps).reshape(x.shape[0], -1)
        y = (o * jax.nn.sigmoid(h @ _f32(mx["w_gate"]))) @ _f32(mx["w_o"])
        return _mlp(p, x + c * y, c, eps)


def block_scores(r, valid, *, kernel_size: int, kernel_stride: int,
                 block_size: int, n_blocks: int):
    """Step 3: ``r`` (..., J) pooled-key scores (``valid`` (..., J) marks the
    kernels that exist) to (..., n_blocks): the largest score among the
    kernels that overlap each block of ``block_size`` tokens; -1 where none
    exists (a score is a probability: never negative)."""
    per = block_size // kernel_stride            # kernels that START in a block
    back = kernel_size // kernel_stride - 1      # ... and reach in from before
    r = jnp.where(valid, r, -1.0)
    lead = r.shape[:-1]
    r = jnp.pad(r.reshape((-1, r.shape[-1])),
                ((0, 0), (back, max(0, n_blocks * per - r.shape[-1]))),
                constant_values=-1.0)
    out = jax.lax.reduce_window(r, -jnp.inf, jax.lax.max, (1, per + back),
                                (1, per), "VALID")
    return out[:, :n_blocks].reshape(lead + (n_blocks,))


def select(R, n, *, block_size: int, topk: int, init_blocks: int,
           window_size: int, forced_in_topk: bool):
    """Step 4 for queries that see ``n`` (...,) positions each, over block
    scores ``R`` (..., G, NB). Returns ``visible`` (..., G, NB) bool and the
    selection margin (..., G): the score of the last block taken less that
    of the first left out, infinite where nothing is left out (where the two
    are one kernel's score, to the nearest other: see below)."""
    NB = R.shape[-1]
    b = jnp.arange(NB)
    n = n[..., None, None]
    last = (n - 1) // block_size                        # the query's own block
    first_w = jnp.maximum(n - window_size, 0) // block_size
    forced = ((b < init_blocks) | (b >= first_w)) & (b <= last)
    score = jnp.where(forced, jnp.inf, jnp.where(b <= last, R, -jnp.inf))
    take = jnp.full(score.shape[:-1], topk)
    most = 0
    if not forced_in_topk:
        most = init_blocks + -(-window_size // block_size) + 1
        take = take + jnp.sum(forced, axis=-1)          # topk BEYOND the forced
    k_max = min(NB, topk + most + 2)
    vals, idx = jax.lax.top_k(score, k_max)             # (..., G, k_max)
    chosen = (jnp.arange(k_max) < take[..., None]) & (vals > -jnp.inf)
    visible = jnp.put_along_axis(jnp.zeros(score.shape, bool), idx, chosen,
                                 axis=-1, inplace=False)
    # the last taken (a), the first left out (z) and their neighbours: +inf
    # in front of the list, -inf behind it (nothing is left out there)
    pad = jnp.full(vals.shape[:-1] + (2,), jnp.inf)
    vals = jnp.concatenate([pad[..., :1], vals, -pad], axis=-1)
    take = jnp.minimum(take, k_max)[..., None]

    def at(i):
        return jnp.take_along_axis(vals, take + i, axis=-1)[..., 0]

    before, a, z, after = at(-1), at(0), at(1), at(2)
    # two blocks that share their best kernel score EXACTLY the same, on
    # every side alike, and the lower index goes first: such a pair cannot
    # swap, so where a == z the margin is to the nearest other score
    margin = jnp.where(a > z, a - z, jnp.minimum(before - z, a - after))
    margin = jnp.where(jnp.isnan(margin) | ~jnp.isfinite(z), jnp.inf, margin)
    return visible, margin


@functools.partial(jax.jit, static_argnames=(
    "c", "eps", "cache_dtype", "dense_len", "kernel_size", "kernel_stride",
    "block_size", "topk", "init_blocks", "window_size", "forced_in_topk"))
def sparse_layer(p, x, *, c: float, eps: float, cache_dtype, dense_len: int,
                 kernel_size: int, kernel_stride: int, block_size: int,
                 topk: int, init_blocks: int, window_size: int,
                 forced_in_topk: bool):
    """One ``minicpm4`` block on x: (T, d). Returns the block's output and
    each position's selection margin (infinite up to ``dense_len``)."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        mx = p["mix"]
        h = _rms(x, p["ln1_g"], eps)
        q = _rms(jnp.einsum("td,dhe->the", h, _f32(mx["w_q"])),
                 mx["q_norm_g"], eps)
        k = _stored(_rms(jnp.einsum("td,dhe->the", h, _f32(mx["w_k"])),
                         mx["k_norm_g"], eps), cache_dtype)
        v = _stored(jnp.einsum("td,dhe->the", h, _f32(mx["w_v"])),
                    cache_dtype)
        H, hd = q.shape[1], q.shape[2]
        G = k.shape[1]
        # step 1: every pooled key of the sequence, as a cache stores it
        J = max(0, (T - kernel_size) // kernel_stride + 1)
        NB = -(-T // block_size)
        if J:
            at = (np.arange(J)[:, None] * kernel_stride
                  + np.arange(kernel_size)[None, :])
            kp = _stored(jnp.mean(k[at], axis=1), cache_dtype)   # (J, G, hd)
        key_pos = jnp.arange(T)
        ends = jnp.arange(J) * kernel_stride + kernel_size       # kp_j needs n >= this
        qb = min(Q_BLOCK, T)

        def attend(args):
            q_blk, start = args                                  # (qb, H, hd)
            q_pos = start + jnp.arange(qb)
            n = q_pos + 1
            see = key_pos[None, :] <= q_pos[:, None]             # (qb, T)
            see = jnp.broadcast_to(see[:, None, :], (qb, G, T))
            margin = jnp.full((qb,), jnp.inf)
            if J and T > dense_len:
                s = jnp.einsum("qgke,jge->qgkj",
                               q_blk.reshape(qb, G, H // G, hd), kp) \
                    / np.sqrt(hd)
                valid = ends[None, :] <= n[:, None]              # (qb, J)
                s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
                prob = jnp.where(valid[:, None, None, :],
                                 jax.nn.softmax(s, axis=-1), 0.0)
                r = jnp.sum(prob, axis=2)                        # (qb, G, J)
                R = block_scores(r, valid[:, None, :],
                                 kernel_size=kernel_size,
                                 kernel_stride=kernel_stride,
                                 block_size=block_size, n_blocks=NB)
                visible, m = select(R, n, block_size=block_size, topk=topk,
                                    init_blocks=init_blocks,
                                    window_size=window_size,
                                    forced_in_topk=forced_in_topk)
                sparse = n > dense_len
                by_pos = jnp.repeat(visible, block_size, axis=-1)[..., :T]
                see = see & (by_pos | ~sparse[:, None, None])
                margin = jnp.where(sparse, jnp.min(m, axis=-1), jnp.inf)
            qg = q_blk.reshape(qb, G, H // G, hd)
            sc = jnp.einsum("qgke,tge->gkqt", qg, k) / np.sqrt(hd)
            sc = jnp.where(jnp.moveaxis(see, 1, 0)[:, None], sc, -jnp.inf)
            out = jnp.einsum("gkqt,tge->qgke", jax.nn.softmax(sc, axis=-1), v)
            return out.reshape(qb, H, hd), margin

        pad = (-T) % qb
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        n_blk = (T + pad) // qb
        a, margin = jax.lax.map(attend, (qp.reshape(n_blk, qb, H, hd),
                                         jnp.arange(n_blk) * qb))
        a = a.reshape(T + pad, H * hd)[:T]
        y = (a * jax.nn.sigmoid(h @ _f32(mx["w_gate"]))) @ _f32(mx["w_o"])
        return _mlp(p, x + c * y, c, eps), margin.reshape(-1)[:T]


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(emb, ids, *, scale: float):
    return _f32(emb["w"][ids]) * scale


@functools.partial(jax.jit, static_argnames=("eps", "divide"))
def _head(ln, head, h, *, eps: float, divide: float):
    with jax.default_matmul_precision("highest"):
        return (_rms(h, ln["gamma"], eps) / divide) @ _f32(head["w"])


def _layers(params):
    keys = sorted(params, key=lambda k: int(k.split("_")[1]))
    return keys[0], keys[1:-2], keys[-2], keys[-1]


def sizes(cfg: dict) -> dict:
    """The sparse layers' sizes and the stream's constants, from the file."""
    sp = cfg["sparse_config"]
    depth = int(cfg.get("published", {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"]))
    return {
        "sparse": dict(
            dense_len=int(sp["dense_len"]), kernel_size=int(sp["kernel_size"]),
            kernel_stride=int(sp["kernel_stride"]),
            block_size=int(sp["block_size"]), topk=int(sp["topk"]),
            init_blocks=int(sp["init_blocks"]),
            window_size=int(sp["window_size"]),
            forced_in_topk=bool(cfg.get("forced_in_topk", True))),
        "depth": depth, "first": int(cfg.get("first_layer", 0)),
        "c": float(cfg["scale_depth"]) / float(np.sqrt(depth)),
        "eps": float(cfg["rms_norm_eps"]),
        "divide": float(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
    }


def hidden(params, ids, cfg: dict):
    """Final hidden states (T, d) of one sequence of token ids (T,)."""
    return hidden_and_margin(params, ids, cfg)[0]


def hidden_and_margin(params, ids, cfg: dict):
    """Final hidden states (T, d) of one sequence of token ids (T,), and
    each position's smallest selection margin over the sparse layers (T,)."""
    emb, blocks, _, _ = _layers(params)
    if len(blocks) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"{len(blocks)} blocks in the tree, "
                         f"num_hidden_layers={cfg['num_hidden_layers']}")
    z = sizes(cfg)
    x = _embed(params[emb], jnp.asarray(ids, jnp.int32),
               scale=float(cfg["scale_emb"]))
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    margin = jnp.full(x.shape[:1], jnp.inf)
    for i, key in enumerate(blocks):
        layer = z["first"] + i
        mixer = cfg["mixer_types"][layer]
        p = params[key]
        if ("o_norm_g" in p["mix"]) != (mixer == "lightning-attn"):
            raise ValueError(f"{key}: mixer_types[{layer}] says {mixer}")
        if mixer == "lightning-attn":
            heads = p["mix"]["w_q"].shape[1]
            if heads != int(cfg["lightning_nh"]):
                raise ValueError(f"{key}: {heads} heads, lightning_nh="
                                 f"{cfg['lightning_nh']}")
            x = lightning_layer(
                p, x, pos, jnp.asarray(decay_rates(heads, layer, z["depth"])),
                c=z["c"], eps=z["eps"], theta=float(cfg["rope_theta"]))
        elif mixer == "minicpm4":
            x, m = sparse_layer(p, x, c=z["c"], eps=z["eps"],
                                cache_dtype=cfg.get("cache_dtype"),
                                **z["sparse"])
            margin = jnp.minimum(margin, m)
        else:
            raise ValueError(f"mixer_types[{layer}] = {mixer!r}")
    return x, margin


def logits(params, h, cfg: dict):
    """Logits (t, vocab) of hidden states (t, d)."""
    _, _, ln, head = _layers(params)
    z = sizes(cfg)
    return _head(params[ln], params[head], h, eps=z["eps"],
                 divide=z["divide"])


@jax.jit
def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, ids, targets, cfg: dict) -> float:
    """Mean next-token cross-entropy of one sequence."""
    return float(_nll(logits(params, hidden(params, ids, cfg), cfg),
                      jnp.asarray(targets, jnp.int32)))
