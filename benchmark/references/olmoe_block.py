"""Plain reference of the OLMoE decoder (``allenai/OLMoE-1B-7B``): token
embedding, ``num_hidden_layers`` blocks, final RMSNorm, untied bias-free head.
One block, for hidden ``x`` of width d, H heads of hd, E experts of width f,
k experts a token::

    h  = rms(x) * g1                            rms(v) = v / sqrt(mean(v^2) + eps)
    q  = rms(h Wq) * gq ;  k_ = rms(h Wk) * gk ;  v = h Wv   (norms over ALL columns, before the heads)
    q, k_ = rope(q), rope(k_)                   theta ``rope_theta``, pairs (i, i + hd/2)
    x  = x + softmax(q k_^T / sqrt(hd), causal) v Wo
    h  = rms(x) * g2
    p  = softmax(h Wr) over all E ;  S = the k largest p ;  no renormalisation (``norm_topk_prob`` false)
    x  = x + sum over e in S of  p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a loop over the experts with a
mask (no sort, no kernel, no capacity, nothing dropped), no cache, no
batching, and nothing imported from the program. It reads the program's
parameter tree by its leaf names only (``layer_0/w`` embedding, blocks
``layer_1 ..`` with ``ln1_g``, ``ln2_g``, ``attn/{w_qkv, q_g, k_g, w_o}``,
``moe/{w_router, w_gate, w_up, w_down}``, then ``gamma``, then the head's
``w``) and casts every leaf to float32 where it is used, one expert at a time.

Departures from the publication, which the program under test shares (so the
reference follows them): none in the forward pass. Outside it: the
load-balancing and router-z auxiliary losses are absent (``loss`` is the
plain next-token cross-entropy), and the weights are seeded N(0, 0.02)
(``initializer_range``), not the released checkpoint.

Attention is computed for blocks of ``Q_BLOCK`` query positions against the
whole context, and each block is one jitted call reused by all layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, theta):
    """x: (T, heads, hd) at positions 0..T-1; pairs (i, i + hd/2)."""
    T, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_head", "kv_heads", "top_k",
                                             "eps", "theta"))
def block(p, x, *, n_head: int, kv_heads: int, top_k: int, eps: float,
          theta: float):
    """One block on x: (T, d) float32. Returns the block's output and each
    position's routing margin: the k-th probability minus the (k+1)-th."""
    with jax.default_matmul_precision("highest"):
        T, d = x.shape
        hd = d // n_head
        at, moe = p["attn"], p["moe"]
        h = _rms(x, p["ln1_g"], eps)
        qkv = h @ _f32(at["w_qkv"])
        q, k, v = jnp.split(qkv, [d, d + kv_heads * hd], axis=-1)
        q = _rope(_rms(q, at["q_g"], eps).reshape(T, n_head, hd), theta)
        k = _rope(_rms(k, at["k_g"], eps).reshape(T, kv_heads, hd), theta)
        k = jnp.repeat(k, n_head // kv_heads, axis=1)
        v = jnp.repeat(v.reshape(T, kv_heads, hd), n_head // kv_heads, axis=1)
        qb = min(Q_BLOCK, T)
        pad = (-T) % qb
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        key_pos = jnp.arange(T)

        def attend(args):
            q_blk, start = args                       # (qb, H, hd)
            s = jnp.einsum("qhd,khd->hqk", q_blk, k) / np.sqrt(hd)
            q_pos = start + jnp.arange(qb)
            s = jnp.where(key_pos[None, None, :] <= q_pos[None, :, None],
                          s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        n_blk = (T + pad) // qb
        a = jax.lax.map(attend, (qp.reshape(n_blk, qb, n_head, hd),
                                 jnp.arange(n_blk) * qb))
        x = x + a.reshape(T + pad, d)[:T] @ _f32(at["w_o"])

        h = _rms(x, p["ln2_g"], eps)
        prob = jax.nn.softmax(h @ _f32(moe["w_router"]), axis=-1)   # (T, E)
        # one more than chosen: the runner-up's score gives the margin
        # (none where every expert is chosen)
        best, chosen = jax.lax.top_k(prob, min(top_k + 1, prob.shape[-1]))
        margin = best[:, top_k - 1] - best[:, top_k] \
            if top_k < prob.shape[-1] else jnp.full((T,), jnp.inf)
        chosen = chosen[:, :top_k]
        mask = jnp.zeros_like(prob).at[jnp.arange(T)[:, None], chosen].set(1.0)
        weight = prob * mask            # p_e for the k largest, 0 elsewhere

        def expert(acc, e):
            w_g, w_u, w_d, w_e = e
            out = (jax.nn.silu(h @ _f32(w_g)) * (h @ _f32(w_u))) @ _f32(w_d)
            return acc + w_e[:, None] * out, None

        m, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (moe["w_gate"], moe["w_up"], moe["w_down"],
                             weight.T))
        return x + m, margin


@jax.jit
def _embed(emb, ids):
    return _f32(emb["w"][ids])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(ln, head, h, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms(h, ln["gamma"], eps) @ _f32(head["w"])


def _layers(params):
    keys = sorted(params, key=lambda k: int(k.split("_")[1]))
    return keys[0], keys[1:-2], keys[-2], keys[-1]


def hidden(params, ids, cfg: dict):
    """Final hidden states (T, d) of one sequence of token ids (T,)."""
    return hidden_and_margin(params, ids, cfg)[0]


def hidden_and_margin(params, ids, cfg: dict):
    """Final hidden states (T, d) of one sequence of token ids (T,), and
    each position's smallest routing margin over the layers (T,): where it is
    smaller than two computations of the scores can agree, which expert runs
    is undefined to rounding, and the harness does not judge the position
    (``harness/agreement.py``; the margin that counts as a tie is the
    configuration file's, set from readings)."""
    emb, blocks, _, _ = _layers(params)
    heads = int(cfg["num_attention_heads"])
    x = _embed(params[emb], jnp.asarray(ids, jnp.int32))
    margin = jnp.full(x.shape[:1], jnp.inf)
    for k in blocks:
        x, m = block(params[k], x, n_head=heads,
                     kv_heads=int(cfg.get("num_key_value_heads", heads)),
                     top_k=int(cfg["num_experts_per_tok"]),
                     eps=float(cfg["rms_norm_eps"]),
                     theta=float(cfg["rope_theta"]))
        margin = jnp.minimum(margin, m)
    return x, margin


def logits(params, h, cfg: dict):
    """Logits (t, vocab) of hidden states (t, d)."""
    _, _, ln, head = _layers(params)
    return _head(params[ln], params[head], h, eps=float(cfg["rms_norm_eps"]))


@jax.jit
def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, ids, targets, cfg: dict) -> float:
    """Mean next-token cross-entropy of one sequence."""
    return float(_nll(logits(params, hidden(params, ids, cfg), cfg),
                      jnp.asarray(targets, jnp.int32)))


@jax.jit
def _gaps(lg, nxt):
    """How far each next token's logit sits below the maximum, and the
    spread of the logits at that position."""
    got = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got, jnp.std(lg, axis=-1)


def greedy_gaps(params, prompt, generated, cfg: dict, pad_to: int,
                last: int = 256):
    """For the last ``last`` generated tokens of one served request: the gap
    between the reference's largest logit and its logit for the served token
    (0 where the served token is the reference's argmax), and the logits'
    standard deviation there. The sequence is right-padded to ``pad_to`` so
    that every request of a cell shares one compiled program (causal: the
    padding cannot reach back; routing is per token)."""
    seq = list(prompt) + list(generated)
    pad_to = max(pad_to, len(seq), last)
    ids = np.zeros(pad_to, np.int32)
    ids[:len(seq)] = seq
    h = hidden(params, ids, cfg)
    lo = max(0, len(seq) - 1 - last)          # row j is position lo + j
    rows = jax.lax.dynamic_slice_in_dim(h, lo, last, axis=0)
    nxt = np.zeros(last, np.int32)            # position t predicts token t+1
    upto = min(last, len(seq) - 1 - lo)
    nxt[:upto] = seq[lo + 1:lo + 1 + upto]
    gap, spread = _gaps(logits(params, rows, cfg), jnp.asarray(nxt))
    keep = slice(max(0, len(prompt) - 1 - lo), upto)
    return np.asarray(gap)[keep], np.asarray(spread)[keep]
