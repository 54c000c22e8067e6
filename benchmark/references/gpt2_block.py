"""Plain reference of the GPT-2 family block, as both configurations of this
benchmark use it: token embedding + learned positions, ``n_layer`` pre-LN
blocks (LayerNorm, biased QKV, causal softmax attention with ``n_head`` query
heads over ``kv_heads`` key/value heads, biased output projection, residual;
LayerNorm, biased 4x MLP with tanh-GELU, residual), final LayerNorm, untied
biased head. Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no batching,
and nothing imported from the program. It reads the program's parameter tree
by its leaf names only (``layer_0/w`` embedding, ``layer_1/pos``, blocks
``layer_2 ..``, then ``gamma``/``beta``, then the head's ``w``/``b``).

Departures from the published models, which the program under test shares
(so the reference follows them): LayerNorm eps 1e-6 (published 1e-5), an
untied output head, tanh-GELU also for Cerebras-GPT (published erf-GELU).

Attention is computed for blocks of ``Q_BLOCK`` query positions against the
whole context, so an 8k context needs 16 x 512 x 8192 scores at a time, and
each block is one jitted call reused by all layers: one small compile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
LN_EPS = 1e-6


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "kv_heads"))
def block(p, x, *, n_head: int, kv_heads: int):
    """One block on x: (T, d) float32."""
    with jax.default_matmul_precision("highest"):
        T, d = x.shape
        hd = d // n_head
        h = _ln(x, p["ln1_g"], p["ln1_b"])
        qkv = h @ p["attn"]["w_qkv"] + p["attn"]["b_qkv"]
        q, k, v = jnp.split(qkv, [d, d + kv_heads * hd], axis=-1)
        q = q.reshape(T, n_head, hd)
        k = jnp.repeat(k.reshape(T, kv_heads, hd), n_head // kv_heads, axis=1)
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
        a = a.reshape(T + pad, d)[:T]
        x = x + a @ p["attn"]["w_o"] + p["attn"]["b_o"]
        h = _ln(x, p["ln2_g"], p["ln2_b"])
        m = _gelu_tanh(h @ p["w_up"] + p["b_up"]) @ p["w_down"] + p["b_down"]
        return x + m


@jax.jit
def _embed(emb, pos, ids):
    return emb["w"][ids].astype(jnp.float32) + pos["pos"][:ids.shape[0]]


@jax.jit
def _head(ln, head, h):
    with jax.default_matmul_precision("highest"):
        return _ln(h, ln["gamma"], ln["beta"]) @ head["w"] + head["b"]


def _layers(params):
    keys = sorted(params, key=lambda k: int(k.split("_")[1]))
    return keys[0], keys[1], keys[2:-2], keys[-2], keys[-1]


def hidden(params, ids, cfg: dict):
    """Final hidden states (T, d) of one sequence of token ids (T,)."""
    emb, pos, blocks, _, _ = _layers(params)
    kv = 1 if cfg.get("multi_query") else int(cfg.get("n_kv_head", cfg["n_head"]))
    x = _embed(params[emb], params[pos], jnp.asarray(ids, jnp.int32))
    for k in blocks:
        x = block(params[k], x, n_head=int(cfg["n_head"]), kv_heads=kv)
    return x


def hidden_and_margin(params, ids, cfg: dict):
    """``hidden`` in the shape the harness asks every reference for
    (``harness/agreement.py``): the hidden states, and each position's
    routing margin, which a model without a router has none of (infinite:
    no position is ever a tie)."""
    h = hidden(params, ids, cfg)
    return h, jnp.full(h.shape[:1], jnp.inf)


def logits(params, h, cfg: dict = None):
    """Logits (t, vocab) of hidden states (t, d); ``cfg`` is not needed and
    is taken so that every reference answers the same call."""
    _, _, _, ln, head = _layers(params)
    return _head(params[ln], params[head], h)


@jax.jit
def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss(params, ids, targets, cfg: dict) -> float:
    """Mean next-token cross-entropy of one sequence."""
    return float(_nll(logits(params, hidden(params, ids, cfg)),
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
    padding cannot reach back)."""
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
    gap, spread = _gaps(logits(params, rows), jnp.asarray(nxt))
    keep = slice(max(0, len(prompt) - 1 - lo), upto)
    return np.asarray(gap)[keep], np.asarray(spread)[keep]
