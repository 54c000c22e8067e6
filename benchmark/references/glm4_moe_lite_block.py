"""Plain reference of the GLM-4.7-Flash decoder (``zai-org/GLM-4.7-Flash``,
``model_type`` ``glm4_moe_lite``): token embedding, ``num_hidden_layers``
blocks of which the first ``first_k_dense_replace`` are dense, final RMSNorm,
untied bias-free head. One block, for hidden ``x`` of width d, H heads,
``rms(v) = v / sqrt(mean(v^2) + eps) * g``::

    h       = rms(x)
    c_q     = rms(h W_qa)                       q = c_q W_qb -> (H, nope + rope) = (q_nope, q_rope)
    [c | r] = h W_kva          (kv_lora_rank + rope)   c = rms(c) ;  k_rope = rope(r)   one rope key for all heads
    [k_nope | v] = c W_kvb -> (H, nope + v_head_dim)
    q_rope  = rope(q_rope)                      theta ``rope_theta``, all rope dims, pairs (i, i + rope/2)
    x       = x + softmax((q_nope k_nope^T + q_rope k_rope^T) / sqrt(nope + rope), causal) v  W_o
    h       = rms(x)
    dense:    x = x + W_d (silu(W_g h) * (W_u h))
    experts:  p = sigmoid(h W_r) ;  S = the k largest of p + b   (``e_score_correction_bias`` selects only;
              ``n_group`` 1, ``topk_group`` 1: one group, no group stage)
              w_e = p_e / (sum over S of p + 1e-20) * ``routed_scaling_factor``   (``norm_topk_prob`` true)
              x = x + sum over e in S of w_e * expert_e(h) + shared(h)          every expert a SwiGLU

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the EXPANDED attention (the
latent ``c`` is multiplied out to per-head keys and values; the program
decodes by the absorbed form and caches ``[c | k_rope]``, so the two are
different algebra), a loop over the experts with a mask (no sort, no kernel,
no capacity, nothing dropped), no cache, no batching, and nothing imported
from the program. It reads the program's parameter tree by its leaf names
only (``layer_0/w`` embedding; blocks ``layer_1 ..`` with ``ln1_g``,
``ln2_g``, ``attn/{w_qa, q_g, w_qb, w_kva, kv_g, w_kvb, w_o}`` and either
``mlp/{w_gate, w_up, w_down}`` or ``moe/{w_router, e_score_correction_bias,
w_gate, w_up, w_down, shared/{w_gate, w_up, w_down}}``; then ``gamma``; then
the head's ``w``) and casts every leaf to float32 where it is used, one
expert at a time.

**What is stored is given; what is computed is compared.** The reference is
fed the program's weights as they are held (bf16 values, cast to float32 where
used). Where the configuration states that the latent cache is held narrower
than float32 (``cache_dtype`` in its file), the reference rounds the two
quantities a deployment stores there, the normed latent ``c`` and the rotated
rope key, to that width once, where they are produced, and computes everything
from them in float32. Without the key nothing is rounded. The reason is the
router (PERF.md section 6, PR 31): the chosen gates are renormalised to sum to
``routed_scaling_factor``, so a tie at the k-th place swaps a whole expert at
weight ~0.45, and under seeded N(0, 0.02) weights the first layer's attention
output is as large as the embedding it is added to; the bf16 rounding of the
cached latent alone then moves the stream by 2e-3 and flips the routing of
one position in twenty, which no tolerance on logits absorbs and no arithmetic
in the program can undo. Rounding it on both sides leaves the check everything
else: the absorbed algebra against the expanded, the paging, the routing, the
precision of every product.

**Positions whose routing is a tie are not judged.** With the gates
renormalised, the model is a step function of its router's scores: where the
k-th and the (k+1)-th selection score of some expert layer lie closer than
two float32 computations of them can agree, which expert runs is undefined to
rounding, and the two candidates' logits differ by a tenth of their spread
and more. ``hidden_and_margin`` returns, beside the hidden states, each
position's smallest such margin over the expert layers, and ``greedy_gaps``
leaves out the positions where it is under ``ROUTING_TIE``; so does the
tool ``tools/check_paged_logits_latent.py``. The rule reads the REFERENCE's
own scores and nothing of the program's. ``ROUTING_TIE`` is set between two
readings (PERF.md section 6, PR 31): the largest margin at which the served
program's choice left the reference's over its seeds, and the margins at
which a computation with bf16 activations leaves it, which must lie above.

Departures from the publication, which the program under test shares (so the
reference follows them): the multi-token-prediction layer
(``num_nextn_predict_layers``) is not built: the published inference path
does not run it. The rope pairs dimension i with i + rope/2 where the
published code interleaves (a fixed permutation of W_qb's and W_kva's rope
columns: immaterial under seeded weights). Outside the forward pass: the
routing bias is a parameter that nothing here updates (the publication
nudges it by expert load while training), and the weights are seeded
N(0, 0.02) (``initializer_range``, assumed), not the released checkpoint.

Attention is computed for blocks of ``Q_BLOCK`` query positions against the
whole context, and each block is one jitted call reused by all layers of its
kind.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
# Since PR 44 the rule "a tie is not judged" is the harness's
# (``harness/agreement.py``) and the margin the configuration file's
# (``agreement.tie_margin``, the same number: benchmark/tests holds the two
# equal). This constant and ``greedy_gaps`` below stay for tier-1's tests of
# this file (``tests/``); no run of the benchmark calls them.
# in units of the selection score (a probability plus the bias); see the
# note on ties above and PERF.md section 6 (PR 31) for the two readings
ROUTING_TIE = 2e-4


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def _rope(x, pos, theta):
    """x: (T, ..., r) at positions ``pos`` (T,); pairs (i, i + r/2). The
    positions are an argument of the jitted block, not a constant inside it:
    a compiler folds ``cos(constant)`` on the host, whose cosine of an angle
    of thousands of radians is not the device's to the last bits, and the
    rope key is about to be rounded to the cache's width."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
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


def _swiglu(h, w_g, w_u, w_d):
    return (jax.nn.silu(h @ _f32(w_g)) * (h @ _f32(w_u))) @ _f32(w_d)


@functools.partial(jax.jit, static_argnames=("nope", "rank", "top_k", "scale",
                                             "eps", "theta", "cache_dtype"))
def block(p, x, pos, *, nope: int, rank: int, top_k: int, scale: float,
          eps: float, theta: float, cache_dtype=None):
    """One block on x: (T, d) float32 at positions ``pos`` (T,) int32; dense
    where ``p`` has ``mlp``.
    Returns the block's output and each position's routing margin: the k-th
    selection score minus the (k+1)-th (infinite for a dense block)."""
    with jax.default_matmul_precision("highest"):
        T, d = x.shape
        at = p["attn"]
        h = _rms(x, p["ln1_g"], eps)
        c_q = _rms(h @ _f32(at["w_qa"]), at["q_g"], eps)
        q = jnp.einsum("tr,rhe->the", c_q, _f32(at["w_qb"]))
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, theta)
        kv = h @ _f32(at["w_kva"])
        c = _stored(_rms(kv[:, :rank], at["kv_g"], eps), cache_dtype)
        k_rope = _stored(_rope(kv[:, rank:], pos, theta), cache_dtype)
        kvb = jnp.einsum("tc,che->the", c, _f32(at["w_kvb"]))
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        n_head, rope = q.shape[1], q_rope.shape[-1]
        qb = min(Q_BLOCK, T)
        pad = (-T) % qb
        qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0)))
        key_pos = jnp.arange(T)

        def attend(args):
            qn_blk, qr_blk, start = args
            s = (jnp.einsum("qhe,khe->hqk", qn_blk, k_nope)
                 + jnp.einsum("qhe,ke->hqk", qr_blk, k_rope)) \
                / np.sqrt(nope + rope)
            q_pos = start + jnp.arange(qb)
            s = jnp.where(key_pos[None, None, :] <= q_pos[None, :, None],
                          s, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(s, axis=-1), v)

        n_blk = (T + pad) // qb
        a = jax.lax.map(attend, (qn.reshape(n_blk, qb, n_head, nope),
                                 qr.reshape(n_blk, qb, n_head, rope),
                                 jnp.arange(n_blk) * qb))
        x = x + a.reshape(T + pad, -1)[:T] @ _f32(at["w_o"])

        h = _rms(x, p["ln2_g"], eps)
        if "mlp" in p:
            m = p["mlp"]
            return (x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"]),
                    jnp.full((T,), jnp.inf))
        moe = p["moe"]
        prob = jax.nn.sigmoid(h @ _f32(moe["w_router"]))            # (T, E)
        # one more than chosen: the runner-up's score gives the margin
        best, chosen = jax.lax.top_k(
            prob + _f32(moe["e_score_correction_bias"]), top_k + 1)
        margin, chosen = best[:, top_k - 1] - best[:, top_k], chosen[:, :top_k]
        mask = jnp.zeros_like(prob).at[jnp.arange(T)[:, None], chosen].set(1.0)
        picked = prob * mask
        weight = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20) \
            * scale

        def expert(acc, e):
            w_g, w_u, w_d, w_e = e
            return acc + w_e[:, None] * _swiglu(h, w_g, w_u, w_d), None

        m, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (moe["w_gate"], moe["w_up"], moe["w_down"],
                             weight.T))
        sh = moe["shared"]
        return (x + m + _swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"]),
                margin)


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
    each position's smallest routing margin over the expert layers (T,)."""
    emb, blocks, _, _ = _layers(params)
    dense = int(cfg["first_k_dense_replace"])
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("this reference renormalises the chosen gates "
                         "(norm_topk_prob true), as the publication does")
    x = _embed(params[emb], jnp.asarray(ids, jnp.int32))
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    margin = jnp.full(x.shape[:1], jnp.inf)
    for i, k in enumerate(blocks):
        if ("mlp" in params[k]) != (i < dense):
            raise ValueError(f"{k}: first_k_dense_replace={dense} says "
                             f"{'dense' if i < dense else 'experts'}")
        x, m = block(params[k], x, pos, nope=int(cfg["qk_nope_head_dim"]),
                     rank=int(cfg["kv_lora_rank"]),
                     top_k=int(cfg["num_experts_per_tok"]),
                     scale=float(cfg["routed_scaling_factor"]),
                     eps=float(cfg["rms_norm_eps"]),
                     theta=float(cfg["rope_theta"]),
                     cache_dtype=cfg.get("cache_dtype"))
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
    standard deviation there; positions whose routing is a tie
    (``ROUTING_TIE``) left out. The sequence is right-padded to ``pad_to`` so
    that every request of a cell shares one compiled program (causal: the
    padding cannot reach back; routing is per token)."""
    seq = list(prompt) + list(generated)
    pad_to = max(pad_to, len(seq), last)
    ids = np.zeros(pad_to, np.int32)
    ids[:len(seq)] = seq
    h, margin = hidden_and_margin(params, ids, cfg)
    lo = max(0, len(seq) - 1 - last)          # row j is position lo + j
    rows = jax.lax.dynamic_slice_in_dim(h, lo, last, axis=0)
    nxt = np.zeros(last, np.int32)            # position t predicts token t+1
    upto = min(last, len(seq) - 1 - lo)
    nxt[:upto] = seq[lo + 1:lo + 1 + upto]
    gap, spread = _gaps(logits(params, rows, cfg), jnp.asarray(nxt))
    keep = slice(max(0, len(prompt) - 1 - lo), upto)
    judged = np.asarray(margin)[lo:lo + last][keep] >= ROUTING_TIE
    return np.asarray(gap)[keep][judged], np.asarray(spread)[keep][judged]
