"""Plain reference of the Laguna decoder (``poolside/Laguna-S-2.1``,
``model_type`` ``laguna``): token embedding, ``num_hidden_layers`` blocks,
final RMSNorm, untied bias-free head. Layer ``l`` is of the kind
``layer_types[l]`` (``full_attention`` or ``sliding_attention``), has
``num_attention_heads_per_layer[l]`` query heads (48 full, 72 sliding) over
``num_key_value_heads`` key/value heads of ``head_dim``, and is dense where
``mlp_layer_types[l]`` says so (layer 0). One block, for hidden ``x`` of width
d, ``rms(v) = v / sqrt(mean(v^2) + eps) * g``::

    h   = rms(x)
    q   = h Wq -> (H_l, hd) ;  k = h Wk -> (Hkv, hd) ;  v = h Wv -> (Hkv, hd)       no bias
    q,k = rope(q), rope(k)     per ``rope_parameters[kind]``:
            sliding_attention: plain, ``rope_theta`` 1e4, the whole head (``partial_rotary_factor`` 1)
            full_attention:    YaRN on the first ``partial_rotary_factor * hd`` dimensions: each frequency
                               theta^(-2i/dim) blended with itself / ``factor`` by the linear ramp over the
                               correction range of (``beta_fast``, ``beta_slow``) within
                               ``original_max_position_embeddings``; cos and sin times ``attention_factor``;
                               the other dimensions unrotated. Pairs (i, i + dim/2)
    a   = softmax(q k^T / sqrt(hd) + mask) v     causal; sliding: key j seen iff 0 <= i - j < ``sliding_window``
    a_h = a_h * squash(h Wgate)_h                ``gating`` per-head: ONE gate a head a token
    x   = x + concat_h(a_h) Wo
    h   = rms(x)
    dense:    x = x + (silu(h Wg) * (h Wu)) Wd
    experts:  s = score(h Wr) over all the router's columns ;  S = the ``num_experts_per_tok`` largest s
              w_e = s_e / sum over S of s * ``moe_routed_scaling_factor``        (``norm_topk_prob`` true)
              x = x + sum over e in S, e HELD, of w_e * expert_e(h) + shared(h)   every expert a SwiGLU

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: full (T, T) masks, a loop over
the experts with a mask (no sort, no kernel, no capacity, nothing dropped),
keys and values repeated to the query heads, no cache, no ring, no batching,
and nothing imported from the program. It reads the program's parameter tree
by its leaf names only (``layer_0/w`` embedding; blocks ``layer_1 ..`` with
``ln1_g``, ``ln2_g``, ``attn/{w_q (d, H, hd), w_k, w_v (d, Hkv, hd),
w_head_gate (d, H), w_o (H * hd, d)}`` and either ``mlp/{w_gate, w_up,
w_down}`` or ``moe/{w_router, w_gate, w_up, w_down, shared/{w_gate, w_up,
w_down}}``; then ``gamma``; then the head's ``w``) and casts every leaf to
float32 where it is used, one expert at a time.

**The same share of the experts.** The configuration is one chip's share of
an expert-parallel deployment (``experts_held`` ``[first, count]`` in its
file): the router has all its columns, the choice and the renormalisation run
over all of them, and only the held experts' products are added; the tree's
expert arrays have ``count`` rows, expert ``first + i`` in row ``i``. Without
the key every expert is held.

**Two choices the published config leaves unsaid** and the file states
(``router_score``: ``softmax`` or ``sigmoid``; ``gate_act``: ``sigmoid`` or
``softplus``), read here from the same keys. No q/k norm, no gate on the shared
expert: the config names neither.

**What is stored is given; what is computed is compared**
(``glm4_moe_lite_block.py`` has the argument in full). The reference is fed
the program's weights as they are held. Where the configuration states that
the cache is held narrower than float32 (``cache_dtype``), the reference
rounds the two quantities a deployment stores there, the rotated keys and the
values, to that width once, where they are produced, and computes everything
from them in float32.

**Positions whose routing is a tie are not judged.** The chosen gates are
renormalised to sum to 2.5, so where the k-th and the (k+1)-th score of some
expert layer lie closer than two float32 computations of them can agree, which
expert runs is undefined to rounding. ``hidden_and_margin`` returns each
position's smallest such margin over the expert layers, and ``greedy_gaps``
leaves out the positions where it is under ``ROUTING_TIE`` (set between two
readings, PERF.md section 6, PR 33). The rule reads the REFERENCE's own scores
and nothing of the program's.

Departures from the publication, which the program under test shares: seeded
N(0, 0.02) weights, not a checkpoint; no auxiliary losses; the stream in
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256
# Since PR 44 the rule "a tie is not judged" is the harness's
# (``harness/agreement.py``) and the margin the configuration file's
# (``agreement.tie_margin``, the same number: benchmark/tests holds the two
# equal). This constant and ``greedy_gaps`` below stay for tier-1's tests of
# this file (``tests/``); no run of the benchmark calls them.
# in units of the router's score (a softmax probability over 256 experts is
# of order 0.004-0.03; the 10th-against-11th margin has a median of 6.6e-5
# over 8 expert layers). The two readings (PERF.md section 6, PR 33; v5e,
# ``tools/check_paged_logits_groups.py``): the served program left the
# reference's choice at margins up to 1.7e-5 (21 swaps in 5632 positions,
# halving in number with every doubling of the margin: a stored key or value
# that rounds to the other bf16 neighbour on one side moves a score by more
# than float32 would); the reference given an 8-bit cache leaves it at
# margins up to 9.6e-4
ROUTING_TIE = 5e-5

SCORES = {"softmax": lambda z: jax.nn.softmax(z, axis=-1),
          "sigmoid": jax.nn.sigmoid}
SQUASH = {"sigmoid": jax.nn.sigmoid, "softplus": jax.nn.softplus}


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * _f32(g)


def rope_frequencies(rope: dict, head_dim: int):
    """(inverse frequencies of the rotated pairs, the factor on cos and sin)
    of one entry of ``rope_parameters``."""
    dim = int(round(head_dim * float(rope.get("partial_rotary_factor", 1))))
    base = float(rope["rope_theta"])
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return freq.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, orig = float(rope["factor"]), float(
        rope["original_max_position_embeddings"])

    def turns_at(rotations):   # the dimension that turns this often in orig
        return dim * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(base))

    low = max(np.floor(turns_at(float(rope["beta_fast"]))), 0)
    high = min(np.ceil(turns_at(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = freq / factor * ramp + freq * (1 - ramp)
    return inv.astype(np.float32), float(rope["attention_factor"])


def _rope(x, pos, inv, scale):
    """x: (T, H, hd) at positions ``pos`` (T,): the first ``2 * len(inv)``
    dimensions rotated in pairs (i, i + len(inv)), the rest passed. The
    positions are an argument of the jitted block, not a constant inside it
    (``glm4_moe_lite_block.py``: a folded cosine is the host's)."""
    half = inv.shape[0]
    ang = pos.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., 2 * half:]], axis=-1)


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


@functools.partial(jax.jit, static_argnames=(
    "window", "rope_scale", "top_k", "scale", "held_first", "eps",
    "router_score", "gate_act", "cache_dtype"))
def block(p, x, pos, inv, *, window, rope_scale: float, top_k: int,
          scale: float, held_first: int, eps: float, router_score: str,
          gate_act: str, cache_dtype=None):
    """One block on x: (T, d) float32 at positions ``pos`` (T,) int32, with
    the rope's inverse frequencies ``inv``; ``window`` None for a
    full-attention layer; dense where ``p`` has ``mlp``. Returns the block's
    output and each position's routing margin: the k-th score minus the
    (k+1)-th (infinite for a dense block)."""
    with jax.default_matmul_precision("highest"):
        T, d = x.shape
        at = p["attn"]
        h = _rms(x, p["ln1_g"], eps)
        q = _rope(jnp.einsum("td,dhe->the", h, _f32(at["w_q"])), pos, inv,
                  rope_scale)
        k = _stored(_rope(jnp.einsum("td,dhe->the", h, _f32(at["w_k"])), pos,
                          inv, rope_scale), cache_dtype)
        v = _stored(jnp.einsum("td,dhe->the", h, _f32(at["w_v"])), cache_dtype)
        n_head, hd = q.shape[1], q.shape[2]
        group = n_head // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        qb = min(Q_BLOCK, T)
        pad = (-T) % qb
        qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        key_pos = jnp.arange(T)

        def attend(args):
            q_blk, start = args
            s = jnp.einsum("qhe,khe->hqk", q_blk, k) / np.sqrt(hd)
            q_pos = start + jnp.arange(qb)
            see = key_pos[None, :] <= q_pos[:, None]
            if window is not None:
                see = see & (q_pos[:, None] - key_pos[None, :] < window)
            s = jnp.where(see[None], s, -jnp.inf)
            return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, axis=-1), v)

        n_blk = (T + pad) // qb
        a = jax.lax.map(attend, (qp.reshape(n_blk, qb, n_head, hd),
                                 jnp.arange(n_blk) * qb))
        a = a.reshape(T + pad, n_head, hd)[:T]
        gate = SQUASH[gate_act](h @ _f32(at["w_head_gate"]))        # (T, H)
        a = a * gate[:, :, None]
        x = x + a.reshape(T, -1) @ _f32(at["w_o"])

        h = _rms(x, p["ln2_g"], eps)
        if "mlp" in p:
            m = p["mlp"]
            return (x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"]),
                    jnp.full((T,), jnp.inf))
        moe = p["moe"]
        score = SCORES[router_score](h @ _f32(moe["w_router"]))     # (T, E)
        # one more than chosen: the runner-up's score gives the margin
        best, chosen = jax.lax.top_k(score, top_k + 1)
        margin, chosen = best[:, top_k - 1] - best[:, top_k], chosen[:, :top_k]
        mask = jnp.zeros_like(score).at[jnp.arange(T)[:, None], chosen].set(1.0)
        picked = score * mask
        weight = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
        count = moe["w_gate"].shape[0]
        weight = jax.lax.dynamic_slice_in_dim(weight, held_first, count, axis=1)

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
    if not cfg.get("norm_topk_prob", True):
        raise ValueError("this reference renormalises the chosen gates "
                         "(norm_topk_prob true), as the publication does")
    if len(blocks) != int(cfg["num_hidden_layers"]):
        raise ValueError(f"{len(blocks)} blocks in the tree, "
                         f"num_hidden_layers={cfg['num_hidden_layers']}")
    hd = int(cfg["head_dim"])
    held_first = int((cfg.get("experts_held") or [0])[0])
    x = _embed(params[emb], jnp.asarray(ids, jnp.int32))
    pos = jnp.arange(x.shape[0], dtype=jnp.int32)
    margin = jnp.full(x.shape[:1], jnp.inf)
    for i, k in enumerate(blocks):
        kind = cfg["layer_types"][i]
        dense = cfg["mlp_layer_types"][i] == "dense"
        if ("mlp" in params[k]) != dense:
            raise ValueError(f"{k}: mlp_layer_types[{i}] says "
                             f"{cfg['mlp_layer_types'][i]}")
        heads = params[k]["attn"]["w_q"].shape[1]
        if heads != int(cfg["num_attention_heads_per_layer"][i]):
            raise ValueError(f"{k}: {heads} query heads, the file says "
                             f"{cfg['num_attention_heads_per_layer'][i]}")
        inv, rope_scale = rope_frequencies(cfg["rope_parameters"][kind], hd)
        x, m = block(params[k], x, pos, jnp.asarray(inv),
                     window=(int(cfg["sliding_window"])
                             if kind == "sliding_attention" else None),
                     rope_scale=rope_scale,
                     top_k=int(cfg["num_experts_per_tok"]),
                     scale=float(cfg["moe_routed_scaling_factor"]),
                     held_first=held_first, eps=float(cfg["rms_norm_eps"]),
                     router_score=cfg.get("router_score", "softmax"),
                     gate_act=cfg.get("gate_act", "sigmoid"),
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
