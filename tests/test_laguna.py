"""Laguna's block on the CPU at a small size (d 64; 2 KV heads of 16 under 4
query heads in full-attention layers and 6 in sliding ones, so groups of 2
and 3; window 16; YaRN on half of each head in full layers; one dense layer
of width 96 and four expert layers of 16 experts top-3 of width 32 beside a
shared one; layers F S S S F; vocab 128), seeded weights, against the plain
reference the benchmark keeps (``benchmark/references/laguna_block.py``: f32,
"highest", full (T, T) masks, keys and values repeated to the query heads, a
loop over experts, nothing imported from the program).

Tolerances, each with its reason:

- ``F32`` (absolute, logits of order 0.3): both sides compute in f32 and
  differ in the order of sums (grouped against repeated heads, one matmul
  over (expert, width) against a loop): a few ulp over five layers. Measured
  1.8e-7 to 2.4e-7; 2e-5 is OLMoE's and GLM's bound.
- bf16 parameters, FULL forward (``WIDE``, in standard deviations of the
  reference's logits): the block holds its stream in f32 and multiplies
  exactly against the bf16 values, and the reference is fed the SAME bf16
  values, so they differ as two f32 programs do: measured 1.1e-6 to 1.5e-6
  over six token seeds.
  The bound 1e-4 is what says "f32 where f32 is stated": the same block with
  every activation rounded to bf16 before it is multiplied reads 0.008 to
  0.22 over four token seeds: a swapped expert where it is large
  (``test_a_bf16_stream_would_fail``).
- bf16 parameters THROUGH THE CACHE (``CACHED``): one more rounding, of k and
  v to the cache's bf16. Measured 0.0048; 0.02 is GLM's bound, and an
  8-bit cache reads 0.30 to 0.36 (``test_an_8_bit_cache_would_fail``).
- ...and against the reference GIVEN what the cache stores (``cache_dtype``:
  k and v rounded to bf16 once), the cached path is held to ``WIDE`` again:
  what is left is paging, the ring, chunking. This is the comparison the
  benchmark's cell makes.
"""

import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import (as_paged, cache_parts,
                                              check_decodes, decode_forward,
                                              generate, init_caches,
                                              paged_parts, ring_blocks)
from deeplearning4j_tpu.nn.layers import (LagunaBlock, experts, glm4_moe_lite,
                                          laguna)
from deeplearning4j_tpu.nn.layers.attention import (rope_rotate,
                                                    rope_rotate_freqs,
                                                    yarn_inv_freq)
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.paged import (BlockAllocator, RingPages,
                                            build_pools)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, BS, CHUNK = 16, 4, 8
ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                           "factor": 4.0,
                           "original_max_position_embeddings": 32,
                           "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.1386294361119891,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
CFG = {"num_hidden_layers": 5, "hidden_size": 64, "head_dim": 16,
       "num_key_value_heads": 2, "sliding_window": WINDOW,
       "layer_types": KINDS,
       "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
       "mlp_layer_types": ["dense"] + ["sparse"] * 4,
       "rope_parameters": ROPE, "num_experts_per_tok": 3,
       "moe_routed_scaling_factor": 2.5, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "vocab_size": 128}
F32 = 2e-5       # absolute
WIDE = 1e-4      # in standard deviations of the reference's logits
CACHED = 0.02    # the same, through a bf16 cache
DTYPES = ["float32", "bfloat16"]


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "laguna_block.py")
    spec = importlib.util.spec_from_file_location("laguna_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def build(dtype="float32", **kw):
    args = dict(
        seed=3, input_shape=(128,), num_layers=5, first_k_dense=1, period=4,
        d_model=64, full_heads=4, sliding_heads=6, num_kv_heads=2,
        head_dim=16, window=WINDOW, dense_width=96, num_experts=16, top_k=3,
        expert_width=32, shared_width=32, full_rotary_dim=8, yarn_factor=4.0,
        yarn_original=32, attention_factor=ROPE["full_attention"][
            "attention_factor"], vocab=128, dtype=dtype)
    args.update(kw)
    m = models.LagunaLM(**args).build()
    m.init()
    return m


def cfg_of(**kw):
    return {**CFG, **kw}


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def ref_logits(params, ids, cfg=CFG):
    return np.asarray(ref.logits(params, ref.hidden(params, ids, cfg), cfg))


def forward_logits(m, ids, params=None):
    params = m.params if params is None else params
    pre, _ = m.forward(params, m.state, jnp.asarray(ids[None]),
                       up_to=len(m.layers) - 1)
    return m.layers[-1].preactivation(params["layer_7"], pre)[0]


def close(got, want, dtype, bf16_bound):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=F32, rtol=0)
    else:
        assert np.abs(got - want).max() <= bf16_bound * want.std()


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float32) - want).max() / want.std())


# ---------------------------------------------------------------- the block
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("router_score,gate_act", [
    ("softmax", "sigmoid"), ("sigmoid", "sigmoid"), ("softmax", "softplus")])
def test_forward_logits_match_the_reference(dtype, router_score, gate_act):
    """Both kinds of layer (the model has full and sliding ones, 70 tokens
    are several windows) and both values of the two fields the published
    config leaves unsaid."""
    m = build(dtype, router_score=router_score, gate_act=gate_act)
    ids = tokens(70, seed=1)
    want = ref_logits(m.params, ids, cfg_of(router_score=router_score,
                                            gate_act=gate_act))
    close(forward_logits(m, ids), want, dtype, WIDE)
    if (router_score, gate_act) != ("softmax", "sigmoid"):
        # ...and the field is read: the other value is another model
        assert rel_err(forward_logits(m, ids), ref_logits(m.params, ids)) > 0.01


def test_a_bf16_stream_would_fail(monkeypatch):
    """What ``WIDE`` is there to catch: activations rounded to bf16 before
    every product, which is what a TPU's default matmul does to f32."""
    m = build("bfloat16")
    ids = tokens(70, seed=1)
    want = ref_logits(m.params, ids)

    def rounded(spec, a, w):
        return jnp.einsum(spec, a.astype(jnp.bfloat16).astype(jnp.float32),
                          w.astype(jnp.float32))

    for mod in (laguna, glm4_moe_lite, experts):
        monkeypatch.setattr(mod, "wide_einsum", rounded)
    assert rel_err(forward_logits(m, ids), want) > 10 * WIDE


def test_layers_differ_in_heads_window_and_rope():
    m = build()
    blocks = [l for l in m.layers if isinstance(l, LagunaBlock)]
    assert [b.num_heads for b in blocks] == [4, 6, 6, 6, 4]
    assert [b.window for b in blocks] == [None, 16, 16, 16, None]
    assert [bool(b.yarn_factor) for b in blocks] == [True, False, False,
                                                    False, True]
    assert [b.num_experts for b in blocks] == [0, 16, 16, 16, 16]
    at = m.params["layer_2"]["attn"]
    assert at["w_q"].shape == (64, 6, 16) and at["w_k"].shape == (64, 2, 16)
    assert at["w_head_gate"].shape == (64, 6)       # ONE gate a head
    assert m.params["layer_1"]["attn"]["w_o"].shape == (4 * 16, 64)
    # a sliding layer states its cache's reach, a full one keeps everything
    assert [(lk, p.window) for lk, p in cache_parts(m)] == [
        ("layer_1", None), ("layer_2", 16), ("layer_3", 16),
        ("layer_4", 16), ("layer_5", None)]
    assert all(dict(p) == {"k": (2, 16), "v": (2, 16)}
               for _, p in cache_parts(m))


def test_the_window_is_a_band():
    """A sliding layer's query sees the last ``window`` keys and no more: a
    change 16 or more tokens back cannot reach it through ONE layer."""
    blk = LagunaBlock(num_heads=6, num_kv_heads=2, head_dim=16, window=16,
                      rope_base=1e4, num_experts=0, ffn_width=32)
    params, _ = blk.init(jax.random.PRNGKey(0), (40, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 64))
    y, _, _ = blk.apply(params, {}, x)
    y2, _, _ = blk.apply(params, {}, x.at[0, 10].add(1.0))
    moved = np.abs(np.asarray(y2 - y)).max(axis=-1)[0]
    assert (moved[:10] == 0).all() and (moved[10:26] > 0).all()
    assert (moved[26:] == 0).all()         # 10 + 16 and later: out of reach


def test_the_gate_is_one_a_head_a_token():
    """``w_head_gate`` (d, H): with rows of ones for ``h``, a zero column
    gates its head at sigmoid(0) = 0.5 and a column of -1 at sigmoid(-64),
    nothing: head 2 shut is the half-gated block without head 2's rows of
    ``w_o``."""
    blk = LagunaBlock(num_heads=4, num_kv_heads=2, head_dim=16,
                      num_experts=0, ffn_width=32, rope_base=1e4)
    params, _ = blk.init(jax.random.PRNGKey(0), (12, 64))
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 2, 16))
    h = jnp.ones((1, 12, 64))
    see = jnp.tril(jnp.ones((12, 12), bool))[None, None, None]
    gates = jnp.zeros((64, 4))

    def attend(w_head_gate, w_o):
        return blk._gate_project({"attn": dict(params["attn"], w_o=w_o,
                                               w_head_gate=w_head_gate)},
                                 h, blk._attend(h, q, k, v, see))

    w_o = params["attn"]["w_o"]
    shut = attend(gates.at[:, 2].set(-1.0), w_o)
    without = attend(gates, w_o.at[32:48].set(0.0))
    np.testing.assert_allclose(np.asarray(shut), np.asarray(without),
                               atol=1e-6)
    assert np.abs(np.asarray(attend(gates, w_o) - without)).max() > 1e-3


# ------------------------------------------------------------------ the rope
def test_yarn_frequencies_and_attention_factor_by_hand():
    """Laguna-S-2.1's full-attention rope: 64 rotated dimensions, theta 5e5,
    factor 128 over 8192, beta 32 and 1. By hand: the correction dimensions
    are 64 ln(8192 / (32 x 2 pi)) / (2 ln 5e5) = 9.04 and 64 ln(8192 / (2
    pi)) / (2 ln 5e5) = 17.49, so the ramp runs from pair 9 to pair 18:
    pairs 0..9 keep theta^(-2i/64), pairs 18.. are divided by 128."""
    inv = yarn_inv_freq(64, 5e5, 128.0, 8192, 32.0, 1.0)
    assert inv.shape == (32,) and inv.dtype == np.float32
    plain = 5e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128.0, rtol=1e-6)
    assert inv[0] == 1.0
    assert inv[9] == pytest.approx(0.024955, rel=1e-3)
    assert inv[18] == pytest.approx(4.8654e-6, rel=1e-3)
    assert inv[31] == pytest.approx(2.3555e-8, rel=1e-3)
    # the ramp between: pair 13 is 4/9 of the way from plain to plain / 128
    assert inv[13] == pytest.approx(
        plain[13] * (1 - 4 / 9) + plain[13] / 128 * 4 / 9, rel=1e-5)
    # the published attention_factor is YaRN's 0.1 ln(factor) + 1
    assert 0.1 * math.log(128) + 1 == pytest.approx(1.4852030263919618)
    lm = models.LagunaLM()
    full = lm.blocks[("full", "experts")]
    assert (full.attention_factor, full.rotary_dim, full.yarn_factor,
            full.rope_base) == (1.4852030263919618, 64, 128.0, 5e5)
    assert np.array_equal(ref.rope_frequencies({
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}, 128)[0], inv)


def test_partial_rotation_leaves_the_rest_of_the_head():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16))
    pos = jnp.arange(5) + 7
    inv = (1e4 ** (-np.arange(4) / 4.0)).astype(np.float32)
    y = rope_rotate_freqs(x, pos, inv, 1.5)
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(x[..., 8:]))
    # the rotated half is rope_rotate's on those 8 dimensions, times 1.5
    np.testing.assert_allclose(
        np.asarray(y[..., :8]),
        1.5 * np.asarray(rope_rotate(x[..., :8], pos, 1e4)), atol=1e-6)
    # per-row positions take the other branch
    rows = jnp.stack([pos, pos + 3])
    z = rope_rotate_freqs(x, rows, inv, 1.5)
    np.testing.assert_allclose(np.asarray(z[0]), np.asarray(y[0]), atol=1e-6)


# ------------------------------------------------------- a share of experts
def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares of 2 experts, plus the shared expert
    once, are the layer that holds all 16: the router scores, ranks and
    renormalises over all 16 in every share."""
    whole = LagunaBlock(num_heads=4, num_kv_heads=2, head_dim=16,
                        num_experts=16, top_k=3, ffn_width=32,
                        shared_width=32, rope_base=1e4)
    params, _ = whole.init(jax.random.PRNGKey(0), (9, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    full, _ = whole._ffn(params, x, None)
    no_shared = jax.tree.map(jnp.zeros_like, params["moe"]["shared"])
    shared_alone = full - whole._ffn(
        dict(params, moe=dict(params["moe"], shared=no_shared)), x, None)[0]
    total = shared_alone
    live = jnp.ones((2, 9), bool)
    held, elsewhere = 0, 0
    for first in range(0, 16, 2):
        part = LagunaBlock(num_heads=4, num_kv_heads=2, head_dim=16,
                           num_experts=16, top_k=3, ffn_width=32,
                           shared_width=32, rope_base=1e4,
                           experts_held=(first, 2))
        moe = dict(params["moe"], shared=no_shared,
                   **{n: params["moe"][n][first:first + 2]
                      for n in ("w_gate", "w_up", "w_down")})
        y, routing = part._ffn(dict(params, moe=moe), x, live)
        total = total + y
        assert routing.shape == (4,)
        held += int(routing[0])
        elsewhere += int(routing[3])
        assert int(routing[0]) + int(routing[3]) == 2 * 9 * 3
    np.testing.assert_allclose(np.asarray(total), np.asarray(full), atol=2e-6)
    assert held == 2 * 9 * 3 and elsewhere == 7 * 2 * 9 * 3
    # a share's own parameters have the held count of rows
    part_params, _ = part.init(jax.random.PRNGKey(0), (9, 64))
    assert part_params["moe"]["w_gate"].shape == (2, 64, 32)
    assert part_params["moe"]["w_router"].shape == (64, 16)
    # holding all of them is the layer as it was: three sums, no fourth
    all_held = LagunaBlock(num_experts=16, top_k=3, experts_held=(0, 16))
    assert all_held.held is None
    assert whole._ffn(params, x, live)[1].shape == (3,)
    with pytest.raises(ValueError, match="experts_held"):
        LagunaBlock(num_experts=16, experts_held=(14, 4)).held


def test_the_chosen_gates_sum_to_the_scaling_factor():
    m = build()
    blk = m.layers[2]
    p = m.params["layer_2"]["moe"]
    h = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (7, 64)))
    score = np.asarray(jax.nn.softmax(h @ np.asarray(p["w_router"]), axis=-1))
    order = np.argsort(-score, axis=-1)[:, :3]
    want = np.zeros_like(score)
    for n in range(7):
        want[n, order[n]] = score[n, order[n]] / score[n, order[n]].sum() * 2.5
    gate, idx = jax.lax.top_k(jnp.asarray(score), 3)
    gate = gate / gate.sum(-1, keepdims=True) * blk.routed_scale
    from deeplearning4j_tpu.nn.layers.experts import assign
    got, _ = assign(gate, idx, 16, None, (7,))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 2.5, atol=1e-5)


# --------------------------------------------------------------- the caches
@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_cache_chunks_and_steps_equal_the_full_forward(dtype):
    """Prefill in chunks, then single steps, through ``generate()``'s dense
    caches (a sliding layer kept at capacity under a band mask), for
    contexts under, at and several times the window."""
    m = build(dtype)
    for n in (11, 16, 70):
        ids = tokens(n + 4, seed=n)
        caches = init_caches(m, 1, 96, m.dtype)
        got = []
        for lo in range(0, n, CHUNK):
            hi = min(lo + CHUNK, n)
            lg, caches = decode_forward(m, m.params, m.state,
                                        jnp.asarray(ids[None, lo:hi]),
                                        caches, lo)
            got.append(np.asarray(lg[0]))
        for t in range(n, n + 4):
            lg, caches = decode_forward(m, m.params, m.state,
                                        jnp.asarray(ids[None, t:t + 1]),
                                        caches, jnp.asarray([t], jnp.int32))
            got.append(np.asarray(lg[0]))
        got = np.concatenate(got)
        close(got, forward_logits(m, ids), dtype, CACHED)
        close(got, ref_logits(m.params, ids, cfg_of(
            cache_dtype=jnp.dtype(m.dtype).name)), dtype, WIDE)


class Paged:
    """Two slots over pools of both groups, as the batcher lays them out:
    the full group's table straight, the window group's a ring of
    ``ring_blocks(16, 8, 4)`` = 7 columns fed by ``RingPages``, physical
    blocks handed out in a scrambled order."""

    def __init__(self, m, slots=2, capacity=96):
        self.m = m
        self.R = ring_blocks(WINDOW, CHUNK, BS)
        self.maxb = capacity // BS
        n_full, n_win = slots * self.maxb + 1, slots * self.R + 1
        self.pools = build_pools(m, {"full": n_full, "window": n_win}, BS,
                                 m.dtype)
        self.names = {lk: tuple(p) for lk, p in cache_parts(m)}
        self.window_of = {lk: p.window for lk, p in cache_parts(m)}
        order = np.random.default_rng(5).permutation(np.arange(1, n_full))
        self.full = np.zeros((slots, self.maxb), np.int32)
        for s in range(slots):
            self.full[s] = order[s * self.maxb:(s + 1) * self.maxb]
        self.alloc = BlockAllocator(n_win)
        self.alloc._free = [int(b) for b in np.random.default_rng(6)
                            .permutation(np.arange(1, n_win))]
        self.rings = [RingPages(self.alloc, BS, WINDOW, self.R)
                      for _ in range(slots)]

    def run(self, ids, rows, pos, true_len=None):
        """One program: ``ids`` (len(rows), Tq) at offsets ``pos``; of a
        right-padded chunk only ``true_len`` tokens are real."""
        Tq = ids.shape[1]
        for r, p in zip(rows, pos):
            self.rings[r].release_behind(p)
            self.rings[r].ensure(p + (true_len or Tq))
        win = np.stack([self.rings[r].row() for r in rows])
        caches = {lk: as_paged(self.pools[lk], jnp.asarray(
            self.full[rows] if self.window_of[lk] is None else win))
            for lk in self.names}
        lg, caches = decode_forward(self.m, self.m.params, self.m.state,
                                    jnp.asarray(ids), caches,
                                    jnp.asarray(pos, jnp.int32))
        self.pools = {lk: paged_parts(caches[lk], self.names[lk])
                      for lk in self.names}
        return np.asarray(lg)


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_cache_of_two_groups_matches_the_reference(dtype):
    """Logits, not tokens, through both block groups: two sequences in
    chunks of 8 (boundaries straddle the ring's laps: 7 columns of 4 hold 28
    positions, the sequences run to 75 and 41), the LAST chunk of each
    right-padded to 8 with its garbage landing in the ring's slack, then
    single steps of both rows at their own positions; then a third sequence
    through the first one's slot (a ring re-used)."""
    m = build(dtype)
    pg = Paged(m)
    assert pg.R == 7
    assert {a.shape[0] for lk, pool in pg.pools.items() for a in pool.values()
            if pg.window_of[lk]} == {15}        # pools of the ring's length
    seqs = [tokens(75, seed=9), tokens(41, seed=10)]
    got = [[], []]
    for row, (ids, upto) in enumerate(zip(seqs, (69, 35))):
        for lo in range(0, upto, CHUNK):
            n = min(CHUNK, upto - lo)
            buf = np.zeros((1, CHUNK), np.int32)
            buf[0, :n] = ids[lo:lo + n]
            lg = pg.run(buf, [row], [lo], true_len=n)
            got[row].append(lg[0, :n])
    for step in range(6):                 # both rows, each at its position
        pos = [69 + step, 35 + step]
        lg = pg.run(np.asarray([[seqs[0][pos[0]]], [seqs[1][pos[1]]]]),
                    [0, 1], pos)
        got[0].append(lg[0])
        got[1].append(lg[1])
    stored = cfg_of(cache_dtype=jnp.dtype(m.dtype).name)
    for ids, rows in zip(seqs, got):
        close(np.concatenate(rows), ref_logits(m.params, ids), dtype, CACHED)
        close(np.concatenate(rows), ref_logits(m.params, ids, stored), dtype,
              WIDE)
    # the ring never held more than its columns, and released as it went
    assert all(len(r.blocks) <= pg.R for r in pg.rings)
    assert min(pg.rings[0].blocks) >= (75 - WINDOW) // BS - 1
    pg.rings[0].release()
    pg.rings[0] = RingPages(pg.alloc, BS, WINDOW, pg.R)
    third, rows = tokens(30, seed=12), []
    for lo, hi in ((0, 8), (8, 16), (16, 23), (23, 24), (24, 30)):
        buf = np.zeros((1, 8 if hi - lo > 1 else 1), np.int32)
        buf[0, :hi - lo] = third[lo:hi]
        rows.append(pg.run(buf, [0], [lo], true_len=hi - lo)[0, :hi - lo])
    close(np.concatenate(rows), ref_logits(m.params, third, stored), dtype,
          WIDE)


def test_an_8_bit_cache_would_fail():
    """What ``CACHED`` and the cell's check are there to catch: the reference
    given k and v as an 8-bit cache would store them leaves the served logits
    by ten times the bound."""
    m = build("bfloat16")
    ids = tokens(75, seed=9)
    got = forward_logits(m, ids)
    eight = ref_logits(m.params, ids, cfg_of(cache_dtype="float8_e4m3fn"))
    assert rel_err(got, eight) > 3 * CACHED


def test_the_reference_leaves_out_routing_ties(monkeypatch):
    m = build()
    seq = tokens(30, seed=13)
    _, margin = ref.hidden_and_margin(m.params, seq, CFG)
    margin = np.asarray(margin)
    assert margin.shape == (30,) and (margin > 0).all()
    prompt, out = list(seq[:20]), list(seq[20:])
    for tie in (0.0, float(np.median(margin[19:29])), 1.0):
        monkeypatch.setattr(ref, "ROUTING_TIE", tie)
        gap, spread = ref.greedy_gaps(m.params, prompt, out, CFG, 30, 10)
        assert len(gap) == len(spread) == int((margin[19:29] >= tie).sum())


# -------------------------------------------------------------- the batcher
def _batcher(m, **kw):
    opts = dict(slots=2, capacity=128, block_size=BS, prefill_chunk=CHUNK,
                metrics=MetricsRegistry())
    opts.update(kw)
    return ContinuousBatcher(m, **opts)


def _counter(snap, name, **labels):
    return sum(s["value"] for s in snap[name]["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def test_the_contract_check_accepts_the_model():
    m = build()
    assert check_decodes(m, 128, "cache capacity", served=True) == 128
    assert all(hasattr(l, "decode") and hasattr(l, "cache_spec")
               for l in m.layers[1:6])


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_serves_what_generate_gives(dtype):
    """Four requests on two slots through both block groups, chunked
    prefill. The third sends the first's prompt and answer back with five
    tokens more (a session's next turn) and adopts the seventeen whole blocks
    of that run, the ones decode steps filled included, with the window's
    tail behind them; the fourth leaves the first's prompt after 32 tokens,
    where the window group no longer holds the tail (the ring had moved on),
    so its hit is cut to nothing. The reference's tokens, and in f32
    ``generate()``'s, token for token."""
    m = build(dtype, experts_held=(4, 8))
    cfg = cfg_of(experts_held=[4, 8], cache_dtype=jnp.dtype(m.dtype).name)
    cb = _batcher(m)
    try:
        shared = tokens(32, seed=5)
        prompts = [np.concatenate([shared, tokens(9, seed=6)]),
                   tokens(21, seed=7)]
        first = cb.generate(prompts[0], 30, temperature=0.0)
        prompts.append(np.concatenate([prompts[0], first, tokens(5, seed=8)]))
        prompts.append(np.concatenate([shared, tokens(7, seed=20)]))
        reqs = [cb.submit(p, 30, temperature=0.0) for p in prompts[1:]]
        outs = [first] + [r.wait() for r in reqs]
        snap = cb.metrics.snapshot()
        stats = cb.kv_block_stats()
    finally:
        cb.shutdown()
    assert _counter(snap, "serve_prefix_cache_hits_total") == 1
    assert _counter(snap, "serve_prefill_tokens_saved_total") \
        == (41 + 30 - 1) // BS * BS == 68
    assert _counter(snap, "serve_prefix_answer_tokens_cached_total") > 0
    assert _counter(snap, "serve_prefix_hits_shortened_total") == 1
    width = 4 if dtype == "float32" else 2
    assert _counter(snap, "serve_kv_token_bytes") == 5 * 2 * 2 * 16 * width
    assert stats["window_group"]["ring_blocks"] == 7
    assert _counter(snap, "serve_kv_window_released_total") > 0
    for prompt, out in zip(prompts, outs):
        assert len(out) == 30
        gap, spread = ref.greedy_gaps(m.params, list(prompt), list(out), cfg,
                                      pad_to=len(prompt) + 30, last=30)
        assert 22 <= len(gap) <= 30     # routing ties (5e-5) are left out
        assert (gap / spread).max() <= (1e-5 if dtype == "float32" else WIDE)
        if dtype == "float32":
            np.testing.assert_array_equal(
                out, generate(m, prompt[None], 30, temperature=0.0)[0])


def test_routing_counters_count_held_experts_and_those_elsewhere():
    """Four of the five layers have experts and hold 8 of 16: a live token's
    3 experts a layer are counted either as held or as elsewhere."""
    m = build(experts_held=(4, 8))
    cb = _batcher(m)
    try:
        cb.generate(tokens(21, seed=10), 6, temperature=0.0)
        snap = cb.metrics.snapshot()
    finally:
        cb.shutdown()
    for prog, toks in (("prefill", 21), ("decode", 5)):
        held = _counter(snap, "serve_moe_assignments_total", program=prog)
        away = _counter(snap, "serve_moe_assignments_elsewhere_total",
                        program=prog)
        assert held + away == toks * 4 * 3 and held > 0 and away > 0
    assert _counter(snap, "serve_moe_layer_programs_total",
                    program="decode") == 4 * 5
    # a model that holds all its experts has no fourth counter
    whole = _batcher(build())
    try:
        assert "serve_moe_assignments_elsewhere_total" \
            not in whole.metrics.snapshot()
    finally:
        whole.shutdown()


@pytest.fixture(scope="module")
def decode_program_text():
    m = build("bfloat16")
    cb = _batcher(m)
    try:
        snap = cb.registry.current()
        (ops,) = cb._programs.signatures(cb._params_for(snap),
                                         snap.state)["gen_decode_paged"]
        return cb._programs._decode.lower(*ops).as_text(debug_info=True)
    finally:
        cb.shutdown()


@pytest.mark.parametrize("scope", [
    "attention/rope_yarn", "attention/cache_read", "attention/attn_gate",
    "mlp", "moe_router", "moe_experts", "moe_shared"])
def test_named_scopes_reach_the_decode_program(decode_program_text, scope):
    assert re.search(rf'"[^"]*[/(]{scope}[/)]', decode_program_text), scope
