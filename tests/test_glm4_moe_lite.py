"""GLM-4.7-Flash's block on the CPU at a small size (d 64, 4 heads, ranks 24
and 16, head sizes 12 + 8 and 16, one dense layer of width 96 and two expert
layers of 8 experts top-2 of width 32 beside a shared one, vocab 128), seeded
weights, a NON-ZERO selection bias, against the plain reference the benchmark
keeps (``benchmark/references/glm4_moe_lite_block.py``: f32, "highest", the
EXPANDED attention, a loop over experts, nothing imported from the program).

Tolerances, each with its reason:

- ``F32`` (absolute, logits of order 0.2): both sides compute in f32 and
  differ in the order of sums and in the algebra (absorbed against expanded
  attention; one matmul over (expert, width) against a loop): a few ulp over
  three layers. Measured 1.5e-7 to 4e-7; 2e-5 is OLMoE's bound.
- bf16 parameters, FULL forward (``WIDE``, in standard deviations of the
  reference's logits): the block holds its stream in f32 and multiplies
  exactly against the bf16 values, and the reference is fed the SAME bf16
  values, so they differ as two f32 programs do: measured 0.9e-6 to 1.3e-6
  over six token seeds. The bound 1e-4 is what says "f32 where f32 is
  stated": the same block with every activation rounded to bf16 before it is
  multiplied, which is what a TPU's default matmul does to an f32 operand,
  reads 0.0057 to 0.0079 (``test_products_rounded_to_bf16_would_fail``).
- bf16 parameters THROUGH THE CACHE (``CACHED``): one more rounding, of the
  latent to the cache's bf16 (2**-9 relative, on a context of 25-40 tokens
  where attention is a large part of the stream; at thousands of tokens it
  is a hundredth of it). Measured 0.0037 to 0.0062; 0.02 is three times
  that, and the 8-bit reference's 0.21 to 0.44 lies ten times above it
  (``test_an_8_bit_computation_would_fail``).
- ...and against the reference GIVEN what the cache stores (``cache_dtype``:
  the latent and the rope key rounded to bf16 once, everything from them in
  f32), the cached path is held to ``WIDE`` again (measured 1.0e-6 to
  1.7e-6): what is left between the two is the algebra (absorbed against
  expanded) in f32. This is the comparison the benchmark's cell makes.
- greedy tokens are compared through the reference's logits: the gap between
  its largest logit and its logit for the token served, 0 where they agree.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import (as_paged, cache_parts,
                                              cache_spec, check_decodes,
                                              decode_forward, generate,
                                              init_caches, paged_parts)
from deeplearning4j_tpu.nn.layers import glm4_moe_lite
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.paged import block_bytes, build_pools

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = {"num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
       "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
       "norm_topk_prob": True, "routed_scaling_factor": 1.8,
       "vocab_size": 128, "rms_norm_eps": 1e-5, "rope_theta": 1e6}
F32 = 2e-5       # absolute
WIDE = 1e-4      # in standard deviations of the reference's logits
CACHED = 0.02    # the same, through a bf16 cache
DTYPES = ["float32", "bfloat16"]
LATENT = 16 + 8


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "glm4_moe_lite_block.py")
    spec = importlib.util.spec_from_file_location("glm4_moe_lite_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def build(dtype="float32"):
    m = models.Glm4MoeLiteLM(
        seed=3, input_shape=(96,), num_layers=3, first_k_dense=1, d_model=64,
        num_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=8, v_head_dim=16, dense_width=96, num_experts=8,
        top_k=2, expert_width=32, vocab=128, dtype=dtype).build()
    m.init()
    # the publication starts the selection bias at 0; a test that left it
    # there would not see it used
    rng = np.random.default_rng(11)
    for k in ("layer_2", "layer_3"):
        m.params[k]["moe"]["e_score_correction_bias"] = jnp.asarray(
            rng.normal(0.0, 0.1, 8), m.dtype)
    return m


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def ref_logits(params, ids, cache_dtype=None):
    """The reference's logits; with ``cache_dtype`` the reference rounds the
    latent and the rope key to it, as a cache of that dtype stores them."""
    cfg = {**CFG, "cache_dtype": cache_dtype} if cache_dtype else CFG
    return np.asarray(ref.logits(params, ref.hidden(params, ids, cfg), cfg))


def forward_logits(m, ids, params=None):
    params = m.params if params is None else params
    pre, _ = m.forward(params, m.state, jnp.asarray(ids[None]),
                       up_to=len(m.layers) - 1)
    return m.layers[-1].preactivation(params["layer_5"], pre)[0]


def close(got, want, dtype, bf16_bound):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=F32, rtol=0)
    else:
        assert np.abs(got - want).max() <= bf16_bound * want.std()


def served_gap(params, prompt, out, dtype):
    gap, spread = ref.greedy_gaps(params, list(prompt), list(out), CFG,
                                  pad_to=len(prompt) + len(out),
                                  last=len(out))
    assert len(out) - 2 <= len(gap) <= len(out)   # a routing tie is left out
    assert (gap / spread).max() <= (1e-5 if dtype == "float32" else CACHED)


@pytest.mark.parametrize("dtype", DTYPES)
def test_forward_logits_match_the_reference(dtype):
    m = build(dtype)
    ids = tokens(40)
    got = forward_logits(m, ids)
    assert got.dtype == jnp.float32      # the stream leaves the blocks in f32
    close(got, ref_logits(m.params, ids), dtype, WIDE)


def test_products_rounded_to_bf16_would_fail(monkeypatch):
    """``WIDE`` has power: the same block with each activation rounded to
    bf16 before it meets a weight (bf16 where f32 is stated; a TPU's
    default matmul) leaves it fifty times over."""
    from deeplearning4j_tpu.nn.layers import experts

    def narrow(spec, a, w):
        return jnp.einsum(spec, a.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)

    m = build("bfloat16")
    ids = tokens(40)
    want = ref_logits(m.params, ids)
    monkeypatch.setattr(glm4_moe_lite, "wide_einsum", narrow)
    monkeypatch.setattr(experts, "wide_einsum", narrow)
    err = np.abs(np.asarray(forward_logits(m, ids), np.float32)
                 - want).max() / want.std()
    assert err > 30 * WIDE


def test_an_8_bit_computation_would_fail():
    m = build("bfloat16")
    ids = tokens(40)
    want = ref_logits(m.params, ids)
    eight = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), m.params)
    err = np.abs(ref_logits(eight, ids) - want).max() / want.std()
    assert err > 5 * CACHED


@pytest.mark.parametrize("dtype", DTYPES)
def test_absorbed_decode_equals_the_expanded_forward(dtype):
    """``decode`` (absorbed, against the dense-layout cache, in chunks and
    single steps) gives the logits of ``apply`` (expanded, no cache): the
    program against itself. In bf16 they differ by the cache's rounding."""
    m = build(dtype)
    ids = tokens(30, seed=4)
    caches = init_caches(m, 1, 48, m.dtype)
    assert {k: {n: a.shape for n, a in c.items()}
            for k, c in caches.items()} == {
        f"layer_{i}": {"latent": (1, 48, 16), "rope": (1, 48, 8)}
        for i in (1, 2, 3)}
    got = []
    for lo, hi in ((0, 16), (16, 27), (27, 28), (28, 29), (29, 30)):
        lg, caches = decode_forward(m, m.params, m.state,
                                    jnp.asarray(ids[None, lo:hi]), caches,
                                    jnp.int32(lo))
        got.append(np.asarray(lg[0]))
    got = np.concatenate(got)
    assert got.dtype == np.float32
    close(got, forward_logits(m, ids), dtype, CACHED)
    close(got, ref_logits(m.params, ids), dtype, CACHED)
    close(got, ref_logits(m.params, ids, jnp.dtype(m.dtype).name), dtype, WIDE)
    out = generate(m, ids[None, :20], 10, temperature=0.0)[0]
    served_gap(m.params, ids[:20], out, dtype)


def test_the_dense_layer_is_the_first():
    """Layer 1 is one SwiGLU of width 96 and keeps no router; the rest have
    8 experts of width 32 and a shared one. Alone, the dense layer matches
    the reference's block."""
    m = build()
    p = m.params
    assert set(p["layer_1"]) == {"ln1_g", "ln2_g", "attn", "mlp"}
    assert p["layer_1"]["mlp"]["w_gate"].shape == (64, 96)
    assert set(p["layer_2"]) == set(p["layer_3"]) \
        == {"ln1_g", "ln2_g", "attn", "moe"}
    assert p["layer_2"]["moe"]["w_gate"].shape == (8, 64, 32)
    assert p["layer_2"]["moe"]["shared"]["w_down"].shape == (32, 64)
    assert p["layer_2"]["attn"]["w_kvb"].shape == (16, 4, 12 + 16)
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 9, 64)),
                    jnp.float32)
    got, _, _ = m.layers[1].apply(p["layer_1"], {}, x)
    want, margin = ref.block(p["layer_1"], x[0], jnp.arange(9), nope=12,
                             rank=16, top_k=2, scale=1.8, eps=1e-5, theta=1e6)
    assert np.isinf(np.asarray(margin)).all()    # a dense layer has no tie
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=F32, rtol=0)


def _router(m, key, x):
    """numpy: the probabilities and the two chosen experts of every row."""
    p = m.params[key]
    h = np.asarray(x, np.float64).reshape(-1, 64)
    h = h / np.sqrt((h ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(p["ln2_g"], np.float64)
    prob = 1 / (1 + np.exp(-h @ np.asarray(p["moe"]["w_router"], np.float64)))
    bias = np.asarray(p["moe"]["e_score_correction_bias"], np.float64)
    return h, prob, np.argsort(-(prob + bias), axis=-1)[:, :2]


def test_the_bias_selects_and_does_not_weigh():
    """With the bias, rows choose other experts than their two most likely;
    the weights are still the chosen PROBABILITIES, renormalised to 1.8."""
    m = build()
    blk, p = m.layers[2], m.params["layer_2"]
    x = jnp.asarray(np.random.default_rng(6).normal(0, 1, (1, 24, 64)),
                    jnp.float32)
    h, prob, chosen = _router(m, "layer_2", x)
    unbiased = np.argsort(-prob, axis=-1)[:, :2]
    moved = [set(a) != set(b) for a, b in zip(chosen, unbiased)]
    assert 3 <= sum(moved) < 24          # the bias changed some sets

    def swiglu(h, g, u, d):
        a = h @ np.asarray(g, np.float64)
        return (a / (1 + np.exp(-a)) * (h @ np.asarray(u, np.float64))) \
            @ np.asarray(d, np.float64)

    moe = p["moe"]
    want = swiglu(h, *(moe["shared"][k] for k in ("w_gate", "w_up", "w_down")))
    for n in range(24):
        gates = prob[n, chosen[n]]
        gates = gates / gates.sum() * 1.8
        for g, e in zip(gates, chosen[n]):
            want[n] += g * swiglu(h[n:n + 1], moe["w_gate"][e],
                                  moe["w_up"][e], moe["w_down"][e])[0]
    got, routing = blk._ffn(p, x, jnp.ones((1, 24), bool))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=F32, rtol=0)
    load = np.bincount(chosen.reshape(-1), minlength=8)
    assert list(np.asarray(routing)) == [48, int((load > 0).sum()),
                                         int(load.max())]


def test_the_chosen_gates_sum_to_the_scaling_factor():
    """Eight copies of ONE expert: whatever the router chooses, the routed
    part is (sum of the gates) x that expert = 1.8 x it (``norm_topk_prob``
    and ``routed_scaling_factor``), beside the shared expert."""
    m = build()
    blk = m.layers[2]
    p = jax.tree.map(lambda a: a, m.params["layer_2"])
    for k in ("w_gate", "w_up", "w_down"):
        p["moe"][k] = jnp.broadcast_to(p["moe"][k][:1], p["moe"][k].shape)
    x = jnp.asarray(np.random.default_rng(7).normal(0, 1, (2, 5, 64)),
                    jnp.float32)
    one = {k: p["moe"][k][0] for k in ("w_gate", "w_up", "w_down")}
    from deeplearning4j_tpu.nn.layers.norm import rms_norm

    h = rms_norm(x, p["ln2_g"], 1e-5).reshape(-1, 64)
    want = 1.8 * glm4_moe_lite._swiglu(h, one) \
        + glm4_moe_lite._swiglu(h, p["moe"]["shared"])
    got, _ = blk._ffn(p, x, None)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, 64),
                               np.asarray(want), atol=F32, rtol=0)


@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_latent_cache_logits_match_the_reference(dtype):
    """The paged layout itself, logits and not tokens: two sequences in
    chunks into blocks handed out in scrambled order, then single steps of
    BOTH rows at their own positions; then a third sequence through the
    first one's slot and blocks, left as they were (a slot re-used: what the
    longer tenant wrote past the newcomer's length must never be seen)."""
    m = build(dtype)
    pools = build_pools(m, 13, 8, m.dtype)
    names = {lk: tuple(parts) for lk, parts in cache_parts(m)}
    assert names == {f"layer_{i}": ("latent", "rope") for i in (1, 2, 3)}
    tables = np.zeros((2, 6), np.int32)
    tables[0], tables[1] = [7, 2, 5, 1, 8, 3], [12, 4, 9, 11, 6, 10]

    def run(ids, pools, rows, pos):
        caches = {lk: as_paged(pools[lk], jnp.asarray(tables[rows]))
                  for lk in names}
        lg, caches = decode_forward(m, m.params, m.state, jnp.asarray(ids),
                                    caches, jnp.asarray(pos, jnp.int32))
        return np.asarray(lg), {lk: paged_parts(caches[lk], names[lk])
                                for lk in names}

    seqs = [tokens(40, seed=9), tokens(29, seed=10)]
    got = [[], []]
    for row, (ids, upto) in enumerate(zip(seqs, (36, 25))):
        for lo in range(0, upto, 16):
            hi = min(lo + 16, upto)
            lg, pools = run(ids[None, lo:hi], pools, [row], [lo])
            got[row].append(lg[0])
    for step in range(4):                 # both rows, each at its position
        pos = [36 + step, 25 + step]
        lg, pools = run(np.asarray([[seqs[0][pos[0]]], [seqs[1][pos[1]]]]),
                        pools, [0, 1], pos)
        got[0].append(lg[0])
        got[1].append(lg[1])
    for ids, rows in zip(seqs, got):
        close(np.concatenate(rows), ref_logits(m.params, ids), dtype, CACHED)
        close(np.concatenate(rows), ref_logits(m.params, ids, jnp.dtype(m.dtype).name),
              dtype, WIDE)
    third, rows = tokens(25, seed=12), []
    for lo, hi in ((0, 16), (16, 23), (23, 24), (24, 25)):
        lg, pools = run(third[None, lo:hi], pools, [0], [lo])
        rows.append(lg[0])
    close(np.concatenate(rows), ref_logits(m.params, third), dtype, CACHED)
    close(np.concatenate(rows), ref_logits(m.params, third, jnp.dtype(m.dtype).name),
          dtype, WIDE)


def test_one_latent_and_one_rope_key_a_token_and_nothing_else():
    """The pool holds ``kv_lora_rank + qk_rope_head_dim`` values a token a
    layer, once: the latent and the rope key, no heads, nothing twice."""
    for dtype, size in (("float32", 4), ("bfloat16", 2)):
        m = build(dtype)
        assert block_bytes(m, 16, m.dtype) == 3 * 16 * LATENT * size
        pools = build_pools(m, 5, 16, m.dtype)
        assert {lk: {n: a.shape for n, a in p.items()}
                for lk, p in pools.items()} == {
            f"layer_{i}": {"latent": (5, 16, 16), "rope": (5, 16, 8)}
            for i in (1, 2, 3)}
    with pytest.raises(ValueError, match="cache_parts"):
        cache_spec(m)      # triples are for layers that keep k and v


def test_the_reference_leaves_out_routing_ties(monkeypatch):
    """``greedy_gaps`` judges the positions whose routing margin (the k-th
    selection score minus the (k+1)-th, smallest over the expert layers) is
    at least ``ROUTING_TIE``, reads that from its own scores alone, and a
    dense layer has no margin."""
    m = build()
    seq = tokens(30, seed=13)
    _, margin = ref.hidden_and_margin(m.params, seq, CFG)
    margin = np.asarray(margin)
    assert margin.shape == (30,) and (margin > 0).all()
    # by hand, for the second expert layer's last position
    pre, _ = m.forward(m.params, m.state, jnp.asarray(seq[None]), up_to=3)
    _, prob, _ = _router(m, "layer_3", np.asarray(pre))
    bias = np.asarray(m.params["layer_3"]["moe"]["e_score_correction_bias"])
    top = np.sort(prob[-1] + bias)[::-1]
    assert margin[-1] <= top[1] - top[2] + 1e-6
    prompt, out = list(seq[:20]), list(seq[20:])
    for tie in (0.0, float(np.median(margin[19:29])), 1.0):
        monkeypatch.setattr(ref, "ROUTING_TIE", tie)
        gap, spread = ref.greedy_gaps(m.params, prompt, out, CFG, 30, 10)
        assert len(gap) == len(spread) == int((margin[19:29] >= tie).sum())


def test_the_contract_check_accepts_the_model():
    m = build()
    assert check_decodes(m, 96, "cache capacity", served=True) == 128
    assert all(hasattr(l, "decode") and hasattr(l, "cache_spec")
               for l in m.layers[1:4])


def _batcher(m, **kw):
    opts = dict(slots=2, capacity=96, block_size=16, prefill_chunk=16,
                metrics=MetricsRegistry())
    opts.update(kw)
    return ContinuousBatcher(m, **opts)


def _counter(snap, name, program=None):
    return sum(s["value"] for s in snap[name]["series"]
               if program is None or s["labels"].get("program") == program)


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_serves_what_generate_gives(dtype):
    """Three requests on two slots through the paged latent pool, chunked
    prefill, the third adopting the first's cached 32-token prefix: the
    reference's tokens, and in f32 ``generate()``'s, token for token."""
    m = build(dtype)
    cb = _batcher(m)
    try:
        shared = tokens(32, seed=5)
        prompts = [np.concatenate([shared, tokens(9, seed=6)]),
                   tokens(21, seed=7),
                   np.concatenate([shared, tokens(5, seed=8)])]
        first = cb.generate(prompts[0], 10, temperature=0.0)
        reqs = [cb.submit(p, 10, temperature=0.0) for p in prompts[1:]]
        outs = [first] + [r.wait() for r in reqs]
        snap = cb.metrics.snapshot()
    finally:
        cb.shutdown()
    assert _counter(snap, "serve_prefix_cache_hits_total") == 1
    assert _counter(snap, "serve_prefill_tokens_saved_total") == 32
    assert _counter(snap, "serve_kv_token_bytes") \
        == 3 * LATENT * (4 if dtype == "float32" else 2)
    for prompt, out in zip(prompts, outs):
        assert len(out) == 10
        served_gap(m.params, prompt, out, dtype)
        if dtype == "float32":
            np.testing.assert_array_equal(
                out, generate(m, prompt[None], 10, temperature=0.0)[0])


def test_routing_counters_count_the_expert_layers_only():
    """Two of the three layers have experts: a program counts 2 of them,
    and a live token 2 experts in each."""
    m = build()
    cb = _batcher(m)
    try:
        cb.generate(tokens(21, seed=10), 6, temperature=0.0)
        snap = cb.metrics.snapshot()
    finally:
        cb.shutdown()
    chunks = _counter(snap, "serve_prefill_chunks_total")
    assert _counter(snap, "serve_moe_layer_programs_total", "prefill") \
        == 2 * chunks
    assert _counter(snap, "serve_moe_layer_programs_total", "decode") == 2 * 5
    assert _counter(snap, "serve_moe_assignments_total", "prefill") \
        == 21 * 2 * 2
    assert _counter(snap, "serve_moe_assignments_total", "decode") \
        == 5 * 2 * 2
    assert _counter(snap, "serve_moe_max_load_total", "decode") == 5 * 2


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
    "attention/mla_q", "attention/mla_kv", "attention/cache_read",
    "attention/mla_attend", "mlp", "moe_router", "moe_experts", "moe_shared"])
def test_named_scopes_reach_the_decode_program(decode_program_text, scope):
    assert re.search(rf'"[^"]*[/(]{scope}[/)]', decode_program_text), scope
