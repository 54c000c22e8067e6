"""OLMoE on the CPU at a small size (d 64, 4 heads, 8 experts top-2, width 32,
2 layers, vocab 128), seeded weights, against the plain reference the
benchmark keeps (``benchmark/references/olmoe_block.py``: f32, "highest", a
loop over experts, nothing imported from the program).

Tolerances, each with its reason:

- f32 parameters (``F32``): both sides compute in f32 and differ only in the
  order of sums (one matmul over (expert, width) against a loop over experts;
  a fused QKV against the same matrix): a few ulp of logits of order 1 over
  two layers. 2e-5 absolute is about twenty of them.
- bf16 parameters (``BF16``): the reference is fed the SAME bf16 values, so
  what differs is the program's bf16 activations against f32. Errors are
  taken relative to the logits' standard deviation. Measured here over six
  token seeds: the largest error 0.021 to 0.032 deviations (a bf16 value
  carries 8 bits: 2**-9 relative rounding after each of some forty
  operations of two layers); the same reference fed float8_e4m3 values
  (16 times bf16's rounding) reads 0.24 to 0.33. The bound 0.08 sits between,
  and ``test_an_8_bit_computation_would_fail`` holds it to that.
- greedy tokens are compared through the reference's logits: the gap between
  its largest logit and its logit for the token served, 0 where they agree.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn import layers as L
from deeplearning4j_tpu.nn.generation import (cache_spec, decode_forward,
                                              generate, init_caches)
from deeplearning4j_tpu.nn.model import NetConfig, SequentialBuilder
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
       "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
       "intermediate_size": 32, "vocab_size": 128, "rms_norm_eps": 1e-5,
       "rope_theta": 10000.0}
F32 = 2e-5      # absolute, on logits of order 1
BF16 = 0.08     # in standard deviations of the reference's logits
DTYPES = ["float32", "bfloat16"]


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references", "olmoe_block.py")
    spec = importlib.util.spec_from_file_location("olmoe_block_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def build(dtype="float32", top_k=2):
    m = models.OlmoeLM(seed=3, input_shape=(96,), num_layers=2,
                       d_model=64, num_heads=4, num_experts=8, top_k=top_k,
                       expert_width=32, vocab=128, dtype=dtype).build()
    m.init()
    return m


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def ref_logits(params, ids):
    return np.asarray(ref.logits(params, ref.hidden(params, ids, CFG), CFG))


def close(got, want, dtype):
    """``got`` against the reference's ``want`` at the dtype's tolerance."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=F32, rtol=0)
    else:
        assert np.abs(got - want).max() <= BF16 * want.std()


def served_gap(params, prompt, out, dtype):
    """The served tokens under the reference's logits: the largest gap, in
    logit deviations, must be a rounding tie."""
    gap, spread = ref.greedy_gaps(params, list(prompt), list(out), CFG,
                                  pad_to=len(prompt) + len(out),
                                  last=len(out))
    assert len(gap) == len(out)
    assert (gap / spread).max() <= (1e-5 if dtype == "float32" else BF16)


@pytest.mark.parametrize("dtype", DTYPES)
def test_forward_logits_match_the_reference(dtype):
    m = build(dtype)
    ids = tokens(40)
    pre, _ = m.forward(m.params, m.state, jnp.asarray(ids[None]),
                       up_to=len(m.layers) - 1)
    got = m.layers[-1].preactivation(m.params["layer_4"], pre)[0]
    assert got.dtype == m.dtype
    close(got, ref_logits(m.params, ids), dtype)


@pytest.mark.parametrize("dtype,top_k", [("float32", 2), ("bfloat16", 8)])
def test_score_and_its_gradient_match_the_reference(dtype, top_k):
    """The sparse backward pass in f32, where both sides route alike. In
    bf16 a near-tie at the k-th place now and then sends a token to another
    expert than in f32 (routing is a step function), which moves that token's
    gradient from one expert's weights to another's: nothing a tolerance on a
    leaf can absorb. So the bf16 arithmetic of the backward pass is checked
    with every expert chosen (top-8 of 8: the same code, no step)."""
    m = build(dtype, top_k=top_k)
    cfg = {**CFG, "num_experts_per_tok": top_k}
    ids = tokens(33, seed=1)
    x, y = ids[None, :-1], ids[None, 1:]

    def loss(p):
        return m.score(p, m.state, jnp.asarray(x),
                       jax.nn.one_hot(y, 128), training=True)[0]

    def ref_loss(p):
        return ref._nll(ref.logits(p, ref.hidden(p, x[0], cfg), cfg),
                        jnp.asarray(y[0]))

    got, g_got = jax.value_and_grad(loss)(m.params)
    want, g_want = jax.value_and_grad(ref_loss)(m.params)
    # a mean over 32 log-probabilities near log(128); in bf16 the loss
    # itself is a bf16 number (steps of 0.03 near 4.8)
    assert abs(float(got) - float(want)) <= \
        (1e-5 if dtype == "float32" else 0.03)
    # each leaf's gradient by its relative L2 error. f32: measured 7e-7.
    # bf16: measured at most 0.010; the same reference on float8_e4m3 values
    # reads 0.06 to 0.27, so 0.03 parts the two
    bound = 1e-5 if dtype == "float32" else 0.03
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= bound * np.linalg.norm(b)


def test_dropless_every_token_on_the_same_two_experts():
    """A router that sends every token to experts 0 and 1: 2N rows on two
    experts of eight. The capacity layer (``MoE``, factor 1.25) would keep
    0.31N rows an expert and drop the rest; this one equals the reference."""
    m = build()
    p = jax.tree.map(lambda a: a, m.params)
    # one column of the embedding the same for every token, so that every
    # normalised hidden state has one large component, and a router that
    # reads it into experts 0 and 1
    emb = np.array(p["layer_0"]["w"])
    emb[:, 5] = 3.0
    p["layer_0"]["w"] = jnp.asarray(emb)
    for k in ("layer_1", "layer_2"):
        w = np.array(p[k]["moe"]["w_router"])
        w[5, :2] = 6.0
        p[k]["moe"]["w_router"] = jnp.asarray(w)
    ids = tokens(24, seed=2)
    x = jnp.take(p["layer_0"]["w"], jnp.asarray(ids[None]), axis=0)
    blk = m.layers[1]
    _, routing = blk._ffn(p["layer_1"], x, jnp.ones((1, 24), bool))
    assert list(np.asarray(routing)) == [48, 2, 24]   # pairs, touched, fullest
    pre, _ = m.forward(p, m.state, jnp.asarray(ids[None]), up_to=4)
    got = m.layers[-1].preactivation(p["layer_4"], pre)[0]
    close(got, ref_logits(p, ids), "float32")


@pytest.mark.parametrize("dtype", DTYPES)
def test_generate_and_decode_forward_dense_layout(dtype):
    """Chunked prefill then single steps through the dense cache give the
    full forward's logits; generate()'s greedy tokens are the reference's."""
    m = build(dtype)
    ids = tokens(30, seed=4)
    caches = init_caches(m, 1, 48, m.dtype)
    got = []
    for lo, hi in ((0, 16), (16, 27), (27, 28), (28, 29), (29, 30)):
        lg, caches = decode_forward(m, m.params, m.state,
                                    jnp.asarray(ids[None, lo:hi]), caches,
                                    jnp.int32(lo))
        got.append(np.asarray(lg[0]))
    got = np.concatenate(got)
    assert got.dtype == np.float32          # logits leave in f32
    close(got, ref_logits(m.params, ids), dtype)
    out = generate(m, ids[None, :20], 12, temperature=0.0)[0]
    served_gap(m.params, ids[:20], out, dtype)


def _batcher(m, **kw):
    opts = dict(slots=2, capacity=96, block_size=16, prefill_chunk=16,
                metrics=MetricsRegistry())
    opts.update(kw)
    return ContinuousBatcher(m, **opts)


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_paged_chunked_prefill_and_prefix_hit(dtype):
    """Three requests on two slots: chunked prefill through the paged pool,
    the third adopting the first's cached 32-token prefix."""
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
        assert snap["serve_prefix_cache_hits_total"]["series"][0]["value"] == 1
        assert snap["serve_prefill_tokens_saved_total"]["series"][0]["value"] == 32
        assert snap["serve_prefill_chunks_total"]["series"][0]["value"] >= 6
    finally:
        cb.shutdown()
    for prompt, out in zip(prompts, outs):
        assert len(out) == 10
        served_gap(m.params, prompt, out, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_fork_mid_decode_continues_as_the_parent(dtype):
    """A fork shares the parent's blocks and its pending token; greedy, the
    child's tokens are the parent's from the fork point on, through a
    copy of the tail block both would have written."""
    import time

    from deeplearning4j_tpu.serve.errors import ServeError

    m = build(dtype)
    cb = _batcher(m, prefix_cache=False)
    try:
        cb.generate(tokens(5, seed=20), 2, temperature=0.0)  # compiled
        real = cb._programs.decode

        def slow(*a):       # so that the fork lands while the parent decodes
            time.sleep(0.02)
            return real(*a)

        cb._programs.decode = slow
        prompt = tokens(21, seed=21)
        req = cb.submit(prompt, 14, temperature=0.0)
        child = None
        while child is None and not req.event.is_set():
            try:
                child = cb.fork(req)
            except ServeError:
                time.sleep(0)       # still queued or prefilling
        out = req.wait()
        assert child is not None, "the parent finished before a fork landed"
        tail = child.wait()
        stats = cb.kv_block_stats()
    finally:
        cb.shutdown()
    assert 1 <= len(tail) <= 14 and stats["forks"] == 1
    np.testing.assert_array_equal(tail, out[-len(tail):])
    served_gap(m.params, prompt, out, dtype)
    assert stats["blocks_used"] == 0 and stats["blocks_shared"] == 0


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_whole_prompt_prefill_equals_chunked(dtype):
    """``prefill_chunk=None`` runs a prompt as one program padded to a
    prompt bucket; chunked, the same prompt is three programs of 16. Both
    serve the reference's tokens, and in f32 the same ones."""
    m = build(dtype)
    prompt = tokens(37, seed=22)
    outs = []
    for chunk in (16, None):
        cb = _batcher(m, prefill_chunk=chunk)
        try:
            outs.append(cb.generate(prompt, 10, temperature=0.0))
            chunks = _counter(cb.metrics.snapshot(),
                              "serve_prefill_chunks_total")
        finally:
            cb.shutdown()
        assert chunks == (3 if chunk else 1)
        served_gap(m.params, prompt, outs[-1], dtype)
    if dtype == "float32":
        np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_seeded_sampled_tokens_repeat(dtype):
    """Sampling is the batcher's seed and the order of admission, nothing
    else: a second batcher gives the first's tokens, another seed others."""
    m = build(dtype)
    prompts = [tokens(21, seed=23), tokens(9, seed=24)]

    def run(seed):
        cb = _batcher(m, seed=seed)
        try:
            return [cb.generate(p, 12, temperature=0.9, top_k=20)
                    for p in prompts]
        finally:
            cb.shutdown()

    first, again, other = run(0), run(0), run(1)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert any(not np.array_equal(a, b) for a, b in zip(first, other))


def test_paged_decode_logits_match_the_reference():
    """The paged layout itself, logits and not tokens: a prompt in chunks
    into pool blocks in scrambled order, then single steps."""
    m = build()
    ids = tokens(40, seed=9)
    (lk1, hkv, hd), (lk2, _, _) = cache_spec(m)
    assert (hkv, hd) == (4, 16)
    pool = lambda: jnp.zeros((9, 8, hkv, hd), jnp.float32)
    table = jnp.asarray([[7, 2, 5, 1, 8, 3]], jnp.int32)
    caches = {lk: {"k_pool": pool(), "v_pool": pool(), "tables": table}
              for lk in (lk1, lk2)}
    got = []
    for lo, hi in ((0, 16), (16, 32), (32, 37), (37, 38), (38, 39), (39, 40)):
        lg, caches = decode_forward(m, m.params, m.state,
                                    jnp.asarray(ids[None, lo:hi]), caches,
                                    jnp.asarray([lo], jnp.int32))
        got.append(np.asarray(lg[0]))
    close(np.concatenate(got), ref_logits(m.params, ids), "float32")


def test_an_8_bit_computation_would_fail():
    """The bf16 bound has power: the reference fed 8-bit values (every
    weight rounded to float8_e4m3, which is the least an 8-bit computation
    would do) leaves it by a wide margin."""
    m = build("bfloat16")
    ids = tokens(40)
    want = ref_logits(m.params, ids)
    eight = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), m.params)
    err = np.abs(ref_logits(eight, ids) - want).max() / want.std()
    assert err > 2 * BF16


def test_batcher_refuses_a_block_that_does_not_say_how_it_decodes():
    """The capacity-dropping ``MoETransformerBlock`` has attention inside and
    no decode(): served through the token-local branch it would attend
    without a cache."""
    m = (SequentialBuilder(NetConfig(seed=0)).input_shape(32)
         .layer(L.EmbeddingSequence(n_in=64, n_out=32))
         .layer(L.MoETransformerBlock(num_heads=4, num_experts=4, causal=True))
         .layer(L.RnnOutput(n_out=64)).build())
    m.init()
    with pytest.raises(ValueError, match=r"MoETransformerBlock does not say "
                                         r"how it decodes.*decode\(params, x, "
                                         r"cache, pos\).*cache_spec"):
        ContinuousBatcher(m, slots=2, capacity=32)
    with pytest.raises(ValueError, match="says_how_it_decodes"):
        generate(m, np.ones((1, 4), np.int32), 2)


def _counter(snap, name, program=None):
    return sum(s["value"] for s in snap[name]["series"]
               if program is None or s["labels"].get("program") == program)


def test_routing_counters_count_real_tokens_only():
    m = build()
    cb = _batcher(m)
    try:
        prompts = [tokens(21, seed=10), tokens(37, seed=11)]
        for p in prompts:
            cb.generate(p, 6, temperature=0.0)
        snap = cb.metrics.snapshot()
    finally:
        cb.shutdown()
    layers, k, experts = 2, 2, 8
    # prefill: every prompt token once (no shared prefix between the two)
    assert _counter(snap, "serve_moe_assignments_total", "prefill") \
        == (21 + 37) * k * layers
    chunks = _counter(snap, "serve_prefill_chunks_total")
    assert _counter(snap, "serve_moe_layer_programs_total", "prefill") \
        == chunks * layers
    # decode: one request at a time on two slots, five steps each (the first
    # of six tokens is the prefill's); the idle slot's row counts nothing
    steps = _counter(snap, "serve_moe_layer_programs_total", "decode") / layers
    assert steps == 10
    assert _counter(snap, "serve_moe_assignments_total", "decode") \
        == 10 * k * layers
    for prog in ("prefill", "decode"):
        touched = _counter(snap, "serve_moe_experts_touched_total", prog)
        programs = _counter(snap, "serve_moe_layer_programs_total", prog)
        assert 0 < touched <= experts * programs
        assert _counter(snap, "serve_moe_max_load_total", prog) >= programs
    # one live token a step: it touches exactly k experts, the fullest has 1
    assert _counter(snap, "serve_moe_experts_touched_total", "decode") \
        == 10 * k * layers
    assert _counter(snap, "serve_moe_max_load_total", "decode") == 10 * layers


def test_a_model_without_experts_registers_no_routing_counter():
    m = models.CausalLM(seed=0, input_shape=(64,), num_layers=1, d_model=32,
                        num_heads=2, vocab=64).build()
    m.init()
    cb = ContinuousBatcher(m, slots=2, capacity=64, metrics=MetricsRegistry())
    try:
        cb.generate(np.arange(1, 9, dtype=np.int32), 3, temperature=0.0)
        names = set(cb.metrics.snapshot())
    finally:
        cb.shutdown()
    assert not {n for n in names if n.startswith("serve_moe_")}


def test_bf16_parameters_are_held_once():
    """``dtype="bfloat16"`` is the dtype of the tree itself: no
    compute_dtype, so ``decode_params`` hands the tree back and the batcher
    makes no copy."""
    from deeplearning4j_tpu.nn.generation import decode_params

    m = build("bfloat16")
    assert m.config.compute_dtype is None
    assert {str(a.dtype) for a in jax.tree.leaves(m.params)} == {"bfloat16"}
    assert decode_params(m, m.params) is m.params
    cb = _batcher(m)
    try:
        cb.generate(tokens(9), 3, temperature=0.0)
        snap = cb.metrics.snapshot()
    finally:
        cb.shutdown()
    assert _counter(snap, "serve_params_cast_total") == 0
    assert {str(p["k"].dtype) for p in cb._programs.pools.values()} == {"bfloat16"}
