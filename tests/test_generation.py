"""Autoregressive generation: KV-cache decode + sampling (nn/generation.py).

The load-bearing oracle is EQUIVALENCE (SURVEY §4): incremental decode with
KV caches must reproduce the full-sequence forward pass position for
position, for both the attention family (CausalLM) and the recurrent family
(TextGenerationLSTM one-hot char models) — the rnnTimeStep contract
(MultiLayerNetwork.java:2800) generalized to attention caches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import CausalLM, TextGenerationLSTM
from deeplearning4j_tpu.nn.generation import (decode_forward, init_caches,
                                              generate, sample_logits)


def _stepwise_logits(model, prompt, capacity):
    """Feed tokens one at a time through the decode path; collect logits."""
    caches = init_caches(model, prompt.shape[0], capacity, model.dtype)
    outs = []
    for t in range(prompt.shape[1]):
        chunk = prompt[:, t:t + 1]
        lg, caches = decode_forward(model, model.params, model.state,
                                     jnp.asarray(chunk), caches, t)
        outs.append(np.asarray(lg[:, 0]))
    return np.stack(outs, axis=1)  # (B, T, V)


class TestCausalLMDecode:
    def setup_method(self):
        self.zm = CausalLM(seed=0, input_shape=(16,), num_layers=2,
                           d_model=32, num_heads=4, vocab=50)
        self.model = self.zm.build()
        self.model.init()
        rng = np.random.RandomState(0)
        self.prompt = rng.randint(0, 50, (2, 10)).astype(np.int32)

    def _full_logprobs(self, ids):
        probs = self.model.output(jnp.asarray(ids))
        return np.log(np.asarray(probs) + 1e-20)

    def test_prefill_matches_full_forward(self):
        caches = init_caches(self.model, 2, 16, self.model.dtype)
        lg, _ = decode_forward(self.model, self.model.params,
                                self.model.state, jnp.asarray(self.prompt),
                                caches, 0)
        got = np.asarray(jax.nn.log_softmax(lg, axis=-1))
        want = self._full_logprobs(self.prompt)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_stepwise_decode_matches_full_forward(self):
        lg = _stepwise_logits(self.model, self.prompt, capacity=16)
        got = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
        want = self._full_logprobs(self.prompt)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_greedy_generate_matches_argmax_rollout(self):
        n_new = 5
        toks = generate(self.model, self.prompt, n_new, temperature=0.0)
        assert toks.shape == (2, n_new)
        # oracle: repeated FULL forward + argmax (no caches involved)
        ids = self.prompt.copy()
        for _ in range(n_new):
            probs = np.asarray(self.model.output(jnp.asarray(ids)))
            nxt = probs[:, -1].argmax(-1).astype(np.int32)
            ids = np.concatenate([ids, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(toks, ids[:, -n_new:])

    def test_sampled_generate_reproducible_and_in_range(self):
        r = jax.random.PRNGKey(7)
        a = generate(self.model, self.prompt, 4, temperature=0.8, rng=r)
        b = generate(self.model, self.prompt, 4, temperature=0.8, rng=r)
        np.testing.assert_array_equal(a, b)
        assert a.min() >= 0 and a.max() < 50

    def test_capacity_and_position_guards(self):
        with pytest.raises(ValueError, match="capacity"):
            generate(self.model, self.prompt, 5, capacity=10)
        zm = CausalLM(seed=0, input_shape=(16,), num_layers=1, d_model=32,
                      num_heads=4, vocab=50)
        m = zm.build()
        m.init()
        # PositionalEmbedding(max_len=512) default is fine; shrink the check
        from deeplearning4j_tpu.nn.layers import PositionalEmbedding
        for i, l in enumerate(m.layers):
            if isinstance(l, PositionalEmbedding):
                m.layers[i] = PositionalEmbedding(max_len=12)
        with pytest.raises(ValueError, match="max_len"):
            generate(m, self.prompt, 5)

    def test_rejects_non_causal_and_sequence_global_models(self):
        from deeplearning4j_tpu.models import BertBase
        bert = BertBase(small=True, num_classes=3, input_shape=(16,)).build()
        bert.init()
        ids = np.zeros((1, 4), np.int32)
        # BERT: non-causal attention first; even with causal blocks, its
        # GlobalPooling head is sequence-global — both must be rejected
        with pytest.raises(ValueError, match="causal"):
            generate(bert, ids, 3)
        from deeplearning4j_tpu.nn.layers import TransformerEncoderBlock
        for i, l in enumerate(bert.layers):
            if isinstance(l, TransformerEncoderBlock):
                bert.layers[i] = TransformerEncoderBlock(
                    num_heads=l.num_heads, causal=True)
        with pytest.raises(ValueError, match="GlobalPooling"):
            generate(bert, ids, 3)

    def test_one_contract_for_generate_and_the_batcher(self):
        """``check_decodes`` is both decode loops' gate: it names the
        context it was asked about, returns the vocabulary, and ``served``
        adds the batcher's terms (embedding front, no carry, Output last)."""
        from deeplearning4j_tpu.nn import layers as L
        from deeplearning4j_tpu.nn.generation import check_decodes
        from deeplearning4j_tpu.nn.model import NetConfig, SequentialBuilder

        assert check_decodes(self.model, 16, "prompt+new tokens") == 50
        assert check_decodes(self.model, 512, "cache capacity",
                             served=True) == 50
        with pytest.raises(ValueError, match="shorter than cache capacity 513"):
            check_decodes(self.model, 513, "cache capacity", served=True)
        rnn = (SequentialBuilder(NetConfig(seed=0)).input_shape(8)
               .layer(L.EmbeddingSequence(n_in=50, n_out=16))
               .layer(L.LSTM(n_out=16))
               .layer(L.RnnOutput(n_out=50)).build())
        assert check_decodes(rnn, 8, "prompt+new tokens") == 50
        with pytest.raises(ValueError, match="recurrent carries"):
            check_decodes(rnn, 8, "cache capacity", served=True)
        chars = TextGenerationLSTM(seed=0, input_shape=(8, 20),
                                   hidden=16).build()
        with pytest.raises(ValueError, match="embedding-front"):
            check_decodes(chars, 8, "cache capacity", served=True)
        mid = (SequentialBuilder(NetConfig(seed=0)).input_shape(8)
               .layer(L.EmbeddingSequence(n_in=50, n_out=16))
               .layer(L.RnnOutput(n_out=16))
               .layer(L.RnnOutput(n_out=50)).build())
        with pytest.raises(ValueError, match="layer 1 RnnOutput does not say"):
            check_decodes(mid, 8, "prompt+new tokens")

    def test_repeated_calls_reuse_compiled_program(self):
        a = generate(self.model, self.prompt, 3, temperature=0.0)
        assert len(self.model.__dict__["_generate_jit_cache"]) == 1
        b = generate(self.model, self.prompt, 3, temperature=0.0)
        assert len(self.model.__dict__["_generate_jit_cache"]) == 1
        np.testing.assert_array_equal(a, b)


class TestRnnDecode:
    def setup_method(self):
        self.zm = TextGenerationLSTM(seed=0, input_shape=(12, 30))
        self.zm.num_classes = 30
        self.model = self.zm.build()
        self.model.init()
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 30, (2, 8))
        self.prompt = np.eye(30, dtype=np.float32)[ids]  # (B, T, V) one-hot

    def test_stepwise_decode_matches_full_forward(self):
        lg = _stepwise_logits(self.model, self.prompt, capacity=16)
        got = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
        probs = self.model.output(jnp.asarray(self.prompt))
        want = np.log(np.asarray(probs) + 1e-20)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_greedy_generate_matches_argmax_rollout(self):
        n_new = 4
        toks = generate(self.model, self.prompt, n_new, temperature=0.0)
        assert toks.shape == (2, n_new)
        x = self.prompt.copy()
        for _ in range(n_new):
            probs = np.asarray(self.model.output(jnp.asarray(x)))
            nxt = probs[:, -1].argmax(-1)
            x = np.concatenate([x, np.eye(30, dtype=np.float32)[nxt][:, None]],
                               axis=1)
        want = x[:, -n_new:].argmax(-1)
        np.testing.assert_array_equal(toks, want)


class TestSampling:
    def test_temperature_zero_is_argmax(self):
        logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 5.0]])
        got = sample_logits(logits, jax.random.PRNGKey(0), temperature=0.0)
        np.testing.assert_array_equal(np.asarray(got), [1, 2])

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]] * 64)
        toks = np.asarray(sample_logits(
            logits, jax.random.PRNGKey(3), temperature=1.0, top_k=2))
        assert set(toks.tolist()) <= {3, 4}

    def test_low_temperature_concentrates(self):
        logits = jnp.asarray([[0.0, 0.5, 1.0]] * 128)
        toks = np.asarray(sample_logits(
            logits, jax.random.PRNGKey(5), temperature=0.05))
        assert (toks == 2).mean() > 0.95


class TestRopeDecode:
    """RoPE (pos="rope") through the same equivalence oracle: incremental
    decode with absolute-position rotation must reproduce the full forward
    (cached keys rotate once, at their own positions)."""

    def setup_method(self):
        self.zm = CausalLM(seed=0, input_shape=(16,), num_layers=2,
                           d_model=32, num_heads=4, vocab=50, pos="rope")
        self.model = self.zm.build()
        self.model.init()
        rng = np.random.RandomState(1)
        self.prompt = rng.randint(0, 50, (2, 10)).astype(np.int32)

    def _full_logprobs(self, ids):
        probs = self.model.output(jnp.asarray(ids))
        return np.log(np.asarray(probs) + 1e-20)

    def test_rope_has_no_learned_table(self):
        from deeplearning4j_tpu.nn.layers.attention import PositionalEmbedding
        assert not any(isinstance(l, PositionalEmbedding)
                       for l in self.model.layers)

    def test_stepwise_decode_matches_full_forward(self):
        lg = _stepwise_logits(self.model, self.prompt, capacity=16)
        got = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
        want = self._full_logprobs(self.prompt)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_greedy_generate_matches_argmax_rollout(self):
        n_new = 4
        toks = generate(self.model, self.prompt, n_new, temperature=0.0)
        x = self.prompt.copy()
        for _ in range(n_new):
            probs = np.asarray(self.model.output(jnp.asarray(x)))
            nxt = probs[:, -1].argmax(-1).astype(np.int32)
            x = np.concatenate([x, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(toks), x[:, -n_new:])

    def test_shift_invariance(self):
        """Attention scores under RoPE depend only on relative distance."""
        from deeplearning4j_tpu.nn.layers.attention import rope_rotate
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 3, 2, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 3, 2, 8), jnp.float32)
        def scores(shift):
            pos = jnp.arange(3) + shift
            qr = rope_rotate(q, pos)
            kr = rope_rotate(k, pos)
            return np.asarray(jnp.einsum("bqhd,bkhd->bhqk", qr, kr))
        np.testing.assert_allclose(scores(0), scores(37), rtol=2e-4, atol=2e-4)

    def test_config_roundtrip(self):
        from deeplearning4j_tpu.nn.model import Sequential
        js = self.model.to_json()
        m2 = Sequential.from_json(js)
        m2.init()
        blocks = [l for l in m2.layers
                  if type(l).__name__ == "TransformerEncoderBlock"]
        assert blocks and all(l.rope for l in blocks)


class TestGQADecode:
    """Grouped-query attention: the KV cache holds only num_kv_heads heads
    (the serving memory win) and decode still reproduces the full forward."""

    def _build(self, kv):
        zm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                      num_heads=4, vocab=50, pos="rope", num_kv_heads=kv)
        m = zm.build()
        m.init()
        return m

    @pytest.mark.parametrize("kv", [1, 2])
    def test_stepwise_decode_matches_full_forward(self, kv):
        model = self._build(kv)
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, 50, (2, 10)).astype(np.int32)
        lg = _stepwise_logits(model, prompt, capacity=16)
        got = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
        want = np.log(np.asarray(model.output(jnp.asarray(prompt))) + 1e-20)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_cache_is_kv_head_sized(self):
        from deeplearning4j_tpu.nn.generation import init_caches
        model = self._build(1)  # MQA
        caches = init_caches(model, 2, 16, model.dtype)
        shapes = {tuple(c["k"].shape) for c in caches.values()
                  if isinstance(c, dict) and "k" in c}
        assert shapes == {(2, 16, 1, 8)}  # 1 kv head, hd=8 — 4x smaller

    def test_config_roundtrip(self):
        from deeplearning4j_tpu.nn.model import Sequential
        model = self._build(2)
        m2 = Sequential.from_json(model.to_json())
        m2.init()
        blocks = [l for l in m2.layers
                  if type(l).__name__ == "TransformerEncoderBlock"]
        assert blocks and all(l.num_kv_heads == 2 for l in blocks)
        # param shapes must match (qkv projection is d + 2*d_kv wide)
        import jax.tree_util as jtu
        s1 = jtu.tree_map(lambda a: a.shape, model.params)
        s2 = jtu.tree_map(lambda a: a.shape, m2.params)
        assert s1 == s2

    def test_indivisible_heads_rejected(self):
        from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention
        lay = MultiHeadAttention(num_heads=4, num_kv_heads=3)
        with pytest.raises(ValueError, match="divisible"):
            lay.init(jax.random.PRNGKey(0), (8, 32))


class TestWindowedDecode:
    """Sliding-window CausalLM: KV-cache decode applies the same band mask
    as training, so stepwise decode == full forward."""

    def test_stepwise_decode_matches_full_forward(self):
        zm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                      num_heads=4, vocab=50, pos="rope", window=5)
        model = zm.build()
        model.init()
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, 50, (2, 12)).astype(np.int32)
        lg = _stepwise_logits(model, prompt, capacity=16)
        got = np.asarray(jax.nn.log_softmax(jnp.asarray(lg), axis=-1))
        want = np.log(np.asarray(model.output(jnp.asarray(prompt))) + 1e-20)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_window_changes_the_distribution(self):
        """Sanity: the band actually restricts attention (windowed logits
        differ from full-causal logits for positions past the window)."""
        common = dict(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                      num_heads=4, vocab=50, pos="rope")
        mw = CausalLM(window=3, **common).build(); mw.init()
        mf = CausalLM(**common).build(); mf.init()
        rng = np.random.RandomState(6)
        prompt = jnp.asarray(rng.randint(0, 50, (1, 12)).astype(np.int32))
        ow = np.asarray(mw.output(prompt))
        of = np.asarray(mf.output(prompt))
        np.testing.assert_allclose(ow[:, :3], of[:, :3], atol=1e-5)  # in-window
        assert np.abs(ow[:, 8:] - of[:, 8:]).max() > 1e-4  # band bites


class TestKVCacheContract:
    """cache_append/cache_read layout contract (serve/paged.py builds on
    this): the paged pool+block-table cache is observationally identical to
    the dense cache for every position actually written, and writes past
    the table — right-padded prefill garbage — land ONLY in trash block 0,
    never corrupting an allocated block."""

    def _paged(self, B=2, Hkv=2, hd=4, bs=4, maxb=3, tables=None):
        if tables is None:  # rows own disjoint blocks 1..B*maxb
            tables = 1 + np.arange(B * maxb).reshape(B, maxb)
        n = 1 + B * maxb
        return {"k_pool": jnp.zeros((n, bs, Hkv, hd), jnp.float32),
                "v_pool": jnp.zeros((n, bs, Hkv, hd), jnp.float32),
                "tables": jnp.asarray(tables, jnp.int32)}

    def test_paged_matches_dense_scalar_and_vector_pos(self):
        from deeplearning4j_tpu.nn.generation import cache_append, cache_read

        B, Hkv, hd = 2, 2, 4
        paged = self._paged()
        dense = {"k": jnp.zeros((B, 12, Hkv, hd), jnp.float32),
                 "v": jnp.zeros((B, 12, Hkv, hd), jnp.float32)}
        rng = np.random.RandomState(0)

        def chunk(T):
            return (jnp.asarray(rng.randn(B, T, Hkv, hd), jnp.float32),
                    jnp.asarray(rng.randn(B, T, Hkv, hd), jnp.float32))

        k, v = chunk(5)  # prefill chunk crossing a block edge, scalar pos
        paged = cache_append(paged, k, v, 0)
        dense = cache_append(dense, k, v, 0)
        k, v = chunk(1)  # decode tick at per-row offsets (vector pos)
        pos = jnp.asarray([5, 3], jnp.int32)
        paged = cache_append(paged, k, v, pos)
        dense = cache_append(dense, k, v, pos)
        pk, pv = cache_read(paged)
        dk, dv = cache_read(dense)
        # both start zero-filled, so the FULL logical views must agree
        assert pk.shape == dk.shape == (B, 12, Hkv, hd)
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(dk))
        np.testing.assert_array_equal(np.asarray(pv), np.asarray(dv))

    def test_out_of_table_writes_hit_only_the_trash_block(self):
        from deeplearning4j_tpu.nn.generation import cache_append, cache_read

        paged = self._paged(maxb=2)  # rows: [1,2], [3,4]; 8 logical slots
        rng = np.random.RandomState(1)
        k = jnp.asarray(rng.randn(2, 4, 2, 4), jnp.float32)
        # positions 6..9: 6,7 are in-table (block row[1], offs 2,3);
        # 8,9 overflow the table -> must be routed to trash block 0
        out = cache_append(paged, k, k, 6)
        rk, _ = cache_read(out)
        np.testing.assert_array_equal(np.asarray(rk[:, 6:8]),
                                      np.asarray(k[:, :2]))
        kp = np.asarray(out["k_pool"])
        assert np.all(kp[1] == 0) and np.all(kp[3] == 0)  # blocks 0..3 clean
        assert np.all(kp[2, :2] == 0) and np.all(kp[4, :2] == 0)
        assert np.abs(kp[0]).sum() > 0  # trash absorbed the overflow

    def test_zero_table_entries_route_to_trash(self):
        from deeplearning4j_tpu.nn.generation import cache_append

        # second logical block unallocated (table entry 0 = trash): the
        # batcher's lazy allocator leaves exactly this state mid-request
        paged = self._paged(maxb=2, tables=[[1, 0], [2, 0]])
        k = jnp.ones((2, 1, 2, 4), jnp.float32)
        out = cache_append(paged, k, k, jnp.asarray([4, 4], jnp.int32))
        kp = np.asarray(out["k_pool"])
        assert np.all(kp[1] == 0) and np.all(kp[2] == 0)  # real blocks clean
        assert np.abs(kp[0, 0]).sum() > 0  # landed in trash instead
