"""``nn.generation.top_k_threshold`` against the formula it replaced (ISSUE 29).

The samplers used to sort the vocabulary to read one value of it, the k-th
largest. The threshold now comes from a search that counts, whatever
``top_k`` is. What may not change is any token: the formula the parent ran
is written out here (``sort(scaled)[V - k]``, the mask, ``categorical``),
and thresholds, masks and tokens are compared with it bit for bit, at the
served vocabularies, on logits rounded to bf16 with ties planted AT the
k-th value.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import generation as G

ROWS = 3


# ------------------------------------------------- the parent's formula
def _old_rows(logits, keys, temps, tks):
    """``serve/programs.py`` before ISSUE 29: every row sorts."""
    V = logits.shape[-1]

    def one(logits, key, temperature, top_k):
        greedy = jnp.argmax(logits, axis=-1)
        scaled = logits / jnp.maximum(temperature, 1e-6)
        srt = jnp.sort(scaled, axis=-1)  # ascending
        kth = jnp.take(srt, V - jnp.clip(top_k, 1, V), axis=-1)
        masked = jnp.where(scaled >= kth, scaled, -1e30)
        samp = jax.random.categorical(key, masked, axis=-1)
        return (jnp.where(temperature <= 0.0, greedy, samp).astype(jnp.int32),
                kth, scaled >= kth)

    return jax.vmap(one)(logits, keys, temps, tks)


def _new_rows(logits, keys, temps, tks):
    """The sampler as it is served: thresholds for the batch, draws by row."""
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    kth = G.top_k_threshold(scaled, tks)

    def one(l, s, key, temp, kth):
        masked = jnp.where(s >= kth, s, -1e30)
        samp = jax.random.categorical(key, masked, axis=-1)
        return (jnp.where(temp <= 0.0, jnp.argmax(l, axis=-1),
                          samp).astype(jnp.int32), s >= kth)

    toks, mask = jax.vmap(one)(logits, scaled, keys, temps, kth)
    return toks, kth, mask


@functools.lru_cache(maxsize=None)
def _programs(vocab):   # one compile a vocabulary: top_k is traced
    return jax.jit(_old_rows), jax.jit(_new_rows)


def _logits(vocab, rows, seed, tie_at=()):
    """bf16-rounded logits; row i gets two more copies of its ``tie_at[i]``-th
    largest value, so that the mask holds more than k entries there."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.standard_normal((rows, vocab)) * 3.0, jnp.bfloat16)
    x = np.array(x.astype(jnp.float32))
    for i, k in enumerate(tie_at):
        if k is None or not 1 <= k <= vocab - 2:
            continue
        order = np.argsort(x[i])
        x[i, order[:2]] = x[i, order[vocab - k]]   # the two smallest, raised
    return x


def _keys(rows, seed):
    return jax.vmap(jax.random.PRNGKey)(jnp.arange(rows) + 100 * seed)


def _k(name, vocab):
    return {"V/2": vocab // 2, "V-1": vocab - 1, "V": vocab,
            None: vocab}.get(name, name)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("k", [1, 2, 40, 256, 257, "V/2", "V-1", "V", None])
@pytest.mark.parametrize("vocab", [257, 49152, 50304])
def test_threshold_mask_and_tokens_are_the_sorts(vocab, k, temperature):
    kk = _k(k, vocab)
    # rows 0 and 1 with ties planted at the k-th value, row 2 as drawn
    logits = _logits(vocab, ROWS, seed=vocab % 97 + kk % 89,
                     tie_at=(kk, kk, None))
    keys = _keys(ROWS, kk % 13)
    temps = np.full(ROWS, temperature, np.float32)
    tks = np.full(ROWS, kk, np.int32)
    old, new = _programs(vocab)
    want_tok, want_kth, want_mask = old(logits, keys, temps, tks)
    got_tok, got_kth, got_mask = new(logits, keys, temps, tks)

    np.testing.assert_array_equal(got_tok, want_tok)
    np.testing.assert_array_equal(np.asarray(got_kth).view(np.uint32),
                                  np.asarray(want_kth).view(np.uint32))
    np.testing.assert_array_equal(got_mask, want_mask)
    planted = 2 if kk <= vocab - 2 else 0
    assert int(np.asarray(got_mask)[0].sum()) >= kk + planted   # ties

    # the reference path (generate()'s sampler) calls the same function
    rng = jax.random.PRNGKey(kk % 7)
    got = G.sample_logits(jnp.asarray(logits), rng, temperature,
                          None if k is None else kk)
    if temperature == 0.0:
        want = jnp.argmax(logits, axis=-1)
    else:
        scaled = jnp.asarray(logits) / temperature
        if k is not None and kk < vocab:
            kth = jnp.sort(scaled, axis=-1)[:, -kk][:, None]
            scaled = jnp.where(scaled >= kth, scaled, -1e30)
        want = jax.random.categorical(rng, scaled, axis=-1)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("large", [False, True], ids=["small", "large"])
@pytest.mark.parametrize("vocab", [1031, 50304])
def test_a_batch_that_mixes_greedy_empty_small_and_large_top_k(vocab, large):
    """One decode step's vectors: a greedy row (with a top_k it never
    uses), an empty slot (temperature as it was left, no restriction),
    rows at small ``top_k`` and, with ``large``, rows at half the
    vocabulary and at all of it but one. Thresholds, masks and tokens are
    the parent's in every row."""
    temps = np.array([0.0, 1.0, 0.8, 0.8, 1.3, 0.0, 0.7], np.float32)
    tks = np.array([40, vocab, 40, 1, vocab // 2 if large else 256, 263,
                    vocab - 1 if large else 2], np.int32)
    rows = len(tks)
    logits = _logits(vocab, rows, seed=5, tie_at=tuple(tks))
    keys = _keys(rows, 3)
    old, new = _programs(vocab)
    want_tok, want_kth, want_mask = old(logits, keys, temps, tks)
    got_tok, got_kth, got_mask = new(logits, keys, temps, tks)
    np.testing.assert_array_equal(got_tok, want_tok)
    np.testing.assert_array_equal(got_kth, want_kth)
    np.testing.assert_array_equal(got_mask, want_mask)
    assert np.asarray(got_mask)[1].all()        # the empty slot: no mask


@pytest.mark.parametrize("nans", [0, 2], ids=["finite", "nan"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_the_search_orders_floats_as_a_sort_does(dtype, nans):
    """Negative values, both zeros, both infinities, the smallest normals
    and runs of ties, at every k: the search's image of the floats in the
    unsigned integers is the order a sort puts them in (the two zeros
    apart, which a mask's ``>=`` cannot tell from each other). A NaN of
    either sign ranks above +inf, as a sort ranks it."""
    rng = np.random.RandomState(0)
    tiny = float(jnp.finfo(dtype).tiny)    # XLA flushes subnormals
    x = np.concatenate([rng.standard_normal(40) * 4, [0.0, -0.0, 0.0, tiny,
                        -tiny, np.inf, -np.inf, -np.inf, 2.5, 2.5, 2.5],
                        [np.nan, np.copysign(np.nan, -1.0)][:nans]])
    x = jnp.asarray(rng.permutation(x), dtype)
    V = x.shape[0]
    ks = jnp.arange(1, V + 1)
    got = jax.jit(G.top_k_threshold)(jnp.broadcast_to(x, (V, V)), ks)
    want = jnp.sort(x)[V - ks]
    assert got.dtype == want.dtype
    assert int(jnp.isnan(want).sum()) == nans
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
