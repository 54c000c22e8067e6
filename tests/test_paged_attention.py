"""The paged-attention decode kernel (``ops/paged_attention.py``) in Pallas
interpreter mode, against what it replaces: ``cache_gather`` and the grouped
einsums of ``attend_cached`` (``wide_einsum``'s where ``q`` is wider than the
pool), on random pools; then one served case a model family, through
``ContinuousBatcher``, against the dense layout's ``generate()``.

The scene every kernel case shares (block size 16, 20 table columns, so a
capacity of 320): seven rows, at length 1, one short of a block, exactly a
block, one past it, 100 (the first six blocks THE SAME physical blocks as the
row at capacity: a prefix hit), the capacity, and an idle row (a zeroed table
row, position 0: it reads the trash block). Physical blocks are a random
permutation. Tolerances: f32 over f32 and f32 over a bf16 pool 2e-6 (both
sides exact products, f32 sums in another order; measured 4e-7); bf16 over
bf16 2e-2 absolute on outputs of order 1 (the kernel rounds the weights to
bf16 before they are normalised, the einsums after: one bf16 rounding each,
measured 4e-3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import (attend_cached, cache_gather,
                                              causal_valid, generate,
                                              reads_in_place)
from deeplearning4j_tpu.nn.layers.experts import wide_einsum
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.ops import paged_attention
from deeplearning4j_tpu.ops.paged_attention import paged_attention_decode
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher

BS, MAXB, HD = 16, 20, 16
LENGTHS = [1, BS - 1, BS, BS + 1, 100, BS * MAXB, 1]     # the last: idle
HEADS = [pytest.param(16, 1, id="mqa"), pytest.param(4, 4, id="mha"),
         pytest.param(12, 2, id="gqa6")]
DTYPES = [pytest.param("bfloat16", "bfloat16", 2e-2, id="bf16"),
          pytest.param("float32", "bfloat16", 2e-6, id="f32-over-bf16"),
          pytest.param("float32", "float32", 2e-6, id="f32")]


def scene(H, Hkv, q_dtype, pool_dtype, seed=0):
    """(q, k_pool, v_pool, tables, pos, named): ``named[n, t]`` says whether
    some row may read position ``t`` of physical block ``n``."""
    rng = np.random.default_rng(seed)
    S = len(LENGTHS)
    N = 1 + sum(-(-n // BS) for n in LENGTHS[:-1])
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((S, MAXB), np.int32)
    for s, n in enumerate(LENGTHS[:-1]):
        for b in range(-(-n // BS)):
            shared = s == 4 and b < 6          # row 4 adopts row 5's prefix
            tables[s, b] = -1 if shared else free.pop()
    tables[4, :6] = tables[5, :6]
    pos = np.asarray(LENGTHS, np.int32) - 1
    named = np.zeros((N, BS), bool)
    for s in range(S):
        for t in range(pos[s] + 1):
            named[tables[s, t // BS], t % BS] = True
    shape = (N, BS, Hkv, HD)
    return (jnp.asarray(rng.normal(size=(S, H, HD)), q_dtype),
            jnp.asarray(rng.normal(size=shape), pool_dtype),
            jnp.asarray(rng.normal(size=shape), pool_dtype),
            jnp.asarray(tables), jnp.asarray(pos), named)


def gathered(q, k_pool, v_pool, tables, pos):
    """What the kernel replaces: the gather at capacity, the mask over it,
    the grouped einsums (``wide_einsum`` multiplies operands of one dtype as
    ``attend_cached``'s own einsums do, and exactly where they differ)."""
    S, H, hd = q.shape
    Hkv = k_pool.shape[2]
    ck, cv = cache_gather({"k_pool": k_pool, "v_pool": v_pool,
                           "tables": tables}, ("k", "v"))
    valid = causal_valid(pos, 1, ck.shape[1])[:, None, None]
    qg = q.reshape(S, 1, Hkv, H // Hkv, hd)
    s = wide_einsum("bqhgd,bkhd->bhgqk", qg, ck) / np.sqrt(hd)
    w = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
    if q.dtype == cv.dtype:
        w = w.astype(cv.dtype)
    return wide_einsum("bhgqk,bkhd->bqhgd", w, cv).reshape(S, H, hd)


def close(got, want, atol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol, rtol=0)


@pytest.mark.parametrize("chunk_rows", [64, paged_attention.CHUNK_ROWS])
@pytest.mark.parametrize("q_dtype,pool_dtype,atol", DTYPES)
@pytest.mark.parametrize("H,Hkv", HEADS)
def test_kernel_is_the_gather_and_the_einsums(monkeypatch, H, Hkv, q_dtype,
                                              pool_dtype, atol, chunk_rows):
    """Every length, the shared prefix and the idle row, with a chunk of a
    few blocks (several chunks a row, the next row's first started at a
    row's end) and with the default (one chunk a row here)."""
    monkeypatch.setattr(paged_attention, "CHUNK_ROWS", chunk_rows)
    q, kp, vp, tables, pos, _ = scene(H, Hkv, q_dtype, pool_dtype)
    out = paged_attention_decode(q, kp, vp, tables, pos)
    assert out.shape == q.shape and out.dtype == q.dtype
    close(out, gathered(q, kp, vp, tables, pos), atol)


@pytest.mark.parametrize("chunk_rows", [64, paged_attention.CHUNK_ROWS])
@pytest.mark.parametrize("H,Hkv", HEADS)
def test_garbage_no_row_may_read_does_not_reach_the_output(monkeypatch, H,
                                                           Hkv, chunk_rows):
    """NaN in every block no table names and in every live block past its
    rows' positions (the trash block past position 0 too): the output is
    what the einsums give on pools with zeros there. (On the NaN pools the
    einsums themselves give NaN: a weight of 0 times NaN.)"""
    monkeypatch.setattr(paged_attention, "CHUNK_ROWS", chunk_rows)
    q, kp, vp, tables, pos, named = scene(H, Hkv, "float32", "bfloat16",
                                          seed=1)
    extra = jnp.asarray(np.random.default_rng(2).normal(
        size=(3,) + kp.shape[1:]), kp.dtype)          # blocks nobody names
    kp, vp = jnp.concatenate([kp, extra]), jnp.concatenate([vp, extra])
    named = np.concatenate([named, np.zeros((3, BS), bool)])
    assert (~named).sum() > 3 * BS + BS - 1
    seen = jnp.asarray(named)[:, :, None, None]
    out = paged_attention_decode(q, jnp.where(seen, kp, jnp.nan),
                                 jnp.where(seen, vp, jnp.nan), tables, pos)
    assert np.isfinite(np.asarray(out)).all()
    close(out, gathered(q, jnp.where(seen, kp, 0), jnp.where(seen, vp, 0),
                        tables, pos), 2e-6)


def test_operands_the_kernel_does_not_multiply_exactly_are_refused():
    q, kp, vp, tables, pos, _ = scene(4, 2, "float32", "float16")
    assert not paged_attention.supports(q.dtype, kp.dtype)
    with pytest.raises(ValueError, match="gather"):
        paged_attention_decode(q, kp, vp, tables, pos)
    with pytest.raises(ValueError, match="fold"):
        paged_attention_decode(q[:, :3].astype(kp.dtype), kp, vp, tables, pos)


def test_a_query_narrower_than_the_pool_is_widened_as_the_einsums_do():
    """``starcoderbase-1b``'s first layer: a bf16 stream over an f32 pool.
    The einsums promote the query to f32 and answer in f32; so does the
    kernel."""
    q, kp, vp, tables, pos, _ = scene(16, 1, "bfloat16", "float32", seed=5)
    assert paged_attention.supports(q.dtype, kp.dtype)
    out = paged_attention_decode(q, kp, vp, tables, pos)
    assert out.dtype == jnp.float32
    close(out, gathered(q.astype(jnp.float32), kp, vp, tables, pos), 2e-6)


@pytest.mark.parametrize("case,reads", [
    ("decode", True), ("chunk", False), ("window", False), ("dense", False),
    ("wide_q", False)])
def test_which_calls_read_in_place(case, reads):
    """A decode step over a paged cache kept whole, and nothing else: a
    prefill chunk, a window kept at capacity, the dense layout and an f32
    query over an f16 pool run the gather's lines."""
    q, kp, vp, tables, pos, _ = scene(4, 2, "float32", "float32")
    cache = {"k_pool": kp, "v_pool": vp, "tables": tables}
    q = q[:, None]
    if case == "chunk":
        q = jnp.concatenate([q, q], axis=1)
    if case == "dense":
        cache = {"k": kp[:7], "v": vp[:7]}
    if case == "wide_q":
        cache = {n: a.astype(jnp.float16) if n != "tables" else a
                 for n, a in cache.items()}
    assert reads_in_place(cache, q, 8 if case == "window" else None) is reads


def test_attend_cached_writes_then_reads_the_pool_in_place():
    """``attend_cached`` on a decode step: this step's keys and values are
    in the pool the kernel reads, and the result is the gathered path's
    (the same call with a window as long as the capacity, which gathers)."""
    q, kp, vp, tables, pos, _ = scene(12, 2, "float32", "float32", seed=3)
    rng = np.random.default_rng(4)
    k = jnp.asarray(rng.normal(size=(len(LENGTHS), 1, 2, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=k.shape), jnp.float32)
    cache = {"k_pool": kp, "v_pool": vp, "tables": tables}
    got, new = attend_cached(q[:, None], k, v, cache, pos)
    want, old = attend_cached(q[:, None], k, v, cache, pos,
                              window=BS * MAXB)
    close(got, want, 2e-6)
    np.testing.assert_array_equal(np.asarray(new["k_pool"]),
                                  np.asarray(old["k_pool"]))


# ---------------------------------------------------------------- served
def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def dense_lm():
    m = models.CausalLM(seed=0, input_shape=(96,), num_layers=2, d_model=64,
                        num_heads=4, num_kv_heads=1, vocab=128).build()
    m.init()
    return m


def olmoe_lm():
    m = models.OlmoeLM(seed=3, input_shape=(96,), num_layers=2, d_model=64,
                       num_heads=4, num_experts=8, top_k=2, expert_width=32,
                       vocab=128).build()
    m.init()
    return m


def laguna_lm():
    m = models.LagunaLM(
        seed=3, input_shape=(96,), num_layers=5, first_k_dense=1, period=4,
        d_model=64, full_heads=4, sliding_heads=6, num_kv_heads=2,
        head_dim=16, window=16, dense_width=96, num_experts=16, top_k=3,
        expert_width=32, shared_width=32, full_rotary_dim=8, yarn_factor=4.0,
        yarn_original=32, attention_factor=1.1386294361119891,
        vocab=128).build()
    m.init()
    return m


@pytest.mark.parametrize("build", [dense_lm, olmoe_lm, laguna_lm])
def test_served_tokens_are_generates(build):
    """Three requests on two slots (chunked prefill, the third adopting the
    first's cached prefix), f32: every decode step reads the pools through
    the kernel, and the tokens are the dense layout's ``generate()``'s."""
    m = build()
    cb = ContinuousBatcher(m, slots=2, capacity=96, block_size=4,
                           prefill_chunk=8, metrics=MetricsRegistry())
    try:
        shared = tokens(24, seed=5)
        prompts = [np.concatenate([shared, tokens(9, seed=6)]),
                   tokens(21, seed=7),
                   np.concatenate([shared, tokens(5, seed=8)])]
        first = cb.generate(prompts[0], 20, temperature=0.0)
        reqs = [cb.submit(p, 20, temperature=0.0) for p in prompts[1:]]
        outs = [first] + [r.wait() for r in reqs]
    finally:
        cb.shutdown()
    for prompt, out in zip(prompts, outs):
        np.testing.assert_array_equal(
            out, generate(m, prompt[None], 20, temperature=0.0)[0])


# ------------------------------------------------- the benchmark's reader
def _reader():
    """``benchmark/layer_metrics/paged_attn_busy_share.py`` and the
    harness module it reads traces with, loaded as the harness loads them."""
    import importlib.util
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import trace_reduce

    spec = importlib.util.spec_from_file_location(
        "layer_metric_paged_attn_busy_share",
        os.path.join(bench, "layer_metrics", "paged_attn_busy_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, trace_reduce


@pytest.mark.parametrize("names,want", [
    (["%fusion.3 = bf16[8]{0} fusion(%p)",
      "%paged_attn_decode.7 = bf16[16,16,128]{2,1,0} custom-call(%a, %b)",
      "%paged_attn_decode = bf16[16,16,128]{2,1,0} custom-call(%a, %b)"],
     50.0),
    (["%fusion.3 = bf16[8]{0} fusion(%p)", "%gather.2 = bf16[8]{0} gather()",
      "%not_paged_attn_decode.1 = f32[] custom-call()"], 0.0),
    (None, None)])
def test_busy_share_reads_zero_where_no_kernel_ran(names, want):
    """Events of 100, 50 and 50 ns: the kernel's two are half of the busy
    time; a trace without the kernel (the parent's) reads 0.0, which the
    line must hold, and an untraced run nothing."""
    mod, tr = _reader()

    class Run:
        trace = None

    if names is not None:
        ops = tr.Line(np.array([0, 200, 300], np.int64),
                      np.array([100, 250, 350], np.int64), names)
        none = tr.Line(np.zeros(0, np.int64), np.zeros(0, np.int64), [])
        Run.trace = {0: tr.Device(ops, none, none)}
    got = mod.read(Run)
    assert got == (want if want is None else pytest.approx(want))


# ------------------------------------- compiled for the chip, without one
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip: the TPU's compiler is installed beside the CPU
    backend and compiles for a chip that is not attached. Skips where it
    cannot be described (another process holds libtpu)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("S,H,Hkv,maxb,q_dtype", [
    pytest.param(16, 16, 1, 512, "bfloat16", id="starcoderbase-1b"),
    pytest.param(32, 16, 16, 64, "bfloat16", id="olmoe-1b-7b"),
    pytest.param(32, 48, 8, 512, "float32", id="laguna-s-2.1")])
def test_mosaic_takes_the_kernel_at_published_widths(one_chip, S, H, Hkv,
                                                     maxb, q_dtype):
    """The three configurations' decode shapes through the chip's compiler
    (interpreter mode knows no tiling and no VMEM limit), and the pools
    handed to the kernel as they lie: no ``copy`` or ``transpose`` of a
    pool-sized array in the compiled program."""
    import re

    N = S * maxb + 1

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda *a: paged_attention_decode(
        *a, interpret=False)).lower(
        sds((S, H, 128), q_dtype), sds((N, 16, Hkv, 128), "bfloat16"),
        sds((N, 16, Hkv, 128), "bfloat16"), sds((S, maxb), "int32"),
        sds((S,), "int32")).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert "paged_attn_decode" in text
    moved = [ln for ln in text.splitlines()
             if re.search(rf"= bf16\[{N},[^\]]*\]\S* (copy|transpose)\(", ln)]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
