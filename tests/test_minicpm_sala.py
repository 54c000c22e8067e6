"""MiniCPM-SALA's block on the CPU at a small size (d 64; a sparse layer of 4
query heads over 2 KV heads of 16, a linear layer of 4 heads; SwiGLU 128;
layers M L L M; vocab 128; the indexer scaled down: kernel 8, stride 4 = the
pool's block size, blocks of 16, top 6, one initial block, window 32,
``dense_len`` 128; ``scale_emb`` 1 and not the published 12, under which the
embedding of the LAST token swamps what the mixers add at this width and a
wrong state or a wrong block moves no token), seeded weights, against the
plain reference the benchmark
keeps (``benchmark/references/minicpm_sala_block.py``: f32, "highest", the
recurrence a step a token, every pooled key scored and a full row of keys
masked for every query, nothing imported from the program).

Tolerances, each with its reason:

- ``F32`` (absolute, logits of order 0.04): both sides compute in f32 and
  differ in the order of sums (the chunked decay form against the recurrence,
  gathered blocks against a masked row): measured 3e-8 to 6e-8; 2e-5 is the
  other blocks' bound.
- bf16 parameters (``WIDE``, in standard deviations of the reference's
  logits): the stream is f32 and multiplies the bf16 values exactly, the
  reference is fed the SAME values and GIVEN what the cache stores
  (``cache_dtype``: k, v and the pooled keys rounded to bf16 once), so they
  differ as two f32 programs do. 1e-4 is what says "f32 where f32 is stated":
  a state held in bf16 reads over 1e-3 (``test_lower_precision_fails``).
- Against the reference NOT given what the cache stores the same path reads
  0.1-0.2 deviations (one more rounding, of k, v and the pooled keys, moves
  which blocks are taken): over ``WIDE`` a thousandfold, which is what
  ``test_lower_precision_fails`` holds.
"""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import models
from deeplearning4j_tpu.nn.generation import (as_paged, cache_parts,
                                              check_decodes, decode_forward,
                                              generate, init_caches,
                                              paged_parts)
from deeplearning4j_tpu.nn.layers import MiniCpmSalaBlock
from deeplearning4j_tpu.nn.layers import minicpm_sala as sala
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve.continuous import ContinuousBatcher
from deeplearning4j_tpu.serve.paged import (PrefixCache, BlockAllocator,
                                            StateGroup, build_pools,
                                            cache_groups, prefix_hashes,
                                            state_slot_bytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS, CHUNK = 4, 16
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6,
              init_blocks=1, window_size=32, dense_len=128)
MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"]
CFG = {"num_hidden_layers": 4, "first_layer": 0, "mixer_types": MIXERS,
       "published": {"num_hidden_layers": 4}, "hidden_size": 64,
       "dim_model_base": 16, "scale_emb": 1, "scale_depth": 1.4,
       "rms_norm_eps": 1e-6, "lightning_nh": 4, "rope_theta": 10000,
       "sparse_config": SPARSE, "forced_in_topk": True, "vocab_size": 128}
F32 = 2e-5       # absolute
WIDE = 1e-4      # in standard deviations of the reference's logits
DTYPES = ["float32", "bfloat16"]


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "minicpm_sala_block.py")
    spec = importlib.util.spec_from_file_location("sala_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def build(dtype="float32", mixers=MIXERS, **kw):
    args = dict(seed=3, input_shape=(512,), num_layers=len(mixers),
                mixer_types=mixers, published_layers=4, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, lightning_heads=4,
                ffn_width=128, dim_model_base=16, sparse=SPARSE, vocab=128,
                scale_emb=1.0, dtype=dtype)
    args.update(kw)
    m = models.MiniCpmSalaLM(**args).build()
    m.init()
    return m


def cfg_of(m=None, **kw):
    cfg = {**CFG, **kw}
    if m is not None and jnp.dtype(m.dtype) != jnp.float32:
        cfg.setdefault("cache_dtype", jnp.dtype(m.dtype).name)
    cfg["num_hidden_layers"] = len(cfg["mixer_types"])
    return cfg


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 128, n).astype(np.int32)


def ref_logits(params, ids, cfg=CFG):
    return np.asarray(ref.logits(params, ref.hidden(params, ids, cfg), cfg))


def close(got, want, dtype, wide=WIDE):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if dtype == "float32":
        assert np.max(np.abs(got - want)) < F32
    else:
        assert np.max(np.abs(got - want)) / np.std(want) < wide


def full_forward(m, ids):
    """The model's full forward: every block's ``apply``."""
    x = jnp.asarray(ids)[None]
    mask = None
    for i, layer in enumerate(m.layers[:-1]):
        x, _, mask = layer.apply(m.params.get(f"layer_{i}", {}), {}, x,
                                 mask=mask)
    return np.asarray(m.layers[-1].preactivation(
        m.params[f"layer_{len(m.layers) - 1}"], x)[0], np.float32)


# ------------------------------------------------------------ full forward
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("mixer", sala.MIXERS)
def test_each_mixer_full_forward_matches_the_reference(mixer, dtype):
    """Two layers of ONE kind, 300 tokens: past ``dense_len`` the sparse
    layers select (19 blocks, 6 taken)."""
    mixers = [mixer] * 2
    m = build(dtype, mixers=mixers)
    ids = tokens(300, seed=1)
    # the full forward rounds nothing it keeps: the reference is not given
    # a cache's width
    close(full_forward(m, ids),
          ref_logits(m.params, ids, cfg_of(mixer_types=mixers)), dtype)


def test_the_stack_is_the_published_one_by_default():
    zoo = models.MiniCpmSalaLM
    assert [i for i, k in enumerate(zoo.MIXER_TYPES) if k == "minicpm4"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    cut = zoo(num_layers=8, first_layer=9, input_shape=(64,))
    assert "".join("M" if b.mixer == "minicpm4" else "L"
                   for b in cut.blocks) == "MLLLLLLM"
    assert [b.decay_layer for b in cut.blocks if b.linear] == list(range(10, 16))
    assert cut.blocks[0].embed_scale == 12.0 and cut.blocks[1].embed_scale == 1
    assert abs(cut.blocks[0].residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert cut.divide == 16.0


# ------------------------------------------------------------ the cache spec
def test_cache_spec_names_a_state_part_and_a_strided_part():
    m = build()
    spec = dict(cache_parts(m))
    sparse, linear = spec["layer_1"], spec["layer_2"]
    assert dict(sparse) == {"k": (2, 16), "v": (2, 16), "kpool": (2, 16)}
    assert sparse.strides == {"kpool": 4} and sparse.state == {}
    assert dict(linear) == {"state": (4, 16, 16)}
    assert linear.state == {"state": "float32"} and linear.strides == {}
    # k and v a token, a pooled key a stride; the state is no token's
    assert state_slot_bytes(m) == 2 * 4 * 16 * 16 * 4
    groups = {g.name: g.layers for g in cache_groups(m)}
    assert groups == {"full": ("layer_1", "layer_4"),
                      "state": ("layer_2", "layer_3")}
    # served: a layer that says how it decodes and names its state is let
    # through, where a RecurrentLayer's carry is refused
    assert check_decodes(m, 512, "cache capacity", served=True) == 128
    dense = init_caches(m, 3, 64, m.dtype)
    assert dense["layer_1"]["kpool"].shape == (3, 16, 2, 16)
    assert dense["layer_2"]["state"].shape == (3, 4, 16, 16)


# ------------------------------------------------- through the paged cache
class Paged:
    """Slots over pools as the batcher lays them out: the sparse layers'
    tables with physical blocks handed out in a scrambled order, the linear
    layers' state pools by slot, with their boundary rows and snapshots."""

    def __init__(self, m, slots=2, capacity=320, snapshots=2):
        self.m, self.slots = m, slots
        self.maxb = capacity // BS
        n = slots * self.maxb + 1
        self.pools = build_pools(m, n, BS, m.dtype,
                                 state_rows=(slots, snapshots))
        self.names = {lk: tuple(p) for lk, p in self.pools.items()}
        self.stateful = set(dict((g.name, g.layers)
                                 for g in cache_groups(m)).get("state", ()))
        order = np.random.default_rng(5).permutation(np.arange(1, n))
        self.tables = order.reshape(slots, self.maxb).astype(np.int32)

    def run(self, ids, rows, pos, true_len=None, load=-2, live_rows=None):
        """One program: a chunk of ONE slot (``rows`` = [slot], right-padded
        to its width, ``true_len`` real) or one token for every slot
        (``rows`` = all; ``live_rows`` marks those that decode)."""
        Tq = ids.shape[1]
        chunk = Tq > 1
        if chunk:
            live = (np.arange(Tq) < (true_len or Tq))[None]
            tables = self.tables[rows]
        else:
            live = np.asarray(live_rows, bool)[:, None]
            tables = np.where(live, self.tables, 0)
        caches = {}
        for lk in self.names:
            if lk in self.stateful:
                caches[lk] = {**{f"{n}_pool": a
                                 for n, a in self.pools[lk].items()},
                              "slot": jnp.asarray(rows, jnp.int32)
                              if chunk else None,
                              "load": jnp.full((1,), load, jnp.int32)
                              if chunk else None, "every": BS}
            else:
                caches[lk] = as_paged(self.pools[lk], jnp.asarray(tables))
            caches[lk]["live"] = jnp.asarray(live)
        lg, caches = decode_forward(self.m, self.m.params, self.m.state,
                                    jnp.asarray(ids), caches,
                                    jnp.asarray(pos, jnp.int32))
        self.pools = {lk: paged_parts(caches[lk], self.names[lk])
                      for lk in self.names}
        self.sparse = {lk: np.asarray(c["sums"])
                       for lk, c in caches.items() if "sums" in c}
        return np.asarray(lg)

    def prefill(self, row, ids, upto, load=-1, start=0):
        out = []
        for lo in range(start, upto, CHUNK):
            n = min(CHUNK, upto - lo)
            buf = np.zeros((1, CHUNK), np.int32)
            buf[0, :n] = ids[lo:lo + n]
            out.append(self.run(buf, [row], [lo], true_len=n,
                                load=load if lo == start else -2)[0, :n])
        return out


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("forced_in_topk", [True, False])
def test_paged_prefill_then_decode_matches_the_reference(forced_in_topk, dtype):
    """Logits, not tokens: two sequences prefilled in chunks of 16, the last
    chunk of each right-padded, then single steps of both rows at their own
    positions: one past ``dense_len`` (it selects: the narrow gather), one
    under it (every block: the wide gather, in the same step); then the
    short one's slot goes dead and the long one decodes alone."""
    m = build(dtype, forced_in_topk=forced_in_topk)
    cfg = cfg_of(m, forced_in_topk=forced_in_topk)
    pg = Paged(m)
    seqs = [tokens(300, seed=9), tokens(110, seed=10)]
    upto = (283, 91)
    got = [pg.prefill(0, seqs[0], upto[0]), pg.prefill(1, seqs[1], upto[1])]
    read = []
    for step in range(17):
        live = [True, step < 12]
        pos = [upto[0] + step, upto[1] + step if live[1] else 0]
        lg = pg.run(np.asarray([[seqs[0][pos[0]]], [seqs[1][pos[1]]]]),
                    [0, 1], pos, live_rows=live)
        got[0].append(lg[0])
        if live[1]:
            got[1].append(lg[1])
        read.append(sum(pg.sparse.values()))
    for ids, rows in zip(seqs, got):
        rows = np.concatenate(rows)
        close(rows, ref_logits(m.params, ids, cfg)[:len(rows)], dtype)
    # what the decode steps read: alone, the long row reads topk blocks of
    # 16 a KV head a sparse layer (6, or 6 beyond the forced 1 + 3) and no
    # more, of the ~300 positions it holds
    taken = 6 if forced_in_topk else 10
    last_read, last_live = read[-1]
    assert last_live == 2 * 2 * (upto[0] + 17)
    assert (taken - 1) * 16 * 4 < last_read <= taken * 16 * 4
    # with the short row beside it, that row read all it had
    first_read, first_live = read[0]
    assert first_live == 2 * 2 * (upto[0] + 1 + upto[1] + 1)
    assert first_read > last_read + 2 * 2 * upto[1] - 64


def _gathers(jaxpr, operand_shape, out):
    """Output shapes of every ``gather`` from an operand of that shape, in
    ``jaxpr`` and everything nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" \
                and eqn.invars[0].aval.shape == operand_shape:
            out.append(eqn.outvars[0].aval.shape)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _gathers(inner, operand_shape, out)
    return out


def test_the_decode_step_gathers_selected_blocks_only():
    """In the decode program no sparse layer forms an operand of slots x
    capacity positions of k or v: every gather from a k or v pool, in either
    branch of the step, brings the selected blocks (at most ``dense_len``
    positions a row a KV head: a row under it reads all it has), where the
    prefill chunk's brings its slot whole."""
    m = build()
    pg = Paged(m, slots=2, capacity=320)
    pool = pg.pools["layer_1"]["k"].shape

    def step(ids, tables, pos, pools, slot=None):
        caches = {lk: ({**{f"{n}_pool": a for n, a in pools[lk].items()},
                        "slot": slot, "load": slot, "every": BS}
                       if lk in pg.stateful
                       else as_paged(pools[lk], tables)) for lk in pg.names}
        for lk in caches:
            caches[lk]["live"] = jnp.ones((ids.shape[0], 1), bool)
        return decode_forward(m, m.params, m.state, ids, caches, pos)[0]

    decode = jax.make_jaxpr(step)(
        jnp.zeros((2, 1), jnp.int32), jnp.asarray(pg.tables),
        jnp.zeros((2,), jnp.int32), pg.pools)
    shapes = _gathers(decode.jaxpr, pool, [])
    assert shapes
    per_row_head = max(int(np.prod(s)) // (2 * 2 * 16) for s in shapes)
    assert per_row_head <= SPARSE["dense_len"] < 320
    chunk = jax.make_jaxpr(step)(
        jnp.zeros((1, CHUNK), jnp.int32), jnp.asarray(pg.tables[:1]),
        jnp.zeros((1,), jnp.int32), pg.pools, jnp.zeros((1,), jnp.int32))
    assert max(int(np.prod(s)) // (2 * 16)
               for s in _gathers(chunk.jaxpr, pool, [])) == 320


# ------------------------------------------- the chunked linear form alone
@pytest.mark.parametrize("state_dtype", ["float32"])
def test_chunked_linear_form_matches_the_recurrence(state_dtype):
    """Three rows through three chunks of 16: one full, one whose last chunk
    is right-padded after 5 tokens, one dead throughout (its state must come
    back untouched), against the recurrence run a token at a time; and the
    state at the block boundary each row last passed."""
    blk = MiniCpmSalaBlock(mixer="lightning-attn", num_heads=4, num_kv_heads=4,
                           head_dim=16, decay_layer=1, decay_depth=4)
    rng = np.random.default_rng(0)
    B, T, H, hd = 3, 48, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
               for _ in range(3))
    S0 = jnp.asarray(rng.normal(size=(B, H, hd, hd)), jnp.float32)
    lens = np.array([48, 37, 0])
    lam = np.exp(-sala.decay_rates(4, 1, 4)).astype(np.float64)

    want_o = np.zeros((B, T, H, hd))
    want_S = np.asarray(S0, np.float64).copy()
    want_b = want_S.copy()
    for b in range(B):
        for t in range(lens[b]):
            want_S[b] = lam[:, None, None] * want_S[b] + np.einsum(
                "hd,he->hde", np.asarray(k[b, t], np.float64),
                np.asarray(v[b, t], np.float64))
            want_o[b, t] = np.einsum("hd,hde->he", np.asarray(q[b, t]),
                                     want_S[b]) / 4.0
            if (t + 1) % BS == 0:
                want_b[b] = want_S[b]

    S, got_o = S0, []
    bound = np.asarray(S0).copy()
    for lo in range(0, T, 16):
        n = np.clip(lens - lo, 0, 16)
        live = jnp.asarray(np.arange(16)[None] < n[:, None])
        sl = slice(lo, lo + 16)
        n_b = (lo + n) // BS * BS - lo
        hit = n > 0
        Sb = blk._state_after(k[:, sl], v[:, sl], S, live,
                              jnp.asarray(np.clip(n_b, 0, n)))
        bound = np.where(hit[:, None, None, None], np.asarray(Sb), bound)
        o, S = blk._lightning(q[:, sl], k[:, sl], v[:, sl], S, live,
                              jnp.asarray(n, jnp.int32))
        got_o.append(np.asarray(o))
    got_o = np.concatenate(got_o, axis=1)
    for b in range(2):
        assert np.max(np.abs(got_o[b, :lens[b]] - want_o[b, :lens[b]])) < 2e-4
    assert np.max(np.abs(np.asarray(S) - want_S)) < 2e-4
    assert np.array_equal(np.asarray(S)[2], np.asarray(S0)[2])   # dead: untouched
    assert np.max(np.abs(bound - want_b)) < 2e-4
    # the fastest head's decay over a 512-token chunk underflows to 0 and
    # nothing divides by it
    big = MiniCpmSalaBlock(mixer="lightning-attn", num_heads=32,
                           num_kv_heads=32, head_dim=8, decay_layer=0)
    x = jnp.ones((1, 512, 32, 8))
    o, S = big._lightning(x, x, x, jnp.zeros((1, 32, 8, 8)),
                          jnp.ones((1, 512), bool), jnp.asarray([512]))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()


# ----------------------------------------------- snapshots, on the programs
def test_a_chunk_that_starts_from_a_snapshot_gives_the_logits_of_no_hit():
    """Slot 0 prefills 96 tokens in chunks; its boundary state after 64 is
    kept as a snapshot (a row copy). Slot 1, whose table adopts slot 0's
    first 64 positions' blocks, starts its first chunk at 64 from that
    snapshot: the logits of the rest are the logits of the whole prefill."""
    m = build()
    pg = Paged(m, snapshots=2)
    ids = tokens(120, seed=4)
    whole = pg.prefill(0, ids, 64)
    for lk in pg.stateful:          # what the batcher's copy_states does
        snap = pg.pools[lk]["state_snap"]
        pg.pools[lk]["state_snap"] = snap.at[2].set(snap[0])
    whole += pg.prefill(0, ids, 120, load=-2, start=64)
    pg.tables[1, :64 // BS] = pg.tables[0, :64 // BS]
    rest = pg.prefill(1, ids, 120, load=2, start=64)
    assert np.max(np.abs(np.concatenate(rest)
                         - np.concatenate(whole)[64:])) < F32
    close(np.concatenate(whole), ref_logits(m.params, ids, cfg_of(m)),
          "float32")
    # from zeros instead, the linear layers have forgotten the prefix
    pg.tables[1, :64 // BS] = pg.tables[0, :64 // BS]
    cold = pg.prefill(1, ids, 120, load=-1, start=64)
    assert np.max(np.abs(np.concatenate(cold)
                         - np.concatenate(whole)[64:])) > 1e-3


# ------------------------------------------------------- the host's ledger
def test_state_group_keeps_snapshots_by_run_and_pins_what_admission_matched():
    g = StateGroup(cache_groups(build())[-1], slots=2, snapshots=2,
                   slot_bytes=8192)
    alloc = BlockAllocator(16)
    px = PrefixCache(alloc, BS, state=g)
    hashes = prefix_hashes(tokens(16), BS)
    blocks = alloc.alloc(4)
    px.insert(hashes, blocks, 0)
    assert px.match_state(hashes, 4) == (0, None)          # no snapshot yet
    assert px.snapshot_row(hashes, 2) == 2 and px.snapshot_row(hashes, 4) == 3
    assert px.snapshot_row(hashes, 4) is None              # has one already
    assert px.match_state(hashes, 4) == (4, 3)
    assert px.match_state(hashes, 3) == (2, 2)             # shortened
    assert px.match_state(hashes, 1) == (0, None)          # to nothing
    g.pin(2)
    other = prefix_hashes(tokens(8, seed=7), BS)
    px.insert(other, alloc.alloc(2), 0)
    assert px.snapshot_row(other, 2) == 3                  # LRU, unpinned
    assert px.match_state(hashes, 4) == (2, 2)
    g.pin(3)
    assert px.snapshot_row(other, 1) is None               # every row pinned
    g.unpin(2)
    assert px.snapshot_row(other, 1) == 2
    # a snapshot dies with its run's entry
    px.flush()
    assert g.used == 0 and g._free == [2]       # row 3 is still pinned
    g.unpin(3)
    assert sorted(g._free) == [2, 3]


# -------------------------------------------------------- the batcher itself
def _batcher(m, **kw):
    args = dict(slots=3, capacity=320, block_size=BS, prefill_chunk=CHUNK,
                metrics=MetricsRegistry())
    args.update(kw)
    return ContinuousBatcher(m, **args)


def _count(cb, name, **labels):
    fam = cb.metrics.snapshot().get(name, {"series": []})
    return sum(s["value"] for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


@pytest.mark.parametrize("dtype", DTYPES)
def test_batcher_matches_whole_batch_generate(dtype):
    """Greedy chains through ModelServer's stack equal ``generate()`` over
    its dense caches: 150-token prompts decode past ``dense_len``."""
    m = build(dtype)
    prompts = np.stack([tokens(150, seed=s) for s in (1, 2)])
    want = generate(m, prompts, 24, temperature=0.0, capacity=192)
    cb = _batcher(m)
    try:
        got = cb.generate(prompts, 24, temperature=0.0)
    finally:
        cb.shutdown()
    assert np.array_equal(got, want)
    assert _count(cb, "serve_state_slot_bytes") == state_slot_bytes(m)
    read = _count(cb, "serve_sparse_kv_positions_read_total")
    live = _count(cb, "serve_sparse_kv_positions_live_total")
    assert 0 < read < live


def test_a_prefix_hit_needs_a_snapshot_and_gives_the_tokens_of_no_hit():
    """A second turn that sends the first answer back adopts the whole run
    through the linear layers (a snapshot stands at its end); a prompt that
    leaves the run between two snapshots is shortened to the earlier one;
    with no snapshots every hit is cut to nothing. The tokens are those of
    a batcher with no prefix cache every time."""
    m = build()
    first = tokens(70, seed=5)

    def turns(cb):
        a = cb.generate(first, 30, temperature=0.0)
        second = np.concatenate([first, a, tokens(9, seed=6)])
        b = cb.generate(second, 12, temperature=0.0)
        # leaves the first run 8 tokens into the answer: between the
        # snapshot at the prompt's end (68) and the answer's (96)
        third = np.concatenate([first, a[:10], tokens(9, seed=8)])
        c = cb.generate(third, 12, temperature=0.0)
        return a, b, c

    plain = _batcher(m, prefix_cache=False)
    try:
        want = turns(plain)
    finally:
        plain.shutdown()
    cb = _batcher(m)
    try:
        got = turns(cb)
        stats = cb.kv_block_stats()
    finally:
        cb.shutdown()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert _count(cb, "serve_prefix_cache_hits_total") == 2
    # turn two adopted prompt ++ answer[:-1] in whole blocks: 99 // 4 * 4
    assert _count(cb, "serve_prefill_tokens_saved_total") == 96 + 68
    assert _count(cb, "serve_prefix_hits_shortened_total", reason="state",
                  left="some") == 1
    assert _count(cb, "serve_state_snapshots_total") \
        == stats["state_group"]["snapshots_taken"] >= 5
    assert _count(cb, "serve_state_snapshot_bytes") \
        == stats["state_group"]["snapshots_used"] * state_slot_bytes(m)



def test_a_hit_whose_snapshots_left_the_pool_is_cut_to_nothing():
    """Three slots keep six snapshots, two a request (its prompt's end, its
    answer's): three other requests later the first one's are gone while its
    blocks still stand, so the second turn's hit is cut to nothing. The
    tokens are those of a batcher with no prefix cache."""
    m = build()
    first = tokens(70, seed=5)

    def turns(cb):
        a = cb.generate(first, 30, temperature=0.0)
        for seed in (11, 12, 13):
            cb.generate(tokens(40, seed=seed), 8, temperature=0.0)
        second = np.concatenate([first, a, tokens(9, seed=6)])
        return a, cb.generate(second, 12, temperature=0.0)

    plain = _batcher(m, prefix_cache=False)
    try:
        want = turns(plain)
    finally:
        plain.shutdown()
    cb = _batcher(m)
    try:
        got = turns(cb)
        stats = cb.kv_block_stats()
    finally:
        cb.shutdown()
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert stats["state_group"]["snapshots_used"] == 6
    assert _count(cb, "serve_prefix_cache_hits_total") == 0
    assert _count(cb, "serve_prefix_hits_shortened_total", reason="state",
                  left="none") == 1


def test_a_shortened_hit_that_waits_for_blocks_counts_once():
    """A request whose hit is shortened and that then waits for blocks is
    matched again at every admission pass until it fits: it is one
    shortened hit."""
    m = build()
    first = tokens(70, seed=5)
    cb = _batcher(m, kv_blocks=110)
    real = cb._programs.decode

    def slow(*a, **kw):
        time.sleep(0.01)
        return real(*a, **kw)

    try:
        a = cb.generate(first, 30, temperature=0.0)
        cb._programs.decode = slow
        # 280 positions: 70 of the pool's 109 blocks committed
        big = cb.submit(tokens(160, seed=9), 120, temperature=0.0)
        while len(big.out) < 4:
            time.sleep(0.005)
        ticks = _count(cb, "serve_gen_ticks_total")
        # leaves the cached run between its two snapshots; 17 shared blocks
        # and 56 more do not fit beside the 70
        late = cb.submit(np.concatenate([first, a[:10], tokens(9, seed=8)]),
                         200, temperature=0.0)
        big.wait()
        assert not late.out and _count(cb, "serve_gen_ticks_total") > ticks + 20
        late.wait()
    finally:
        cb.shutdown()
    assert _count(cb, "serve_prefix_cache_hits_total") == 1
    assert _count(cb, "serve_prefix_hits_shortened_total", reason="state") == 1


def test_fork_copies_the_state():
    """A greedy child forked mid-decode goes on exactly as its parent does:
    its slot took the parent's recurrent states."""
    m = build()
    cb = _batcher(m)
    real = cb._programs.decode

    def slow(*a, **kw):
        time.sleep(0.02)
        return real(*a, **kw)

    cb._programs.decode = slow
    try:
        req = cb.submit(tokens(140, seed=3), 60, temperature=0.0)
        while len(req.out) < 8:
            time.sleep(0.005)
        child = cb.fork(req, max_new_tokens=20)
        at = len(req.out)   # the child starts at or after this token
        tail, parent = child.wait(), req.wait()
    finally:
        cb.shutdown()
    starts = [i for i in range(at - 2, at + 6)
              if np.array_equal(parent[i:i + 20], tail)]
    assert starts, (at, parent, tail)


# ----------------------------------------------------- what would fail them
@pytest.mark.parametrize("what", ["state", "cache"])
def test_lower_precision_fails(what):
    """The stated tolerance separates the stated widths from the next one
    down: a state held in bf16 (f32 stated) and, for a bf16 model, a
    reference not given what the cache stores."""
    ids = tokens(200, seed=2)
    if what == "state":
        m = build(state_dtype="bfloat16")
        caches = init_caches(m, 1, 256, m.dtype)
        got = []
        for lo in range(0, 200, CHUNK):
            lg, caches = decode_forward(
                m, m.params, m.state, jnp.asarray(ids[None, lo:lo + CHUNK]),
                caches, lo)
            got.append(np.asarray(lg[0]))
        want = ref_logits(m.params, ids, cfg_of(m))
        err = np.max(np.abs(np.concatenate(got) - want)) / np.std(want)
        assert err > 10 * WIDE
    else:
        m = build("bfloat16")
        pg = Paged(m)
        got = np.concatenate(pg.prefill(0, ids, 200))
        close(got, ref_logits(m.params, ids, cfg_of(m)), "bfloat16")
        want = ref_logits(m.params, ids, cfg_of())
        assert np.max(np.abs(got - want)) / np.std(want) > WIDE
