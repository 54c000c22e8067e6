"""Tests for the aot/ persistent executable store (ISSUE 6).

The load-bearing properties, each tested directly:

- keys: every compilation-shaping component (tag, arch, signature,
  donation, jax/jaxlib + topology) re-keys the store — a version skew is a
  clean MISS, never a crash and never a wrong executable;
- store: atomic publish, content verification, corrupt entries quarantined
  and surfaced as typed errors, manifest rebuildable from entry files,
  LRU GC bounded by bytes, readers racing GC see clean misses;
- AotFunction: a second process-alike (fresh wrapper, same store) loads
  every executable with ZERO compiles; every store failure (corrupt blob,
  version skew, bad pickle) degrades to live tracing counted on
  ``serve_aot_fallback_total{cause}``;
- publish warming: ``ModelRegistry.publish`` runs warmers against the
  candidate BEFORE the flip; a failing warmer raises a typed
  ``PublishError`` with history, generation counter and lease accounting
  untouched — the old generation keeps serving;
- the ``python -m deeplearning4j_tpu.aot`` CLI: list/stats/verify/gc
  against a real store, verify exit code flips on quarantine.
"""

import hashlib
import itertools
import pickle
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.aot import (AotCorruptEntry, AotFunction, AotStore,
                                    arch_fingerprint, cache_key,
                                    call_signature, runtime_fingerprint)
from deeplearning4j_tpu.aot import keys as aot_keys
from deeplearning4j_tpu.aot.keys import structural_key
from deeplearning4j_tpu.obs.metrics import MetricsRegistry


def _key(i=0):
    return hashlib.sha256(f"entry-{i}".encode()).hexdigest()


def _series(metrics, name):
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in metrics.snapshot().get(name, {}).get("series", [])}


def _fallbacks_by_cause(metrics):
    return {dict(k)["cause"]: v for k, v in
            _series(metrics, "serve_aot_fallback_total").items()}


_F32 = np.zeros((2, 3), np.float32)
#: name -> (the class it belongs to, an operand list): two lists of one class
#: must share an executable, two of different classes must not
_OPERANDS = {
    "zeros": ("base", (_F32, np.int32(7))),
    "other_values": ("base", (np.ones((2, 3), np.float32), np.int32(9))),
    "jax_array": ("base", (jnp.zeros((2, 3), jnp.float32), jnp.int32(1))),
    "shape_dtype_struct": ("base", (jax.ShapeDtypeStruct((2, 3), jnp.float32),
                                    jax.ShapeDtypeStruct((), jnp.int32))),
    "other_shape": ("shape", (np.zeros((2, 4), np.float32), np.int32(7))),
    "other_rank": ("rank", (np.zeros((2, 3, 1), np.float32), np.int32(7))),
    "float16": ("f16", (np.zeros((2, 3), np.float16), np.int32(7))),
    "bfloat16": ("bf16", (jnp.zeros((2, 3), jnp.bfloat16), np.int32(7))),
    "bfloat16_struct": ("bf16", (jax.ShapeDtypeStruct((2, 3), jnp.bfloat16),
                                 np.int32(0))),
    "none_for_array": ("none", (None, np.int32(7))),
    "python_float": ("pyfloat", (_F32, 7.0)),
    "python_int": ("pyint", (_F32, 7)),
    "numpy_float32": ("npfloat", (_F32, np.float32(7.0))),
    "dict_of_leaves": ("dict", ({"a": _F32, "b": np.int32(7)},)),
    "dict_other_names": ("dict2", ({"a": _F32, "c": np.int32(7)},)),
    "tuple_of_leaves": ("tuple", ((_F32, np.int32(7)),)),
}


class TestKeys:
    def test_deterministic_and_component_sensitive(self):
        rt = {"jax": "1", "jaxlib": "1", "backend": "cpu",
              "device_kind": "cpu", "device_count": 1, "process_count": 1}
        base = cache_key("decode", "abc", ("(4,):int32",), runtime=rt)
        assert base == cache_key("decode", "abc", ("(4,):int32",), runtime=rt)
        assert base != cache_key("prefill", "abc", ("(4,):int32",), runtime=rt)
        assert base != cache_key("decode", "xyz", ("(4,):int32",), runtime=rt)
        assert base != cache_key("decode", "abc", ("(8,):int32",), runtime=rt)
        assert base != cache_key("decode", "abc", ("(4,):int32",),
                                 donate=(3,), runtime=rt)

    def test_version_or_topology_skew_rekeys(self):
        # a jaxlib upgrade (or moving CPU -> TPU slice) must be a clean miss
        rt = runtime_fingerprint()
        sig = ("(2, 4):float32",)
        base = cache_key("fwd", "a", sig, runtime=rt)
        for field, value in (("jaxlib", "999.0"), ("jax", "999.0"),
                             ("backend", "tpu"), ("device_kind", "TPU v5e"),
                             ("device_count", rt["device_count"] + 8),
                             ("process_count", rt["process_count"] + 1)):
            skewed = cache_key("fwd", "a", sig, runtime={**rt, field: value})
            assert skewed != base, f"{field} skew did not re-key"

    def test_arch_fingerprint_shapes_not_values(self):
        p1 = {"a": np.zeros((3, 4), np.float32), "b": np.ones(5, np.int32)}
        p2 = {"a": np.full((3, 4), 7.0, np.float32),
              "b": np.arange(5, dtype=np.int32)}
        assert arch_fingerprint(p1) == arch_fingerprint(p2)  # values free
        p3 = {"a": np.zeros((3, 5), np.float32), "b": np.ones(5, np.int32)}
        assert arch_fingerprint(p1) != arch_fingerprint(p3)  # shapes bind
        p4 = {"a": np.zeros((3, 4), np.float64), "b": np.ones(5, np.int32)}
        assert arch_fingerprint(p1) != arch_fingerprint(p4)  # dtypes bind
        assert arch_fingerprint(p1, {"s": np.zeros(2)}) \
            != arch_fingerprint(p1)  # state binds

    def test_call_signature_hashable_and_shape_exact(self):
        a = call_signature((np.zeros((2, 3), np.float32), np.int32(7)))
        b = call_signature((np.ones((2, 3), np.float32), np.int32(9)))
        assert a == b and hash(a)  # values/scalars traced, not keyed
        c = call_signature((np.zeros((2, 4), np.float32), np.int32(7)))
        assert a != c
        # abstract shapes produce the SAME signature as concrete arrays —
        # what makes warm() interchangeable with a real call
        d = call_signature((jax.ShapeDtypeStruct((2, 3), jnp.float32),
                            jax.ShapeDtypeStruct((), jnp.int32)))
        assert a == d

    @pytest.mark.parametrize(
        "a, b", itertools.combinations(sorted(_OPERANDS), 2),
        ids=lambda name: name)
    def test_structural_key_partitions_as_the_signature(self, a, b):
        """The executable map's key and the string signature put two
        operand lists together, or apart, alike: what ``warm()`` or an
        earlier call acquired under one is what the next call finds under
        the other, and no two signatures share an executable."""
        (same_a, args_a), (same_b, args_b) = _OPERANDS[a], _OPERANDS[b]
        ka, kb = structural_key(args_a), structural_key(args_b)
        assert (ka == kb) == (same_a == same_b)
        assert (call_signature(args_a) == call_signature(args_b)) \
            == (same_a == same_b)
        if same_a == same_b:
            assert hash(ka) == hash(kb)


class TestStore:
    def test_roundtrip_and_manifest(self, tmp_path):
        store = AotStore(tmp_path)
        blob = b"executable-bytes" * 100
        assert store.put(_key(), blob, meta={"tag": "decode"})
        assert store.get(_key()) == blob
        assert store.get(_key(1)) is None  # clean miss
        entry = store.entries()[_key()]
        assert entry["meta"]["tag"] == "decode"
        st = store.stats()
        assert st["entries"] == 1 and st["quarantined"] == 0

    def test_corrupt_entry_quarantined(self, tmp_path):
        store = AotStore(tmp_path)
        store.put(_key(), b"payload" * 50)
        path = store._entry_path(_key())
        with open(path, "r+b") as f:
            f.seek(45)
            f.write(b"\xde\xad\xbe\xef")
        with pytest.raises(AotCorruptEntry):
            store.get(_key())
        # moved aside atomically: re-reads are clean misses, stats see it
        assert store.get(_key()) is None
        assert store.stats()["quarantined"] == 1
        assert _key() not in store.entries()

    def test_index_rebuilt_from_entries(self, tmp_path):
        store = AotStore(tmp_path)
        for i in range(3):
            store.put(_key(i), f"blob-{i}".encode())
        (tmp_path / "index.json").write_text("{ not json")
        assert sorted(AotStore(tmp_path).entries()) == sorted(
            _key(i) for i in range(3))
        assert store.rebuild_index() == 3
        assert store.get(_key(1)) == b"blob-1"

    def test_lru_gc_bounded(self, tmp_path):
        store = AotStore(tmp_path, max_bytes=0)  # no eviction at write time
        for i in range(6):
            store.put(_key(i), bytes(200))
        for i in (0, 3):  # touch -> most recently used
            store.get(_key(i))
        per_entry = store.entries()[_key(0)]["size"]
        evicted = store.gc(max_bytes=3 * per_entry)
        assert len(evicted) == 3
        assert _key(0) not in evicted and _key(3) not in evicted
        assert store.stats()["entries"] == 3

    def test_concurrent_readers_during_gc(self, tmp_path):
        # an evicted-underfoot entry is a clean miss, never an exception
        store = AotStore(tmp_path, max_bytes=0)
        keys = [_key(i) for i in range(16)]
        for k in keys:
            store.put(k, bytes(300))
        errors, stop = [], threading.Event()

        def reader():
            while not stop.is_set():
                for k in keys:
                    try:
                        got = store.get(k)
                        assert got is None or got == bytes(300)
                    except Exception as e:  # noqa: BLE001 — the assertion
                        errors.append(e)
                        return

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        for bound in (12, 8, 4, 0):
            store.gc(max_bytes=max(bound, 1) * 400)
        stop.set()
        for t in threads:
            t.join(10)
        assert not errors, errors

    def test_verify_quarantines_and_reports(self, tmp_path):
        store = AotStore(tmp_path)
        store.put(_key(0), b"good")
        store.put(_key(1), b"bad")
        with open(store._entry_path(_key(1)), "r+b") as f:
            f.seek(41)
            f.write(b"\x00\x00")
        out = store.verify()
        assert out["ok"] == [_key(0)] and out["quarantined"] == [_key(1)]

    def test_malformed_key_rejected(self, tmp_path):
        store = AotStore(tmp_path)
        with pytest.raises(ValueError):
            store.put("../../escape", b"x")


@pytest.fixture()
def jitted():
    return jax.jit(lambda p, x: x @ p + 1.0)


_P = np.ones((4, 4), np.float32)
_X = np.arange(8, dtype=np.float32).reshape(2, 4)


def _wrapper(jitted, store, metrics, tag="fwd"):
    return AotFunction(jitted, tag=tag, store=store, metrics=metrics,
                       arch=arch_fingerprint(_P), component="generate",
                       compile_counter=metrics.counter(
                           "serve_compile_misses_total",
                           {"component": "generate"}))


class TestAotFunction:
    def test_second_boot_zero_compiles(self, tmp_path, jitted):
        m1 = MetricsRegistry()
        f1 = _wrapper(jitted, AotStore(tmp_path), m1)
        y1 = np.asarray(f1(_P, _X))
        assert m1.counter("serve_compile_misses_total",
                          {"component": "generate"}).value == 1
        # fresh wrapper + fresh store handle = a process restart
        m2 = MetricsRegistry()
        f2 = _wrapper(jitted, AotStore(tmp_path), m2)
        y2 = np.asarray(f2(_P, _X))
        np.testing.assert_array_equal(y1, y2)
        assert m2.counter("serve_compile_misses_total",
                          {"component": "generate"}).value == 0
        assert _series(m2, "serve_aot_hits_total")[
            (("component", "generate"),)] == 1

    def test_warm_is_abstract_and_sufficient(self, tmp_path, jitted):
        m = MetricsRegistry()
        f = _wrapper(jitted, AotStore(tmp_path), m)
        assert f.warm(jax.ShapeDtypeStruct((4, 4), jnp.float32),
                      jax.ShapeDtypeStruct((5, 4), jnp.float32))
        assert f.acquire_seconds > 0
        counter = m.counter("serve_compile_misses_total",
                            {"component": "generate"})
        before = counter.value
        f(_P, np.ones((5, 4), np.float32))  # same signature: no new compile
        assert counter.value == before

    def test_corrupt_entry_degrades_to_tracing(self, tmp_path, jitted):
        store = AotStore(tmp_path)
        m1 = MetricsRegistry()
        f1 = _wrapper(jitted, store, m1)
        want = np.asarray(f1(_P, _X))
        key = store.keys()[0]
        with open(store._entry_path(key), "r+b") as fo:
            fo.seek(60)
            fo.write(b"\xff\xff\xff\xff")
        m2 = MetricsRegistry()
        f2 = _wrapper(jitted, AotStore(tmp_path), m2)
        np.testing.assert_array_equal(np.asarray(f2(_P, _X)), want)
        assert _fallbacks_by_cause(m2) == {"corrupt": 1}
        assert AotStore(tmp_path).stats()["quarantined"] == 1
        # the traced fallback re-persisted the entry: third boot hits again
        m3 = MetricsRegistry()
        f3 = _wrapper(jitted, AotStore(tmp_path), m3)
        np.testing.assert_array_equal(np.asarray(f3(_P, _X)), want)
        assert _fallbacks_by_cause(m3) == {}

    def test_jaxlib_version_mismatch_key_is_miss_not_crash(
            self, tmp_path, jitted, monkeypatch):
        store = AotStore(tmp_path)
        m1 = MetricsRegistry()
        _wrapper(jitted, store, m1)(_P, _X)  # populate under the real key
        # simulate the NEXT boot running an upgraded jaxlib: keys re-derive
        from deeplearning4j_tpu.aot import compile as aot_compile

        real = runtime_fingerprint()
        monkeypatch.setattr(aot_compile, "runtime_fingerprint",
                            lambda: {**real, "jaxlib": "999.0.0"})
        m2 = MetricsRegistry()
        f2 = _wrapper(jitted, AotStore(tmp_path), m2)
        np.asarray(f2(_P, _X))  # miss -> live trace, NOT a crash
        assert _series(m2, "serve_aot_misses_total")[
            (("component", "generate"),)] == 1
        assert _fallbacks_by_cause(m2) == {}

    def test_blob_version_skew_falls_back(self, tmp_path, jitted):
        # defense in depth: a blob whose embedded jax/jaxlib pair disagrees
        # (same key — e.g. a hand-copied store) degrades with cause=version
        store = AotStore(tmp_path)
        m1 = MetricsRegistry()
        _wrapper(jitted, store, m1)(_P, _X)
        key = store.keys()[0]
        rec = pickle.loads(store.get(key))
        rec["jaxlib"] = "0.0.1"
        store.put(key, pickle.dumps(rec))
        m2 = MetricsRegistry()
        f2 = _wrapper(jitted, AotStore(tmp_path), m2)
        np.asarray(f2(_P, _X))
        assert _fallbacks_by_cause(m2) == {"version": 1}

    def test_garbage_pickle_falls_back(self, tmp_path, jitted):
        store = AotStore(tmp_path)
        m1 = MetricsRegistry()
        _wrapper(jitted, store, m1)(_P, _X)
        key = store.keys()[0]
        store.put(key, b"not a pickle at all")  # valid checksum, bad payload
        m2 = MetricsRegistry()
        f2 = _wrapper(jitted, AotStore(tmp_path), m2)
        np.asarray(f2(_P, _X))
        assert _fallbacks_by_cause(m2) == {"deserialize": 1}

    def test_plain_callable_passes_through(self, tmp_path):
        f = AotFunction(lambda p, x: x @ p, tag="plain",
                        store=AotStore(tmp_path))
        assert f.store is None
        np.testing.assert_array_equal(np.asarray(f(_P, _X)), _X @ _P)
        assert AotStore(tmp_path).stats()["entries"] == 0


def _sig_strings(metrics):
    """``serve_aot_signature_strings_total`` by tag."""
    return {dict(k)["tag"]: v for k, v in
            _series(metrics, "serve_aot_signature_strings_total").items()}


class TestLookupBuildsNoString:
    """The call path finds its executable by ``structural_key``; the string
    signature is built where an executable is acquired and nowhere else."""

    #: the runtime the pinned key below was computed under
    RUNTIME = {"jax": "0.9.0", "jaxlib": "0.9.0", "backend": "tpu",
               "device_kind": "TPU v5 lite", "device_count": 1,
               "process_count": 1}

    @staticmethod
    def _batcher(store, metrics, **kw):
        from deeplearning4j_tpu import models
        from deeplearning4j_tpu.serve.continuous import ContinuousBatcher

        model = models.CausalLM(seed=0, input_shape=(32,), num_layers=2,
                                d_model=32, num_heads=4, num_kv_heads=1,
                                vocab=50).build()
        model.init()
        # one chunk bucket: the static set is one executable a tag
        return ContinuousBatcher(model, slots=2, capacity=32, block_size=4,
                                 prefill_chunk=8, prompt_buckets=(8, 32),
                                 seed=0, aot_store=store, metrics=metrics,
                                 **kw)

    def test_gen_programs_one_string_an_executable(self, tmp_path,
                                                   monkeypatch):
        from deeplearning4j_tpu.serve.errors import AotTraceError

        m = MetricsRegistry()
        cb = self._batcher(AotStore(tmp_path), m)
        try:
            progs = cb._programs
            assert progs.chunk_buckets == (8,)
            assert _sig_strings(m) == {"gen_sample": 1, "gen_decode_paged": 1,
                                       "gen_prefill_chunk": 1}
            entered, calls = [], {}
            real_leaf_sig = aot_keys._leaf_sig
            monkeypatch.setattr(
                aot_keys, "_leaf_sig",
                lambda leaf: entered.append(1) or real_leaf_sig(leaf))
            for name in ("decode", "prefill_chunk", "sample"):
                def counted(*a, _real=getattr(progs, name), _name=name, **k):
                    calls[_name] = calls.get(_name, 0) + 1
                    return _real(*a, **k)
                monkeypatch.setattr(progs, name, counted)
            rng = np.random.RandomState(5)
            for i in range(5):
                prompt = rng.randint(1, 50, (3 + i,)).astype(np.int32)
                out = cb.generate(prompt, 12, temperature=0.7 * (i % 2),
                                  top_k=5)
                assert len(out) == 12
            assert calls["decode"] >= 50 and calls["prefill_chunk"] == 5 \
                and calls["sample"] == 5, calls
            assert not entered, "a call formatted its signature again"
            assert _sig_strings(m) == {"gen_sample": 1, "gen_decode_paged": 1,
                                       "gen_prefill_chunk": 1}
        finally:
            cb.shutdown()
        # the store that boot wrote serves a strict replica, and an operand
        # list of another shape is refused before anything runs
        m2 = MetricsRegistry()
        cb2 = self._batcher(AotStore(tmp_path), m2, strict_aot=True)
        try:
            compiles = m2.counter("serve_compile_misses_total",
                                  {"component": "generate"})
            fns = cb2.aot_functions()
            held = {t: set(f.executables) for t, f in fns.items()}
            with pytest.raises(AotTraceError):
                cb2._programs.sample(jnp.zeros((51,), jnp.float32),
                                     jnp.zeros((2,), jnp.uint32), 0.0, 50)
            assert compiles.value == 0
            assert {t: set(f.executables) for t, f in fns.items()} == held
            assert _series(m2, "serve_aot_strict_misses_total")[
                (("component", "generate"),)] == 1
            assert len(cb2.generate(np.array([3, 4, 5], np.int32), 4,
                                    temperature=0.0)) == 4
        finally:
            cb2.shutdown()

    def test_strict_refuses_another_shape_and_runs_nothing(self, tmp_path):
        from deeplearning4j_tpu.serve.errors import AotTraceError

        traced = []

        def fwd(p, x):
            traced.append(x.shape)
            return x @ p + 1.0

        _wrapper(jax.jit(fwd), AotStore(tmp_path), MetricsRegistry())(_P, _X)
        assert traced == [(2, 4)]
        m = MetricsRegistry()
        f = AotFunction(jax.jit(fwd), tag="fwd", store=AotStore(tmp_path),
                        metrics=m, arch=arch_fingerprint(_P),
                        component="generate", strict=True)
        np.testing.assert_array_equal(np.asarray(f(_P, _X)), _X @ _P + 1.0)
        with pytest.raises(AotTraceError):
            f(_P, np.ones((3, 4), np.float32))
        with pytest.raises(AotTraceError):
            f.warm(_P, jax.ShapeDtypeStruct((2, 4), jnp.bfloat16))
        assert traced == [(2, 4)]  # the strict wrapper traced nothing
        assert len(f.executables) == 1
        assert _series(m, "serve_aot_strict_misses_total")[
            (("component", "generate"),)] == 2

    def test_store_keys_are_the_old_lookups(self, tmp_path):
        """The key on disk is a hash of the STRING signature: it is what it
        was when the string also keyed the in-memory map (the literal below
        was computed by that code), so a store built then is hit now."""
        args = ({"w": _P, "b": None}, _X, np.int32(3), 0.5)
        pinned = ("63dbeba1e8dcc5372963a50f9beb7bb6"
                  "214cce21fdbf3510cb63811243b31737")
        assert cache_key("fwd", "arch0", call_signature(args), donate=(1,),
                         runtime=self.RUNTIME) == pinned

        def wrapper(metrics):
            f = AotFunction(
                jax.jit(lambda p, x, n, t: x @ p["w"] * t + n),
                tag="fwd", store=AotStore(tmp_path), metrics=metrics,
                arch="arch0", component="generate", donate_argnums=(1,),
                compile_counter=metrics.counter(
                    "serve_compile_misses_total", {"component": "generate"}))
            f._runtime = self.RUNTIME
            return f

        m1 = MetricsRegistry()
        f1 = wrapper(m1)
        f1.warm(*args)
        assert AotStore(tmp_path).keys() == [pinned] == f1.warmed_keys()
        (sig,) = f1.executables
        assert sig == call_signature(args) and f1.store_key(sig) == pinned
        m2 = MetricsRegistry()
        f2 = wrapper(m2)
        f2(*args)
        assert m2.counter("serve_compile_misses_total",
                          {"component": "generate"}).value == 0
        assert _series(m2, "serve_aot_hits_total")[
            (("component", "generate"),)] == 1
        assert _sig_strings(m2) == {"fwd": 1}


class TestPublishWarming:
    def test_failed_publish_leaves_registry_intact(self):
        from deeplearning4j_tpu.serve import ModelRegistry, PublishError

        params = {"w": np.ones((2, 2), np.float32)}
        reg = ModelRegistry(params, {})
        warmed = []
        reg.add_warmer(lambda p, s: warmed.append(np.asarray(p["w"]).sum()))
        reg.add_warmer(lambda p, s: (_ for _ in ()).throw(
            RuntimeError("candidate cannot compile")))
        before = reg.history()
        with pytest.raises(PublishError, match="old generation keeps"):
            reg.publish({"w": np.full((2, 2), 5.0, np.float32)})
        assert reg.history() == before
        assert reg.generation == 1
        assert not reg.inflight()  # no leaked leases
        assert warmed == [20.0]  # first warmer DID see the candidate
        with reg.lease() as snap:  # still serving the old params
            assert np.asarray(snap.params["w"]).sum() == 4.0

    def test_warmers_run_before_flip(self):
        from deeplearning4j_tpu.serve import ModelRegistry

        params = {"w": np.ones(3, np.float32)}
        reg = ModelRegistry(params, {})
        gen_at_warm = []
        reg.add_warmer(lambda p, s: gen_at_warm.append(reg.generation))
        snap = reg.publish({"w": np.zeros(3, np.float32)})
        assert snap.generation == 2
        assert gen_at_warm == [1]  # candidate warmed while gen 1 still live


class TestCli:
    def _run(self, *argv):
        from deeplearning4j_tpu.aot.__main__ import main
        return main(list(argv))

    def test_list_stats_verify_gc(self, tmp_path, capsys):
        store = AotStore(tmp_path)
        for i in range(3):
            store.put(_key(i), bytes(150), meta={"tag": f"t{i}", "arch": "a"})
        root = str(tmp_path)
        assert self._run("--store", root, "list") == 0
        assert "3 entries" in capsys.readouterr().out
        assert self._run("--store", root, "stats") == 0
        assert '"entries": 3' in capsys.readouterr().out
        assert self._run("--store", root, "verify") == 0
        assert self._run("--store", root, "rebuild-index") == 0
        capsys.readouterr()
        assert self._run("--store", root, "gc", "--max-bytes", "200") == 0
        assert "evicted 2" in capsys.readouterr().out

    def test_verify_exit_code_flags_quarantine(self, tmp_path, capsys):
        store = AotStore(tmp_path)
        store.put(_key(), b"data")
        with open(store._entry_path(_key()), "r+b") as f:
            f.seek(41)
            f.write(b"\x00")
        assert self._run("--store", str(tmp_path), "verify") == 1
        assert "quarantined" in capsys.readouterr().out
