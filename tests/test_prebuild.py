"""Tests for the compile-surface prebuild farm (ISSUE 16).

The contract under test, layer by layer:

- **parity**: the enumeration pass's pure-stdlib bucket derivations are
  bit-identical to what a booted ``ContinuousBatcher`` actually warms —
  the manifest can never drift from the serving code;
- **enumeration**: budgeted sites expand to the exact cross product of
  their bound's bucket tables; non-serving / wrong-KV / unknown-bound
  sites land in ``excluded`` with reasons; an unresolvable factor raises
  (an under-covering manifest must never be written silently);
- **manifest + coverage records**: self-hash verification on load, the
  (runtime fingerprint x manifest hash) coverage key, and every
  ``missing_signatures`` failure layer (no record, never-prebuilt tag,
  partial warm, evicted store entry);
- **strict AotFunction**: a store miss raises a typed
  :class:`AotTraceError` (counted on ``serve_aot_strict_misses_total``)
  and never traces — the compile counter and the store stay untouched;
- **end to end**: ``analysis --enumerate-manifest`` over the real serve
  tree -> ``aot prebuild --from-surface`` into a fresh store -> a strict
  ``ModelServer`` boots from it and serves mixed bucket traffic with
  ZERO compile misses and ZERO fallbacks; deleting one store entry fails
  the next strict boot with ``AotTraceError`` (HTTP 503), never a trace.
"""

import json
import os
import shutil
from pathlib import Path

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.analysis.enumerate import (
    SITE_TAGS, chunk_buckets, default_prompt_buckets, enumerate_surface,
    manifest_hash, resolve_tables, write_manifest)
from deeplearning4j_tpu.aot import (AotFunction, AotStore, arch_fingerprint,
                                    load_coverage, load_manifest,
                                    missing_signatures, record_coverage)
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve import AotTraceError

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "scripts" / "serve_config.json").read_text())


def _series(metrics, name):
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in metrics.snapshot().get(name, {}).get("series", [])}


def _total(metrics, name):
    return sum(_series(metrics, name).values())


def _model():
    from deeplearning4j_tpu.models import model_by_name

    return model_by_name(CONFIG["model"], seed=CONFIG["seed"],
                         **CONFIG["model_kwargs"]).init()


# --- bucket-table parity: analysis/enumerate.py vs serve/continuous.py ---

class TestBucketParity:
    def test_default_prompt_buckets_bit_identical(self):
        from deeplearning4j_tpu.serve.continuous import \
            _default_prompt_buckets

        for capacity in (8, 12, 16, 64, 100, 256, 1000):
            assert default_prompt_buckets(capacity) == \
                _default_prompt_buckets(capacity), f"capacity={capacity}"

    def test_paged_chunk_buckets_match_booted_batcher(self, monkeypatch):
        from deeplearning4j_tpu.serve import ContinuousBatcher

        # the parity contract is about the bucket TABLES the batcher
        # derives at construction, not its executables — skip the warm
        # pass so this test doesn't pay seconds of XLA compiles
        monkeypatch.setattr(ContinuousBatcher, "_warm_for",
                            lambda self, params, state: None)
        cb = ContinuousBatcher(_model(), slots=2, capacity=16,
                               block_size=16, prefill_chunk=8,
                               seed=0, metrics=MetricsRegistry())
        try:
            assert chunk_buckets(cb.prompt_buckets, cb.prefill_chunk) == \
                tuple(cb._chunk_buckets)
            tables = resolve_tables(CONFIG)
            assert tables["prompt_buckets"] == list(cb.prompt_buckets)
            assert tables["_chunk_buckets"] == list(cb._chunk_buckets)
        finally:
            cb.shutdown()

    def test_whole_prompt_prefill(self):
        assert chunk_buckets((8, 16), None) == (8, 16)


# --- enumeration over a synthetic surface report ---

_BUDGET = {"sites": {
    "deeplearning4j_tpu.serve.engine:fwd":
        {"bound": "|batch_buckets|*|length_buckets|", "why": "t"},
    "deeplearning4j_tpu.serve.programs:_decode_paged_fn":
        {"bound": "1", "why": "t"},
    "deeplearning4j_tpu.serve.programs:_prefill_chunk_fn":
        {"bound": "|_chunk_buckets|", "why": "t"},
    "deeplearning4j_tpu.serve.programs:_sample_dynamic":
        {"bound": "?", "why": "t"},
    "pkg.train:step": {"bound": "?", "why": "training-side"},
}}


def _report(sites):
    return {"sites": [{"site": s, "bound": b, "path": "x.py", "line": 1}
                      for s, b in sites]}


class TestEnumerate:
    def test_cross_product_and_exclusions(self):
        report = _report([
            ("deeplearning4j_tpu.serve.engine:fwd",
             "|batch_buckets|*|length_buckets|"),
            ("deeplearning4j_tpu.serve.programs:_decode_paged_fn", "1"),
            ("deeplearning4j_tpu.serve.programs:_prefill_chunk_fn",
             "|_chunk_buckets|"),
            ("deeplearning4j_tpu.serve.programs:_sample_dynamic", "?"),
            ("pkg.train:step", "?"),
            ("pkg.other:helper", "1"),
        ])
        manifest = enumerate_surface(report, _BUDGET, CONFIG)
        by_tag = {s["tag"]: s for s in manifest["sites"]}
        # |batch|*|length| with no length_buckets: 4 batches x [None]
        fwd = by_tag["engine_forward"]
        assert fwd["cardinality"] == 4
        assert fwd["signatures"] == [
            {"batch_buckets": b, "length_buckets": None}
            for b in (1, 2, 4, 8)]
        # bound "1": the empty product — exactly one signature
        assert by_tag["gen_decode_paged"]["signatures"] == [{}]
        assert by_tag["gen_prefill_chunk"]["signatures"] == [
            {"_chunk_buckets": 8}]
        assert manifest["total_signatures"] == 4 + 1 + 1
        reasons = {e["site"]: e["reason"] for e in manifest["excluded"]}
        # a serving-tagged site whose bound the analysis could not close
        assert "not statically enumerable" in reasons[
            "deeplearning4j_tpu.serve.programs:_sample_dynamic"]
        assert "not a serving executable" in reasons["pkg.train:step"]
        assert "no budget entry" in reasons["pkg.other:helper"]

    def test_unresolvable_factor_raises(self):
        report = _report([
            ("deeplearning4j_tpu.serve.engine:fwd", "|mystery_buckets|")])
        with pytest.raises(ValueError, match="under-cover"):
            enumerate_surface(report, _BUDGET, CONFIG)

    def test_hash_roundtrip_and_tamper_detection(self, tmp_path):
        report = _report([
            ("deeplearning4j_tpu.serve.engine:fwd",
             "|batch_buckets|*|length_buckets|")])
        manifest = enumerate_surface(report, _BUDGET, CONFIG)
        assert manifest["hash"] == manifest_hash(manifest)
        path = tmp_path / "m.json"
        write_manifest(manifest, str(path))
        assert load_manifest(str(path))["hash"] == manifest["hash"]
        edited = json.loads(path.read_text())
        edited["sites"][0]["cardinality"] = 1  # hand-trimmed surface
        path.write_text(json.dumps(edited))
        with pytest.raises(ValueError, match="hash mismatch"):
            load_manifest(str(path))

    def test_every_serving_budget_site_has_a_tag(self):
        budget = json.loads(
            (REPO / "scripts" / "compile_budget.json").read_text())
        for site in budget["sites"]:
            if site.startswith("deeplearning4j_tpu.serve."):
                assert site in SITE_TAGS, \
                    f"{site} is budgeted but has no AOT tag mapping"


# --- coverage records ---

def _fake_manifest(cardinality=2):
    return {"hash": "deadbeefdeadbeef",
            "sites": [{"site": "pkg.m:fn", "tag": "t",
                       "cardinality": cardinality, "signatures": []}],
            "total_signatures": cardinality}


def _keyed(i):
    import hashlib

    return hashlib.sha256(f"cov-{i}".encode()).hexdigest()


class TestCoverage:
    def test_record_roundtrip_and_all_missing_layers(self, tmp_path):
        store = AotStore(tmp_path)
        manifest = _fake_manifest(cardinality=2)
        # layer 1: no record at all
        (msg,) = missing_signatures(store, manifest)
        assert "no coverage record" in msg
        k1, k2 = _keyed(1), _keyed(2)
        store.put(k1, b"blob-1")
        store.put(k2, b"blob-2")
        record_coverage(store, manifest, {"t": [k1, k2]})
        assert load_coverage(store, manifest)["total_keys"] == 2
        assert missing_signatures(store, manifest) == []
        # layer 3: a recorded key whose entry was evicted/deleted
        os.remove(store._entry_path(k2))
        (msg,) = missing_signatures(AotStore(tmp_path), manifest)
        assert "is gone" in msg
        # layer 2a: partial warm
        record_coverage(store, manifest, {"t": [k1]})
        (msg,) = missing_signatures(store, manifest)
        assert "warmed 1 of 2" in msg
        # layer 2b: tag never prebuilt
        record_coverage(store, manifest, {})
        (msg,) = missing_signatures(store, manifest)
        assert "never prebuilt" in msg

    def test_record_is_runtime_keyed(self, tmp_path):
        store = AotStore(tmp_path)
        manifest = _fake_manifest(cardinality=1)
        k = _keyed(3)
        store.put(k, b"blob")
        rt_a = {"jax": "1", "jaxlib": "1", "backend": "cpu",
                "device_kind": "cpu", "device_count": 1,
                "process_count": 1}
        rt_b = {**rt_a, "jaxlib": "999"}
        record_coverage(store, manifest, {"t": [k]}, runtime=rt_a)
        assert missing_signatures(store, manifest, runtime=rt_a) == []
        # a build host with the wrong jaxlib cannot fake coverage
        (msg,) = missing_signatures(store, manifest, runtime=rt_b)
        assert "no coverage record" in msg

    def test_coverage_dir_invisible_to_store_maintenance(self, tmp_path):
        store = AotStore(tmp_path)
        k = _keyed(4)
        store.put(k, b"blob")
        record_coverage(store, _fake_manifest(1), {"t": [k]})
        assert store.stats()["entries"] == 1       # record is not an entry
        assert store.verify()["quarantined"] == []
        store.gc(max_bytes=1)                      # evict everything
        fresh = AotStore(tmp_path)
        assert fresh.stats()["entries"] == 0
        # ... but the coverage record survives (and now reports the hole)
        (msg,) = missing_signatures(fresh, _fake_manifest(1))
        assert "is gone" in msg


# --- strict AotFunction: a miss is a typed refusal, never a trace ---

_P = np.ones((4, 4), np.float32)
_X = np.arange(8, dtype=np.float32).reshape(2, 4)


def _wrapper(store, metrics, strict):
    return AotFunction(jax.jit(lambda p, x: x @ p + 1.0), tag="fwd",
                       store=store, metrics=metrics,
                       arch=arch_fingerprint(_P), component="engine",
                       strict=strict,
                       compile_counter=metrics.counter(
                           "serve_compile_misses_total",
                           {"component": "engine"}))


class TestStrictAotFunction:
    def test_miss_raises_typed_and_never_traces(self, tmp_path):
        m = MetricsRegistry()
        f = _wrapper(AotStore(tmp_path), m, strict=True)
        with pytest.raises(AotTraceError) as ei:
            f(_P, _X)
        assert ei.value.http_status == 503
        assert ei.value.cause == "aot_trace"
        # refusal is counted on its own metric; NO trace happened, so the
        # compile counter and the store are untouched
        assert _total(m, "serve_aot_strict_misses_total") == 1
        assert _total(m, "serve_compile_misses_total") == 0
        assert AotStore(tmp_path).stats()["entries"] == 0

    def test_prebuilt_signature_serves_with_zero_compiles(self, tmp_path):
        m1 = MetricsRegistry()
        builder = _wrapper(AotStore(tmp_path), m1, strict=False)
        assert builder.warm(jax.ShapeDtypeStruct((4, 4), np.float32),
                            jax.ShapeDtypeStruct((2, 4), np.float32))
        assert len(builder.warmed_keys()) == 1
        m2 = MetricsRegistry()
        f = _wrapper(AotStore(tmp_path), m2, strict=True)
        np.testing.assert_allclose(np.asarray(f(_P, _X)), _X @ _P + 1.0)
        assert _total(m2, "serve_compile_misses_total") == 0
        assert _total(m2, "serve_aot_strict_misses_total") == 0

    def test_strict_requires_store_and_lowerable_fn(self, tmp_path):
        with pytest.raises(ValueError, match="strict"):
            _wrapper(None, MetricsRegistry(), strict=True)
        with pytest.raises(ValueError, match="strict"):
            # a plain callable cannot be store-backed, so it cannot be
            # strict either — it would trace on every new signature
            AotFunction(lambda p, x: x @ p, tag="plain",
                        store=AotStore(tmp_path), strict=True)

    def test_strict_constructors_require_store(self):
        from deeplearning4j_tpu.serve import (ContinuousBatcher,
                                              ModelServer, ServeEngine)

        model = _model()
        with pytest.raises(ValueError, match="strict_aot"):
            ServeEngine(model, strict_aot=True)
        with pytest.raises(ValueError, match="strict_aot"):
            ContinuousBatcher(model, strict_aot=True)
        with pytest.raises(ValueError, match="strict_aot"):
            ModelServer(model, port=0, strict_aot=True)


# --- the shipped compile_miss page ---

class TestCompileMissAlert:
    def test_shipped_rule(self):
        from deeplearning4j_tpu.obs.alerts import default_rules

        rules = {r.name: r for r in default_rules()}
        rule = rules["compile_miss"]
        assert rule.metric == "serve_compile_misses_total"
        assert rule.op == ">" and rule.value == 0.0
        assert rule.severity == "page"
        # appended last: existing positional consumers keep their indices
        assert default_rules()[0].name == "gold_burn_high"
        assert default_rules()[-1].name == "compile_miss"


# --- end to end: enumerate -> prebuild -> strict boot -> traffic ---

@pytest.fixture(scope="module")
def prebuilt(tmp_path_factory):
    """Real pipeline: jaxlint enumeration over the serve tree, then
    ``aot prebuild --from-surface`` into a fresh store."""
    from deeplearning4j_tpu.analysis.__main__ import main as analysis_main
    from deeplearning4j_tpu.aot.__main__ import main as aot_main

    out = tmp_path_factory.mktemp("prebuild")
    manifest = out / "prebuild_manifest.json"
    cwd = os.getcwd()
    os.chdir(REPO)  # module ids derive from relative tree paths
    try:
        rc = analysis_main([
            "deeplearning4j_tpu/serve", "deeplearning4j_tpu/nn",
            "--compile-surface", str(out / "compile_surface.json"),
            "--budget", "scripts/compile_budget.json",
            "--enumerate-manifest", str(manifest),
            "--serve-config", "scripts/serve_config.json"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    store_dir = out / "store"
    assert aot_main(["--store", str(store_dir), "prebuild",
                     "--from-surface", str(manifest)]) == 0
    return store_dir, manifest


def _strict_server(store_dir, manifest=None, metrics=None):
    from deeplearning4j_tpu.serve import ModelServer

    gen = CONFIG["gen"]
    return ModelServer(
        _model(), port=0,
        batch_buckets=tuple(CONFIG["engine"]["batch_buckets"]),
        input_dtype=np.dtype(CONFIG["dtype"]),
        gen_slots=gen["slots"], gen_capacity=gen["capacity"],
        gen_block_size=gen["block_size"],
        gen_prefill_chunk=gen["prefill_chunk"], seed=gen["seed"],
        metrics=metrics if metrics is not None else MetricsRegistry(),
        aot_store=AotStore(store_dir), strict_aot=True,
        aot_manifest=str(manifest) if manifest is not None else None)


class TestStrictEndToEnd:
    def test_verify_manifest_gate(self, prebuilt, capsys):
        from deeplearning4j_tpu.aot.__main__ import main as aot_main

        store_dir, manifest = prebuilt
        assert aot_main(["--store", str(store_dir), "verify",
                         "--manifest", str(manifest)]) == 0
        assert "fully covered" in capsys.readouterr().out

    def test_strict_boot_serves_mixed_buckets_zero_misses(self, prebuilt):
        store_dir, manifest = prebuilt
        m = MetricsRegistry()
        srv = _strict_server(store_dir, manifest, metrics=m)
        try:
            rng = np.random.RandomState(0)
            # predict traffic across every batch bucket
            for rows in (1, 2, 3, 8):
                y = srv.engine.predict(
                    rng.randint(0, 50, (rows, 16)).astype(np.int32),
                    timeout_ms=60000)
                assert y.shape[0] == rows
            # generation traffic spanning both prompt buckets (<=8, <=16)
            cb = srv.batcher()
            for plen in (3, 8, 12):
                toks = cb.generate(
                    rng.randint(0, 50, (plen,)).astype(np.int32), 3,
                    temperature=0.0)
                assert len(toks) == 3
            assert _total(m, "serve_compile_misses_total") == 0, \
                "a strict prebuilt replica traced at request time"
            assert _total(m, "serve_aot_fallback_total") == 0
            assert _total(m, "serve_aot_strict_misses_total") == 0
            assert _total(m, "serve_aot_hits_total") > 0
        finally:
            srv.stop()

    def test_uncovered_signature_is_typed_503_through_the_batcher(
            self, prebuilt):
        # the dispatcher thread must NOT launder a strict-mode
        # AotTraceError into a generic internal ServeError: an uncovered
        # signature submitted through the batched path keeps its cause
        # ("aot_trace") and 503 status all the way to the caller
        from deeplearning4j_tpu.serve import AotTraceError

        store_dir, manifest = prebuilt
        m = MetricsRegistry()
        srv = _strict_server(store_dir, manifest, metrics=m)
        try:
            bad = np.zeros((2, 8), np.int32)  # covered time length is 16
            with pytest.raises(AotTraceError) as ei:
                srv.engine.submit(bad, timeout_ms=60000).wait()
            assert ei.value.http_status == 503
            assert ei.value.cause == "aot_trace"
            assert _total(m, "serve_compile_misses_total") == 0
            assert _total(m, "serve_aot_strict_misses_total") >= 1
        finally:
            srv.stop()

    def test_incomplete_store_fails_boot_typed_never_traces(
            self, prebuilt, tmp_path):
        store_dir, manifest = prebuilt
        broken = tmp_path / "broken-store"
        shutil.copytree(store_dir, broken)
        store = AotStore(broken)
        record = load_coverage(store, load_manifest(str(manifest)))
        victim = record["tags"]["gen_sample"][0]
        os.remove(store._entry_path(victim))
        entries_before = AotStore(broken).stats()["entries"]

        # with the manifest gate: refused BEFORE any stack is built
        m1 = MetricsRegistry()
        with pytest.raises(AotTraceError, match="does not cover"):
            _strict_server(broken, manifest, metrics=m1)
        # without the gate: the batcher's warm-at-construction pass hits
        # the hole and raises the same typed error at boot
        m2 = MetricsRegistry()
        with pytest.raises(AotTraceError):
            _strict_server(broken, manifest=None, metrics=m2)
        for m in (m1, m2):
            assert _total(m, "serve_compile_misses_total") == 0, \
                "an uncovered strict boot traced instead of failing"
        assert AotStore(broken).stats()["entries"] == entries_before, \
            "the failed boot compiled something into the store"
