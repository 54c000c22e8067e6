#!/usr/bin/env python
"""CI smoke serve: boot a ModelServer on a small CausalLM, fire mixed
predict/generate traffic at it concurrently, and assert the ISSUE-4/5
acceptance surface — every request answered (zero drops below capacity),
greedy /generate matches whole-batch ``nn.generation.generate`` on both the
buffered and the SSE-streamed path, the executable set stays bounded, a
long-prompt burst that OVERCOMMITS the paged-KV pool queues and completes
(with a truly-impossible request shed as a typed ``CapacityError``), and
the Prometheus scrape exposes the serving histograms/counters plus the
paged-KV block gauges — so a regression in the serving path fails CI before
it reaches a real deployment.

ISSUE-6 addition: the server is then booted TWICE against one persistent
AOT store directory — the second boot must serve identical results with
ZERO decode-path XLA compiles (``serve_compile_misses_total`` stays 0) and
``serve_aot_hits_total > 0`` in its scrape.

ISSUE-16 addition: the full prebuild farm loop — the jaxlint enumeration
manifest (compile-surface bounds x the committed scripts/serve_config.json)
is compiled into a fresh store by ``aot prebuild --from-surface``, a STRICT
replica boots from it and serves mixed bucket traffic with ZERO compile
misses/fallbacks, and a deliberately incomplete store fails the next strict
boot with a typed ``AotTraceError`` — never a trace.

Artifacts land in $CI_ARTIFACTS_DIR (default: ./ci-artifacts/):
smoke_serve_metrics.prom (the final /metrics scrape of the main server),
smoke_serve_warmboot.prom (the warm second boot's scrape), aot_store/
(the store both boots shared), prebuild_manifest.json + prebuild_coverage.json
(the enumeration manifest and the store's stamped coverage record),
smoke_serve_strict.prom (the strict replica's scrape — carries the
``profile_*`` and ``serve_padding_waste_ratio`` families), and
cost_profile.json (the continuous profiler's measured CostProfile,
also persisted into the prebuilt store for tuner-boot calibration).
"""

import concurrent.futures as cf
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

PREDICTS = 12
GENERATES = 6

REQUIRED_METRICS = (
    "serve_queue_depth", "serve_queue_seconds_bucket",
    "serve_device_seconds_bucket", "serve_batch_occupancy_bucket",
    "serve_batches_total", "serve_requests_total",
    "serve_compile_misses_total", "serve_model_generation",
    "serve_gen_admitted_total", "serve_gen_completed_total",
    "serve_gen_tokens_total", "http_request_seconds_bucket",
    # paged-KV + chunked-prefill surface (ISSUE 5)
    "serve_kv_blocks_total", "serve_kv_blocks_used",
    "serve_kv_block_utilization", "serve_kv_live_bytes",
    "serve_prefill_chunks_total", "serve_lease_total",
    # prefix-cache / CoW / fork surface (ISSUE 20)
    "serve_prefix_cache_hits_total", "serve_prefix_cache_misses_total",
    "serve_prefill_tokens_saved_total", "serve_prefix_blocks_shared",
    "serve_kv_cow_copies_total", "serve_gen_forks_total",
)


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def _sse_generate(port, body):
    """POST /generate on the default (streaming) path; return the token
    list from the per-token SSE events, cross-checked against the final
    ``done`` event."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.headers["Content-Type"] == "text/event-stream", \
            "/generate did not stream by default"
        for line in r:
            if line.startswith(b"data: "):
                events.append(json.loads(line[len(b"data: "):]))
    assert events and events[-1].get("done"), events[-1:]
    toks = [e["token"] for e in events[:-1]]
    assert events[-1]["tokens"] == toks, "SSE final event disagrees"
    return toks


def _overcommit_burst(model):
    """Long-prompt burst against a deliberately tiny block pool: total
    demand (6 requests x 10 tokens) overcommits the 4-usable-block pool
    (16 KV tokens), so requests queue on block availability and ALL must
    still complete bit-exactly; a request that can NEVER fit is shed as a
    typed CapacityError at submit."""
    import concurrent.futures as cf

    from deeplearning4j_tpu.nn.generation import generate
    from deeplearning4j_tpu.serve import CapacityError, ContinuousBatcher

    cb = ContinuousBatcher(model, slots=4, capacity=32, block_size=4,
                           kv_blocks=5, prefill_chunk=8, queue_limit=16,
                           seed=0)
    try:
        rng = np.random.RandomState(42)
        prompts = [rng.randint(0, 50, (6,)).astype(np.int32)
                   for _ in range(6)]
        with cf.ThreadPoolExecutor(6) as ex:
            outs = list(ex.map(
                lambda p: cb.generate(p, 4, temperature=0.0), prompts))
        for p, o in zip(prompts, outs):
            want = generate(model, p[None], 4, temperature=0.0)[0]
            assert o.tolist() == want.tolist(), "overcommit corrupted decode"
        cb.flush_prefix_cache()  # cache-retained blocks count as used
        stats = cb.kv_block_stats()
        assert stats["blocks_used"] == 0, stats  # everything retired
        try:
            cb.submit(np.zeros(12, np.int32), 8)  # 20 tokens > 16-token pool
            raise AssertionError("impossible request was admitted")
        except CapacityError:
            pass
        return stats["blocks_total"]
    finally:
        cb.shutdown()


def _prefix_cache_scenario(model):
    """ISSUE-20 acceptance: N concurrent requests share one system prompt.
    A primer request populates the prefix cache; the burst must take cache
    hits (counters move), decode bit-identically to whole-batch dense
    ``generate``, compile NOTHING new (adoption changes block-table
    contents, never shapes), and after drain + flush every refcount is
    back to zero (``blocks_used == 0``, nothing cached or shared)."""
    import concurrent.futures as cf

    from deeplearning4j_tpu.nn.generation import generate
    from deeplearning4j_tpu.serve import ContinuousBatcher

    cb = ContinuousBatcher(model, slots=2, capacity=16, block_size=4,
                           kv_blocks=16, prefill_chunk=4,
                           prompt_buckets=(4, 8, 12, 16), queue_limit=16,
                           seed=0)
    try:
        rng = np.random.RandomState(11)
        sys_prompt = rng.randint(0, 50, (8,)).astype(np.int32)  # 2 blocks
        prompts = [np.concatenate(
            [sys_prompt, rng.randint(0, 50, (3,)).astype(np.int32)])
            for _ in range(6)]
        # primer: warms every executable and inserts the shared blocks
        cb.generate(np.concatenate(
            [sys_prompt, rng.randint(0, 50, (3,)).astype(np.int32)]),
            4, temperature=0.0)
        sigs_before = set(cb.compile_signatures)
        with cf.ThreadPoolExecutor(6) as ex:
            outs = list(ex.map(
                lambda p: cb.generate(p, 4, temperature=0.0), prompts))
        for p, o in zip(prompts, outs):
            want = generate(model, p[None], 4, temperature=0.0)[0]
            assert o.tolist() == want.tolist(), \
                "cached decode diverged from dense"
        assert set(cb.compile_signatures) == sigs_before, \
            "prefix-cache burst compiled a new executable"
        stats = cb.kv_block_stats()
        px = stats["prefix_cache"]
        assert px["hits"] >= len(prompts), px  # every burst request hit
        saved = cb.metrics.counter("serve_prefill_tokens_saved_total").value
        assert saved >= len(prompts) * 8, saved  # 2 whole blocks each
        assert stats["blocks_cached"] > 0, stats  # cache is live pre-flush
        cb.flush_prefix_cache()
        stats = cb.kv_block_stats()
        assert stats["blocks_used"] == 0, stats  # every refcount back to 0
        assert stats["blocks_cached"] == 0 and stats["blocks_shared"] == 0, \
            stats
        return int(px["hits"]), int(saved)
    finally:
        cb.shutdown()


def _prom_total(scrape, name):
    """Sum every series of one metric in a Prometheus text scrape."""
    total = 0.0
    for line in scrape.splitlines():
        if line.startswith(name) and len(line) > len(name) \
                and line[len(name)] in "{ ":
            total += float(line.rsplit(" ", 1)[1])
    return total


def _aot_warm_boot(out_dir):
    """Boot a server twice against ONE persistent AOT store. Boot 1 traces
    live and persists every executable; boot 2 must load them all back —
    identical greedy output, serve_aot_hits_total > 0, and ZERO XLA
    compiles on the compile-miss counter (the ISSUE-6 acceptance gate)."""
    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.serve import ModelServer

    store_dir = os.path.join(out_dir, "aot_store")

    def boot():
        model = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                         num_heads=4, vocab=50).build()
        model.init()
        srv = ModelServer(model, port=0, input_dtype=np.int32,
                          batch_buckets=(1, 2, 4, 8), gen_slots=2,
                          gen_capacity=16,
                          aot_store=AotStore(store_dir)).start()
        try:
            pred = _post(srv.port, "/predict",
                         {"ndarray": [[1] * 8, [2] * 8]})["output"]
            toks = _post(srv.port, "/generate?stream=false",
                         {"prompt": [1, 2, 3], "max_new_tokens": 3,
                          "temperature": 0.0})["tokens"]
            models = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/models", timeout=10).read())
            scrape = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=10).read().decode()
        finally:
            srv.stop()
        return pred, toks, models, scrape

    pred1, toks1, _, _ = boot()          # cold: trace + persist
    pred2, toks2, models, scrape = boot()  # warm: disk only
    assert toks1 == toks2 and pred1 == pred2, \
        "warm boot changed serving output"
    assert models.get("aot_store", {}).get("entries", 0) > 0, models
    hits = _prom_total(scrape, "serve_aot_hits_total")
    compiles = _prom_total(scrape, "serve_compile_misses_total")
    fallbacks = _prom_total(scrape, "serve_aot_fallback_total")
    assert hits > 0, "second boot took no AOT store hits"
    assert compiles == 0, \
        f"second boot traced ({compiles} compile misses) despite warm store"
    assert fallbacks == 0, f"warm store fell back {fallbacks} time(s)"
    with open(os.path.join(out_dir, "smoke_serve_warmboot.prom"), "w") as f:
        f.write(scrape)
    return int(hits)


def _strict_prebuilt_scenario(out_dir):
    """ISSUE-16 acceptance: enumerate -> ``aot prebuild --from-surface``
    -> a strict replica boots from the prebuilt store, serves traffic
    spanning every batch/prompt bucket with serve_compile_misses_total
    == 0 and zero fallbacks; then one store entry is deleted and the next
    strict boot fails with a typed AotTraceError (the 503 family), never
    a trace.

    ISSUE-17 addition: the continuous profiler (obs/profile) rides the
    strict replica's mixed traffic — every budgeted decode/prefill
    executable must appear in the capture with nonzero dispatches,
    ``serve_padding_waste_ratio`` must be on the scrape, the derived
    CostProfile lands in $CI_ARTIFACTS_DIR/cost_profile.json AND in the
    prebuilt store (resolved back as a counted profile_store hit — the
    artifact the sim tuner calibrates from at boot)."""
    import glob
    import shutil

    from deeplearning4j_tpu.analysis.__main__ import main as analysis_main
    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.aot.__main__ import main as aot_main
    from deeplearning4j_tpu.models import model_by_name
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.serve import AotTraceError, ModelServer

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = json.load(open(os.path.join(repo, "scripts",
                                         "serve_config.json")))
    manifest_path = os.path.join(out_dir, "prebuild_manifest.json")
    if not os.path.exists(manifest_path):
        # ci.sh writes the manifest during its jaxlint step; standalone
        # runs enumerate here (module ids derive from repo-relative paths)
        cwd = os.getcwd()
        os.chdir(repo)
        try:
            rc = analysis_main([
                "deeplearning4j_tpu/serve", "deeplearning4j_tpu/nn",
                "--compile-surface",
                os.path.join(out_dir, "compile_surface.json"),
                "--budget", "scripts/compile_budget.json",
                "--enumerate-manifest", manifest_path,
                "--serve-config", "scripts/serve_config.json"])
        finally:
            os.chdir(cwd)
        assert rc == 0, "enumeration pass failed"

    store_dir = os.path.join(out_dir, "prebuild_store")
    assert aot_main(["--store", store_dir, "prebuild",
                     "--from-surface", manifest_path]) == 0, \
        "prebuild --from-surface failed"
    assert aot_main(["--store", store_dir, "verify",
                     "--manifest", manifest_path]) == 0, \
        "freshly prebuilt store failed its own coverage gate"
    records = glob.glob(os.path.join(store_dir, "coverage", "*.json"))
    assert records, "prebuild stamped no coverage record"
    shutil.copy(records[0], os.path.join(out_dir, "prebuild_coverage.json"))

    gen = config["gen"]

    def boot(store_root, metrics=None):
        model = model_by_name(config["model"], seed=config["seed"],
                              **config["model_kwargs"]).init()
        return ModelServer(
            model, port=0, input_dtype=np.dtype(config["dtype"]),
            batch_buckets=tuple(config["engine"]["batch_buckets"]),
            gen_slots=gen["slots"], gen_capacity=gen["capacity"],
            gen_block_size=gen["block_size"],
            gen_prefill_chunk=gen["prefill_chunk"], seed=gen["seed"],
            metrics=metrics, aot_store=AotStore(store_root),
            strict_aot=True, aot_manifest=manifest_path)

    from deeplearning4j_tpu.aot import arch_fingerprint
    from deeplearning4j_tpu.obs import profile as prof_mod

    m = MetricsRegistry()
    srv = boot(store_dir, metrics=m).start()
    # the profiler shares the server's registry so profile_* families and
    # the padding-waste gauge ride the same scrape artifact
    prof = prof_mod.install(prof_mod.Profiler(sample_rate=4, metrics=m))
    try:
        model_fp = arch_fingerprint(srv.model.params, srv.model.state)
        rng = np.random.RandomState(7)
        # every batch bucket (1, 2, 4, 8 rows) at the model's native time
        # length — with length_buckets unset that IS the enumerated axis
        for rows in (1, 2, 4, 8):
            ids = rng.randint(0, 50, (rows, 16)).tolist()
            out = _post(srv.port, "/predict", {"ndarray": ids})["output"]
            assert len(out) == rows
        # ... and prompts spanning both prompt buckets (<=8, <=16)
        for plen in (3, 8, 12):
            prompt = rng.randint(0, 50, (plen,)).tolist()
            toks = _post(srv.port, "/generate?stream=false",
                         {"prompt": prompt, "max_new_tokens": 3,
                          "temperature": 0.0})["tokens"]
            assert len(toks) == 3
        debug = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/debug/profile",
            timeout=10).read())
        assert debug.get("enabled") and debug.get("executables"), debug
        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read().decode()
    finally:
        snap = prof.snapshot(include_pairs=True)
        prof_mod.uninstall()
        srv.stop()

    # every budgeted decode/prefill executable family took live traffic
    tags = {e["tag"] for e in snap["executables"] if e["dispatches"] > 0}
    assert "engine_forward" in tags, tags
    assert any("prefill" in t for t in tags), tags
    assert any("decode" in t for t in tags), tags
    assert "serve_padding_waste_ratio{" in scrape, \
        "padding-waste gauge missing from strict scrape"
    assert "profile_dispatch_device_seconds" in scrape, \
        "profile histograms missing from strict scrape"

    # the CostProfile artifact: CI upload + AOT-store roundtrip (the
    # tuner-boot path resolves it exactly like this, counted as a hit)
    from deeplearning4j_tpu.obs.costmodel import (ProfileAccumulator,
                                                  get_profile, put_profile)
    cost = ProfileAccumulator().fold(snap).profile()
    with open(os.path.join(out_dir, "cost_profile.json"), "w") as f:
        f.write(cost.to_json())
    assert put_profile(AotStore(store_dir), model_fp, cost) is not None
    m2 = MetricsRegistry()
    got = get_profile(AotStore(store_dir), model_fp, metrics=m2)
    assert got is not None and got.executables, "profile did not roundtrip"
    phits = sum(s["value"] for s in m2.snapshot().get(
        "profile_store_hits_total", {}).get("series", []))
    assert phits == 1, f"profile resolution not counted as a hit: {phits}"

    hits = _prom_total(scrape, "serve_aot_hits_total")
    compiles = _prom_total(scrape, "serve_compile_misses_total")
    fallbacks = _prom_total(scrape, "serve_aot_fallback_total")
    refusals = _prom_total(scrape, "serve_aot_strict_misses_total")
    assert compiles == 0, \
        f"strict prebuilt replica traced ({compiles} compile misses)"
    assert fallbacks == 0, f"strict replica fell back {fallbacks} time(s)"
    assert refusals == 0, f"strict replica refused {refusals} signature(s)"
    assert hits > 0, "strict replica took no AOT store hits"
    with open(os.path.join(out_dir, "smoke_serve_strict.prom"), "w") as f:
        f.write(scrape)

    # delete ONE executable: the next strict boot must fail with the typed
    # error at the manifest gate — before any stack is built, never a trace
    broken = store_dir + "_broken"
    shutil.rmtree(broken, ignore_errors=True)
    shutil.copytree(store_dir, broken)
    victim = glob.glob(os.path.join(broken, "*", "*.aotx"))[0]
    os.remove(victim)
    m = MetricsRegistry()
    try:
        boot(broken, metrics=m).stop()
        raise AssertionError("strict boot served from an incomplete store")
    except AotTraceError as e:
        assert e.http_status == 503 and e.cause == "aot_trace", e
    traced = sum(s["value"] for s in m.snapshot().get(
        "serve_compile_misses_total", {}).get("series", []))
    assert traced == 0, "the refused boot traced instead of failing"
    assert aot_main(["--store", broken, "verify",
                     "--manifest", manifest_path]) == 1, \
        "verify --manifest passed an incomplete store"
    shutil.rmtree(broken, ignore_errors=True)
    return int(hits)


def _fleet_scenario(out_dir):
    """ISSUE-7 acceptance: two named models share an HBM budget that fits
    only ONE, served over the routed fleet front door by two tenants.
    Concurrent cross-model traffic forces page-ins UNDER LOAD and every
    response must still match its own model (zero wrong-params answers);
    the throttled tenant's sheds surface as HTTP 429 + Retry-After and as
    ``serve_shed_total{cause="quota",tenant=...}`` on the shared scrape,
    which lands in $CI_ARTIFACTS_DIR as smoke_serve_fleet.prom."""
    import urllib.error

    import jax

    from deeplearning4j_tpu.fleet import FleetRegistry, FleetServer
    from deeplearning4j_tpu.models import CausalLM

    models = {}
    for name, seed in (("alpha", 0), ("beta", 1)):
        m = CausalLM(seed=seed, input_shape=(16,), num_layers=2, d_model=32,
                     num_heads=4, vocab=50).build()
        m.init()
        models[name] = m
    wb = sum(int(np.asarray(leaf).nbytes) for leaf in
             jax.tree.leaves((models["alpha"].params,
                              models["alpha"].state)))
    fleet = FleetRegistry(hbm_budget_bytes=wb + wb // 2)  # one resident
    for name, m in models.items():
        fleet.add(name, m, input_dtype=np.int32,
                  engine_opts={"batch_buckets": (1, 2, 4)})
    fleet.tenants.register("pro", rate_per_s=500, slo="standard")
    fleet.tenants.register("free", rate_per_s=1.0, burst=2.0, slo="batch")
    srv = FleetServer(fleet, port=0).start()
    try:
        rng = np.random.RandomState(3)
        prompts = rng.randint(0, 50, (4, 2, 16)).astype(np.int32)
        refs = {n: [np.asarray(m.output(p)) for p in prompts]
                for n, m in models.items()}

        def post(name, j, tenant):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/models/{name}/predict",
                data=json.dumps({"ndarray": prompts[j].tolist()}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Tenant": tenant})
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        # interleaved cross-model traffic: every round trips a page cycle,
        # and the paging happens while other requests are in flight
        jobs = [(("alpha", "beta")[i % 2], i % len(prompts))
                for i in range(12)]
        with cf.ThreadPoolExecutor(4) as ex:
            outs = list(ex.map(lambda nj: (nj, post(*nj, "pro")), jobs))
        for (name, j), reply in outs:
            assert reply["model"] == name
            np.testing.assert_allclose(
                np.asarray(reply["output"]), refs[name][j],
                rtol=1e-4, atol=1e-5,
                err_msg=f"wrong-params response from {name}")

        # quota tenant: the bucket admits the burst, then 429 + Retry-After
        quota = []
        for _ in range(6):
            try:
                post("alpha", 0, "free")
                quota.append(200)
            except urllib.error.HTTPError as e:
                body = json.loads(e.read())
                quota.append((e.code, body["cause"],
                              e.headers.get("Retry-After")))
        sheds = [q for q in quota if q != 200]
        assert 200 in quota and sheds, quota
        assert all(q[0] == 429 and q[1] == "quota" and int(q[2]) >= 1
                   for q in sheds), quota

        status = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v1/fleet", timeout=10).read())
        page_ins = status["pager"]["page_ins"]
        assert page_ins >= 3, status["pager"]  # paging happened under load
        assert status["tenants"]["free"]["shed"] >= 1, status["tenants"]

        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read().decode()
        for needle in ('serve_shed_total{cause="quota"', 'tenant="free"',
                       "fleet_page_in_total{model=", "fleet_page_out_total",
                       "fleet_resident_bytes", "fleet_hbm_budget_bytes",
                       'serve_lease_total{model='):
            assert needle in scrape, f"missing {needle} in fleet /metrics"
        with open(os.path.join(out_dir, "smoke_serve_fleet.prom"), "w") as f:
            f.write(scrape)
        return page_ins, len(sheds)
    finally:
        srv.stop()


def main() -> int:
    out_dir = os.environ.get("CI_ARTIFACTS_DIR", "ci-artifacts")
    os.makedirs(out_dir, exist_ok=True)

    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.nn.generation import generate
    from deeplearning4j_tpu.obs import reqtrace as reqtrace_mod
    from deeplearning4j_tpu.obs.reqtrace import RequestTracer
    from deeplearning4j_tpu.serve import ModelServer

    # request tracing on for the whole run: every histogram observation in
    # the serving path carries its request's trace_id, so the OpenMetrics
    # artifact below must come out exemplar-bearing
    reqtrace_mod.install(RequestTracer())

    model = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                     num_heads=4, vocab=50).build()
    model.init()
    srv = ModelServer(model, port=0, input_dtype=np.int32,
                      batch_buckets=(1, 2, 4, 8), gen_slots=2,
                      gen_capacity=16).start()
    try:
        rng = np.random.RandomState(0)
        jobs = []
        for _ in range(PREDICTS):
            ids = rng.randint(0, 50, (int(rng.randint(1, 5)), 8)).tolist()
            jobs.append(("/predict", {"ndarray": ids}))
        for _ in range(GENERATES):
            prompt = rng.randint(0, 50, (int(rng.randint(3, 9)),)).tolist()
            jobs.append(("/generate?stream=false",
                         {"prompt": prompt, "max_new_tokens": 4,
                          "temperature": 0.0}))
        rng.shuffle(jobs)
        with cf.ThreadPoolExecutor(8) as ex:
            replies = list(ex.map(lambda j: (j, _post(srv.port, *j)), jobs))
        assert len(replies) == PREDICTS + GENERATES, "dropped responses"

        # greedy /generate is bit-identical to whole-batch generation
        for (path, body), reply in replies:
            if path == "/predict":
                want = np.asarray(model.output(
                    np.asarray(body["ndarray"], np.int32)))
                np.testing.assert_allclose(np.asarray(reply["output"]), want,
                                           rtol=1e-4, atol=1e-5)
            else:
                want = generate(model, np.asarray([body["prompt"]], np.int32),
                                4, temperature=0.0)[0]
                assert reply["tokens"] == want.tolist(), \
                    (path, body, reply, want)

        # default /generate streams SSE, token-identical to the buffered path
        sse_prompt = rng.randint(0, 50, (7,)).tolist()
        sse_body = {"prompt": sse_prompt, "max_new_tokens": 4,
                    "temperature": 0.0}
        sse_toks = _sse_generate(srv.port, sse_body)
        assert sse_toks == _post(srv.port, "/generate?stream=false",
                                 sse_body)["tokens"], "SSE != buffered"

        # bounded executables: engine <= |batch buckets|, batcher <=
        # |prompt buckets| + one decode step
        n_eng = len(srv.engine.compile_signatures)
        assert n_eng <= 4, srv.engine.compile_signatures
        bat = srv.batcher()
        n_gen = len(bat.compile_signatures)
        assert n_gen <= len(bat.prompt_buckets) + 1, bat.compile_signatures

        # long-prompt burst overcommitting a tiny pool (separate batcher so
        # the server's own pool sizing is untouched)
        pool_blocks = _overcommit_burst(model)

        # shared-system-prompt burst: cache hits, zero new compiles,
        # bit-identical decode, refcounts drain to zero after flush
        px_hits, px_saved = _prefix_cache_scenario(model)

        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/health", timeout=10).read())
        assert health["status"] == "ok"
        scrape = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read().decode()
        for needle in REQUIRED_METRICS:
            assert needle in scrape, f"missing {needle} in /metrics"

        prom_path = os.path.join(out_dir, "smoke_serve_metrics.prom")
        with open(prom_path, "w") as f:
            f.write(scrape)
        # OpenMetrics negotiation: same registry, exemplar-bearing syntax
        om = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/metrics",
            headers={"Accept": "application/openmetrics-text"}),
            timeout=10).read().decode()
        assert om.rstrip("\n").endswith("# EOF"), "OM scrape not terminated"
        assert '# {trace_id="' in om, "no exemplars in OpenMetrics scrape"
        with open(os.path.join(out_dir, "smoke_serve_metrics_om.prom"),
                  "w") as f:
            f.write(om)
        print(f"smoke_serve: {PREDICTS} predicts + {GENERATES} generates "
              f"+ SSE + overcommit burst ({pool_blocks}-block pool) "
              f"+ prefix-cache burst ({px_hits} hits, {px_saved} prompt "
              f"tokens saved), {n_eng} engine compile(s), {n_gen} generate "
              f"compile(s), generation {health['generation']} -> {prom_path}")
    finally:
        srv.stop()

    # cold-start acceptance: second boot against a warm AOT store serves
    # with zero XLA compiles
    aot_hits = _aot_warm_boot(out_dir)
    print(f"smoke_serve: warm second boot served from the AOT store "
          f"({aot_hits} executable loads, 0 compiles)")

    # prebuild-farm acceptance: enumerated manifest -> prebuilt store ->
    # strict replica with zero compile misses; incomplete store = typed
    # boot failure
    strict_hits = _strict_prebuilt_scenario(out_dir)
    print(f"smoke_serve: strict prebuilt replica OK — {strict_hits} store "
          f"loads, 0 compiles, incomplete store refused with AotTraceError; "
          f"cost profile captured -> cost_profile.json (+ store roundtrip)")

    # fleet acceptance: two models sharing a one-model budget, two tenants,
    # page-ins under load, quota sheds on the scrape
    page_ins, quota_sheds = _fleet_scenario(out_dir)
    print(f"smoke_serve: fleet scenario OK — {page_ins} page-ins under "
          f"load, {quota_sheds} quota shed(s) with Retry-After")

    reqtrace_mod.uninstall()

    # every scrape artifact this run wrote must survive the exposition
    # validator — a scrape Prometheus would reject is worse than none
    import glob

    from deeplearning4j_tpu.obs.promcheck import check_file

    paths = sorted(glob.glob(os.path.join(out_dir, "smoke_serve*.prom")))
    assert paths, "no scrape artifacts written"
    bad = {p: check_file(p)[:3] for p in paths if check_file(p)}
    assert not bad, f"invalid scrape artifacts: {bad}"
    print(f"smoke_serve: promcheck OK over {len(paths)} scrape artifact(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
