#!/usr/bin/env python
"""Benchmark driver entry — ResNet-50 training throughput (images/sec/chip).

Mirrors the reference's benchmark surface (BASELINE.md): dl4j-zoo ResNet-50
(ResNet50.java:80) trained via the data-parallel wrapper with the synthetic
BenchmarkDataSetIterator (BenchmarkDataSetIterator.java:20) isolating compute
from ETL. Prints one JSON line per result:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

vs_baseline: achieved model FLOPs utilization (MFU) divided by the driver's
north-star 70% MFU target (BASELINE.json) — >1.0 beats the target. The
reference publishes no absolute numbers (BASELINE.md), so MFU-vs-target is the
comparable, hardware-normalized ratio.

Every mode (default, --serve, --coldstart, --fleet, --elastic) runs in this
one process, needs a TPU and exits non-zero without one; a job that raises
ends the run. Results go to stdout and to ``chiprun_out/`` — never over a
record at the repo root. (The benchmark itself — cells, traffic, trace
reduction — is ROADMAP S0.)
"""

import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
sys.path.insert(0, os.path.join(ROOT, "scripts"))

# ResNet-50 @224 forward: 4.09e9 MACs = 8.18e9 FLOPs at the standard
# 2-flops-per-MAC convention (the SAME convention as the peak table in
# scripts/model_benches.py, and as XLA's cost model:
# compiled.cost_analysis() reports 2.248e10 flops/image for our train step).
# Training ~= 3x forward (PaLM MFU rule).
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.18e9


def _require_tpu():
    """The device every number below is a statement about. No TPU, no
    number: a CPU timing of this program says nothing about it."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no TPU: JAX's backend is {dev.platform!r} "
            f"({dev.device_kind}); the benchmark only runs on a TPU")
    return dev


def _fresh_dir(name: str) -> str:
    """An empty directory under the output directory (AOT stores, elastic
    checkpoints): nothing left by an earlier run is what gets measured."""
    path = os.path.join(OUT_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _emit(name: str, result: dict) -> None:
    """One JSON line on stdout and the same under ``chiprun_out/``."""
    print(json.dumps(result), flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"bench: {name} -> {path}", file=sys.stderr)


def _measure(batch: int, img: int, steps: int):
    """Build + train-step ResNet-50 at one batch size; returns
    (images_per_sec, final_loss, telemetry_snapshot)."""
    import jax

    from deeplearning4j_tpu.data import BenchmarkIterator
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.obs import StepTelemetry
    from deeplearning4j_tpu.train import Trainer

    zm = ResNet50(num_classes=1000, seed=0, input_shape=(img, img, 3))
    model = zm.build()
    model.config.compute_dtype = "bfloat16"  # MXU-native; params stay f32
    model.init()

    tr = Trainer(model)
    step = tr._make_step()
    ds = next(iter(BenchmarkIterator((img, img, 3), 1000, batch, 1)))
    x = jax.device_put(np.asarray(ds.features))
    y = jax.device_put(np.asarray(ds.labels))
    params, opt_state, state = tr.params, tr.opt_state, tr.state
    rng = jax.random.PRNGKey(0)

    def run(k):
        nonlocal params, opt_state, state
        t0 = time.perf_counter()
        for _ in range(k):
            params, opt_state, state, loss = step(params, opt_state, state,
                                                  x, y, rng)
        jax.block_until_ready(loss)
        return time.perf_counter() - t0, float(loss)

    run(3)  # compile + warm-up
    elapsed, lf = run(steps)
    per_step = elapsed / steps

    # per-step latency distribution + compile count, each step fenced
    tel = StepTelemetry(memory_every=0)
    sig = ("resnet50", batch, img)

    def probe_step():
        nonlocal params, opt_state, state
        params, opt_state, state, loss = step(params, opt_state, state,
                                              x, y, rng)
        return loss

    for _ in range(max(steps // 4, 3)):
        tel.step(probe_step, sig=sig, batch_size=batch)
    snap = tel.snapshot()
    telemetry = {"steps_per_sec": round(snap["steps_per_sec"], 3),
                 "p50_step_seconds": round(snap["p50_step_seconds"], 6),
                 "p95_step_seconds": round(snap["p95_step_seconds"], 6),
                 "compile_count": snap["compile_cache_misses"]}
    return batch / per_step, lf, telemetry


def _breadth(deadline: float) -> dict:
    """Breadth + envelope evidence: after the headline ResNet-50 number,
    measure the other BASELINE configs (LeNet, GravesLSTM char-RNN, VGG16)
    and the matmul-dominated envelope cases (738M d=2048 CausalLM + flash
    kernel; BERT-base fine-tune at T=128) while time remains. Running out
    of deadline records the skip by name; a job that raises ends the run."""
    import model_benches as mb
    from deeplearning4j_tpu.models import (BertBase, GravesLSTMCharRNN, LeNet,
                                           VGG16)

    jobs = [
        # envelope case: d=2048 12L (738M) + flash kernel (batch 4 beat 8 in
        # the 2026-07 capture — HBM pressure)
        ("causal_lm_738m_flash", lambda: mb.bench_transformer(
            d_model=2048, batch=4, flash=True)),
        # LeNet/char-RNN single steps are 1-3 ms — dispatch dominates;
        # spe= measures the steps_per_execution megastep (K steps as one
        # compiled scan, Trainer._make_multi_step)
        ("lenet_mnist", lambda: mb.bench_model(
            "lenet_mnist",
            lambda: LeNet(num_classes=10, seed=0, input_shape=(28, 28, 1)).build(),
            1024, (28, 28, 1), 10, spe=16)),
        ("graves_lstm_char_rnn", lambda: mb.bench_model(
            "graves_lstm_char_rnn",
            lambda: GravesLSTMCharRNN(seed=0, tbptt=0).build(),
            128, (64, 98), 98, seq=True, spe=8)),
        ("vgg16", lambda: mb.bench_model(
            "vgg16",
            lambda: VGG16(num_classes=1000, seed=0,
                          input_shape=(224, 224, 3)).build(),
            64, (224, 224, 3), 1000)),
        ("bert_base_t128", lambda: mb.bench_model(
            "bert_base_t128",
            lambda: BertBase(num_classes=2, seed=0,
                             input_shape=(128,)).build(),
            64, (128,), 2, token_vocab=30522)),
    ]
    out = {}
    for name, fn in jobs:
        if time.time() > deadline:
            out[name] = {"skipped": "deadline"}
            continue
        out[name] = dict(fn(), captured=time.strftime("%Y-%m-%d"))
    return out


def _bench_chunked_prefill(model, seconds):
    """Mixed-traffic inter-token latency: chunked vs whole-prompt prefill.

    A few closed-loop streaming decoders measure per-token gaps while a
    burst client keeps ramming near-capacity prompts in. With whole-prompt
    prefill each long prompt monopolizes the device and every in-flight
    decode stalls behind it — the p99 inter-token gap is the cost of the
    LONGEST prefill. Chunked prefill bounds that stall at one chunk.
    Also tracks the paged pool's peak live-KV bytes so the O(live tokens)
    HBM claim is captured next to the latency it buys."""
    import concurrent.futures as cf
    import threading

    from deeplearning4j_tpu.serve import ContinuousBatcher, ServeError
    from deeplearning4j_tpu.serve.paged import block_bytes, blocks_needed

    per_block = block_bytes(model, 16, np.float32)

    def run(prefill_chunk):
        cb = ContinuousBatcher(model, slots=4, capacity=128, block_size=16,
                               prompt_buckets=(16, 32, 64, 96),
                               prefill_chunk=prefill_chunk, queue_limit=64,
                               seed=0)
        cb.generate(np.arange(1, 9, dtype=np.int32), 2,
                    temperature=0.0)  # warm the executables untimed
        gaps, lock, stop = [], threading.Lock(), threading.Event()
        peak = {"blocks": 0, "bytes": 0}

        def decoder(i):
            r = np.random.RandomState(100 + i)
            while not stop.is_set():
                p = r.randint(0, 256, (8,)).astype(np.int32)
                last, first = time.perf_counter(), True
                try:
                    for _ in cb.stream(p, 24, temperature=0.0):
                        now = time.perf_counter()
                        if not first:  # gap 0 is TTFT, not inter-token
                            with lock:
                                gaps.append((now - last) * 1e3)
                        last, first = now, False
                except ServeError:
                    return

        def burster():
            r = np.random.RandomState(7)
            while not stop.is_set():
                p = r.randint(0, 256, (96,)).astype(np.int32)
                try:
                    cb.generate(p, 4, temperature=0.0)
                except ServeError:
                    return

        def poller():
            while not stop.is_set():
                s = cb.kv_block_stats()
                peak["blocks"] = max(peak["blocks"], s["blocks_used"])
                peak["bytes"] = max(peak["bytes"], s["live_bytes"])
                time.sleep(0.002)

        workers = ([threading.Thread(target=decoder, args=(i,))
                    for i in range(3)]
                   + [threading.Thread(target=burster),
                      threading.Thread(target=poller)])
        for w in workers:
            w.start()
        time.sleep(seconds)
        stop.set()
        for w in workers:
            w.join(60)
        stats = cb.kv_block_stats()
        sigs = sorted(map(str, cb.compile_signatures))
        cb.shutdown()
        lat = np.sort(np.asarray(gaps)) if gaps else np.asarray([0.0])
        return {
            "prefill_chunk": prefill_chunk,
            "inter_token_p50_ms": round(float(np.percentile(lat, 50)), 3),
            "inter_token_p99_ms": round(float(np.percentile(lat, 99)), 3),
            "tokens_streamed": len(gaps),
            "kv_peak_blocks_used": peak["blocks"],
            "kv_peak_live_bytes": peak["bytes"],
            "kv_blocks_total": stats["blocks_total"],
            # what the dense layout would reserve for the same 4 slots
            "kv_dense_equiv_bytes": 4 * blocks_needed(128, 16) * per_block,
            "compile_signatures": sigs,
        }

    chunked = run(64)
    whole = run(None)
    return {"chunked": chunked, "unchunked": whole}


def _bench_prefix_cache(model):
    """Shared-prefix burst: N concurrent greedy generations sharing one
    40-token system prompt, cached vs uncached.

    A primer request runs first in both modes (warming executables; in
    cached mode it also populates the prefix cache), then the burst fires
    concurrently and every request's TTFT is measured at its first
    streamed token. With the cache, each burst request adopts the system
    prompt's whole blocks and prefills only its private tail — fewer
    chunks per request AND a queue that drains proportionally faster, so
    the p99 TTFT improvement compounds under the burst. Also asserts the
    cached paged output is bit-identical to whole-batch dense
    ``nn.generation.generate`` and records the tokens-saved counter."""
    import concurrent.futures as cf

    from deeplearning4j_tpu.nn.generation import generate
    from deeplearning4j_tpu.serve import ContinuousBatcher

    rng = np.random.RandomState(7)
    sys_prompt = rng.randint(0, 256, (40,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(0, 256, (8,)).astype(np.int32)])
               for _ in range(12)]

    def run(prefix_cache):
        cb = ContinuousBatcher(model, slots=4, capacity=64, block_size=8,
                               prompt_buckets=(8, 16, 24, 32, 40, 48),
                               prefill_chunk=8, queue_limit=64,
                               prefix_cache=prefix_cache, seed=0)
        # primer: warms prefill/decode executables untimed and (cached
        # mode) inserts the shared prompt's whole blocks
        primer = np.concatenate([
            sys_prompt, rng.randint(0, 256, (8,)).astype(np.int32)])
        cb.generate(primer, 8, temperature=0.0)

        def one(p):
            t0 = time.perf_counter()
            it = cb.stream(p, 8, temperature=0.0)
            toks = [next(it)]
            ttft = (time.perf_counter() - t0) * 1e3
            toks.extend(it)
            return ttft, np.asarray(toks, np.int32)

        with cf.ThreadPoolExecutor(len(prompts)) as ex:
            results = list(ex.map(one, prompts))
        stats = cb.kv_block_stats()
        saved = cb.metrics.counter("serve_prefill_tokens_saved_total").value
        compiles = len(cb.compile_signatures)
        cb.shutdown()
        ttfts = np.sort(np.asarray([r[0] for r in results]))
        out = {
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 3),
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)), 3),
            "prefill_tokens_saved": int(saved),
            "compile_signatures": compiles,
        }
        px = stats.get("prefix_cache")
        if px is not None:
            out["hits"], out["misses"] = px["hits"], px["misses"]
        return out, [r[1] for r in results]

    cached, cached_out = run(True)
    uncached, _ = run(False)
    want = [np.asarray(generate(model, p[None], 8, temperature=0.0)[0])
            for p in prompts[:4]]
    identical = all(np.array_equal(a, b)
                    for a, b in zip(cached_out[:4], want))
    return {
        "shared_prefix_len": int(sys_prompt.shape[0]),
        "burst": len(prompts),
        "cached": cached,
        "uncached": uncached,
        "ttft_p99_speedup": round(
            uncached["ttft_p99_ms"] / max(cached["ttft_p99_ms"], 1e-9), 2),
        "bit_identical_to_dense": bool(identical),
    }


def _stamp(headline: dict, source: str,
           workload_fp: "str | None" = None) -> dict:
    """Top-level provenance on every result: which bench entry produced it
    and when — a reader deciding whether a number is current should not
    have to know each bench's detail schema. ``workload_fp`` (sim/
    workload.py) additionally stamps WHICH offered-load mix produced the
    numbers: two results are comparable iff their fingerprints match."""
    headline["source"] = source
    headline["captured"] = time.strftime("%Y-%m-%d")
    if workload_fp is not None:
        headline["workload_fingerprint"] = workload_fp
    return headline


def _profile_summary(cost, sample_rate: int) -> dict:
    """Round-file digest of a captured CostProfile: the top-3 executables
    by estimated device time plus the overall padding-waste ratio, so a
    round answers "which executable is slow / how much padding did we
    burn" without re-running the bench."""
    waste = cost.waste_ratio()
    return {
        "sample_rate": sample_rate,
        "waste_ratio": None if waste is None else round(waste, 4),
        "top_executables": [
            {"component": e.get("component"), "tag": e.get("tag"),
             "dispatches": e.get("dispatches"),
             "us_per_dispatch": round(e.get("us_per_dispatch", 0.0), 1),
             "device_s_est": round(e.get("device_s_est", 0.0), 6)}
            for e in cost.top_executables(3)],
    }


def _bench_serving():
    """``python bench.py --serve``: serving-path latency/throughput.

    Closed-loop clients fire single-row predicts at a ServeEngine (the
    ParallelInference/ModelServer hot path minus HTTP framing) plus greedy
    generations at a ContinuousBatcher on a small CausalLM. Then a mixed
    prompt-burst scenario compares chunked vs whole-prompt prefill on the
    paged batcher (p99 inter-token latency + peak live-KV bytes). The
    continuous profiler (obs/profile) rides the timed window at sample
    rate 1/16 — the configuration whose overhead budget the profiling
    round asserts — and the captured CostProfile summary (top-3
    executables, overall padding-waste ratio) is stamped into the round
    JSON. Env: BENCH_SERVE_CLIENTS (8), BENCH_SERVE_SECONDS (5),
    BENCH_SERVE_GENERATES (8).
    """
    import concurrent.futures as cf
    import threading

    import jax

    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.obs import profile as prof_mod
    from deeplearning4j_tpu.obs.costmodel import ProfileAccumulator
    from deeplearning4j_tpu.serve import ContinuousBatcher, ServeEngine

    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    seconds = float(os.environ.get("BENCH_SERVE_SECONDS", 5))
    n_gen = int(os.environ.get("BENCH_SERVE_GENERATES", 8))
    dev = jax.devices()[0]

    model = CausalLM(seed=0, input_shape=(32,), num_layers=2, d_model=64,
                     num_heads=4, vocab=256).build()
    model.init()
    # store-backed so the dispatch seam carries executable identity —
    # the profiler keys on (component, tag, signature, AOT cache key)
    store = AotStore(_fresh_dir("bench_serve_aot"))
    eng = ServeEngine(model, batch_buckets=(1, 2, 4, 8, 16),
                      queue_limit=4 * clients, max_wait_ms=1.0,
                      aot_store=store)
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 256, (64, 1, 16)).astype(np.int32)
    eng.predict(prompts[0])  # warm the compile outside the timed window
    prof = prof_mod.install(prof_mod.Profiler(sample_rate=16))

    lat_ms, stop_at = [], [0.0]
    lock = threading.Lock()

    def client(i):
        n, r = 0, np.random.RandomState(i)
        while time.perf_counter() < stop_at[0]:
            x = prompts[r.randint(len(prompts))]
            t0 = time.perf_counter()
            eng.predict(x)
            with lock:
                lat_ms.append((time.perf_counter() - t0) * 1e3)
            n += 1
        return n

    stop_at[0] = time.perf_counter() + seconds
    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(clients) as ex:
        total = sum(ex.map(client, range(clients)))
    wall = time.perf_counter() - t0
    eng.shutdown()

    cb = ContinuousBatcher(model, slots=4, capacity=32,
                           prompt_buckets=(8, 16), seed=0,
                           aot_store=store)
    g0 = time.perf_counter()
    with cf.ThreadPoolExecutor(4) as ex:
        toks = sum(len(t) for t in ex.map(
            lambda i: cb.generate(
                rng.randint(0, 256, (int(rng.randint(4, 13)),)), 16,
                temperature=0.0), range(n_gen)))
    gen_wall = time.perf_counter() - g0
    cb.shutdown()
    cost = ProfileAccumulator().fold(
        prof.snapshot(include_pairs=True)).profile()
    prof_mod.uninstall()

    prefill = _bench_chunked_prefill(model, seconds)
    prefix = _bench_prefix_cache(model)

    lat = np.sort(np.asarray(lat_ms))
    headline = {
        "metric": "serve_predict_requests_per_sec",
        "value": round(total / wall, 2),
        "unit": "req/s",
        "detail": {
            "clients": clients, "requests": total,
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
            "engine_compiles": len(eng.compile_signatures),
            "gen_tokens_per_sec": round(toks / gen_wall, 2),
            "gen_compiles": len(cb.compile_signatures),
            "chunked_prefill": prefill,
            "prefix_cache": prefix,
            "cost_profile": _profile_summary(cost, prof.sample_rate),
            "device": str(dev.device_kind),
            "captured": time.strftime("%Y-%m-%d"),
        },
    }
    _emit("bench_serve", _stamp(headline, "bench.py --serve"))


def _bench_coldstart():
    """``python bench.py --coldstart``: time-to-first-token, cold vs warm
    AOT store.

    Boots the full serving stacks (ServeEngine + paged ContinuousBatcher)
    twice against ONE store directory (BENCH_COLDSTART_STORE or an empty
    one under the output directory). Run 1 is cold: every executable is
    traced and persisted. Run 2 loads them back from disk — zero
    decode-path XLA compiles, asserted via the compile-miss counter. Note:
    run 1 may hit JAX's own persistent compilation cache
    (utils/compile_cache.py), which shortens *compiling* what it traces;
    the store win measured here is skipping tracing altogether, so both
    numbers are reported side by side. A third leg re-boots the same stacks in STRICT AOT mode
    (ISSUE 16): every executable must come from the prebuilt store — a
    miss would raise a typed AotTraceError instead of tracing — so the
    strict number is the true production replica boot cost, with the
    tracer provably out of the path.
    """
    import jax

    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry
    from deeplearning4j_tpu.serve import ContinuousBatcher, ServeEngine

    store_dir = (os.environ.get("BENCH_COLDSTART_STORE")
                 or _fresh_dir("bench_coldstart_aot"))
    dev = jax.devices()[0]

    def run(strict=False):
        model = CausalLM(seed=0, input_shape=(32,), num_layers=2, d_model=64,
                         num_heads=4, vocab=256).build()
        model.init()
        m = MetricsRegistry()
        store = AotStore(store_dir)
        t0 = time.perf_counter()
        eng = ServeEngine(model, batch_buckets=(1, 2, 4, 8), metrics=m,
                          aot_store=store, strict_aot=strict)
        eng.warm(np.int32)
        cb = ContinuousBatcher(model, slots=4, capacity=32,
                               prompt_buckets=(8, 16), metrics=m,
                               aot_store=store, strict_aot=strict)
        boot_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        handle = cb.submit(np.arange(12, dtype=np.int32) % 256, 8,
                           temperature=0.0)
        next(iter(handle.stream()))  # time-to-first-token
        ttft = time.perf_counter() - t1
        handle.wait()
        t2 = time.perf_counter()
        eng.predict(np.zeros((1, 32), np.int32))
        predict_s = time.perf_counter() - t2
        cb.shutdown()
        eng.shutdown()
        snap = m.snapshot()

        def total(name):
            return sum(s["value"]
                       for s in snap.get(name, {}).get("series", []))

        return {"boot_seconds": round(boot_s, 3),
                "ttft_seconds": round(ttft, 4),
                "first_predict_seconds": round(predict_s, 4),
                "aot_hits": total("serve_aot_hits_total"),
                "aot_misses": total("serve_aot_misses_total"),
                "aot_fallbacks": total("serve_aot_fallback_total"),
                "compile_misses": total("serve_compile_misses_total")}

    cold = run()
    warm = run()
    # leg 3: the production configuration — strict mode, prebuilt store.
    # Any miss here would raise (typed AotTraceError), so compile_misses
    # == 0 is enforced by construction, not just asserted after the fact.
    strict = run(strict=True)
    assert strict["compile_misses"] == 0, strict
    headline = {
        "metric": "serve_cold_start_speedup",
        "value": round(cold["boot_seconds"] / max(warm["boot_seconds"], 1e-9),
                       2),
        "unit": "x",
        "detail": {"store": store_dir, "cold": cold, "warm": warm,
                   "strict_prebuilt": strict,
                   "device": str(dev.device_kind),
                   "captured": time.strftime("%Y-%m-%d")},
    }
    _emit("bench_coldstart", _stamp(headline, "bench.py --coldstart"))


def _bench_fleet():
    """``python bench.py --fleet``: multi-model multi-tenant fleet serving.

    Three named CausalLM models share an HBM weight budget sized for ~2.2
    of them, so the LRU pager churns under mixed traffic. Closed-loop
    clients ride three tenants: ``gold`` (predict on alpha/beta, 1s SLO),
    ``standard`` (generate on gamma), and ``free`` (2 req/s — exists to be
    throttled), plus a ``knn`` tenant whose BruteForceKNN queries are gated
    through the SAME tenant admission (quota machinery is not
    model-specific). Every response is checked against a precomputed
    reference — the headline is only honest if ``wrong_responses == 0``
    across page-out/page-in cycles. A shared AOT store is warmed before
    the timed window so page-ins transfer weights instead of re-tracing.
    Env: BENCH_FLEET_SECONDS (5), BENCH_FLEET_TOKENS (8).
    """
    import threading

    import jax

    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.fleet import FleetRegistry, QuotaError
    from deeplearning4j_tpu.knn import BruteForceKNN
    from deeplearning4j_tpu.models import CausalLM
    from deeplearning4j_tpu.nn.generation import generate as refgen
    from deeplearning4j_tpu.serve import ServeError

    seconds = float(os.environ.get("BENCH_FLEET_SECONDS", 5))
    gen_tokens = int(os.environ.get("BENCH_FLEET_TOKENS", 8))
    dev = jax.devices()[0]

    models = {}
    for name, seed in (("alpha", 0), ("beta", 1), ("gamma", 2)):
        m = CausalLM(seed=seed, input_shape=(16,), num_layers=2, d_model=32,
                     num_heads=4, vocab=50).build()
        m.init()
        models[name] = m
    weight_bytes = sum(int(np.asarray(leaf).nbytes) for leaf in
                       jax.tree.leaves((models["alpha"].params,
                                        models["alpha"].state)))
    budget = int(2.2 * weight_bytes)  # fits 2 of 3 — paging is mandatory

    store_dir = _fresh_dir("bench_fleet_aot")
    fleet = FleetRegistry(hbm_budget_bytes=budget,
                          aot_store=AotStore(store_dir))
    for name, m in models.items():
        gen = {"slots": 2, "capacity": 32} if name == "gamma" else None
        fleet.add(name, m, input_dtype=np.int32,
                  engine_opts={"batch_buckets": (1, 2, 4),
                               "queue_limit": 64},
                  gen_opts=gen)
    fleet.tenants.register("gold", rate_per_s=500, slo="gold")
    fleet.tenants.register("standard", rate_per_s=500, slo="standard")
    fleet.tenants.register("free", rate_per_s=2.0, burst=2.0, slo="batch")
    fleet.tenants.register("knn", rate_per_s=200, slo="standard")

    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 50, (4, 1, 16)).astype(np.int32)
    refs = {n: [np.asarray(m.output(p)) for p in prompts]
            for n, m in models.items() if n != "gamma"}
    gen_prompt = rng.randint(0, 50, (6,)).astype(np.int32)
    gen_want = refgen(models["gamma"], gen_prompt[None], gen_tokens,
                      temperature=0.0)[0].tolist()
    knn_points = rng.rand(512, 16).astype(np.float32)
    knn_index = BruteForceKNN(knn_points)
    knn_query = rng.rand(16).astype(np.float32)
    knn_want = np.argsort(
        np.linalg.norm(knn_points - knn_query, axis=1))[:5].tolist()

    # untimed warmup: page each model in once so the AOT store holds every
    # executable — timed page-ins then measure drain + transfer, not tracing
    for i, name in enumerate(("alpha", "beta", "gamma")):
        if name == "gamma":
            fleet.generate(name, gen_prompt, 2, tenant="standard",
                           temperature=0.0)
        else:
            fleet.predict(name, prompts[i % len(prompts)], tenant="gold")
    warm_stats = dict(fleet.pager.stats())

    from deeplearning4j_tpu.obs import profile as prof_mod
    from deeplearning4j_tpu.obs.costmodel import ProfileAccumulator
    prof = prof_mod.install(prof_mod.Profiler(sample_rate=16))

    lat, lock = {}, threading.Lock()
    counts = {"wrong": 0, "errors": 0, "quota_shed": 0, "knn_queries": 0}
    stop_at = [0.0]

    def record(tenant, ms):
        with lock:
            lat.setdefault(tenant, []).append(ms)

    def predict_client(i):
        r = np.random.RandomState(10 + i)
        while time.perf_counter() < stop_at[0]:
            name = ("alpha", "beta")[r.randint(2)]
            j = r.randint(len(prompts))
            t0 = time.perf_counter()
            try:
                res = fleet.predict(name, prompts[j], tenant="gold")
            except ServeError:
                with lock:
                    counts["errors"] += 1
                continue
            record("gold", (time.perf_counter() - t0) * 1e3)
            if not np.allclose(res.output, refs[name][j],
                               rtol=1e-4, atol=1e-5):
                with lock:
                    counts["wrong"] += 1

    def generate_client():
        while time.perf_counter() < stop_at[0]:
            t0 = time.perf_counter()
            try:
                toks = fleet.generate("gamma", gen_prompt, gen_tokens,
                                      tenant="standard", temperature=0.0)
            except ServeError:
                with lock:
                    counts["errors"] += 1
                continue
            record("standard", (time.perf_counter() - t0) * 1e3)
            if list(toks) != gen_want:
                with lock:
                    counts["wrong"] += 1

    def free_client():
        while time.perf_counter() < stop_at[0]:
            try:
                fleet.predict("alpha", prompts[0], tenant="free")
            except QuotaError:
                with lock:
                    counts["quota_shed"] += 1
            except ServeError:
                with lock:
                    counts["errors"] += 1
            time.sleep(0.05)  # 20 req/s offered against a 2 req/s quota

    def knn_client():
        while time.perf_counter() < stop_at[0]:
            try:
                fleet.tenants.admit("knn", model="knn")
            except QuotaError:
                with lock:
                    counts["quota_shed"] += 1
                time.sleep(0.01)
                continue
            t0 = time.perf_counter()
            idx, _ = knn_index.search(knn_query, 5)
            record("knn", (time.perf_counter() - t0) * 1e3)
            if idx.tolist() != knn_want:
                with lock:
                    counts["wrong"] += 1
            with lock:
                counts["knn_queries"] += 1

    workers = ([threading.Thread(target=predict_client, args=(i,))
                for i in range(2)]
               + [threading.Thread(target=generate_client),
                  threading.Thread(target=free_client),
                  threading.Thread(target=knn_client)])
    stop_at[0] = time.perf_counter() + seconds
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    for w in workers:
        w.join(120)
    wall = time.perf_counter() - t0
    pager = fleet.pager.stats()
    tenants = fleet.tenants.stats()
    cost = ProfileAccumulator().fold(
        prof.snapshot(include_pairs=True)).profile()
    prof_mod.uninstall()
    fleet.shutdown()

    def pct(tenant):
        xs = np.sort(np.asarray(lat.get(tenant, [0.0])))
        return {"requests": len(lat.get(tenant, [])),
                "p50_ms": round(float(np.percentile(xs, 50)), 3),
                "p99_ms": round(float(np.percentile(xs, 99)), 3)}

    per_tenant = {t: pct(t) for t in ("gold", "standard", "knn")}
    total = sum(v["requests"] for v in per_tenant.values())
    gold_slo_ms = 1000.0
    headline = {
        "metric": "fleet_requests_per_sec",
        "value": round(total / wall, 2),
        "unit": "req/s",
        "detail": {
            "models": sorted(models),
            "budget_bytes": budget,
            "weights_sum_bytes": 3 * weight_bytes,
            "seconds": round(wall, 2),
            "tenants": per_tenant,
            "wrong_responses": counts["wrong"],
            "errors": counts["errors"],
            "quota_sheds": counts["quota_shed"],
            "free_tenant": {"admitted": tenants["free"]["admitted"],
                            "shed": tenants["free"]["shed"]},
            "page_ins": pager["page_ins"],
            "page_outs": pager["page_outs"],
            "timed_page_ins": pager["page_ins"] - warm_stats["page_ins"],
            "gold_within_slo":
                bool(per_tenant["gold"]["p99_ms"] <= gold_slo_ms),
            "gold_slo_ms": gold_slo_ms,
            "cost_profile": _profile_summary(cost, prof.sample_rate),
            "device": str(dev.device_kind),
            "captured": time.strftime("%Y-%m-%d"),
        },
    }
    # Scenario descriptor for comparability: a WorkloadSpec capturing the
    # offered mix (models, tenant/SLO weights, fixed lengths, window).
    # base_rate_rps=0 marks it closed-loop — the clients here are paced by
    # service completions, not a trace — but the fingerprint still pins the
    # mix, so two BENCH_fleet rounds are comparable iff fingerprints match.
    from deeplearning4j_tpu.sim import LengthDist, WorkloadSpec
    wl_spec = WorkloadSpec(
        seed=0, duration_s=seconds, base_rate_rps=0.0,
        prompt_len=LengthDist("fixed", 16, 0.0, 16),
        output_len=LengthDist("fixed", gen_tokens, 0.0, max(1, gen_tokens)),
        vocab=50,
        tenants={"gold": {"weight": 2.0, "slo": "gold"},
                 "standard": {"weight": 1.0, "slo": "standard"},
                 "free": {"weight": 1.0, "slo": "batch"},
                 "knn": {"weight": 1.0, "slo": "standard"}},
        models={"alpha": {"weight": 1.0, "generate_frac": 0.0},
                "beta": {"weight": 1.0, "generate_frac": 0.0},
                "gamma": {"weight": 1.0, "generate_frac": 1.0},
                "knn": {"weight": 1.0, "generate_frac": 0.0}})
    _emit("bench_fleet", _stamp(headline, "bench.py --fleet",
                                workload_fp=wl_spec.fingerprint()))


def _bench_training():
    """Default mode: the ResNet-50 headline, then the breadth jobs."""
    import jax

    from model_benches import peak_bf16

    t_start = time.time()
    dev = jax.devices()[0]
    img = int(os.environ.get("BENCH_IMG", 224))
    steps = int(os.environ.get("BENCH_STEPS", 40))
    if os.environ.get("BENCH_BATCH"):  # explicit single batch wins (back-compat)
        batches = [int(os.environ["BENCH_BATCH"])]
    else:
        batches = [int(b) for b in os.environ.get(
            "BENCH_BATCHES", "128,256").split(",")]

    # sweep batch sizes, keep the best (larger batches lift MXU utilization
    # until HBM runs out); a size that fails ends the run
    results = {b: _measure(b, img, steps) for b in batches}
    batch = max(results, key=lambda b: results[b][0])
    images_per_sec, loss, telemetry = results[batch]
    # scale flops if benchmarking at reduced resolution (flops ~ HW)
    flops_per_image = RESNET50_TRAIN_FLOPS_PER_IMAGE * (img / 224.0) ** 2
    mfu = images_per_sec * flops_per_image / peak_bf16(dev.device_kind)
    vs_baseline = mfu / 0.70  # north-star: >70% MFU (BASELINE.json)

    headline = {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(vs_baseline, 4),
        "detail": {
            "batch": batch, "image_size": img, "steps": steps,
            "device": str(dev.device_kind), "mfu": round(mfu, 4),
            "loss_finite": bool(np.isfinite(loss)),
            "captured": time.strftime("%Y-%m-%d"),
            "swept": {str(b): round(r[0], 2) for b, r in results.items()},
            "flops_per_image": flops_per_image,
            # fenced per-step snapshot at the winning batch (obs/ probe):
            # steps/sec, p50/p95 step latency, compile count
            "telemetry": telemetry,
        },
    }
    _emit("bench", _stamp(headline, "bench.py"))

    # breadth + envelope evidence (LeNet / char-RNN / VGG16 / BERT-base /
    # 738M-flash transformer) after the headline
    if os.environ.get("BENCH_BREADTH", "1") != "0":
        deadline = t_start + float(os.environ.get("BENCH_DEADLINE", 480))
        _emit("bench_breadth", {"device": str(dev.device_kind),
                                "breadth": _breadth(deadline)})


def _bench_elastic():
    """``python bench.py --elastic``: what elasticity costs.

    Three numbers (ISSUE 19): the elastic trainer's steady-state step
    time against a plain ``Trainer.fit`` on the same model and batch
    stream (the price of membership supervision + ZeRO sharding +
    logical-clock bookkeeping per step); the wall latency of one
    chaos-triggered resize (checkpoint + planned reshard + checkpoint);
    and the redistribution planner's moved bytes against the naive
    full re-gather it replaces. Needs four devices.
    Env: BENCH_ELASTIC_STEPS (30).
    """
    import statistics

    import jax

    from deeplearning4j_tpu.chaos import FaultPlane, install, uninstall
    from deeplearning4j_tpu.data import ArrayIterator
    from deeplearning4j_tpu.elastic import ElasticTrainer
    from deeplearning4j_tpu.nn import NetConfig, SequentialBuilder
    from deeplearning4j_tpu.nn import layers as L
    from deeplearning4j_tpu.train import Trainer

    steps = int(os.environ.get("BENCH_ELASTIC_STEPS", 30))
    batch, feat = 24, 64

    def build():
        return (SequentialBuilder(
            NetConfig(seed=0, updater={"type": "adam",
                                       "learning_rate": 1e-2}))
            .input_shape(feat)
            .layer(L.Dense(n_out=256, activation="relu"))
            .layer(L.Output(n_out=12, activation="softmax", loss="mcxent"))
            .build())

    def batch_fn(step):
        rng = np.random.RandomState(1000 + step)
        x = rng.randn(batch, feat).astype(np.float32)
        y = np.eye(12, dtype=np.float32)[rng.randint(0, 12, batch)]
        return x, y

    # plain baseline: same model/optimizer, single-process Trainer.fit on
    # the identical batch stream (one epoch = `steps` minibatches)
    xs = np.concatenate([batch_fn(i)[0] for i in range(steps)])
    ys = np.concatenate([batch_fn(i)[1] for i in range(steps)])
    tr = Trainer(build())
    tr.fit(ArrayIterator(xs, ys, batch, shuffle=False), epochs=1,
           prefetch=False)  # warm the jit
    t0 = time.perf_counter()
    tr.fit(ArrayIterator(xs, ys, batch, shuffle=False), epochs=1,
           prefetch=False)
    plain_step_ms = (time.perf_counter() - t0) / steps * 1e3

    wd = _fresh_dir("bench_elastic_work")
    try:
        et = ElasticTrainer(build(), workdir=wd, dp=4, dp_min=2, seed=0)
        et.fit(batch_fn, 5)  # warm every ladder width, settle the jit
        times = []
        mark = et.iteration
        t0 = time.perf_counter()
        et.fit(batch_fn, mark + steps)
        times.append((time.perf_counter() - t0) / steps * 1e3)
        elastic_step_ms = statistics.median(times)

        # one chaos-triggered resize 4 -> 3, timed end to end
        fp = FaultPlane(seed=0).inject_spec(
            "elastic.step:error:scope=w1,times=1")
        install(fp)
        try:
            et.fit(batch_fn, et.iteration + 4)
        finally:
            uninstall()
        assert et.dp == 3 and et.resizes, "bench drill failed to resize"
        rec = et.resizes[0]
        post_traces = et.trace_count()
        et.fit(batch_fn, et.iteration + 2)
        assert et.trace_count() == post_traces, "post-resize compile miss"
    finally:
        shutil.rmtree(wd, ignore_errors=True)

    headline = {
        "metric": "elastic_step_overhead",
        "value": round(elastic_step_ms / max(plain_step_ms, 1e-9), 2),
        "unit": "x",
        "detail": {
            "steps": steps,
            "plain_step_ms": round(plain_step_ms, 3),
            "elastic_step_ms": round(elastic_step_ms, 3),
            "resize_seconds": round(rec["seconds"], 4),
            "resize": {k: rec[k] for k in ("step", "from", "to", "cause")},
            "reshard_bytes_moved": rec["bytes_moved"],
            "reshard_bytes_naive": rec["bytes_naive"],
            "reshard_savings": round(
                1.0 - rec["bytes_moved"] / max(rec["bytes_naive"], 1), 4),
            "device": str(jax.devices()[0].device_kind),
        },
    }
    _emit("bench_elastic", _stamp(headline, "bench.py --elastic"))


def main(argv=None) -> None:
    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    modes = {"--serve": _bench_serving, "--coldstart": _bench_coldstart,
             "--fleet": _bench_fleet, "--elastic": _bench_elastic}
    args = sys.argv[1:] if argv is None else argv
    mode = next((fn for flag, fn in modes.items() if flag in args),
                _bench_training)
    use_compile_cache()
    _require_tpu()
    mode()


if __name__ == "__main__":
    main()
