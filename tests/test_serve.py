"""Tests for the serve/ subsystem (ISSUE 4).

The load-bearing properties, each tested directly:

- coalescing: concurrent requests SHARE device batches (batch_seq collisions);
- bounded executables: randomized traffic compiles at most
  ``|batch buckets| x |length buckets|`` signatures — never one per shape;
- overload is typed, never a hang: shed at admission (ShedError), expiry at
  dispatch (DeadlineExceededError), drain at shutdown (ServerClosingError);
- hot-swap atomicity: one registry generation per device batch, results
  always match the generation that ran them;
- continuous batching: greedy token chains are bit-identical to whole-batch
  ``nn.generation.generate`` while slots are reused across > slots requests;
- the ParallelInference shim regressions: padded partial batches on every
  path (incl. shutdown drain) and no truncation of oversized requests.

Paged-KV + chunked-prefill properties (ISSUE 5):

- block allocator: randomized alloc/free never double-hands a block, the
  trash block is untouchable, exhaustion is a typed atomic failure;
- paged greedy decode is BIT-identical to the dense-cache batcher across
  prompt buckets, chunked and un-chunked;
- executable bound: ONE decode executable + <= |prompt buckets| prefill
  chunk executables, asserted on ``_decode_sigs``/``_prefill_sigs``;
- overcommit: total requested tokens past the pool size queue and complete;
  a typed ``CapacityError`` only when a single request can NEVER fit;
- rope capacity decoupling: no ``PositionalEmbedding`` table => per-request
  capacity may exceed the model's training context;
- streaming: token-at-a-time ``stream()`` and the SSE ``/generate`` path,
  including error-after-partial-output and graceful drain mid-stream.
"""

import concurrent.futures as cf
import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.nn.layers import Dense, Output
from deeplearning4j_tpu.nn.model import NetConfig, Sequential
from deeplearning4j_tpu.parallel import ParallelInference
from deeplearning4j_tpu.aot import AotStore
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.serve import (BlockAllocator, CapacityError,
                                      ContinuousBatcher,
                                      DeadlineExceededError, ModelRegistry,
                                      ModelServer, PrefillScheduler,
                                      PublishError, ServeEngine,
                                      ServerClosingError, ShedError)


def _dense_model(n_in=4, n_out=3, seed=0):
    m = Sequential(NetConfig(seed=seed),
                   [Dense(n_out=6, activation="tanh"),
                    Output(n_out=n_out, loss="mcxent", activation="softmax")],
                   (n_in,))
    m.init()
    return m


def _slow_forward(model, delay_s):
    """Un-jitted forward with a host-side stall — deterministic device-time
    inflation for queue/deadline/shed tests."""

    def fwd(params, state, x):
        time.sleep(delay_s)
        y, _ = model.forward(params, state, x, training=False)
        return np.asarray(y)

    return fwd


@pytest.fixture(scope="module")
def lm():
    from deeplearning4j_tpu.models import CausalLM

    zm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                  num_heads=4, vocab=50)
    model = zm.build()
    model.init()
    return model


class TestModelRegistry:
    def test_generations_monotonic_and_rollback(self):
        m = _dense_model()
        reg = ModelRegistry(m.params, m.state, version="base")
        p2 = jax.tree.map(lambda a: a * 2.0, m.params)
        s2 = reg.publish(p2, version="double")
        assert s2.generation == 2
        s3 = reg.rollback()
        assert s3.generation == 3  # rollback is a fresh generation...
        assert s3.version == "base"  # ...of the previous version
        got = np.asarray(reg.current().params["layer_0"]["w"])
        np.testing.assert_array_equal(got,
                                      np.asarray(m.params["layer_0"]["w"]))
        assert [g for g, _ in reg.history()][-1] == 3

    def test_publish_drain_waits_for_old_leases(self):
        m = _dense_model()
        reg = ModelRegistry(m.params, m.state)
        entered, release = threading.Event(), threading.Event()

        def worker():
            with reg.lease():
                entered.set()
                release.wait(5)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        assert entered.wait(5)
        reg.publish(jax.tree.map(lambda a: a + 1.0, m.params))  # non-draining
        assert reg.drain(timeout=0.2) is False  # old lease still out
        release.set()
        assert reg.drain(timeout=5) is True
        t.join(5)

    def test_publish_rejects_donated_buffers(self):
        # the trainer's step donates param buffers; a checkpoint captured by
        # reference would 500 at request time — publish must fail fast
        import jax.numpy as jnp

        m = _dense_model()
        reg = ModelRegistry(m.params, m.state)
        leaf = jnp.ones(8, jnp.float32)
        jax.jit(lambda z: z * 2, donate_argnums=(0,))(leaf)  # deletes leaf
        assert leaf.is_deleted()
        with pytest.raises(ValueError, match="donated"):
            reg.publish({"layer_0": {"w": leaf}})

    def test_history_bounded(self):
        m = _dense_model()
        reg = ModelRegistry(m.params, m.state, keep=3)
        for _ in range(6):
            reg.publish(m.params)
        assert len(reg.history()) == 3


class TestServeEngine:
    def test_predict_matches_direct(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(2, 4, 8))
        try:
            x = np.random.RandomState(0).randn(5, 4).astype(np.float32)
            np.testing.assert_allclose(eng.predict(x),
                                       np.asarray(m.output(x)),
                                       rtol=1e-5, atol=1e-6)
            one = eng.predict(x[0])  # single example grows a batch dim
            np.testing.assert_allclose(one[0], np.asarray(m.output(x))[0],
                                       rtol=1e-5, atol=1e-6)
        finally:
            eng.shutdown()

    def test_concurrent_requests_coalesce(self):
        m = _dense_model()
        # long window so concurrent submits land in the same device batch
        eng = ServeEngine(m, batch_buckets=(1, 2, 4, 8), max_wait_ms=60.0)
        try:
            x = np.random.RandomState(0).randn(8, 1, 4).astype(np.float32)
            with cf.ThreadPoolExecutor(8) as ex:
                handles = list(ex.map(lambda i: eng.submit(x[i]), range(8)))
            for h in handles:
                h.wait()
            seqs = [h.batch_seq for h in handles]
            batches = len(set(seqs))
            assert batches < len(handles), \
                f"no coalescing: {len(handles)} requests -> {batches} batches"
            # at least one batch carried >= 2 requests
            assert max(seqs.count(s) for s in set(seqs)) >= 2
            assert eng.metrics.counter("serve_batches_total").value == batches
        finally:
            eng.shutdown()

    def test_compile_count_bounded_under_randomized_traffic(self):
        """Acceptance: executables <= |batch buckets| x |length buckets|."""
        m = _dense_model()  # Dense acts on the last axis: (B, T, 4) works
        batch_buckets, length_buckets = (2, 4), (8, 16)
        eng = ServeEngine(m, batch_buckets=batch_buckets,
                          length_buckets=length_buckets, max_wait_ms=1.0)
        try:
            rng = np.random.RandomState(7)
            cases = [(int(rng.randint(1, 5)), int(rng.randint(1, 17)))
                     for _ in range(25)]

            def run(case):
                rows, t = case
                x = rng.randn(rows, t, 4).astype(np.float32)
                return x, eng.predict(x)

            with cf.ThreadPoolExecutor(4) as ex:
                outs = list(ex.map(run, cases))
            for x, y in outs:
                assert y.shape[:2] == x.shape[:2]  # un-padded back to true T
                np.testing.assert_allclose(y, np.asarray(m.output(x)),
                                           rtol=1e-4, atol=1e-5)
            limit = len(batch_buckets) * len(length_buckets)
            sigs = eng.compile_signatures
            assert len(sigs) <= limit, f"{len(sigs)} sigs > {limit}: {sigs}"
            assert eng.metrics.counter(
                "serve_compile_misses_total",
                {"component": "engine"}).value == len(sigs)
            # every signature is an exact (bucket, padded-length) pair
            for bucket, shape, _ in sigs:
                assert bucket in batch_buckets and shape[0] in length_buckets
        finally:
            eng.shutdown()

    def test_over_length_is_typed_error(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(2,), length_buckets=(8,))
        try:
            with pytest.raises(CapacityError):
                eng.predict(np.zeros((1, 9, 4), np.float32))
        finally:
            eng.shutdown()

    def test_deadline_expiry_is_typed_error_not_hang(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1, 4), max_wait_ms=1.0,
                          forward=_slow_forward(m, 0.08))
        try:
            x = np.zeros((1, 4), np.float32)
            r1 = eng.submit(x)          # occupies the device ~80ms
            time.sleep(0.02)            # ensure r1's batch has dispatched
            r2 = eng.submit(x, timeout_ms=5.0)  # expires while queued
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceededError):
                r2.wait()
            assert time.perf_counter() - t0 < 5.0  # typed error, not a hang
            r1.wait()  # undeadlined request unaffected
            assert eng.metrics.counter(
                "serve_deadline_expired_total").value >= 1
        finally:
            eng.shutdown()

    def test_shed_past_queue_limit_zero_drops_below(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1, 2), max_wait_ms=1.0,
                          queue_limit=2, forward=_slow_forward(m, 0.05))
        try:
            x = np.zeros((1, 4), np.float32)
            handles, sheds = [], 0
            for _ in range(12):  # flood far past queue_limit
                try:
                    handles.append(eng.submit(x))
                except ShedError as e:
                    assert e.cause == "queue_full"
                    sheds += 1
            assert sheds > 0, "queue never shed past its limit"
            for h in handles:  # every admitted request completes
                assert h.wait().shape == (1, 3)
            assert eng.metrics.counter(
                "serve_shed_total", {"cause": "queue_full"}).value == sheds
            # sub-capacity traffic afterwards: zero dropped responses
            outs = [eng.predict(x) for _ in range(3)]
            assert all(o.shape == (1, 3) for o in outs)
        finally:
            eng.shutdown()

    def test_hot_swap_under_load_never_mixes_generations(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1, 2, 4, 8), max_wait_ms=10.0,
                          queue_limit=512)
        try:
            params_by_gen = {1: eng.registry.current().params}
            stop = threading.Event()

            def publisher():
                g = 1
                while not stop.is_set() and g < 6:
                    time.sleep(0.01)
                    scaled = jax.tree.map(
                        lambda a, k=g: a * (1.0 + 0.5 * k),
                        params_by_gen[1])
                    snap = eng.registry.publish(scaled, drain=True)
                    params_by_gen[snap.generation] = scaled
                    g = snap.generation

            pub = threading.Thread(target=publisher, daemon=True)
            pub.start()
            x = np.random.RandomState(3).randn(1, 4).astype(np.float32)
            with cf.ThreadPoolExecutor(8) as ex:
                handles = list(ex.map(lambda i: eng.submit(x), range(60)))
            def done(h):  # wait() first: the batch run sets seq/generation
                out = h.wait()
                return h.batch_seq, h.generation, out

            results = [done(h) for h in handles]
            stop.set()
            pub.join(10)
            by_batch = {}
            for seq, gen, out in results:
                by_batch.setdefault(seq, set()).add(gen)
                # the result matches the generation that claims to have run it
                want = np.asarray(m.output(x, params_by_gen[gen], m.state))
                np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
            for seq, gens in by_batch.items():
                assert len(gens) == 1, \
                    f"batch {seq} mixed params generations {gens}"
        finally:
            eng.shutdown()

    def test_graceful_drain_completes_inflight(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1, 2), max_wait_ms=1.0,
                          queue_limit=64, forward=_slow_forward(m, 0.02))
        try:
            x = np.random.RandomState(1).randn(1, 4).astype(np.float32)
            handles = [eng.submit(x) for _ in range(6)]
        finally:
            eng.shutdown(drain=True)  # returns only after the queue drains
        for h in handles:
            assert h.wait().shape == (1, 3)  # no errors, no hangs
        with pytest.raises(ServerClosingError):
            eng.submit(x)
        assert eng.metrics.counter(
            "serve_shed_total", {"cause": "shutting_down"}).value == 1

    def test_shutdown_without_drain_errors_pending(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1,), max_wait_ms=1.0,
                          queue_limit=64, forward=_slow_forward(m, 0.05))
        handles = [eng.submit(np.zeros((1, 4), np.float32))
                   for _ in range(5)]
        eng.shutdown(drain=False)
        outcomes = []
        for h in handles:
            try:
                h.wait()
                outcomes.append("ok")
            except ServerClosingError:
                outcomes.append("closed")
        assert "closed" in outcomes  # pending work answered, not hung


class TestParallelInferenceShim:
    """The ISSUE-4 satellite: partial-batch padding on every path and the
    recompile-count regression, via the engine's signature tracking."""

    def test_partial_batch_pads_even_on_shutdown_drain(self):
        m = _dense_model()
        pi = ParallelInference(m, batch_limit=8, buckets=(4, 8),
                               max_wait_ms=1.0)
        x = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        want = np.asarray(m.output(x))
        req = pi.engine.submit(x)   # 3 rows: must pad to bucket 4
        pi.shutdown()               # drain path runs the same padded code
        np.testing.assert_allclose(req.wait(), want, rtol=1e-5, atol=1e-6)
        for bucket, _, _ in pi.engine.compile_signatures:
            assert bucket in (4, 8), \
                f"un-padded batch shape {bucket} escaped to the device"

    def test_oversized_request_not_truncated(self):
        # seed bug: 10 rows with largest bucket 8 were cut to 8 and the
        # tail requests got empty slices back
        m = _dense_model()
        pi = ParallelInference(m, batch_limit=8, buckets=(4, 8),
                               max_wait_ms=1.0)
        try:
            x = np.random.RandomState(2).randn(10, 4).astype(np.float32)
            out = pi.output(x)
            assert out.shape == (10, 3)
            np.testing.assert_allclose(out, np.asarray(m.output(x)),
                                       rtol=1e-5, atol=1e-6)
        finally:
            pi.shutdown()

    def test_recompile_count_regression(self):
        """Compile-miss counting idiom from obs/ (_batch_sig-style): a new
        signature == one XLA compile; arbitrary request sizes must stay
        within the bucket set."""
        m = _dense_model()
        pi = ParallelInference(m, batch_limit=8, buckets=(1, 2, 4, 8),
                               max_wait_ms=0.5)
        try:
            rng = np.random.RandomState(4)
            for rows in (1, 3, 2, 7, 5, 8, 1, 6, 4):
                x = rng.randn(rows, 4).astype(np.float32)
                assert pi.output(x).shape == (rows, 3)
            n_sigs = len(pi.engine.compile_signatures)
            assert n_sigs <= 4
            assert pi.engine.metrics.counter(
                "serve_compile_misses_total",
                {"component": "engine"}).value == n_sigs
        finally:
            pi.shutdown()

    def test_update_model_swaps_atomically(self):
        m = _dense_model()
        pi = ParallelInference(m, batch_limit=4, max_wait_ms=0.5)
        try:
            x = np.random.RandomState(5).randn(2, 4).astype(np.float32)
            before = pi.output(x)
            p2 = jax.tree.map(lambda a: a * 3.0, pi.params)
            pi.update_model(p2)
            np.testing.assert_allclose(
                pi.output(x), np.asarray(m.output(x, p2, m.state)),
                rtol=1e-5, atol=1e-6)
            assert not np.allclose(before, pi.output(x))
            assert pi.registry.generation == 2
        finally:
            pi.shutdown()


class TestContinuousBatcher:
    def test_greedy_matches_lockstep_generate(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        cb = ContinuousBatcher(lm, slots=2, capacity=16, seed=0)
        try:
            rng = np.random.RandomState(0)
            for tp in (8, 5):  # exact-bucket AND padded-prefill prompts
                prompt = rng.randint(0, 50, (tp,)).astype(np.int32)
                got = cb.generate(prompt, 6, temperature=0.0)
                want = generate(lm, prompt[None], 6, temperature=0.0)[0]
                assert np.array_equal(got, want), (got, want)
        finally:
            cb.shutdown()

    def test_slot_reuse_serves_more_requests_than_slots(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        cb = ContinuousBatcher(lm, slots=2, capacity=16, queue_limit=16,
                               seed=0)
        try:
            rng = np.random.RandomState(1)
            prompts = [rng.randint(0, 50, (int(rng.randint(3, 9)),)
                                   ).astype(np.int32) for _ in range(5)]
            with cf.ThreadPoolExecutor(5) as ex:
                outs = list(ex.map(
                    lambda p: cb.generate(p, 5, temperature=0.0), prompts))
            for p, o in zip(prompts, outs):
                want = generate(lm, p[None], 5, temperature=0.0)[0]
                assert np.array_equal(o, want)
            assert cb.peak_active_slots <= 2  # never over-subscribed
            m = cb.metrics
            assert m.counter("serve_gen_admitted_total").value == 5
            assert m.counter("serve_gen_completed_total").value == 5
        finally:
            cb.shutdown()

    def test_eos_frees_slot_early(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, seed=0)
        try:
            prompt = np.random.RandomState(2).randint(
                0, 50, (6,)).astype(np.int32)
            free_run = cb.generate(prompt, 5, temperature=0.0)
            eos = int(free_run[0])
            stopped = cb.generate(prompt, 5, temperature=0.0, eos_id=eos)
            assert stopped.tolist() == [eos]  # stopped at the first token
        finally:
            cb.shutdown()

    def test_compile_count_bounded(self, lm):
        cb = ContinuousBatcher(lm, slots=2, capacity=16,
                               prompt_buckets=(8, 16), seed=0)
        try:
            rng = np.random.RandomState(3)
            for tp in (3, 5, 8, 11, 13, 4):
                cb.generate(rng.randint(0, 50, (tp,)).astype(np.int32), 2,
                            temperature=0.0)
            sigs = cb.compile_signatures
            # <= |prompt buckets| prefills + ONE decode executable
            assert len(sigs) <= 3, sigs
            assert ("decode", 2) in sigs
        finally:
            cb.shutdown()

    def test_capacity_and_contract_errors_are_typed(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, seed=0)
        try:
            with pytest.raises(CapacityError):
                cb.submit(np.zeros(14, np.int32), 8)  # 14 + 8 > 16
        finally:
            cb.shutdown()
        # non-token model is rejected up front, not at first request
        with pytest.raises(ValueError, match="embedding-front"):
            ContinuousBatcher(_dense_model(), slots=1, capacity=8)

    def test_drain_completes_inflight_generations(self, lm):
        cb = ContinuousBatcher(lm, slots=2, capacity=16, queue_limit=16,
                               seed=0)
        rng = np.random.RandomState(4)
        reqs = [cb.submit(rng.randint(0, 50, (4,)).astype(np.int32), 4,
                          temperature=0.0) for _ in range(4)]
        cb.shutdown(drain=True)
        for r in reqs:
            assert r.wait().shape == (4,)
        with pytest.raises(ServerClosingError):
            cb.submit(np.zeros(4, np.int32), 2)


class TestModelServerHTTP:
    def _post(self, port, path, body, timeout=30):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())

    def test_predict_generate_health_metrics(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        srv = ModelServer(lm, port=0, input_dtype=np.int32, gen_slots=2,
                          gen_capacity=16).start()
        try:
            rng = np.random.RandomState(0)
            ids = rng.randint(0, 50, (2, 8))
            out = self._post(srv.port, "/predict", {"ndarray": ids.tolist()})
            want = np.asarray(lm.output(ids.astype(np.int32)))
            np.testing.assert_allclose(np.asarray(out["output"]), want,
                                       rtol=1e-4, atol=1e-5)
            assert out["generation"] == 1

            prompt = rng.randint(0, 50, (6,)).tolist()
            gen = self._post(srv.port, "/generate?stream=false",
                             {"prompt": prompt, "max_new_tokens": 4,
                              "temperature": 0.0})
            want_t = generate(lm, np.asarray([prompt], np.int32), 4,
                              temperature=0.0)[0]
            assert gen["tokens"] == want_t.tolist()

            base = f"http://127.0.0.1:{srv.port}"
            health = json.loads(urllib.request.urlopen(
                base + "/health", timeout=10).read())
            assert health["status"] == "ok" and health["generation"] == 1
            ready = json.loads(urllib.request.urlopen(
                base + "/ready", timeout=10).read())
            assert ready["status"] == "ready"
            scrape = urllib.request.urlopen(
                base + "/metrics", timeout=10).read().decode()
            for name in ("serve_queue_depth", "serve_batches_total",
                         "serve_batch_occupancy", "serve_queue_seconds",
                         "serve_device_seconds", "serve_gen_tokens_total",
                         "serve_compile_misses_total", "http_request_seconds",
                         "serve_kv_blocks_total", "serve_kv_blocks_used",
                         "serve_kv_block_utilization", "serve_kv_live_bytes",
                         "serve_prefill_chunks_total"):
                assert name in scrape, f"{name} missing from /metrics"
        finally:
            srv.stop()

    def test_bad_payload_400_overload_503(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1,), max_wait_ms=1.0,
                          queue_limit=1, forward=_slow_forward(m, 0.05))
        srv = ModelServer(m, port=0, engine=eng).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._post(srv.port, "/predict", {"x": 1})
            assert ei.value.code == 400

            codes = []

            retry_after = []

            def fire(_):
                try:
                    self._post(srv.port, "/predict",
                               {"ndarray": [[0.0] * 4]}, timeout=30)
                    return 200
                except urllib.error.HTTPError as e:
                    if e.code == 503:
                        retry_after.append(e.headers.get("Retry-After"))
                    return (e.code, json.loads(e.read())["cause"])

            with cf.ThreadPoolExecutor(10) as ex:
                codes = list(ex.map(fire, range(10)))
            assert len(codes) == 10  # zero hangs: every request answered
            assert 200 in codes
            assert (503, "queue_full") in codes, codes
            # every 503 tells well-behaved clients when to come back
            assert retry_after and all(
                ra is not None and int(ra) >= 1 for ra in retry_after), \
                retry_after
        finally:
            srv.stop()

    def test_graceful_drain_over_http(self):
        m = _dense_model()
        eng = ServeEngine(m, batch_buckets=(1, 2, 4), max_wait_ms=30.0,
                          queue_limit=64, forward=_slow_forward(m, 0.03))
        srv = ModelServer(m, port=0, engine=eng).start()
        results = []

        def fire(_):
            results.append(self._post(srv.port, "/predict",
                                      {"ndarray": [[0.1] * 4]}, timeout=30))

        threads = [threading.Thread(target=fire, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)      # let every request get admitted
        srv.stop(drain=True)  # flips readiness, drains, then closes
        for t in threads:
            t.join(30)
        assert len(results) == 4  # all in-flight requests completed with 200
        for r in results:
            assert len(r["output"][0]) == 3


class TestBlockAllocator:
    def test_randomized_alloc_free_invariants(self):
        from deeplearning4j_tpu.serve.paged import TRASH_BLOCK

        rng = np.random.RandomState(0)
        a = BlockAllocator(33)  # 32 usable + trash
        held = {}
        for step in range(600):
            if held and rng.rand() < 0.45:
                key = list(held)[rng.randint(len(held))]
                a.release(held.pop(key))
            else:
                n = int(rng.randint(1, 6))
                if n <= a.available:
                    ids = a.alloc(n)
                    assert TRASH_BLOCK not in ids
                    out = {b for blocks in held.values() for b in blocks}
                    assert not set(ids) & out  # never double-handed
                    held[step] = ids
                else:
                    before = (a.used, a.available)
                    with pytest.raises(CapacityError):
                        a.alloc(n)
                    assert (a.used, a.available) == before  # atomic
            total = sum(len(v) for v in held.values())
            assert a.used == total
            assert a.available == a.usable - total  # conservation
        for ids in held.values():
            a.release(ids)
        assert a.available == a.usable == 32  # fully drained, nothing leaked

    def test_lifo_reuse_and_trash_protection(self):
        a = BlockAllocator(6)
        ids = a.alloc(4)
        a.release(ids[:2])
        # a freed block is the next handed out (compact working set)
        assert set(a.alloc(2)) == set(ids[:2])
        with pytest.raises(ValueError, match="double free"):
            a.release([ids[3], ids[3]])
        with pytest.raises(ValueError, match="trash"):
            a.release([0])

    def test_exhaustion_is_typed(self):
        a = BlockAllocator(4)  # 3 usable
        a.alloc(2)
        with pytest.raises(CapacityError):
            a.alloc(2)
        assert a.available == 1  # failed alloc took nothing


class TestPrefillScheduler:
    def test_edf_order_and_budget(self):
        class J:
            def __init__(self, deadline, enq_t):
                self.deadline, self.enq_t = deadline, enq_t

        jobs = [J(None, 3.0), J(9.0, 2.0), J(1.0, 4.0), J(None, 1.0)]
        sched = PrefillScheduler(decode_chunks=2, idle_chunks=3)
        # deadline-bearing jobs first (earliest deadline), then FIFO
        busy = sched.plan(jobs, decoding=True)
        assert [(j.deadline, j.enq_t) for j in busy] == [(1.0, 4.0),
                                                         (9.0, 2.0)]
        idle = sched.plan(jobs, decoding=False)
        assert len(idle) == 3 and idle[-1].enq_t == 1.0
        with pytest.raises(ValueError):
            PrefillScheduler(decode_chunks=0)


class TestPagedKV:
    def test_paged_greedy_bit_identical_to_dense(self, lm):
        """The equivalence claim the paged cache came in on: chunked and
        whole-prompt paged decoding produce token-for-token the greedy
        chains ``nn.generation.generate`` produces over its contiguous
        caches, across prompt buckets (padded AND exact)."""
        from deeplearning4j_tpu.nn.generation import generate

        chunked = ContinuousBatcher(lm, slots=2, capacity=16, block_size=4,
                                    prefill_chunk=8, prompt_buckets=(8, 16),
                                    seed=0)
        whole = ContinuousBatcher(lm, slots=2, capacity=16, block_size=4,
                                  prefill_chunk=None, prompt_buckets=(8, 16),
                                  seed=0)
        try:
            rng = np.random.RandomState(7)
            for tp in (3, 5, 8, 10):  # bucket-8 padded/exact, bucket-16
                prompt = rng.randint(0, 50, (tp,)).astype(np.int32)
                want = generate(lm, prompt[None], 6,
                                temperature=0.0)[0].tolist()
                assert chunked.generate(
                    prompt, 6, temperature=0.0).tolist() == want, tp
                assert whole.generate(
                    prompt, 6, temperature=0.0).tolist() == want, tp
        finally:
            chunked.shutdown()
            whole.shutdown()

    def test_one_decode_executable_bounded_prefill_chunks(self, lm):
        buckets = (8, 16)
        cb = ContinuousBatcher(lm, slots=3, capacity=16, block_size=4,
                               prefill_chunk=8, prompt_buckets=buckets,
                               queue_limit=16, seed=0)
        try:
            rng = np.random.RandomState(11)
            prompts = [rng.randint(0, 50, (tp,)).astype(np.int32)
                       for tp in (1, 3, 5, 8, 9, 10, 7, 2)]
            with cf.ThreadPoolExecutor(8) as ex:
                list(ex.map(
                    lambda p: cb.generate(p, 4, temperature=0.0), prompts))
            # ONE decode executable for the server's lifetime...
            assert cb._decode_sigs == {("decode", 3)}, cb._decode_sigs
            # ...and at most |prompt buckets| prefill-chunk executables
            assert len(cb._prefill_sigs) <= len(buckets), cb._prefill_sigs
        finally:
            cb.shutdown()

    def test_overcommit_queues_and_completes(self, lm):
        from deeplearning4j_tpu.nn.generation import generate

        # pool = 8 usable blocks x 4 tokens = 32 KV tokens, but slots x
        # capacity = 64: the dense layout's reservation would not fit.
        # 6 requests x 8 tokens = 48 live tokens demanded over the run —
        # paging + worst-case admission makes them queue and ALL complete.
        cb = ContinuousBatcher(lm, slots=4, capacity=16, block_size=4,
                               kv_blocks=9, prefill_chunk=None,
                               queue_limit=32, seed=0)
        try:
            rng = np.random.RandomState(13)
            prompts = [rng.randint(0, 50, (4,)).astype(np.int32)
                       for _ in range(6)]
            with cf.ThreadPoolExecutor(6) as ex:
                outs = list(ex.map(
                    lambda p: cb.generate(p, 4, temperature=0.0), prompts))
            for p, o in zip(prompts, outs):
                want = generate(lm, p[None], 4, temperature=0.0)[0]
                assert np.array_equal(o, want)
            cb.flush_prefix_cache()  # drop cache-retained blocks
            stats = cb.kv_block_stats()
            assert stats["blocks_used"] == 0  # every block retired
            assert stats["blocks_committed"] == 0
        finally:
            cb.shutdown()

    def test_impossible_request_sheds_typed_capacity_error(self, lm):
        # 2 usable blocks x 4 = 8 KV tokens total
        cb = ContinuousBatcher(lm, slots=1, capacity=16, block_size=4,
                               kv_blocks=3, seed=0)
        try:
            with pytest.raises(CapacityError, match="KV blocks"):
                cb.submit(np.zeros(8, np.int32), 4)  # 12 tokens NEVER fit
            # a fitting request on the same batcher still succeeds
            out = cb.generate(np.arange(1, 5, dtype=np.int32), 4,
                              temperature=0.0)
            assert out.shape == (4,)
        finally:
            cb.shutdown()

    def test_live_kv_gauges_track_allocation(self, lm):
        from deeplearning4j_tpu.serve.paged import block_bytes

        cb = ContinuousBatcher(lm, slots=1, capacity=64, block_size=4,
                               seed=0)
        try:
            req = cb.submit(np.arange(1, 9, dtype=np.int32), 40,
                            temperature=0.0)
            peak, deadline = 0, time.time() + 30
            while time.time() < deadline:
                stats = cb.kv_block_stats()
                peak = max(peak, stats["blocks_used"])
                if req.event.is_set():
                    break
                time.sleep(0.001)
            req.wait()
            # mid-flight usage covered at least the prompt's blocks and
            # live bytes scale with the allocator, not slots x capacity
            assert peak >= 2, peak
            cb.flush_prefix_cache()  # cache-held blocks count as used
            assert cb.kv_block_stats()["blocks_used"] == 0
            assert cb.kv_block_stats()["live_bytes"] == 0
            per_block = block_bytes(lm, 4, np.float32)
            assert cb.metrics.gauge("serve_kv_blocks_total").value \
                == cb.kv_block_stats()["blocks_total"]
            assert per_block > 0
        finally:
            cb.shutdown()

    def test_rope_capacity_decoupled_from_positional_table(self, lm):
        from deeplearning4j_tpu.models import CausalLM

        # learned positions: capacity is pinned to the embedding table
        with pytest.raises(ValueError, match="[Pp]ositional"):
            ContinuousBatcher(lm, slots=1, capacity=1024)
        # rope has NO table: per-request capacity may exceed the model's
        # build-time sequence length (16), bounded only by KV blocks
        rope = CausalLM(seed=0, input_shape=(16,), num_layers=1, d_model=32,
                        num_heads=4, vocab=50, pos="rope").build()
        rope.init()
        cb = ContinuousBatcher(rope, slots=1, capacity=1024, block_size=16,
                               prompt_buckets=(16,), seed=0)
        try:
            out = cb.generate(np.arange(1, 7, dtype=np.int32), 4,
                              temperature=0.0)
            assert out.shape == (4,)
        finally:
            cb.shutdown()


class TestStreaming:
    def test_stream_yields_tokens_matching_generate(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, seed=0)
        try:
            p = np.arange(2, 8, dtype=np.int32)
            want = cb.generate(p, 6, temperature=0.0).tolist()
            assert list(cb.stream(p, 6, temperature=0.0)) == want
        finally:
            cb.shutdown()

    def test_stream_raises_typed_error_while_queued(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, queue_limit=8,
                               seed=0)
        try:
            blocker = cb.submit(np.arange(1, 9, dtype=np.int32), 8,
                                temperature=0.0)  # occupies the only slot
            doomed = cb.submit(np.arange(1, 5, dtype=np.int32), 4,
                               temperature=0.0, timeout_ms=0.5)
            with pytest.raises(DeadlineExceededError):
                list(doomed.stream())
            assert blocker.wait().shape == (8,)
        finally:
            cb.shutdown()

    def test_stream_completes_through_drain(self, lm):
        cb = ContinuousBatcher(lm, slots=1, capacity=16, seed=0)
        p = np.arange(3, 9, dtype=np.int32)
        want = cb.generate(p, 8, temperature=0.0).tolist()
        it = cb.stream(p, 8, temperature=0.0)
        got = [next(it)]  # stream is live...
        closer = threading.Thread(target=cb.shutdown, kwargs={"drain": True})
        closer.start()     # ...when drain begins
        got.extend(it)     # drain finishes the in-flight stream, not cuts it
        closer.join(30)
        assert got == want

    def test_http_sse_streams_per_token(self, lm):
        srv = ModelServer(lm, port=0, input_dtype=np.int32, gen_slots=2,
                          gen_capacity=16).start()
        try:
            body = {"prompt": list(range(2, 8)), "max_new_tokens": 5,
                    "temperature": 0.0}
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            events = []
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.headers["Content-Type"] == "text/event-stream"
                for line in r:
                    if line.startswith(b"data: "):
                        events.append(json.loads(line[len(b"data: "):]))
            assert events[-1]["done"] is True
            toks = [e["token"] for e in events[:-1]]
            assert len(toks) == 5 and events[-1]["tokens"] == toks
            # buffered answer agrees with the streamed one
            breq = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate?stream=false",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(breq, timeout=30) as r:
                assert json.loads(r.read())["tokens"] == toks
        finally:
            srv.stop()

    def test_http_admission_error_is_typed_not_streamed(self, lm):
        srv = ModelServer(lm, port=0, input_dtype=np.int32, gen_slots=1,
                          gen_capacity=16).start()
        try:
            body = {"prompt": list(range(1, 15)), "max_new_tokens": 8}
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            # 14 + 8 > capacity: refused BEFORE the stream starts as a
            # typed status, not an SSE body
            assert ei.value.code == 400  # CapacityError
            assert json.loads(ei.value.read())["cause"] == "over_capacity"
        finally:
            srv.stop()

    def test_client_disconnect_mid_sse_frees_slot(self, lm):
        """ISSUE 10 satellite: a client that drops the socket mid-stream is
        shed load (``serve_shed_total{cause="client_gone"}``), the decode
        slot is reclaimed, and nothing lands in serve_http_errors_total."""
        srv = ModelServer(lm, port=0, input_dtype=np.int32, gen_slots=1,
                          gen_capacity=64).start()
        try:
            body = json.dumps({"prompt": list(range(2, 8)),
                               "max_new_tokens": 40}).encode()
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
            s.sendall(b"POST /generate HTTP/1.1\r\nHost: t\r\n"
                      b"Content-Type: application/json\r\n"
                      + f"Content-Length: {len(body)}\r\n\r\n".encode()
                      + body)
            buf = b""
            while buf.count(b"data: ") < 2:  # the stream is live
                buf += s.recv(4096)
            # SO_LINGER(0): close sends RST, so the server's next flush
            # fails immediately instead of filling the kernel buffer
            s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                         struct.pack("ii", 1, 0))
            s.close()
            shed = srv.metrics.counter("serve_shed_total",
                                       {"cause": "client_gone"})
            slots = srv.metrics.gauge("serve_gen_active_slots")
            deadline = time.monotonic() + 15
            while ((shed.value < 1 or slots.value > 0)
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert shed.value == 1, "disconnect was not counted as shed"
            assert slots.value == 0, "decode slot still held by a dead client"
            # slot actually reusable: a fresh generation completes
            breq = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate?stream=false",
                data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4,
                                 "temperature": 0.0}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(breq, timeout=30) as r:
                assert len(json.loads(r.read())["tokens"]) == 4
            scrape = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=10).read().decode()
            assert "serve_http_errors_total" not in scrape, \
                "client disconnect was misfiled as a server error"
        finally:
            srv.stop()


class TestAotPublishUnderLoad:
    """ISSUE 6: hot-swap against a live AOT-backed batcher. A same-
    architecture publish must reuse the already-warm executables — ZERO
    stray compiles after the flip — and a candidate that cannot compile
    must abort as a typed PublishError while the old generation serves."""

    def test_publish_under_load_zero_stray_compiles(self, lm, tmp_path):
        m = MetricsRegistry()
        cb = ContinuousBatcher(lm, slots=2, capacity=16, prompt_buckets=(8,),
                               metrics=m, aot_store=AotStore(tmp_path),
                               seed=0)
        try:
            compiles = m.counter("serve_compile_misses_total",
                                 {"component": "generate"})
            rng = np.random.RandomState(0)
            prompts = [rng.randint(0, 50, (5,)).astype(np.int32)
                       for _ in range(8)]
            with cf.ThreadPoolExecutor(4) as ex:
                futs = [ex.submit(cb.generate, p, 3, temperature=0.0)
                        for p in prompts[:4]]
                # warm-at-construction traced everything; the flip (same
                # architecture -> same cache keys) must add NOTHING
                before = compiles.value
                scaled = jax.tree.map(lambda a: a * 1.25,
                                      cb.registry.current().params)
                snap = cb.registry.publish(scaled, drain=True)
                futs += [ex.submit(cb.generate, p, 3, temperature=0.0)
                         for p in prompts[4:]]
                outs = [f.result(timeout=120) for f in futs]
            assert snap.generation == 2
            assert all(len(o) == 3 for o in outs)
            assert compiles.value == before, \
                "publish traced new executables despite the pre-flip warm"

            # a candidate whose shapes cannot run the warmers aborts BEFORE
            # the flip: typed error, generation unchanged, still serving,
            # and the failed warm did not inflate the compile counter
            bad = jax.tree.map(
                lambda a: np.zeros(tuple(s + 1 for s in np.shape(a)),
                                   np.asarray(a).dtype), snap.params)
            with pytest.raises(PublishError):
                cb.registry.publish(bad)
            assert cb.registry.generation == 2
            assert compiles.value == before
            out = cb.generate(prompts[0], 3, temperature=0.0)
            assert len(out) == 3
        finally:
            cb.shutdown()
