"""Tests for the obs/ telemetry subsystem (ISSUE 2): registry correctness
and thread safety, histogram quantile accuracy, Prometheus/Chrome-trace
export validity, the strict no-op-when-disabled guarantee (including that a
plain ``fit`` makes zero obs calls), the 5-step instrumented fit acceptance
surface, and a live /metrics round-trip against the knn server."""

import json
import math
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.obs import (DEFAULT_BUCKETS, MetricsRegistry,
                                    StepTelemetry, TelemetryListener, Tracer)
from deeplearning4j_tpu.obs import metrics as obs_metrics
from deeplearning4j_tpu.obs import step as obs_step
from deeplearning4j_tpu.obs import trace as obs_trace


def _toy_trainer():
    from deeplearning4j_tpu.nn.layers import Dense, Output
    from deeplearning4j_tpu.nn.model import NetConfig, Sequential
    from deeplearning4j_tpu.train import Trainer

    model = Sequential(
        NetConfig(updater={"type": "sgd", "learning_rate": 0.1}),
        [Dense(n_out=8, activation="relu"),
         Output(n_out=3, loss="mcxent", activation="softmax")], (5,))
    return Trainer(model)


def _toy_iterator(n=80, batch=16, seed=0):
    from deeplearning4j_tpu.data import ArrayIterator

    rng = np.random.RandomState(seed)
    x = rng.rand(n, 5).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return ArrayIterator(x, y, batch_size=batch)


# --- registry primitives ---
class TestRegistry:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        g = MetricsRegistry().gauge("g_bytes")
        g.set(10)
        g.inc(5)
        g.dec(3)
        assert g.value == 12

    def test_same_name_same_instrument(self):
        reg = MetricsRegistry()
        assert reg.counter("a_total") is reg.counter("a_total")
        assert reg.counter("a_total", {"k": "1"}) is not reg.counter("a_total")

    def test_type_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("m_total")
        with pytest.raises(ValueError):
            reg.gauge("m_total")

    def test_invalid_names_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("bad-name")
        with pytest.raises(ValueError):
            reg.counter("ok_total", {"bad-label": "v"})

    def test_thread_safety_concurrent_writers(self):
        reg = MetricsRegistry()
        c = reg.counter("hits_total")
        h = reg.histogram("lat_seconds")
        n_threads, n_iter = 8, 2000

        def work():
            for i in range(n_iter):
                c.inc()
                h.observe(i * 1e-4)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_iter
        assert h.count == n_threads * n_iter

    def test_concurrent_registration_one_instrument(self):
        reg = MetricsRegistry()
        got = []

        def grab():
            got.append(reg.counter("shared_total"))

        threads = [threading.Thread(target=grab) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(g is got[0] for g in got)


class TestHistogram:
    def test_quantile_accuracy_uniform(self):
        # uniform samples over (0, 0.1): quantile estimates must land within
        # one bucket width of the true value
        h = MetricsRegistry().histogram("h_seconds")
        vals = np.linspace(0.0005, 0.0995, 1000)
        for v in vals:
            h.observe(float(v))
        for q in (0.5, 0.95, 0.99):
            true = float(np.quantile(vals, q))
            # containing bucket's width bounds the estimation error
            bounds = [b for b in DEFAULT_BUCKETS if b >= true]
            width = bounds[0] - max([b for b in DEFAULT_BUCKETS if b < true],
                                    default=0.0)
            assert abs(h.quantile(q) - true) <= width

    def test_quantile_edge_cases(self):
        h = MetricsRegistry().histogram("h2_seconds")
        assert h.quantile(0.5) == 0.0  # empty
        h.observe(0.02)
        assert 0.0 < h.quantile(0.5) <= 0.025
        h2 = MetricsRegistry().histogram("h3_seconds")
        h2.observe(1000.0)  # overflow bucket: max tightens the estimate
        assert h2.quantile(0.99) == pytest.approx(1000.0)
        with pytest.raises(ValueError):
            h.quantile(1.5)

    def test_sum_count_mean_minmax(self):
        h = MetricsRegistry().histogram("h4_seconds")
        for v in (0.01, 0.02, 0.03):
            h.observe(v)
        assert h.count == 3
        assert h.sum == pytest.approx(0.06)
        assert h.mean == pytest.approx(0.02)
        snap = h._snapshot()
        assert snap["min"] == pytest.approx(0.01)
        assert snap["max"] == pytest.approx(0.03)

    def test_bucket_counts_cumulative(self):
        h = MetricsRegistry().histogram("h5_seconds", buckets=(1.0, 2.0))
        for v in (0.5, 1.5, 5.0):
            h.observe(v)
        snap = h._snapshot()
        assert snap["buckets"] == [(1.0, 1), (2.0, 2), (math.inf, 3)]


class TestPrometheus:
    def _parse(self, text):
        """Minimal exposition-format parser: {name{labels}: value}."""
        out = {}
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            key, val = line.rsplit(" ", 1)
            out[key] = val
        return out

    def test_exposition_parses(self):
        reg = MetricsRegistry()
        reg.counter("req_total", {"code": "200"}, help="requests").inc(3)
        reg.gauge("mem_bytes").set(1024)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = reg.to_prometheus()
        series = self._parse(text)
        assert series['req_total{code="200"}'] == "3"
        assert series["mem_bytes"] == "1024"
        assert series['lat_seconds_bucket{le="0.1"}'] == "1"
        assert series['lat_seconds_bucket{le="1"}'] == "2"
        assert series['lat_seconds_bucket{le="+Inf"}'] == "2"
        assert series["lat_seconds_count"] == "2"
        assert float(series["lat_seconds_sum"]) == pytest.approx(0.55)
        assert "# TYPE lat_seconds histogram" in text
        assert "# HELP req_total requests" in text

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("esc_total", {"path": 'a"b\\c\nd'}).inc()
        text = reg.to_prometheus()
        assert '\\"' in text and "\\\\" in text and "\\n" in text

    def test_json_snapshot_roundtrips(self):
        reg = MetricsRegistry()
        reg.histogram("lat_seconds").observe(0.01)
        reg.counter("c_total").inc()
        snap = json.loads(reg.to_json())
        assert snap["c_total"]["type"] == "counter"
        assert snap["lat_seconds"]["series"][0]["count"] == 1
        assert "quantiles" in snap["lat_seconds"]["series"][0]


class TestTracer:
    def test_chrome_trace_schema(self):
        tr = Tracer()
        with tr.span("outer", tag="x"):
            with tr.span("inner"):
                time.sleep(0.001)
        tr.instant("mark", n=1)
        doc = json.loads(tr.export())
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        by_name = {e["name"]: e for e in events}
        assert by_name["thread_name"]["ph"] == "M"
        for name in ("outer", "inner"):
            e = by_name[name]
            assert e["ph"] == "X"
            assert {"ts", "dur", "pid", "tid"} <= set(e)
            assert e["dur"] >= 0
        inner, outer = by_name["inner"], by_name["outer"]
        # nesting: inner lies within outer, and records its parent
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert inner["args"]["parent"] == "outer"
        assert by_name["mark"]["ph"] == "i"

    def test_per_thread_stacks(self):
        tr = Tracer()

        def worker():
            with tr.span("w"):
                pass

        t = threading.Thread(target=worker, name="worker-thread")
        with tr.span("main"):
            t.start()
            t.join()
        events = tr.events
        tids = {e["tid"] for e in events if e["ph"] == "X"}
        assert len(tids) == 2
        w = next(e for e in events if e["name"] == "w")
        assert "parent" not in w.get("args", {})  # stacks are per-thread
        names = {e["args"]["name"] for e in events if e["ph"] == "M"}
        assert "worker-thread" in names

    def test_max_events_drops_counted(self):
        tr = Tracer(max_events=3)
        for i in range(10):
            tr.instant(f"e{i}")
        doc = tr.to_chrome()
        # budget of 3 = 1 thread_name metadata + 2 instants; the other 8
        # instants are dropped and counted, never silently lost
        assert len(doc["traceEvents"]) == 3
        assert doc["otherData"]["dropped_events"] == 8

    def test_export_to_file(self, tmp_path):
        tr = Tracer()
        with tr.span("s"):
            pass
        p = tmp_path / "trace.json"
        tr.export(str(p))
        assert json.loads(p.read_text())["traceEvents"]


class TestDisabled:
    def test_disabled_registry_nulls(self):
        reg = MetricsRegistry(enabled=False)
        reg.counter("c_total").inc(5)
        reg.gauge("g").set(1)
        reg.histogram("h_seconds").observe(1.0)
        assert reg.to_prometheus() == ""
        assert reg.snapshot() == {}
        # shared null instruments — no per-call allocation
        assert reg.counter("a_total") is reg.counter("b_total")

    def test_disabled_tracer_null_span(self):
        tr = Tracer(enabled=False)
        s1 = tr.span("a")
        s2 = tr.span("b")
        assert s1 is s2  # one shared null CM
        with s1:
            pass
        tr.instant("x")
        assert tr.events == []

    def test_fit_without_telemetry_makes_zero_obs_calls(self, monkeypatch):
        """The acceptance guarantee: a plain fit never touches obs/."""
        calls = []

        def spy(name):
            def record(*a, **k):
                calls.append(name)
                raise AssertionError(f"obs call on plain fit path: {name}")
            return record

        monkeypatch.setattr(obs_step.StepTelemetry, "step",
                            spy("StepTelemetry.step"))
        monkeypatch.setattr(obs_step.StepTelemetry, "wrap_iterator",
                            spy("StepTelemetry.wrap_iterator"))
        monkeypatch.setattr(obs_metrics.Histogram, "observe",
                            spy("Histogram.observe"))
        monkeypatch.setattr(obs_metrics.Counter, "inc", spy("Counter.inc"))
        monkeypatch.setattr(obs_trace.Tracer, "span", spy("Tracer.span"))
        tr = _toy_trainer()
        tr.fit(_toy_iterator(), epochs=1)
        assert calls == []
        assert tr.iteration == 5


class TestStepTelemetry:
    def test_five_step_fit_acceptance(self, tmp_path):
        """ISSUE 2 acceptance: 5 instrumented steps → Perfetto-loadable
        trace + a scrape with the three required metric families."""
        tel = StepTelemetry()
        tr = _toy_trainer()
        tr.fit(_toy_iterator(), epochs=1, telemetry=tel)
        assert tr.iteration == 5

        prom = tel.to_prometheus()
        assert "# TYPE train_step_seconds histogram" in prom
        assert "compile_cache_misses_total 1" in prom
        # the CPU backend keeps no allocator stats: no device-memory series
        assert "device_memory_bytes" not in prom
        assert "train_step_seconds_count 5" in prom
        assert "train_data_wait_seconds" in prom
        assert "train_device_compute_seconds" in prom

        p = tmp_path / "fit_trace.json"
        tel.export_trace(str(p))
        doc = json.loads(p.read_text())
        steps = [e for e in doc["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "train_step"]
        assert len(steps) == 5
        phases = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert {"dispatch", "device_compute", "data_wait"} <= phases

        snap = tel.snapshot()
        assert snap["steps"] == 5
        assert snap["steps_per_sec"] > 0
        assert snap["compile_cache_misses"] == 1
        assert snap["p95_step_seconds"] >= snap["p50_step_seconds"]

    def test_compile_miss_on_shape_change(self):
        tel = StepTelemetry(fence=False, memory_every=0)
        tel.step(lambda: 1, sig=("a", (16, 5)))
        tel.step(lambda: 1, sig=("a", (16, 5)))
        tel.step(lambda: 1, sig=("a", (7, 5)))  # ragged tail batch
        assert tel.snapshot()["compile_cache_misses"] == 2

    def test_fit_shape_change_counts_misses(self):
        # 80 rows / batch 32 -> batches of 32, 32, 16: two signatures
        tel = StepTelemetry()
        _toy_trainer().fit(_toy_iterator(n=80, batch=32), epochs=1,
                           telemetry=tel)
        assert tel.snapshot()["compile_cache_misses"] == 2

    def test_telemetry_disables_megastep(self):
        # steps_per_execution with telemetry must still report per-iteration
        tel = StepTelemetry()
        tr = _toy_trainer()
        tr.fit(_toy_iterator(), epochs=1, steps_per_execution=4,
               telemetry=tel)
        assert tel.snapshot()["steps"] == 5

    def test_record_memory_omits_series_without_device_stats(self):
        """Host memory is never written under a device-memory name: the CPU
        backend reports no allocator stats, so there is no series; a device
        that does report them gets one gauge per stat."""
        tel = StepTelemetry()
        tel.record_memory()
        assert "device_memory_bytes" not in tel.registry.snapshot()

    def test_record_memory_gauges_reported_stats(self, monkeypatch):
        import jax

        class _Dev:
            platform, id = "tpu", 0

            def memory_stats(self):
                return {"bytes_in_use": 5, "peak_bytes_in_use": 9,
                        "bytes_limit": 16}

        monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
        tel = StepTelemetry()
        tel.record_memory()
        series = tel.registry.snapshot()["device_memory_bytes"]["series"]
        assert {s["labels"]["kind"]: s["value"] for s in series} == {
            "bytes_in_use": 5.0, "peak_bytes_in_use": 9.0,
            "bytes_limit": 16.0}
        assert all(s["labels"]["device"] == "tpu:0" for s in series)

    def test_wrap_iterator_times_data_wait(self):
        tel = StepTelemetry()
        out = list(tel.wrap_iterator([1, 2, 3]))
        assert out == [1, 2, 3]
        assert tel.registry.histogram("train_data_wait_seconds").count == 3


class TestTelemetryListener:
    def test_bridges_into_stats_storage(self):
        from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

        storage = InMemoryStatsStorage()
        lst = TelemetryListener(storage=storage, frequency=2)
        tr = _toy_trainer()
        # auto-adoption: fit picks up lst.telemetry, no telemetry= needed
        tr.fit(_toy_iterator(), epochs=1, listeners=[lst])
        assert lst.telemetry.snapshot()["steps"] == 5
        static = storage.get_static_info(lst.session_id, "telemetry_0")
        assert static["type"] == "telemetry"
        updates = storage.get_updates(lst.session_id, "telemetry_0")
        assert len(updates) == 3  # iterations 0, 2, 4
        _, rec = updates[-1]
        assert rec["telemetry"]["steps"] >= 1
        assert "train_step_seconds" in rec["metrics"]
        # records must be JSON-serializable for the UI fetch path
        json.dumps(rec)

    def test_storage_none_is_carrier_only(self):
        lst = TelemetryListener()
        tr = _toy_trainer()
        tr.fit(_toy_iterator(), epochs=1, listeners=[lst])
        assert lst.telemetry.snapshot()["steps"] == 5


class TestServerMetrics:
    def _scrape(self, port):
        # request handling records metrics AFTER replying; one tiny grace
        # window keeps the scrape race-free
        time.sleep(0.05)
        r = urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics")
        assert r.headers["Content-Type"].startswith("text/plain")
        assert "version=0.0.4" in r.headers["Content-Type"]
        return r.read().decode()

    def test_knn_metrics_roundtrip(self):
        from deeplearning4j_tpu.knn.server import NearestNeighborsServer

        pts = np.random.RandomState(0).rand(20, 4).astype(np.float32)
        srv = NearestNeighborsServer(pts, port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            urllib.request.urlopen(base + "/health").read()
            req = urllib.request.Request(
                base + "/knn", data=json.dumps({"ndarray": 3, "k": 2}).encode(),
                headers={"Content-Type": "application/json"})
            assert len(json.loads(urllib.request.urlopen(req).read())["results"]) == 2
            text = self._scrape(srv.port)
            assert 'http_requests_total{endpoint="/health",method="GET"} 1' in text
            assert 'http_requests_total{endpoint="/knn",method="POST"} 1' in text
            assert 'http_request_seconds_bucket' in text
        finally:
            srv.stop()

    def test_ui_metrics_route_collapsed(self):
        from deeplearning4j_tpu.ui.server import UIServer

        srv = UIServer(port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            urllib.request.urlopen(base + "/train/sessions").read()
            urllib.request.urlopen(base + "/train/sess_abc/overview").read()
            urllib.request.urlopen(base + "/train/sess_xyz/overview").read()
            text = self._scrape(srv.port)
            # parameterized sessions collapse into ONE bounded label
            assert ('http_requests_total{endpoint="/train/{sid}/overview",'
                    'method="GET"} 2') in text
            assert "sess_abc" not in text
        finally:
            srv.stop()

    def test_streaming_serve_has_metrics(self):
        from deeplearning4j_tpu.streaming.serve import InferenceRoute

        tr = _toy_trainer()
        srv = InferenceRoute(tr.model, params=tr.params, state=tr.state,
                             port=0).start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            req = urllib.request.Request(
                base + "/predict",
                data=json.dumps({"ndarray": [[0.1] * 5]}).encode(),
                headers={"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req).read())
            assert len(out["output"][0]) == 3
            text = self._scrape(srv.port)
            assert ('http_requests_total{endpoint="/predict",method="POST"} 1'
                    in text)
        finally:
            srv.stop()


class TestStreamingDroppedFrames:
    def test_dropped_frame_counts_and_logs(self, caplog):
        import logging

        from deeplearning4j_tpu.obs.metrics import default_registry
        from deeplearning4j_tpu.streaming.ndarray import _default_on_error

        c = default_registry().counter("streaming_dropped_frames_total")
        before = c.value
        with caplog.at_level(logging.WARNING,
                             logger="deeplearning4j_tpu.streaming"):
            _default_on_error(ValueError("bad frame"))
        assert c.value == before + 1
        assert "dropped frame" in caplog.text


class TestParallelTelemetry:
    def test_parallel_wrapper_records_replica_gauges(self):
        import jax

        from deeplearning4j_tpu.parallel import ParallelWrapper

        if len(jax.devices()) < 2:
            pytest.skip("needs the 8-device virtual CPU mesh")
        tel = StepTelemetry()
        tr = _toy_trainer()
        pw = ParallelWrapper(tr.model)
        pw.fit(_toy_iterator(n=64, batch=32), epochs=1, telemetry=tel)
        snap = tel.registry.snapshot()
        assert "parallel_step_seconds" in snap
        assert "parallel_samples_per_second" in snap
        replicas = snap["parallel_replica_step_seconds"]["series"]
        assert len(replicas) == len(jax.devices())
        assert tel.snapshot()["steps"] == 2
