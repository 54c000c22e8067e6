"""Tests for request-scoped tracing, the flight recorder, exemplars, and
SLO burn accounting (ISSUE 9).

The load-bearing properties:

- W3C ``traceparent`` parse/format roundtrip; malformed headers start a
  fresh trace instead of failing the request;
- span-stack unwind regression: exiting an outer span past an orphaned
  inner one restores the recorded depth (parent attribution stays sane);
- ``RequestContext`` accumulates cross-thread stages into one
  ``RequestRecord`` and emits async events stitched by ``trace_id``;
- the flight recorder ring is bounded, dumps are atomic and slot-rotated;
- histogram exemplars ride into the OpenMetrics exposition and the
  exposition survives :mod:`~deeplearning4j_tpu.obs.promcheck` (whose
  negative cases are also exercised);
- SLO burn math matches the SRE-workbook definition on a fake clock;
- **disabled tracing is a strict no-op on the decode path** — booby-trap
  every RequestContext entry point and run real traffic;
- end to end: concurrent fleet traffic scraped mid-flight yields a valid
  exemplar-bearing OpenMetrics exposition, and a watchdog-shed generation
  stitches one ``trace_id`` across >= 3 distinct threads.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from deeplearning4j_tpu.obs import flight as flight_mod
from deeplearning4j_tpu.obs import reqtrace as reqtrace_mod
from deeplearning4j_tpu.obs.flight import FlightRecorder
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.promcheck import check_text
from deeplearning4j_tpu.obs.reqtrace import (RequestTracer, format_traceparent,
                                             parse_traceparent)
from deeplearning4j_tpu.obs.slo import SloBurn
from deeplearning4j_tpu.obs.trace import Tracer

TRACE32 = "4bf92f3577b34da6a3ce929d0e0e4736"
SPAN16 = "00f067aa0ba902b7"


@pytest.fixture(autouse=True)
def _clean_globals():
    """Every test starts and ends with tracing/recording uninstalled."""
    reqtrace_mod.uninstall()
    flight_mod.uninstall()
    yield
    reqtrace_mod.uninstall()
    flight_mod.uninstall()


# ------------------------------------------------------------- traceparent
class TestTraceparent:
    def test_roundtrip(self):
        hdr = format_traceparent(TRACE32, SPAN16)
        assert hdr == f"00-{TRACE32}-{SPAN16}-01"
        assert parse_traceparent(hdr) == (TRACE32, SPAN16)

    def test_case_and_whitespace_tolerated(self):
        assert parse_traceparent(
            f"  00-{TRACE32.upper()}-{SPAN16.upper()}-01 ") \
            == (TRACE32, SPAN16)

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", f"00-{TRACE32}-{SPAN16}",        # truncated
        f"ff-{TRACE32}-{SPAN16}-01",                          # forbidden ver
        f"00-{'0' * 32}-{SPAN16}-01",                         # zero trace
        f"00-{TRACE32}-{'0' * 16}-01",                        # zero span
        f"00-{TRACE32[:-1]}x-{SPAN16}-01",                    # non-hex
    ])
    def test_malformed_is_none_never_raises(self, bad):
        assert parse_traceparent(bad) is None

    def test_begin_propagates_upstream_trace(self):
        rt = RequestTracer()
        ctx = rt.begin("predict",
                       traceparent=format_traceparent(TRACE32, SPAN16))
        assert ctx.trace_id == TRACE32 and ctx.parent_id == SPAN16
        # outgoing header advertises OUR span as the new parent
        tid, span = parse_traceparent(ctx.traceparent())
        assert tid == TRACE32 and span == ctx.span_id != SPAN16

    def test_begin_fresh_trace_on_malformed(self):
        rt = RequestTracer()
        ctx = rt.begin("predict", traceparent="not-a-header")
        assert len(ctx.trace_id) == 32 and ctx.parent_id is None


# ----------------------------------------------------------- span unwind
class TestSpanUnwind:
    def test_outer_exit_unwinds_past_orphaned_inner(self):
        """Regression: exiting an outer span while an inner span is still
        on the stack (exception between enters) must restore the outer's
        recorded depth — later spans must not inherit a stale parent."""
        tr = Tracer()
        a = tr.span("a")
        a.__enter__()
        b = tr.span("b")
        b.__enter__()
        a.__exit__(None, None, None)  # unwinds "b" too
        with tr.span("c"):
            pass
        by_name = {e["name"]: e for e in tr.events if e.get("ph") == "X"}
        assert "parent" not in by_name["c"].get("args", {})
        assert tr._stack() == []  # the orphan was cleared, not skipped

    def test_async_events_stitch_by_id_across_tids(self):
        tr = Tracer()
        t0 = time.perf_counter_ns()
        tr.async_event("stage1", "trace-x", t0, t0 + 1000)
        tr.async_event("stage2", "trace-x", t0 + 1000, t0 + 2000, tid=999)
        evs = [e for e in tr.events if e.get("id") == "trace-x"]
        assert [e["ph"] for e in evs] == ["b", "e", "b", "e"]
        assert {e["cat"] for e in evs} == {"request"}
        assert evs[2]["tid"] == evs[3]["tid"] == 999
        # the foreign tid must not steal a thread_name metadata record
        assert not any(e.get("ph") == "M" and e.get("tid") == 999
                       for e in tr.events)


# -------------------------------------------------------- request context
class TestRequestContext:
    def _rt(self):
        return RequestTracer(tracer=Tracer(), flight=FlightRecorder())

    def test_stages_accumulate_into_record(self):
        rt = self._rt()
        ctx = rt.begin("generate", model="lm", tenant="gold",
                       slo_class="gold")
        with ctx.stage("admit"):
            pass
        t = time.perf_counter_ns()
        ctx.add_stage("prefill_chunk", t, t + 2_000_000, offset=0)
        ctx.decode_begin()
        ctx.decode_tick(t, t + 1_000_000)
        ctx.decode_tick(t + 1_000_000, t + 3_000_000)
        ctx.finish_work(tokens=7)
        rec = ctx.finish()
        assert rec["status"] == "ok" and rec["error"] is None
        assert rec["model"] == "lm" and rec["slo_class"] == "gold"
        assert rec["ticks"] == 2
        assert rec["decode_ms"] == pytest.approx(3.0)
        assert [s["name"] for s in rec["stages"]] \
            == ["admit", "prefill_chunk", "decode"]
        assert rec["meta"]["tokens"] == 7
        # the record landed in the flight ring and the umbrella event in
        # the tracer, keyed by the trace id
        assert rt.flight.requests()[-1] is rec
        umb = [e for e in rt.tracer.events
               if e.get("id") == ctx.trace_id and e["name"] == "request"]
        assert len(umb) == 2

    def test_finish_is_idempotent(self):
        rt = self._rt()
        ctx = rt.begin("predict")
        assert ctx.finish() is not None
        assert ctx.finish() is None
        assert len(rt.flight.requests()) == 1

    def test_error_records_shed_stage_from_calling_thread(self):
        rt = self._rt()
        ctx = rt.begin("generate")
        ctx.decode_begin()
        ctx.decode_tick(time.perf_counter_ns(),
                        time.perf_counter_ns() + 1000)
        out = []
        t = threading.Thread(  # the "watchdog" sheds on the worker's behalf
            target=lambda: (ctx.finish_work(error="worker_stall"),
                            out.append(threading.get_ident())))
        t.start()
        t.join()
        rec = ctx.finish()
        assert rec["status"] == "error" and rec["error"] == "worker_stall"
        stages = {s["name"]: s for s in rec["stages"]}
        assert stages["shed"]["args"]["cause"] == "worker_stall"
        assert stages["shed"]["tid"] == out[0] != stages["decode"]["tid"]

    def test_stage_cap_counts_drops(self):
        rt = RequestTracer(max_stages=2)
        ctx = rt.begin("generate")
        t = time.perf_counter_ns()
        for i in range(5):
            ctx.add_stage("s", t, t + 1)
        rec = ctx.finish()
        assert len(rec["stages"]) == 2 and rec["stages_dropped"] == 3


# -------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_is_bounded(self):
        fr = FlightRecorder(capacity=3, event_capacity=2)
        for i in range(10):
            fr.record_request({"request_id": i})
            fr.record_event("health", f"e{i}")
        assert [r["request_id"] for r in fr.requests()] == [7, 8, 9]
        assert [e["name"] for e in fr.events()] == ["e8", "e9"]

    def test_dump_rotates_slots_atomically(self, tmp_path):
        fr = FlightRecorder(out_dir=str(tmp_path), max_dumps=2)
        fr.record_request({"request_id": "r1"})
        paths = [fr.dump(f"reason{i}") for i in range(3)]
        assert paths[0].endswith("flight_00.json")
        assert paths[1].endswith("flight_01.json")
        assert paths[2] == paths[0]  # slot reuse, bounded disk
        assert sorted(os.listdir(tmp_path)) \
            == ["flight_00.json", "flight_01.json"]
        body = json.loads(open(paths[0]).read())
        assert body["reason"] == "reason2" and body["seq"] == 3
        assert body["requests"][0]["request_id"] == "r1"
        # every dump trigger is itself an event (visible even live-only)
        assert [e["name"] for e in fr.events()
                if e["kind"] == "dump"] == ["reason0", "reason1", "reason2"]

    def test_live_only_dump_returns_none(self):
        fr = FlightRecorder()
        assert fr.dump("oops") is None
        assert fr.events()[-1]["kind"] == "dump"


# ------------------------------------------------- exemplars + promcheck
class TestExemplarsAndPromcheck:
    def test_exemplar_rides_into_openmetrics(self):
        m = MetricsRegistry()
        h = m.histogram("rpc_seconds", help="x")
        h.observe(0.004, trace_id=TRACE32)
        h.observe(0.004)  # untraced observe must not clobber the exemplar
        m.counter("rpc_total", help="x").inc()
        om = m.to_openmetrics()
        assert f'# {{trace_id="{TRACE32}"}} 0.004' in om
        assert om.rstrip("\n").endswith("# EOF")
        assert check_text(om) == [], check_text(om)
        # 0.0.4 text stays exemplar-free and valid too
        prom = m.to_prometheus()
        assert "# {" not in prom
        assert check_text(prom, openmetrics=False) == []

    @pytest.mark.parametrize("text,needle", [
        # exemplar outside OpenMetrics
        ("# TYPE h histogram\n"
         'h_bucket{le="+Inf"} 1 # {trace_id="a"} 1\nh_count 1\nh_sum 1\n',
         "not OpenMetrics"),
        # exemplar on a gauge sample
        ("# TYPE g gauge\ng 1 # {trace_id=\"a\"} 1\n# EOF\n",
         "only _bucket/_total"),
        # non-cumulative buckets
        ("# TYPE h histogram\n"
         'h_bucket{le="0.1"} 5\nh_bucket{le="1"} 3\n'
         'h_bucket{le="+Inf"} 5\nh_count 5\nh_sum 1\n# EOF\n',
         "not cumulative"),
        # missing +Inf bucket
        ("# TYPE h histogram\n"
         'h_bucket{le="0.1"} 5\nh_count 5\nh_sum 1\n# EOF\n',
         "+Inf"),
        # family reopened later
        ("# TYPE a counter\na_total 1\n# TYPE b counter\nb_total 1\n"
         "a_total 2\n# EOF\n", "twice"),
        # content after the terminator
        ("# TYPE g gauge\ng 1\n# EOF\ng 2\n", "after # EOF"),
        # blank line inside OpenMetrics framing
        ("# TYPE g gauge\n\ng 1\n# EOF\n", "blank line"),
        # broken escape in a label value
        ('# TYPE g gauge\ng{x="a\\q"} 1\n# EOF\n', "invalid escape"),
        # missing # EOF entirely (forced OM)
        ("# TYPE g gauge\ng 1\n", "missing terminating"),
    ])
    def test_invalid_expositions_rejected(self, text, needle):
        # force OM only for the missing-EOF case; others auto-detect
        om = True if needle == "missing terminating" else None
        errors = check_text(text, openmetrics=om)
        assert any(needle in e for e in errors), errors


# --------------------------------------------------------------- slo burn
class TestSloBurn:
    def test_burn_is_bad_fraction_over_budget(self):
        now = [1000.0]
        burn = SloBurn(windows=(60.0, 600.0), clock=lambda: now[0])
        for _ in range(99):
            burn.record("m", "standard", good=True)
        burn.record("m", "standard", good=False)
        snap = burn.snapshot()["m"]["standard"]
        # 1% bad on a 1% budget (target 0.99) burns at exactly 1.0
        assert snap["good"] == 99 and snap["bad"] == 1
        assert snap["burn"]["1m"] == pytest.approx(1.0)
        assert snap["burn"]["10m"] == pytest.approx(1.0)

    def test_gold_burns_faster_than_standard(self):
        now = [1000.0]
        burn = SloBurn(clock=lambda: now[0])
        for cls in ("gold", "standard"):
            for i in range(10):
                burn.record("m", cls, good=i > 0)  # 10% bad
        snap = burn.snapshot()["m"]
        assert snap["gold"]["burn"]["1m"] == pytest.approx(100.0)
        assert snap["standard"]["burn"]["1m"] == pytest.approx(10.0)

    def test_window_forgets_old_failures(self):
        now = [1000.0]
        burn = SloBurn(windows=(60.0, 600.0), clock=lambda: now[0])
        burn.record("m", "standard", good=False)
        now[0] += 120  # outside 1m, inside 10m
        burn.record("m", "standard", good=True)
        snap = burn.snapshot()["m"]["standard"]
        assert snap["burn"]["1m"] == 0.0
        assert snap["burn"]["10m"] > 0.0
        assert snap["good"] == 1 and snap["bad"] == 1  # cumulative stay

    def test_metrics_emitted(self):
        m = MetricsRegistry()
        burn = SloBurn(metrics=m)
        burn.record("lm", "gold", good=False)
        text = m.to_prometheus()
        assert ('fleet_slo_requests_total{model="lm",outcome="bad",'
                'slo_class="gold"} 1') in text
        assert 'fleet_slo_burn_rate{model="lm"' in text


# ---------------------------------------------- zero overhead when off
class TestZeroOverheadWhenDisabled:
    def test_no_reqtrace_calls_on_serving_hot_paths(self, monkeypatch):
        """With no request tracer installed, the serving stack must never
        touch RequestContext/RequestTracer/FlightRecorder — booby-trap
        every entry point and run real predict + generate traffic."""
        from deeplearning4j_tpu.models import CausalLM
        from deeplearning4j_tpu.nn.layers import Dense, Output
        from deeplearning4j_tpu.nn.model import NetConfig, Sequential
        from deeplearning4j_tpu.serve import ContinuousBatcher, ServeEngine

        def boom(*a, **k):
            raise AssertionError("request tracing touched while disabled")

        for meth in ("add_stage", "stage", "decode_begin", "decode_tick",
                     "finish_work", "finish", "annotate"):
            monkeypatch.setattr(reqtrace_mod.RequestContext, meth, boom)
        monkeypatch.setattr(reqtrace_mod.RequestTracer, "begin", boom)
        monkeypatch.setattr(flight_mod.FlightRecorder, "record_request",
                            boom)
        monkeypatch.setattr(flight_mod.FlightRecorder, "record_event", boom)
        assert reqtrace_mod.ACTIVE is None and flight_mod.ACTIVE is None

        dense = Sequential(
            NetConfig(seed=0),
            [Dense(n_out=6, activation="tanh"),
             Output(n_out=3, loss="mcxent", activation="softmax")], (4,))
        dense.init()
        eng = ServeEngine(dense, batch_buckets=(1, 2), max_wait_ms=1.0)
        try:
            y = eng.predict(np.zeros((4,), np.float32))
            assert np.asarray(y).shape[-1] == 3
        finally:
            eng.shutdown(drain=True)

        lm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                      num_heads=4, vocab=50).build()
        lm.init()
        cb = ContinuousBatcher(lm, slots=2, capacity=8, seed=0)
        try:
            toks = cb.generate(np.arange(4, dtype=np.int32), 4,
                               temperature=0.0)
            assert len(toks) == 4
        finally:
            cb.shutdown()


# --------------------------------------------------------- end to end
class _Client:
    def __init__(self, port):
        self.port = port

    def post(self, path, body, headers=None, timeout=60):
        hdrs = {"Content-Type": "application/json", **(headers or {})}
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}",
            data=json.dumps(body).encode(), headers=hdrs)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read()), dict(r.headers)

    def get(self, path, headers=None, timeout=30):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", headers=headers or {})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read().decode(), dict(r.headers)


class TestFleetTracingEndToEnd:
    def _dense(self, seed=0):
        from deeplearning4j_tpu.nn.layers import Dense, Output
        from deeplearning4j_tpu.nn.model import NetConfig, Sequential

        m = Sequential(
            NetConfig(seed=seed),
            [Dense(n_out=6, activation="tanh"),
             Output(n_out=3, loss="mcxent", activation="softmax")], (4,))
        m.init()
        return m

    def test_concurrent_traffic_scraped_midflight(self):
        """Concurrent traced predict traffic + a mid-flight OpenMetrics
        scrape: the exposition validates, carries trace_id exemplars, and
        burn accounting shows up on /v1/fleet."""
        from deeplearning4j_tpu.fleet import FleetRegistry, FleetServer

        fleet = FleetRegistry()
        fleet.add("d", self._dense(), engine_opts={"batch_buckets": (1, 2)})
        rt = reqtrace_mod.install(
            RequestTracer(tracer=Tracer(), flight=flight_mod.install(
                FlightRecorder())))
        srv = FleetServer(fleet, port=0).start()
        cl = _Client(srv.port)
        try:
            x = [[0.1, -0.2, 0.3, -0.4]]
            upstream = format_traceparent(TRACE32, SPAN16)

            def one(i):
                hdrs = {"traceparent": upstream} if i == 0 else {}
                return cl.post("/v1/models/d/predict", {"ndarray": x},
                               headers=hdrs)

            results = [one(0), one(1)]  # warm round: exemplars exist
            with ThreadPoolExecutor(max_workers=4) as ex:
                futs = [ex.submit(one, i) for i in range(2, 12)]
                scrape, hdrs = cl.get(
                    "/metrics",
                    headers={"Accept": "application/openmetrics-text"})
                results += [f.result() for f in futs]

            # every response echoes its request's trace context
            for _, h in results:
                assert parse_traceparent(h["traceparent"]) is not None
                assert h["X-Request-Id"]
            assert parse_traceparent(results[0][1]["traceparent"])[0] \
                == TRACE32  # upstream trace id propagated through

            # mid-flight OpenMetrics scrape: negotiated, valid, exemplars
            assert hdrs["Content-Type"].startswith(
                "application/openmetrics-text")
            assert check_text(scrape) == [], check_text(scrape)[:5]
            assert '# {trace_id="' in scrape

            # a final scrape definitely contains the upstream exemplar id
            final, _ = cl.get(
                "/metrics",
                headers={"Accept": "application/openmetrics-text"})
            assert check_text(final) == []

            # debug endpoints expose the ring live
            dbg, _ = cl.get("/v1/debug/requests")
            recs = json.loads(dbg)["requests"]
            assert len(recs) >= 12
            stages = {s["name"] for r in recs for s in r["stages"]}
            assert {"admit", "queue", "device", "flush"} <= stages
            assert all(r["status"] == "ok" for r in recs)
            fl, _ = cl.get("/v1/debug/flight")
            assert json.loads(fl)["requests"]

            # SLO burn accounting on the fleet status surface
            slo = json.loads(cl.get("/v1/fleet")[0])["slo"]
            assert slo["d"]["standard"]["good"] >= 12
            assert slo["d"]["standard"]["burn"]["1m"] == 0.0
        finally:
            srv.stop()
            assert rt is reqtrace_mod.uninstall()

    def test_watchdog_shed_stitches_three_threads(self):
        """Phase-C shape: a hung decode tick under a short watchdog. The
        faulted generation's trace must cross >= 3 distinct threads (HTTP
        handler, batcher worker, watchdog) stitched by one trace_id, and
        its RequestRecord must land in the flight ring with the shed."""
        from deeplearning4j_tpu.chaos import FaultPlane, install, uninstall
        from deeplearning4j_tpu.fleet import FleetRegistry, FleetServer
        from deeplearning4j_tpu.models import CausalLM

        lm = CausalLM(seed=0, input_shape=(16,), num_layers=2, d_model=32,
                      num_heads=4, vocab=50).build()
        lm.init()
        # deadline comfortably above a CPU compile pause (3.8 s since PR 46:
        # the decode step holds the paged-attention kernel's interpreter-mode
        # lowering, and more when the whole suite loads the machine), far
        # below the injected hang — the warm pass must not trip a false stall
        fleet = FleetRegistry(watchdog_s=8.0)
        fleet.add("g", lm, gen_opts={"slots": 2, "capacity": 24, "seed": 0})
        tracer = Tracer()
        reqtrace_mod.install(RequestTracer(
            tracer=tracer, flight=flight_mod.install(FlightRecorder())))
        srv = FleetServer(fleet, port=0).start()
        cl = _Client(srv.port)
        fp = install(FaultPlane(seed=0))
        try:
            body = {"prompt": [3, 1, 4], "max_new_tokens": 6,
                    "temperature": 0.0, "stream": False}
            cl.post("/v1/models/g/generate", body)  # warm, fault-free
            fp.inject_spec("serve.decode_step:hang:hang_s=20,times=1")
            with pytest.raises(urllib.error.HTTPError) as ei:
                cl.post("/v1/models/g/generate", body)
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["cause"] == "worker_stall"
            trace_id = parse_traceparent(
                ei.value.headers["traceparent"])[0]

            # the faulted request's record is in the flight ring with the
            # full admit -> queue -> prefill -> decode -> shed shape (the
            # handler files it AFTER the reply is written: poll briefly)
            deadline = time.monotonic() + 5.0
            while True:
                rec = [r for r in flight_mod.ACTIVE.requests()
                       if r["trace_id"] == trace_id]
                if rec or time.monotonic() > deadline:
                    break
                time.sleep(0.02)
            assert len(rec) == 1
            rec = rec[0]
            assert rec["status"] == "error" \
                and rec["error"] == "worker_stall"
            names = [s["name"] for s in rec["stages"]]
            for want in ("admit", "queue", "prefill_chunk", "decode",
                         "shed"):
                assert want in names, (want, names)

            # one trace id, >= 3 distinct threads in the stitched flow
            tids = {e["tid"] for e in tracer.events
                    if e.get("id") == trace_id}
            assert len(tids) >= 3, tids
            # the watchdog restart landed in the event ring too
            kinds = {e["kind"] for e in flight_mod.ACTIVE.events()}
            assert "watchdog" in kinds
        finally:
            uninstall()  # release the parked hang before joining workers
            srv.stop()
            reqtrace_mod.uninstall()
