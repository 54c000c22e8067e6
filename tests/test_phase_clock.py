"""The generation worker's clock (ISSUE 40): ``obs/trace.py:PhaseClock`` at the
sites the worker already traced by, on with or without a profiler session.

- on an injected clock: the phases add up to the elapsed time to the
  nanosecond, nested phases are exclusive, ``gen.wait`` takes the idle time, a
  restarted worker's clock counts no nanosecond twice; one long tick in twenty
  is one stall under the phase it fell in, and a chunk-bearing tick, slow
  ticks throughout, the gap across an idle wait and the gap after the last
  slot retired are none;
- on the tiny served model: an injected hang is one stall under the phase it
  fell in, in the counters always and in the flight recorder where one is
  installed (with none, nothing of ``obs/flight.py`` is called); every
  streamed token's write lag is observed once; the phase counters hold the
  worker's wall time, across a ``restart_worker`` too; a worker that stands
  still for a second gets every thread's stack written while it stands.

Every threshold is on the injected clock. The tests of the served model assert
what was counted (which stall, under which phase, once), never how fast.
"""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.chaos import faults
from deeplearning4j_tpu.obs import flight as obs_flight
from deeplearning4j_tpu.obs import trace as T
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.trace import PhaseClock


class FakeNow:
    """``perf_counter_ns`` by hand."""

    def __init__(self):
        self.t = 5_000_000_000

    def __call__(self):
        return self.t

    def ms(self, ms):
        self.t += int(round(ms * 1e6))


def _clock(**kw):
    reg, now = MetricsRegistry(), FakeNow()
    clock = PhaseClock(reg, {"model": "m"}, now=now, **kw)
    return clock, reg, now


def _turn(clock, now, readback=14.0, chunk=0.0, left=1):
    """One loop iteration as the worker makes it; 20 ms with the defaults."""
    with clock.span(T.GEN_ADMIT):
        now.ms(0.2)
    with clock.span(T.GEN_TURN):
        now.ms(0.3)
        if chunk:
            with clock.span(T.GEN_PREFILL_CHUNK):
                now.ms(chunk)
        with clock.span(T.GEN_TICK):
            with clock.span(T.GEN_TICK_PREPARE):
                now.ms(0.6)
                with clock.span(T.GEN_KV_RELEASE):
                    now.ms(0.4)
            with clock.span(T.GEN_TICK_DISPATCH):
                now.ms(3.0)
            with clock.span(T.GEN_TICK_READBACK):
                now.ms(readback)
            with clock.span(T.GEN_TICK_PUBLISH):
                now.ms(0.5)
                clock.tick()
                now.ms(0.5)
                clock.decoding(left)
        now.ms(0.5)
    clock.turn_end()


def _wait(clock, now, ms):
    with clock.span(T.GEN_ADMIT):
        now.ms(0.1)
    clock.idle()
    now.ms(ms)


def _value(reg, name, **labels):
    fam = reg.snapshot().get(name, {"series": []})
    return sum(s.get("value", s.get("count", 0)) for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _by_phase(reg, name):
    return {s["labels"]["phase"]: s["value"]
            for s in reg.snapshot()[name]["series"]}


# ------------------------------------------------------ (a) closed accounting
def test_phases_add_up_to_the_elapsed_time_to_the_nanosecond():
    clock, reg, now = _clock()
    rng = np.random.RandomState(0)
    for i in range(50):
        now.t += int(rng.randint(1, 999))       # odd nanoseconds everywhere
        if i % 7 == 3:
            _wait(clock, now, 50.0)
        _turn(clock, now, readback=float(rng.randint(1, 40)),
              chunk=float(rng.randint(0, 3)))
        totals = clock.totals()
        assert sum(totals.values()) == now.t - clock.t_start
    assert set(totals) == set(T.WORKER_PHASES)
    clock.close()
    assert clock.t_end == now.t
    # ... and the counters, flushed, hold the same seconds
    flushed = _value(reg, "serve_gen_phase_seconds_total")
    assert flushed == pytest.approx((now.t - clock.t_start) * 1e-9, rel=1e-12)
    assert _value(reg, "serve_gen_ticks_total") == 50


def test_nested_phases_are_exclusive_and_the_wait_takes_the_idle_time():
    clock, reg, now = _clock()
    _turn(clock, now)
    _wait(clock, now, 50.0)
    _turn(clock, now, chunk=2.0)
    ms = {k: v / 1e6 for k, v in clock.totals().items()}
    assert ms[T.GEN_TICK] == 0                  # all of it is its children's
    assert ms[T.GEN_TICK_PREPARE] == pytest.approx(1.2)     # less kv_release
    assert ms[T.GEN_KV_RELEASE] == pytest.approx(0.8)
    assert ms[T.GEN_TICK_DISPATCH] == pytest.approx(6.0)
    assert ms[T.GEN_TICK_READBACK] == pytest.approx(28.0)
    assert ms[T.GEN_TICK_PUBLISH] == pytest.approx(2.0)
    assert ms[T.GEN_PREFILL_CHUNK] == pytest.approx(2.0)
    assert ms[T.GEN_TURN] == pytest.approx(1.6)             # outside children
    assert ms[T.GEN_ADMIT] == pytest.approx(0.5)
    assert ms[T.GEN_WAIT] == pytest.approx(50.0)
    assert ms[T.GEN_FIRST_TOKEN] == 0
    # every phase has its series from the start, zero or not
    fam = reg.snapshot()["serve_gen_phase_seconds_total"]["series"]
    assert {s["labels"]["phase"] for s in fam} == set(T.WORKER_PHASES)
    assert all(s["labels"]["model"] == "m" for s in fam)
    # the worker's phases are the spans it opens and the wait, one table
    assert set(T.WORKER_PHASES) - {T.GEN_WAIT, T.GEN_KV_RELEASE} \
        == set(T.SPAN_NAMES) - {T.HTTP_STREAM_WRITE, T.GC_PAUSE}


def test_a_phase_keeps_its_last_stamps_and_passes_metadata_on():
    clock, _, now = _clock()
    with clock.span(T.GEN_TICK) as tick:
        now.ms(1.0)
        tick.set_metadata(active=3)             # the annotation takes it
        with clock.span(T.GEN_TICK_DISPATCH) as d:
            now.ms(2.0)
        with clock.span(T.GEN_TICK_READBACK) as r:
            now.ms(5.0)
    assert (r.t1 - d.t0) == 7_000_000 and tick.t1 - tick.t0 == 8_000_000
    assert clock.span(T.GEN_TICK) is tick       # made once


def test_an_exception_through_open_phases_leaves_the_clock_in_the_wait():
    clock, _, now = _clock()
    with pytest.raises(RuntimeError):
        with clock.span(T.GEN_TURN):
            with clock.span(T.GEN_TICK):
                now.ms(1.0)
                raise RuntimeError("a dying tick")
    now.ms(3.0)
    assert clock.totals()[T.GEN_WAIT] == 3_000_000


def test_a_restarted_workers_clock_counts_no_nanosecond_twice():
    """The new clock starts at the stamp up to which the old one had charged
    the counters; what the old worker had not charged yet (it hangs in a
    readback) goes to the phase it was last seen in, once; and whatever the
    staled worker still stamps is charged nowhere."""
    old, reg, now = _clock()
    old.bind()
    _turn(old, now)
    _turn(old, now)                             # flushed up to here: 40 ms
    charged_to = now.t
    hung = old.span(T.GEN_TURN).__enter__(), old.span(T.GEN_TICK).__enter__(), \
        old.span(T.GEN_TICK_READBACK).__enter__()
    now.ms(700.0)                               # ... and hangs in the readback
    new = PhaseClock(reg, {"model": "m"}, now=now, after=old)
    assert new.t_start == charged_to
    now.ms(1.0)
    new.bind()                                  # on the new worker's thread
    _turn(new, now)
    for phase in reversed(hung):                # the staled worker wakes,
        now.ms(5.0)                             # unwinds and exits
        phase.__exit__(None, None, None)
    old.turn_end()
    old.close()
    _wait(new, now, 30.0)
    new.close()
    assert _value(reg, "serve_gen_phase_seconds_total") == pytest.approx(
        (now.t - old.t_start) * 1e-9, rel=1e-12)
    by_phase = _by_phase(reg, "serve_gen_phase_seconds_total")
    assert by_phase[T.GEN_TICK_READBACK] == pytest.approx(
        (2 * 14.0 + 701.0 + 14.0) * 1e-3)
    assert _value(reg, "serve_gen_ticks_total") == 3


def test_a_tick_published_and_not_yet_counted_comes_along_with_a_restart():
    old, reg, now = _clock()
    _turn(old, now)
    with old.span(T.GEN_TURN), old.span(T.GEN_TICK), \
            old.span(T.GEN_TICK_PUBLISH):
        old.tick()                              # pushed; the back edge not reached
        new = PhaseClock(reg, {"model": "m"}, now=now, after=old)
    old.turn_end()                              # retired: charges nothing
    assert _value(reg, "serve_gen_ticks_total") == 1
    new.bind()
    _turn(new, now)
    assert _value(reg, "serve_gen_ticks_total") == 3
    new.close()


# ---------------------------------------------------------------- (b) stalls
def test_one_long_tick_in_twenty_is_one_stall_under_its_phase():
    clock, reg, now = _clock()
    for i in range(20):
        _turn(clock, now, readback=514.0 if i == 12 else 14.0)
        assert (clock.stall is not None) == (i == 12)
        if i == 12:
            stall = dict(clock.stall)
    assert _value(reg, "serve_gen_stalls_total") == 1
    assert _value(reg, "serve_gen_stall_seconds") == 1       # the histogram
    by_phase = _by_phase(reg, "serve_gen_stall_seconds_total")
    assert by_phase[T.GEN_TICK_READBACK] == pytest.approx(0.514)
    assert sum(by_phase.values()) == pytest.approx(0.520)    # the whole gap
    assert stall["gap_s"] == pytest.approx(0.520)
    assert stall["phase"] == T.GEN_TICK_READBACK
    assert sum(stall["phase_s"].values()) == pytest.approx(stall["gap_s"])
    assert stall["mean_gap_s"] == pytest.approx(0.020)
    assert stall["runqueue_s"] is None          # never bound to a thread
    assert stall["gc_s"] == 0.0
    assert stall["thread_cpu_s"] >= 0.0 and stall["process_cpu_s"] >= 0.0
    # the ring: the twelve turns before it, oldest first, each 20 ms
    assert len(stall["turns"]) == 12
    assert all(sum(t["ms"].values()) == pytest.approx(20.0)
               for t in stall["turns"][1:])
    ends = [t["end_ns"] for t in stall["turns"]]
    assert ends == sorted(ends)
    h = reg.snapshot()["serve_gen_stall_seconds"]["series"][0]
    assert [b for b, _ in h["buckets"]] == [0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                                            10.0, "+Inf"]
    assert h["max"] == pytest.approx(0.520)


def test_the_ring_keeps_the_last_thirty_two_turns():
    clock, _, now = _clock()
    for i in range(50):
        _turn(clock, now, readback=514.0 if i == 49 else 14.0)
    turns = clock.stall["turns"]
    assert len(turns) == T.STALL_TURNS == 32
    assert all(sum(t["ms"].values()) == pytest.approx(20.0) for t in turns)


def _a_tick_at_1_6_x_the_mean(clock, now):
    for i in range(40):                         # 32 ms where the rest take 20
        _turn(clock, now, chunk=12.0 if i % 3 == 0 else 0.0)


def _ticks_of_45_ms_throughout(clock, now):
    for i in range(40):                         # ... and 72 with a chunk
        _turn(clock, now, readback=39.0, chunk=27.0 if i % 4 == 0 else 0.0)


def _the_gap_across_an_idle_wait(clock, now):
    for i in range(30):
        if i == 20:
            _wait(clock, now, 5000.0)
        _turn(clock, now)


def _the_gap_after_the_last_slot_retired(clock, now):
    for i in range(30):
        _turn(clock, now, left=0 if i == 20 else 1)
        if i == 20:                             # prefill-only turns: 2 s
            for _ in range(40):
                with clock.span(T.GEN_TURN):
                    with clock.span(T.GEN_PREFILL_CHUNK):
                        now.ms(50.0)
                clock.turn_end()


def _a_first_gap_under_the_floor(clock, now):
    _turn(clock, now)
    _turn(clock, now, readback=90.0)            # no mean yet: the floor alone


@pytest.mark.parametrize("traffic", [
    _a_tick_at_1_6_x_the_mean, _ticks_of_45_ms_throughout,
    _the_gap_across_an_idle_wait, _the_gap_after_the_last_slot_retired,
    _a_first_gap_under_the_floor], ids=lambda f: f.__name__.strip("_"))
def test_no_stall(traffic):
    clock, reg, now = _clock()
    traffic(clock, now)
    assert clock.stall is None
    assert _value(reg, "serve_gen_stalls_total") == 0
    assert _value(reg, "serve_gen_stall_seconds_total") == 0
    assert _value(reg, "serve_gen_stall_seconds") == 0


def test_a_stall_enters_the_mean_at_the_limit_and_hides_nothing():
    clock, reg, now = _clock()
    for i in range(30):
        _turn(clock, now, readback=2000.0 if i in (10, 12) else 14.0)
    assert _value(reg, "serve_gen_stalls_total") == 2
    # a lasting change of pace is learned, not reported for ever
    for _ in range(200):
        _turn(clock, now, readback=144.0)       # 150 ms from here on
    stalls = _value(reg, "serve_gen_stalls_total")
    for _ in range(50):
        _turn(clock, now, readback=144.0)
    assert _value(reg, "serve_gen_stalls_total") == stalls < 60


def test_the_tick_histogram_has_buckets_a_tick_can_be_read_in():
    from deeplearning4j_tpu.serve.continuous import TICK_BUCKETS

    b = list(TICK_BUCKETS)
    assert b == sorted(set(b))
    fine = [x for x in b if 2e-3 <= x <= 0.25]
    assert all(hi / lo < 1.16 for lo, hi in zip(fine, fine[1:]))
    assert b[0] == 1e-4 and b[-1] == 60.0


# ----------------------------------------------- (c) on the tiny served model
def _lm():
    from deeplearning4j_tpu.models import CausalLM

    lm = CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=32,
                  num_heads=4, vocab=50).build()
    lm.init()
    return lm


@pytest.fixture
def batcher():
    from deeplearning4j_tpu.serve import ContinuousBatcher

    reg = MetricsRegistry()
    cb = ContinuousBatcher(_lm(), slots=2, capacity=64, seed=0,
                           prefill_chunk=8, metrics=reg)
    cb.generate(np.arange(1, 13, dtype=np.int32), 4, temperature=0.0)  # compiled
    yield cb, reg
    faults.uninstall()
    obs_flight.uninstall()
    cb.shutdown()


def _hang(cb, hang_s, new=24):
    """One request whose sixth tick hangs at the chaos seam
    (``serve/continuous.py:_tick``, before ``gen.tick`` opens: in
    ``gen.turn``)."""
    faults.install(faults.FaultPlane(seed=0)).inject_spec(
        f"serve.decode_step:hang:hang_s={hang_s},after=5,times=1")
    try:
        out = cb.generate(np.arange(1, 6, dtype=np.int32), new, temperature=0.0)
    finally:
        faults.uninstall()
    assert len(out) == new


def _the_hang(stalls, hang_s):
    """Of the stalls caught, the one that holds the hang. A loaded machine may
    add stalls of its own; the hang is counted once whatever else is."""
    held = [d for d in stalls if d["phase_s"].get(T.GEN_TURN, 0.0) >= hang_s]
    assert len(held) == 1, stalls
    return held[0]


def test_an_injected_hang_is_one_stall_under_its_phase_in_the_recorder(
        batcher, tmp_path):
    cb, reg = batcher
    rec = obs_flight.install(obs_flight.FlightRecorder())
    before = time.perf_counter_ns()
    _hang(cb, 0.3)
    events = [e for e in rec.events() if e["kind"] == "stall"]
    assert _value(reg, "serve_gen_stalls_total") == len(events)
    d = _the_hang([e["data"] for e in events], 0.3)
    ev = [e for e in events if e["data"] is d][0]
    assert ev["name"] == "gen" and ev["thread"].startswith(
        "serve-continuous-batcher")
    assert d["phase"] == T.GEN_TURN             # the seam lies before gen.tick
    assert d["gap_s"] >= 0.3
    assert sum(d["phase_s"].values()) == pytest.approx(d["gap_s"], abs=1e-9)
    assert d["thread_cpu_s"] >= 0.0 and d["process_cpu_s"] >= 0.0
    assert d["gc_s"] >= 0.0
    assert d["active_slots"] == 1 and d["queue_depth"] == 0 \
        and d["prefill_jobs"] == 0 and d["sheds"] == 0
    assert before <= d["perf_counter_ns"] <= time.perf_counter_ns()
    assert d["time_ns"] * 1e-9 == pytest.approx(ev["t_unix"], abs=60.0)
    assert 1 <= len(d["turns"]) <= T.STALL_TURNS
    assert set(d["turns"][-1]) == {"end_ns", "ms"}
    if os.path.exists(T.SCHEDSTAT):
        assert d["runqueue_s"] >= 0.0
        assert "serve_gen_stall_runqueue_seconds_total" in reg.snapshot()
    else:
        assert d["runqueue_s"] is None
        assert "serve_gen_stall_runqueue_seconds_total" not in reg.snapshot()
    json.dumps(rec.snapshot())                  # /v1/debug/flight serves it
    # the counters say the same
    by_phase = _by_phase(reg, "serve_gen_stall_seconds_total")
    assert by_phase[T.GEN_TURN] >= 0.3
    assert sum(by_phase.values()) == pytest.approx(
        sum(e["data"]["gap_s"] for e in events))
    h = reg.snapshot()["serve_gen_stall_seconds"]["series"][0]
    assert h["count"] == len(events) and h["max"] >= 0.3
    assert not list(tmp_path.iterdir())         # live-only: no watchdog


def test_with_no_recorder_the_stall_is_counted_and_flight_is_not_called(
        batcher, monkeypatch):
    cb, reg = batcher

    def boom(*a, **k):
        raise AssertionError("obs/flight.py called with no recorder installed")

    for meth in ("record_event", "watch_stacks", "unwatch_stacks",
                 "close_stacks", "note_stacks", "dump"):
        monkeypatch.setattr(obs_flight.FlightRecorder, meth, boom)
    assert obs_flight.ACTIVE is None
    _hang(cb, 0.3)
    assert cb.worker_alive()
    assert _value(reg, "serve_gen_stalls_total") >= 1
    assert _by_phase(reg, "serve_gen_stall_seconds_total")[T.GEN_TURN] >= 0.3
    h = reg.snapshot()["serve_gen_stall_seconds"]["series"][0]
    assert h["max"] >= 0.3


def test_a_worker_that_stands_still_a_second_gets_every_stack_written(
        batcher, tmp_path):
    cb, reg = batcher
    rec = obs_flight.install(obs_flight.FlightRecorder(out_dir=str(tmp_path)))
    _hang(cb, 2.0)
    text = (tmp_path / obs_flight.STACKS_FILE).read_text()
    assert "Timeout (0:00:01)!" in text
    # every thread, the worker parked in the fault plane among them
    assert text.count("Thread 0x") + text.count("Current thread 0x") >= 2
    assert "faults.py" in text and "_tick" in text
    assert "# stall of " in text                # the worker's note under it
    _the_hang([e["data"] for e in rec.events() if e["kind"] == "stall"], 2.0)
    cb.shutdown()                               # nothing decodes: disarmed
    assert rec._stacks_armed_ns is None


def test_under_a_profiler_session_a_stall_leaves_an_instant_on_the_workers_line(
        batcher, tmp_path):
    import jax
    from jax.profiler import ProfileData

    cb, reg = batcher
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1               # as the benchmark traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _hang(cb, 0.3)
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    lines = [[(e.name, dict(e.stats)) for e in line.events]
             for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines]
    with_stall = [ln for ln in lines if any(n == T.GEN_STALL for n, _ in ln)]
    assert len(with_stall) == 1
    assert any(n == T.GEN_TICK for n, _ in with_stall[0])   # the worker's
    stalls = [st for n, st in with_stall[0] if n == T.GEN_STALL]
    assert len(stalls) == _value(reg, "serve_gen_stalls_total")
    long = [st for st in stalls if float(st["gap_ms"]) >= 300.0
            and st["phase"] == T.GEN_TURN]
    assert len(long) == 1
    assert T.GEN_STALL not in T.SPAN_NAMES and T.GEN_WAIT not in T.SPAN_NAMES


def test_with_no_session_a_stall_opens_no_annotation_of_its_own(
        batcher, monkeypatch):
    cb, reg = batcher
    opened = []
    real = T._annotation()

    class Spy(real):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(T, "_ANNOTATION", Spy)
    _hang(cb, 0.3)
    assert _value(reg, "serve_gen_stalls_total") >= 1
    assert opened and T.GEN_STALL not in opened
    assert set(opened) <= set(T.WORKER_PHASES) - {T.GEN_WAIT}


def test_phase_seconds_hold_the_workers_wall_time_across_a_restart(batcher):
    cb, reg = batcher
    outer = time.perf_counter_ns()
    first = cb._clock
    reqs = [cb.submit(np.arange(1, 4 + i, dtype=np.int32), 6 + i,
                      temperature=0.0) for i in range(5)]
    for r in reqs:
        r.wait()
    assert cb.restart_worker("test")
    assert cb._clock is not first and cb._clock.t_start >= first.t_start
    assert len(cb.generate(np.arange(1, 5, dtype=np.int32), 5,
                           temperature=0.0)) == 5
    assert cb.shutdown()                        # the worker's last flush
    counted = _value(reg, "serve_gen_phase_seconds_total")
    # every nanosecond from the first worker's start to the last one's exit,
    # once: no tolerance but the floats'
    assert counted == pytest.approx(
        (cb._clock.t_end - first.t_start) * 1e-9, rel=1e-9)
    assert counted <= (time.perf_counter_ns() - first.t_start) * 1e-9
    assert first.t_start <= outer
    assert _by_phase(reg, "serve_gen_phase_seconds_total")[T.GEN_WAIT] > 0
    # the warm-up's ticks, the five requests' (35 tokens on two slots) and the
    # last one's, less at most the one whose tail the restart raced
    assert _value(reg, "serve_gen_ticks_total") >= 3 + 18 + 4 - 1


def test_the_tick_and_chunk_histograms_are_fed_from_the_clocks_stamps(batcher):
    cb, reg = batcher
    reqs = [cb.submit(np.arange(1, 4 + i, dtype=np.int32), 6 + i,
                      temperature=0.0) for i in range(5)]
    for r in reqs:
        r.wait()
    assert cb.shutdown()                        # the worker's last flush
    snap = reg.snapshot()
    ticks = _value(reg, "serve_gen_ticks_total")
    assert ticks >= 3 + 18
    # once a tick, once a chunk
    assert snap["serve_gen_decode_seconds"]["series"][0]["count"] == ticks
    assert snap["serve_gen_prefill_seconds"]["series"][0]["count"] \
        == _value(reg, "serve_prefill_chunks_total")
    by_phase = _by_phase(reg, "serve_gen_phase_seconds_total")
    # a step's own first dispatch stamp to its own readback's return (PR 41):
    # its dispatch whole, and since the next step is enqueued in between, no
    # instant of the worker's in more than two steps' intervals
    assert by_phase[T.GEN_TICK_DISPATCH] \
        <= snap["serve_gen_decode_seconds"]["series"][0]["sum"] \
        <= 2 * sum(by_phase.values())
    # a chunk's histogram holds the span whole, and nothing nests in it
    assert by_phase[T.GEN_PREFILL_CHUNK] == pytest.approx(
        snap["serve_gen_prefill_seconds"]["series"][0]["sum"])
    assert by_phase[T.GEN_KV_RELEASE] == 0      # no window group


def test_every_streamed_token_has_its_write_lag_observed_once():
    from deeplearning4j_tpu.serve.http import WRITE_LAG_BUCKETS, ModelServer

    srv = ModelServer(_lm(), port=0, input_dtype=np.int32, gen_slots=2,
                      gen_capacity=32, gen_prefill_chunk=8).start()
    try:
        counts = []
        for prompt, n in (([1, 2, 3, 4], 3), (list(range(1, 13)), 6)):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps({"prompt": prompt, "max_new_tokens": n,
                                 "temperature": 0.0}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                events = [json.loads(line[6:]) for line in r
                          if line.startswith(b"data: ")]
            assert events[-1]["done"] is True
            counts.append(sum("token" in e for e in events))
        assert counts == [3, 6]
        # a buffered reply writes no SSE event and observes nothing
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": [1, 2], "max_new_tokens": 2,
                             "stream": False}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert len(json.load(r)["tokens"]) == 2
    finally:
        srv.stop()                              # every handler has returned
    h = srv.metrics.snapshot()["serve_http_token_write_lag_seconds"]
    (series,) = h["series"]
    assert series["count"] == 9
    assert 0 < series["min"]
    assert [b for b, _ in series["buckets"]][:-1] == list(WRITE_LAG_BUCKETS)
    assert WRITE_LAG_BUCKETS[0] == 5e-5 and WRITE_LAG_BUCKETS[-1] == 5.0


def test_pushed_stamps_stand_beside_the_tokens(batcher):
    cb, _ = batcher
    t0 = time.perf_counter_ns()
    req = cb.submit(np.arange(1, 6, dtype=np.int32), 7, temperature=0.0)
    req.wait()
    assert len(req.pushed_ns) == len(req.out) == 7
    assert t0 <= req.pushed_ns[0] and req.pushed_ns == sorted(req.pushed_ns)
    assert req.pushed_ns[0] * 1e-9 == pytest.approx(req.first_t, abs=1e-9)
    assert req.pushed_ns[-1] <= time.perf_counter_ns()
