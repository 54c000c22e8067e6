"""The readers of the generation worker's clock (``turn_*_ms``, ``stall_*``)
and of the SSE writers' lag (``write_lag_*``), each against a pair of counter
snapshots made by hand: the families and labels the program exports
(``deeplearning4j_tpu/obs/trace.py:PhaseClock``, ``serve/http.py``)."""

import pytest

from harness import env, layer_metrics as lm

TURNS = ("turn_prepare_ms", "turn_dispatch_ms", "turn_readback_ms",
         "turn_publish_ms", "turn_admit_ms", "turn_prefill_ms", "turn_self_ms")
STALL_SHARES = ("stall_readback_share", "stall_offcpu_share",
                "stall_proc_cpu_share")
NEW = TURNS + ("turn_host_ms", "stall_count", "stall_share", "stall_max_ms") \
    + STALL_SHARES + ("write_lag_p50_ms", "write_lag_p99_ms")
SERVING = ["sc1b-longctx-decode", "sc1b-chat-burst", "olmoe-chat-decode",
           "glm47f-longchat-decode", "laguna-mixedctx-decode"]
# milliseconds a tick by phase, as a window of 100 ticks would read them
PHASE_MS = {"gen.wait": 0.01, "gen.admit": 0.2, "gen.turn": 0.8,
            "gen.prefill_chunk": 1.0, "gen.first_token": 0.0625,
            "gen.tick": 0.05, "gen.tick.prepare": 0.6, "gen.kv_release": 0.4,
            "gen.tick.dispatch": 3.0, "gen.tick.readback": 14.0,
            "gen.tick.publish": 0.95}
STALL_BOUNDS = [0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, "+Inf"]
LAG_BOUNDS = [5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2,
              5e-2, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, "+Inf"]


class Cell:
    name = "cell"

    def metrics(self, group):
        return []


def family(rows):
    return {"series": [{"labels": labels, "value": v} for labels, v in rows]}


def hist(bounds, values):
    """A histogram series as ``MetricsRegistry.snapshot()`` gives it."""
    rows = [[b, sum(1 for v in values
                    if b == "+Inf" or v <= b)] for b in bounds]
    return {"series": [{"labels": {}, "count": len(values),
                        "sum": sum(values), "buckets": rows,
                        "max": max(values) if values else None}]}


def snapshot(ticks, stalls=(), lags=()):
    """The program's counters after ``ticks`` ticks of ``PHASE_MS`` each and
    the ``stalls`` (whole gap, its readback part, the thread's and the
    process's CPU seconds of each)."""
    lbl = {"model": "m"}
    snap = {
        "serve_gen_phase_seconds_total": family(
            [({**lbl, "phase": p}, ticks * ms * 1e-3)
             for p, ms in PHASE_MS.items()]),
        "serve_gen_ticks_total": family([(lbl, ticks)]),
        "serve_gen_stalls_total": family([(lbl, len(stalls))]),
        "serve_gen_stall_seconds": hist(STALL_BOUNDS, [s[0] for s in stalls]),
        "serve_gen_stall_seconds_total": family(
            [({**lbl, "phase": "gen.tick.readback"}, sum(s[1] for s in stalls)),
             ({**lbl, "phase": "gen.turn"},
              sum(s[0] - s[1] for s in stalls))]),
        "serve_gen_stall_thread_cpu_seconds_total": family(
            [(lbl, sum(s[2] for s in stalls))]),
        "serve_gen_stall_process_cpu_seconds_total": family(
            [(lbl, sum(s[3] for s in stalls))]),
        "serve_http_token_write_lag_seconds": hist(LAG_BOUNDS, list(lags))}
    return snap


def run_over(start, end, window_s=45.0):
    return lm.Run(Cell(), {"platform": "cpu", "kind": "cpu", "count": 1},
                  counters_start=start, counters_end=end,
                  client={"window_s": window_s})


LAGS = [2e-4] * 100 + [7e-4] * 100 + [1.2e-3] * 100 + [0.04] * 6


def test_the_seven_phase_metrics_add_up_and_read_what_the_clock_kept():
    run = run_over(snapshot(10), snapshot(110))
    got = {n: lm.read(run, n) for n in TURNS + ("turn_host_ms",)}
    assert got["turn_prepare_ms"] == pytest.approx(1.0)     # kv_release inside
    assert got["turn_dispatch_ms"] == pytest.approx(3.0)
    assert got["turn_readback_ms"] == pytest.approx(14.0)
    assert got["turn_publish_ms"] == pytest.approx(0.95)
    assert got["turn_admit_ms"] == pytest.approx(0.2)
    assert got["turn_prefill_ms"] == pytest.approx(1.0625)  # with first_token
    assert got["turn_self_ms"] == pytest.approx(0.85)       # gen.turn + gen.tick
    assert got["turn_host_ms"] + got["turn_readback_ms"] \
        == pytest.approx(sum(got[n] for n in TURNS))
    # nothing of the worker's time but gen.wait is outside the seven
    assert sum(got[n] for n in TURNS) + PHASE_MS["gen.wait"] \
        == pytest.approx(sum(PHASE_MS.values()))


@pytest.mark.parametrize("before", [[], [(0.3, 0.3, 0.0, 0.0)]])
def test_a_window_without_a_stall_reads_every_stall_metric_as_zero(before):
    """The driver refuses a traced run whose line lacks a metric that lists
    the cell, so a quiet window says 0 (``stall_count`` 0 beside the shares
    says which zero it is), with or without a stall before the window."""
    run = run_over(snapshot(10, before), snapshot(110, before))
    for name in ("stall_count", "stall_share", "stall_max_ms") + STALL_SHARES:
        assert lm.read(run, name) == 0.0, name
    # ... and so does the line: read_all keeps what was read
    class Listed(Cell):
        def metrics(self, group):
            return [{"name": n, "unit": "x"} for n in NEW]
    run.cell = Listed()
    assert set(lm.read_all(run)) == set(NEW) \
        - {"write_lag_p50_ms", "write_lag_p99_ms"}    # nothing was streamed


def test_a_stall_is_counted_shared_out_and_its_size_read():
    before = [(0.3, 0.3, 0.0, 0.0)]                 # one before the window
    inside = before + [(1.8, 1.35, 0.09, 0.45), (0.2, 0.2, 0.01, 0.0)]
    run = run_over(snapshot(10, before), snapshot(110, inside))
    assert lm.read(run, "stall_count") == 2.0
    assert lm.read(run, "stall_share") == pytest.approx(100 * 2.0 / 45.0)
    assert lm.read(run, "stall_readback_share") == pytest.approx(100 * 1.55 / 2.0)
    assert lm.read(run, "stall_offcpu_share") == pytest.approx(100 * (1 - 0.1 / 2.0))
    assert lm.read(run, "stall_proc_cpu_share") == pytest.approx(100 * 0.45 / 2.0)
    assert lm.read(run, "stall_max_ms") == pytest.approx(1800.0)


def test_the_longest_stall_past_the_last_bound_is_cut_at_the_series_max():
    run = run_over(snapshot(10), snapshot(110, [(12.5, 12.5, 0.0, 0.0)]))
    assert lm.read(run, "stall_max_ms") == pytest.approx(12500.0)


def test_write_lag_quantiles():
    run = run_over(snapshot(10), snapshot(110, lags=LAGS))
    # 306 observations: 100 each at 0.2, 0.7 and 1.2 ms, six at 40 ms
    assert 0.5 < lm.read(run, "write_lag_p50_ms") <= 1.0
    assert 25.0 < lm.read(run, "write_lag_p99_ms") <= 40.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(name):
    """The parent commit under this PR's benchmark files: other counters, none
    of these (``stall_count`` says 0 stalls counted, as its file says)."""
    other = {"serve_gen_tokens_total": family([({}, 5)])}
    run = run_over(other, other)
    assert lm.read(run, name) == (0.0 if name == "stall_count" else None)


def test_the_manifest_lists_them_for_the_five_serving_cells_at_its_end():
    """All sixteen, in their order, for the five serving cells (a later PR
    appends its metrics behind them: where they stand is not asserted)."""
    per_layer = env.load_json(env.MANIFEST)["per_layer"]
    mine = [m for m in per_layer if m["name"] in NEW]
    assert tuple(m["name"] for m in mine) == NEW
    for m in mine:
        assert m["workloads"] == SERVING and m["moves"] == "itl_p50_ms"
        assert m["source"] == "program_counter"
        assert m["layer"].startswith(
            "HTTP front" if m["name"].startswith("write_lag")
            else "generation scheduler")
