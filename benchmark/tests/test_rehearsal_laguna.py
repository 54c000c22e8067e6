"""The Laguna configuration's files end to end on the CPU, through ``run.py``
as the driver calls it: ``tiny-laguna`` (layers F S S S F with 4 and 6 query
heads over 2 KV heads, window 32, a dense layer and four expert layers that
HOLD 32 of 64 experts, 4 a token, beside a shared one; bf16 parameters held
once; width 64) under ``tiny-mixedctx`` (contexts from under the window to
ten times it), from ``data/BENCHMARK.laguna.test.json``. Covers the reference
``references/laguna_block.py`` (the agreement check runs it), the readers the
configuration brought, and a program without the counters, as the parent of
PR 33 is, which reads as nothing."""

import os

from harness import env, layer_metrics

from test_rehearsal import last_line, run_cell

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "BENCHMARK.laguna.test.json")
CELL = "tiny-laguna-mixedctx"
NEW = ("swa_moe_decode_roofline", "kv_window_live_share",
       "kv_window_released_share", "moe_held_experts_touched_share",
       "moe_held_load_imbalance")


def test_untraced_run_judges_the_serving_metrics():
    out = last_line(run_cell(CELL, trace=0, manifest=MANIFEST))
    # the time to first token is judged here too: a turn extends its
    # session's cached prompt, and the prefix hit needs the window's tail
    assert set(out["metrics"]) == {"itl_p50_ms", "ttft_p50_ms", "setup_s"}
    assert out["failed"] == 0
    assert out["checks"] == {k: True for k in out["checks"]}, out["agreement"]
    assert out["agreement"]["control_flip_share"] >= 0.5   # it can fail


def test_traced_run_reports_both_groups_and_the_held_experts():
    out = last_line(run_cell(CELL, trace=1, manifest=MANIFEST))
    got = out["metrics"]
    # five layers x k and v x 2 heads x 16 x 2 bytes, both groups together
    assert got["kv_token_bytes"] == {"value": 5 * 2 * 2 * 16 * 2.0, "unit": "B"}
    # three of five layers are window layers and hold a ring, not a context
    assert 0 < got["kv_window_live_share"]["value"] < 60.0
    # contexts of several windows: most ring blocks are released behind one
    assert 0 < got["kv_window_released_share"]["value"] <= 100.0
    # 3 sessions x 4 of 64 experts a step, half of them held
    assert 0 < got["moe_held_experts_touched_share"]["value"] <= 100.0
    assert 1.0 <= got["moe_held_load_imbalance"]["value"] <= 32.0
    assert "swa_moe_decode_roofline" not in got   # a device number: no chip
    # a turn re-sends its session's history: most of it is a prefix hit,
    # which in this model needs the window group's tail behind it
    assert got["prefix_saved_share"]["value"] > 50.0
    assert "tick_mean_ms" in got             # the cell's other metrics too


def _run(snap, platform="tpu"):
    cell = env.Cell(MANIFEST, CELL)
    return layer_metrics.Run(cell, {"platform": platform, "kind": "TPU v5 lite"},
                             counters_start=snap, counters_end=snap,
                             trace={"planes": []})


def test_readers_find_nothing_in_a_program_without_the_counters():
    """What the parent commit gives the new readers: no group gauges, no
    window counters, no routing counters. Nothing is read, nothing raises."""
    snap = {"serve_gen_tokens_total": {"series": [{"labels": {}, "value": 9}]},
            "serve_kv_live_bytes": {"series": [{"labels": {}, "value": 7e6}]}}
    for name in NEW:
        assert layer_metrics.read(_run(snap), name) is None


def test_roofline_reads_a_number_from_a_trace(monkeypatch):
    """The new device reader on made-up counters and step times: the bytes of
    ``costs_laguna`` over the peak rate over the median step."""
    from harness import costs_laguna as costs, peaks, trace_reduce

    def series(value, **labels):
        return {"series": [{"labels": labels, "value": value}]}

    end = {"serve_moe_experts_touched_total": series(24 * 10, program="decode"),
           "serve_moe_layer_programs_total": series(10, program="decode"),
           "serve_kv_live_bytes": series(5e6)}
    cell = env.Cell(MANIFEST, CELL)
    run = layer_metrics.Run(cell, {"platform": "tpu", "kind": "TPU v5 lite"},
                            counters_start={}, counters_end=end,
                            trace={"planes": ["x"]})
    monkeypatch.setattr(trace_reduce, "module_busy_ms",
                        lambda trace, match: [0.5, 0.4, 0.6])
    got = layer_metrics.read(run, "swa_moe_decode_roofline")
    nbytes = costs.decode_step_bytes(cell.config, 24 / 32, 5e6, 2)
    want = 100.0 * nbytes / peaks.peak("TPU v5 lite").hbm_bytes_s / 0.5e-3
    assert got is not None and abs(got - want) < 1e-9 * want
    assert 0 < got < 100


def test_the_group_readers_on_made_up_gauges():
    def series(*pairs):
        return {"series": [{"labels": lab, "value": v} for lab, v in pairs]}

    start = {"serve_kv_window_released_total": series(({}, 10)),
             "serve_kv_window_allocated_total": series(({}, 20))}
    end = {"serve_kv_group_live_bytes": series(({"group": "full"}, 900.0),
                                               ({"group": "window"}, 100.0)),
           "serve_kv_live_bytes": series(({}, 1000.0)),
           "serve_kv_window_released_total": series(({}, 70)),
           "serve_kv_window_allocated_total": series(({}, 100))}
    cell = env.Cell(MANIFEST, CELL)
    run = layer_metrics.Run(cell, {"platform": "cpu", "kind": "cpu"},
                            counters_start=start, counters_end=end)
    assert layer_metrics.read(run, "kv_window_live_share") == 10.0
    assert layer_metrics.read(run, "kv_window_released_share") == 75.0
