"""The MiniCPM-SALA configuration's files end to end on the CPU, through
``run.py`` as the driver calls it: ``tiny-minicpm-sala`` (layers M L L M: two
sparse layers of 4 query heads over 2 KV heads that select 4 blocks of 64 past
``dense_len`` 128, two linear layers of 4 heads whose state is 8 KB a
sequence; bf16 parameters held once; width 64) under ``tiny-longctx``
(contexts of 150-400 tokens: every decode step selects), from
``data/BENCHMARK.sala.test.json``. Covers the reference
``references/minicpm_sala_block.py`` (the agreement check runs it), the
readers the configuration brought, and a program without the counters, as the
parent of PR 45 is, which reads as nothing."""

import os

from harness import agreement, env, layer_metrics

from test_rehearsal import last_line, run_cell

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "BENCHMARK.sala.test.json")
CELL = "tiny-sala-longctx"
NEW = ("sala_decode_roofline", "sparse_kv_read_share", "state_slot_bytes",
       "state_snapshot_shortened_share")


def test_untraced_run_judges_the_serving_metrics():
    out = last_line(run_cell(CELL, trace=0, manifest=MANIFEST))
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["failed"] == 0
    assert out["checks"] == {k: True for k in out["checks"]}, out["agreement"]
    agree = out["agreement"]
    rule = agreement.rules(env.Cell(MANIFEST, CELL).config)
    # the cell's agreement, asserted against the configuration's own bounds
    assert agree["ok"] and agree["failed"] == []
    assert agree["positions"] >= 40
    assert agree["left_out_share"] <= rule["left_out_share_max"]
    assert agree["max_gap_rel"] <= rule["gap_backstop"]
    assert agree["big_gap_share"] <= agree["big_gap_share_limit"]
    assert agree["cache_dtype_given"] == "bfloat16"
    assert agree["control_flip_share"] >= 0.5   # it can fail


def test_traced_run_reports_the_selection_the_state_and_the_snapshots():
    out = last_line(run_cell(CELL, trace=1, manifest=MANIFEST))
    got = out["metrics"]
    # two sparse layers x (k and v x 2 heads x 16 x 2 bytes + a pooled key
    # of 2 x 16 x 2 bytes every 16 tokens)
    assert got["kv_token_bytes"] == {"value": 2 * (128 + 4.0), "unit": "B"}
    # two linear layers x 4 heads x 16 x 16 x 4 bytes
    assert got["state_slot_bytes"] == {"value": 8192.0, "unit": "B"}
    # 4 blocks of 64 of contexts of 150-500: never all of them
    assert 10.0 < got["sparse_kv_read_share"]["value"] < 100.0
    # a turn extends its session's cached run, which ends at a snapshot
    assert got["state_snapshot_shortened_share"]["value"] < 25.0
    assert got["session_prefix_saved_share"]["value"] > 50.0
    assert "sala_decode_roofline" not in got   # a device number: no chip
    assert "tick_mean_ms" in got               # the cell's other metrics too


def _run(snap, platform="tpu"):
    cell = env.Cell(MANIFEST, CELL)
    return layer_metrics.Run(cell, {"platform": platform, "kind": "TPU v5 lite"},
                             counters_start=snap, counters_end=snap,
                             trace={"planes": []})


def test_readers_find_nothing_in_a_program_without_the_counters():
    """What the parent commit gives the new readers: no sparse counters, no
    state gauge, no labelled shortening counter. Nothing is read, nothing
    raises."""
    snap = {"serve_gen_tokens_total": {"series": [{"labels": {}, "value": 9}]},
            "serve_prefix_cache_hits_total": {"series": [{"labels": {},
                                                          "value": 7}]},
            "serve_kv_live_bytes": {"series": [{"labels": {}, "value": 7e6}]}}
    for name in NEW:
        assert layer_metrics.read(_run(snap), name) is None


def test_roofline_reads_a_number_from_a_trace(monkeypatch):
    """The new device reader on made-up counters and step times: the bytes of
    ``costs_minicpm_sala`` over the peak rate over the median step."""
    from harness import costs_minicpm_sala as costs, peaks, trace_reduce

    def series(value, **extra):
        return {"series": [{"labels": {}, "value": value, **extra}]}

    end = {"serve_sparse_kv_positions_read_total": series(10 * 1000.0),
           "serve_sparse_kv_positions_live_total": series(10 * 4000.0),
           "serve_gen_decode_seconds": series(0.0, count=10, sum=0.05),
           "serve_gen_slot_occupancy": series(0.0, count=10, sum=7.5),
           "serve_state_slot_bytes": series(8192.0)}
    cell = env.Cell(MANIFEST, CELL)
    run = layer_metrics.Run(cell, {"platform": "tpu", "kind": "TPU v5 lite"},
                            counters_start={}, counters_end=end,
                            trace={"planes": ["x"]})
    monkeypatch.setattr(trace_reduce, "module_busy_ms",
                        lambda trace, match: [0.5, 0.4, 0.6])
    got = layer_metrics.read(run, "sala_decode_roofline")
    nbytes = costs.decode_step_bytes(cell.config, 1000.0, 4000.0, 3.0,
                                     8192.0, 2)
    want = 100.0 * nbytes / peaks.peak("TPU v5 lite").hbm_bytes_s / 0.5e-3
    assert got is not None and abs(got - want) < 1e-9 * want
    assert 0 < got < 100


def test_the_share_readers_on_made_up_counters():
    def series(*pairs):
        return {"series": [{"labels": lab, "value": v} for lab, v in pairs]}

    state = {"reason": "state"}
    start = {"serve_prefix_hits_shortened_total": series(
        ({**state, "left": "some"}, 1), ({**state, "left": "none"}, 1)),
        "serve_prefix_cache_hits_total": series(({}, 10)),
        "serve_sparse_kv_positions_read_total": series(({}, 100)),
        "serve_sparse_kv_positions_live_total": series(({}, 1000))}
    end = {"serve_prefix_hits_shortened_total": series(
        ({**state, "left": "some"}, 3), ({**state, "left": "none"}, 2)),
        "serve_prefix_cache_hits_total": series(({}, 29)),
        "serve_sparse_kv_positions_read_total": series(({}, 400)),
        "serve_sparse_kv_positions_live_total": series(({}, 2200)),
        "serve_state_slot_bytes": series(({}, 8192.0))}
    cell = env.Cell(MANIFEST, CELL)
    run = layer_metrics.Run(cell, {"platform": "cpu", "kind": "cpu"},
                            counters_start=start, counters_end=end)
    # 2 + 1 shortened of 19 hits + 1 cut to nothing
    assert layer_metrics.read(run, "state_snapshot_shortened_share") == 15.0
    assert layer_metrics.read(run, "sparse_kv_read_share") == 25.0
    assert layer_metrics.read(run, "state_slot_bytes") == 8192.0


def test_every_limit_stands_between_the_sound_runs_and_the_8_bit_control():
    """``minicpm-sala``'s own bounds through ``judge``, as
    ``test_agreement.py`` holds the other configurations': the largest
    reading the standing tree gave on the chip passes every bound, the
    smallest the 8-bit control gave fails each number it is held against."""
    group = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                       "minicpm-sala.json"))["agreement"]
    rule = agreement.rules({"name": "minicpm-sala", "agreement": group})
    sound, low = group["sound_max"], group["control_8bit_min"]

    def as_run(reading):
        return {"positions": group["positions_min"], "finite": True,
                "left_out_share": sound["left_out_share"], **reading}

    repeated = {"flip_share": group["repeated_token_min"]}
    assert rule["tie_margin"] == 0.0 == rule["left_out_share_max"]
    assert agreement.judge(as_run(sound), repeated, rule)["failed"] == []
    assert agreement.judge(as_run(low), repeated, rule)["failed"] \
        == ["big_gap_share", "max_gap_rel"]
    assert low["max_gap_rel"] >= agreement.SEPARATION * sound["max_gap_rel"]
    assert sound["max_gap_rel"] < rule["gap_backstop"] <= min(
        agreement.BACKSTOP_MULTIPLE * sound["max_gap_rel"],
        agreement.CONTROL_SHARE * low["max_gap_rel"])
    assert sound["max_gap_rel"] <= rule["big_gap"]
    limit = agreement.big_gap_share_limit(group["positions_min"])
    assert sound["big_gap_share"] <= limit / 3
    assert low["big_gap_share"] >= agreement.SEPARATION * limit
