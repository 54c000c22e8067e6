"""The OLMoE configuration's files end to end on the CPU, through ``run.py``
as the driver calls it: ``tiny-olmoe`` (64 experts, 8 a token, bf16
parameters held once; two layers at width 64) under ``tiny-sessions``, from
``data/BENCHMARK.olmoe.test.json``. Covers the reference
``references/olmoe_block.py`` (the agreement check runs it) and the three
routing readers; and a program without the counters reads as nothing."""

import os

from harness import env, layer_metrics

from test_rehearsal import last_line, run_cell

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "BENCHMARK.olmoe.test.json")
CELL = "tiny-olmoe-sessions"


def test_untraced_run_judges_the_serving_metrics():
    out = last_line(run_cell(CELL, trace=0, manifest=MANIFEST))
    assert set(out["metrics"]) == {"itl_p50_ms", "ttft_p50_ms", "setup_s"}
    assert out["checks"]["no_compile_in_window"]
    assert out["agreement"]["control_flip_share"] >= 0.5   # it can fail


def test_traced_run_reports_what_routing_did():
    out = last_line(run_cell(CELL, trace=1, manifest=MANIFEST))
    got = out["metrics"]
    # 4 sessions x 8 of 64 experts: at most half are touched by a decode
    # step, and a chunk of 8-24 tokens touches more
    assert 12.5 <= got["moe_experts_touched_share"]["value"] <= 100.0
    assert got["moe_experts_touched_share"]["unit"] == "%"
    # the fullest expert holds at least the mean: 1.0 is an even spread
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 8.0
    assert "moe_decode_roofline" not in got      # a device number: no chip
    assert got["prefix_saved_share"]["value"] > 50.0
    assert "tick_mean_ms" in got             # the cell's other metrics too


def test_readers_find_nothing_in_a_program_without_experts():
    """What the parent commit, and any dense model, gives the new readers."""
    cell = env.Cell(MANIFEST, CELL)
    snap = {"serve_gen_tokens_total": {"series": [{"labels": {}, "value": 9}]}}
    run = layer_metrics.Run(cell, {"platform": "tpu", "kind": "TPU v5 lite"},
                            counters_start=snap, counters_end=snap,
                            trace={"planes": []})
    for name in ("moe_experts_touched_share", "moe_load_imbalance",
                 "moe_decode_roofline"):
        assert layer_metrics.read(run, name) is None
