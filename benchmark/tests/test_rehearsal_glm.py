"""The GLM-4.7-Flash configuration's files end to end on the CPU, through
``run.py`` as the driver calls it: ``tiny-glm4-moe-lite`` (one dense layer
and one expert layer of 64 experts, 4 a token, beside a shared one; latent
attention; bf16 parameters held once; width 64) under ``tiny-longchat``, from
``data/BENCHMARK.glm.test.json``. Covers the reference
``references/glm4_moe_lite_block.py`` (the agreement check runs it), the
routing readers on their second configuration and the two readers the
configuration brought; and a program without the counters, as the parent of
PR 31 is, reads as nothing."""

import os

from harness import env, layer_metrics

from test_rehearsal import last_line, run_cell

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "BENCHMARK.glm.test.json")
CELL = "tiny-glm-longchat"


def test_untraced_run_judges_the_serving_metrics():
    out = last_line(run_cell(CELL, trace=0, manifest=MANIFEST))
    # no time to first token is judged in this cell (PERF.md section 2)
    assert set(out["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["failed"] == 0
    # every check of the rehearsal; the tiny model's agreement too: the block
    # computes in f32 against the bf16 values the reference is fed
    assert out["checks"] == {k: True for k in out["checks"]}, out["agreement"]
    assert out["agreement"]["control_flip_share"] >= 0.5   # it can fail


def test_traced_run_reports_the_cache_and_what_routing_did():
    out = last_line(run_cell(CELL, trace=1, manifest=MANIFEST))
    got = out["metrics"]
    # two layers x (16 + 8) values a token x 2 bytes: one latent, no heads
    assert got["kv_token_bytes"] == {"value": 2 * 24 * 2.0, "unit": "B"}
    # one expert layer; 3 sessions x 4 of 64 experts a decode step, a chunk
    # of 8-24 tokens more
    assert 6.25 <= got["moe_experts_touched_share"]["value"] <= 100.0
    assert 1.0 <= got["moe_load_imbalance"]["value"] <= 16.0
    assert "mla_moe_decode_roofline" not in got   # a device number: no chip
    assert "tick_mean_ms" in got             # the cell's other metrics too


def _run(snap, platform="tpu"):
    cell = env.Cell(MANIFEST, CELL)
    return layer_metrics.Run(cell, {"platform": platform, "kind": "TPU v5 lite"},
                             counters_start=snap, counters_end=snap,
                             trace={"planes": []})


def test_readers_find_nothing_in_a_program_without_the_counters():
    """What the parent commit gives the new readers: no gauge, no routing
    counters. Nothing is read and nothing raises."""
    snap = {"serve_gen_tokens_total": {"series": [{"labels": {}, "value": 9}]}}
    for name in ("kv_token_bytes", "mla_moe_decode_roofline",
                 "moe_experts_touched_share", "moe_load_imbalance"):
        assert layer_metrics.read(_run(snap), name) is None


def test_roofline_reads_a_number_from_a_trace(monkeypatch):
    """The new device reader on made-up counters and step times: the bytes of
    ``costs_glm4_moe_lite`` over the peak rate over the median step."""
    from harness import costs_glm4_moe_lite as costs, peaks, trace_reduce

    def series(value, **labels):
        return {"series": [{"labels": labels, "value": value}]}

    end = {"serve_moe_experts_touched_total": series(48 * 10, program="decode"),
           "serve_moe_layer_programs_total": series(10, program="decode"),
           "serve_kv_live_bytes": series(5e6)}
    cell = env.Cell(MANIFEST, CELL)
    run = layer_metrics.Run(cell, {"platform": "tpu", "kind": "TPU v5 lite"},
                            counters_start={}, counters_end=end,
                            trace={"planes": ["x"]})
    monkeypatch.setattr(trace_reduce, "module_busy_ms",
                        lambda trace, match: [0.5, 0.4, 0.6])
    got = layer_metrics.read(run, "mla_moe_decode_roofline")
    nbytes = costs.decode_step_bytes(cell.config, 48 / 64, 5e6, 2)
    want = 100.0 * nbytes / peaks.peak("TPU v5 lite").hbm_bytes_s / 0.5e-3
    assert got is not None and abs(got - want) < 1e-9 * want
    assert 0 < got < 100
