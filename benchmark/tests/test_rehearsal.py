"""Each runner end to end on the CPU, through ``run.py`` as the driver calls
it, with the ``tiny-test`` configurations of ``data/BENCHMARK.test.json``
(in no cell of ``BENCHMARK.json``): the last line of stdout has the fixed
keys, names the CPU, and every check passes. And ``run.py`` refuses a cell of
the repository's manifest where there is no TPU."""

import json
import os
import subprocess
import sys

import pytest

from harness import env

RUN = os.path.join(env.BENCH_DIR, "run.py")
TEST_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "BENCHMARK.test.json")


def run_cell(workload, trace, devices=1, manifest=TEST_MANIFEST, seconds=3):
    envv = {**os.environ, "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    envv.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", "5",
         "--seconds", str(seconds), "--trace", str(trace),
         "--manifest", manifest],
        env=envv, text=True, capture_output=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["device"]["platform"] == "cpu"      # it says where it ran
    # Every check, the agreement gate among them: the rehearsal's
    # configurations carry bounds set by the gate's own rule from CPU
    # readings (``data/configs/*.json``, group ``agreement``), every answer
    # the schedule marked came, and the line says so in its own words.
    assert out["failed_checks"] == [], (out["checks"], out.get("agreement"),
                                       proc.stderr[-2000:])
    assert out["checks"] == {k: True for k in out["checks"]}
    assert out["correct"] is True
    assert list(out)[-1] == "compared"      # each number beside its limit
    assert all(len(pair) == 2 for pair in out["compared"].values())
    if "agreement" in out:                  # a serving cell
        agree = out["agreement"]
        assert out["checks"]["checked_requests"] and agree["sequences"] >= 2
        assert agree["control_flip_share"] >= agree["control_flip_share_min_limit"]
        for name in ("big_gap_share", "max_gap_rel", "left_out_share"):
            assert agree[name] <= agree[name + "_limit"]
    assert out["failed"] == 0 and out["attempted"] > 0
    return out


@pytest.mark.parametrize("workload", ["tiny-bursts", "tiny-sessions"])
def test_serving_runners(workload):
    out = last_line(run_cell(workload, trace=0))
    judged = {"itl_p50_ms", "setup_s"}
    if workload == "tiny-sessions":      # a time to first token is judged
        judged.add("ttft_p50_ms")        # in the closed-loop cell only
    assert set(out["metrics"]) == judged
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["metrics"]["itl_p50_ms"]["unit"] == "ms"
    assert out["checks"]["no_compile_in_window"]
    assert out["agreement"]["control_flip_share"] >= 0.5   # it can fail


def test_traced_serving_run_reports_per_layer_metrics_and_warm_setup():
    """Second run of a cell: nothing compiles (XLA cache) and every
    executable loads from the AotStore."""
    run_cell("tiny-sessions", trace=0)
    out = last_line(run_cell("tiny-sessions", trace=1))
    assert out["setup"]["xla_cache_misses"] == 0
    assert out["metrics"]["aot_hit_share"]["value"] == 100.0
    assert out["metrics"]["prefix_saved_share"]["value"] > 50.0
    assert out["metrics"]["shed_share"]["value"] == 0.0
    assert 0 < out["metrics"]["slot_occupancy"]["value"] <= 100.0
    assert out["metrics"]["ttft_p90_ms"]["value"] > 0
    # throughput is reported, not judged: the window's total and the median second
    assert out["metrics"]["window_tokens_per_s"]["unit"] == "tokens/s"
    assert out["metrics"]["tokens_per_s_p50"]["value"] > 0
    assert out["metrics"]["itl_max_ms"]["value"] > 0
    assert not {"ttft_p50_ms", "itl_p50_ms"} & set(out["metrics"])   # per-layer only
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_training_runner_over_four_virtual_devices():
    out = last_line(run_cell("tiny-pretrain", trace=0, devices=4))
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["device"]["count"] == 4
    assert out["losses"]["last"] < out["losses"]["first"]
    assert abs(out["losses"]["first"] - out["losses"]["reference"]) \
        <= 1e-3 * out["losses"]["reference"]
    traced = last_line(run_cell("tiny-pretrain", trace=1, devices=4))
    assert traced["metrics"]["train_step_ms"]["value"] > 0


def test_a_cell_needs_its_chips():
    proc = run_cell("tiny-pretrain", trace=0, devices=1)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
    assert "needs 4 chips" in proc.stderr


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      env.load_json(env.MANIFEST)["workloads"]])
def test_manifest_cells_refuse_the_cpu(workload):
    proc = run_cell(workload, trace=0, manifest=env.MANIFEST)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""                # no result line at all
    assert "no TPU" in proc.stderr
