"""A later PR's move, rehearsed in a scratch copy: a new cell made from a
fourth traffic file, and a counter-backed per-layer metric, with new files
and new entries in the manifest only. No file that was there is edited, and
the harness finds all three by name."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from harness import env


def digest(root):
    out = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_and_metric_need_no_edit(tmp_path):
    copy = str(tmp_path / "benchmark")
    shutil.copytree(env.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = digest(copy)
    data = os.path.join(copy, "tests", "data")

    # 1. a fourth traffic file: parameters only
    traffic = env.load_json(os.path.join(data, "traffic", "tiny-sessions.json"))
    traffic["sessions"]["count"] = 3
    traffic["sessions"]["turn_output_len"] = {"kind": "fixed", "p1": 6, "max": 6}
    with open(os.path.join(data, "traffic", "tiny-short-turns.json"), "w") as f:
        json.dump(traffic, f)
    # 2. a counter-backed per-layer metric: one declarative file
    layer = "generation scheduler serve/continuous.py serve/paged.py"
    with open(os.path.join(copy, "layer_metrics", "prefix_hit_share.json"), "w") as f:
        json.dump({"layer": layer, "unit": "%", "moves": "ttft_p50_ms",
                   "source": {"type": "ratio", "scale": 100.0,
                              "num": {"counter": "serve_prefix_cache_hits_total"},
                              "den": {"sum": [
                                  {"counter": "serve_prefix_cache_hits_total"},
                                  {"counter": "serve_prefix_cache_misses_total",
                                   "missing": 0.0}]}}}, f)
    # 3. new entries in a manifest of its own (the PR's BENCHMARK.json)
    manifest = env.load_json(os.path.join(data, "BENCHMARK.test.json"))
    manifest["workloads"].append(
        {"name": "tiny-short-turns", "config": "tiny-test",
         "traffic": "tiny-short-turns", "chips": 1, "why": "the added cell"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-sessions" in m.get("workloads", []):
            m["workloads"].append("tiny-short-turns")
    manifest["per_layer"].append(
        {"name": "prefix_hit_share", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": layer, "moves": "ttft_p50_ms",
         "workloads": ["tiny-short-turns"]})
    new_manifest = os.path.join(data, "BENCHMARK.extended.json")
    with open(new_manifest, "w") as f:
        json.dump(manifest, f)

    after = digest(copy)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert set(after) - set(before) == {
        os.path.join("tests", "data", "traffic", "tiny-short-turns.json"),
        os.path.join("layer_metrics", "prefix_hit_share.json"),
        os.path.join("tests", "data", "BENCHMARK.extended.json")}

    proc = subprocess.run(
        [sys.executable, os.path.join(copy, "run.py"),
         "--workload", "tiny-short-turns", "--seed", "1", "--seconds", "3",
         "--trace", "1", "--manifest", new_manifest],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": env.ROOT},
        text=True, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # see test_rehearsal.last_line: the agreement check may report the
    # program's open fault here
    assert all(v for k, v in out["checks"].items() if k != "agreement"), out["checks"]
    assert out["metrics"]["prefix_hit_share"]["value"] == 100.0
    assert out["metrics"]["prefix_hit_share"]["unit"] == "%"
    assert "tick_mean_ms" in out["metrics"]      # the cell's other metrics too
