"""The declarative sources of per-layer metrics, on counters made by hand."""

import json
import os

import pytest

import xplane_writer as xw
from harness import env, layer_metrics as lm, trace_reduce


class FakeCell:
    name = "cell"
    traffic = {"job": {"seq_len": 2048}}
    config = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                        "cerebras-gpt-1.3b.json"))

    def metrics(self, group):
        return []


def counter(value, **labels):
    return {"series": [{"labels": labels, "value": value}]}


def hist(buckets, total, mx):
    cum, rows = 0, []
    for bound, c in buckets:
        cum += c
        rows.append([bound, cum])
    return {"series": [{"labels": {}, "count": cum, "sum": total, "max": mx,
                        "buckets": rows}]}


@pytest.fixture
def run():
    start = {"serve_shed_total": counter(1, cause="queue_full"),
             "saved": counter(100),
             "tick": hist([[0.05, 10], [0.1, 0], ["+Inf", 0]], 0.4, 0.05)}
    end = {"serve_shed_total": {"series": [
               {"labels": {"cause": "queue_full"}, "value": 3},
               {"labels": {"cause": "client_gone"}, "value": 4}]},
           "saved": counter(900),
           "tick": hist([[0.05, 10], [0.1, 40], ["+Inf", 0]], 3.4, 0.09)}
    boot = {"hits": counter(7), "misses": counter(1)}
    return lm.Run(FakeCell(), {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
                  counters_boot=boot, counters_start=start, counters_end=end,
                  client={"attempted": 50, "prompt_tokens_sent": 1000},
                  result={"train_tokens_per_s": 20000.0, "chips": 4,
                          "memory_peak_bytes": 9_000_000_000},
                  spans=[{"name": "train_step", "dur": d} for d in
                         (9e6, 8e6, 400e3, 420e3, 410e3)] + [{"name": "other", "dur": 1}])


def test_ratio_of_window_deltas(run):
    src = {"type": "ratio", "scale": 100.0,
           "num": {"counter": "serve_shed_total", "at": "window"},
           "den": {"client": "attempted"}}
    assert lm.read_declared(run, src) == pytest.approx(100 * (7 - 1) / 50)
    src["num"]["labels"] = {"cause": "queue_full"}
    assert lm.read_declared(run, src) == pytest.approx(100 * 2 / 50)
    src["den"] = {"client": "nothing"}
    assert lm.read_declared(run, src) is None


def test_missing_counter_reads_as_nothing_unless_told(run):
    src = {"type": "term", "term": {"counter": "never_incremented"}}
    assert lm.read_declared(run, src) is None
    src["term"]["missing"] = 0.0
    assert lm.read_declared(run, src) == 0.0


def test_boot_terms_and_sums(run):
    src = {"type": "ratio", "scale": 100.0,
           "num": {"counter": "hits", "at": "boot"},
           "den": {"sum": [{"counter": "hits", "at": "boot"},
                           {"counter": "misses", "at": "boot"}]}}
    assert lm.read_declared(run, src) == pytest.approx(87.5)


def test_histogram_quantile_and_mean_inside_the_window(run):
    # the window holds 40 observations, all in (0.05, 0.1], the largest 0.09
    src = {"type": "histogram_quantile", "name": "tick", "q": 0.5, "scale": 1000.0}
    assert lm.read_declared(run, src) == pytest.approx(1000 * (0.05 + 0.04 * 0.5))
    mean = {"type": "ratio", "scale": 1000.0,
            "num": {"counter": "tick", "field": "sum"},
            "den": {"counter": "tick", "field": "count"}}
    assert lm.read_declared(run, mean) == pytest.approx(1000 * 3.0 / 40)


def test_span_quantile_skips_the_warm_up(run):
    src = {"type": "span_quantile", "name": "train_step", "q": 0.5, "skip": 2,
           "scale": 0.001}
    assert lm.read_declared(run, src) == pytest.approx(410.0)


def test_result_terms(run):
    src = {"type": "term", "scale": 1e-9, "term": {"result": "memory_peak_bytes"}}
    assert lm.read_declared(run, src) == pytest.approx(9.0)


def test_trace_sources(run, tmp_path):
    assert lm.read_declared(run, {"type": "trace_idle"}) is None   # untraced
    path = str(tmp_path / "t.xplane.pb")
    xw.write(path, [xw.plane(1, "/device:TPU:0", {
        "XLA Ops": [("fusion.1", 0, 300), ("all-reduce.2", 300, 100),
                    ("fusion.3", 600, 400)],
        "XLA Modules": [("jit__decode_paged_fn(9)", 0, 400),
                        ("jit__decode_paged_fn(9)", 600, 400)]})])
    run.trace = trace_reduce.load(path)
    assert lm.read_declared(run, {"type": "trace_idle"}) == pytest.approx(20.0)
    assert lm.read_declared(run, {"type": "trace_module", "match": "decode_paged",
                                  "reduce": "median"}) == pytest.approx(400e-6)
    assert lm.read_declared(run, {"type": "trace_ops", "match": "^fusion"}) \
        == pytest.approx(100 * 700 / 800)
    assert lm.read_declared(run, {"type": "trace_collectives",
                                  "field": "collective_share"}) == pytest.approx(12.5)
    assert lm.read_declared(run, {"type": "trace_collectives",
                                  "field": "exposed_share"}) == pytest.approx(10.0)


def test_python_reader_train_mfu(run):
    # 20k tokens/s x 8.47 GFLOP over 4 x 197 TFLOP/s
    assert lm.read(run, "train_mfu") == pytest.approx(100 * 20000 * 8.47e9 / 788e12,
                                                      rel=2e-3)
    with pytest.raises(FileNotFoundError, match="no reader"):
        lm.read(run, "no_such_metric")


@pytest.mark.parametrize("name", ["ttft_p90_ms", "prefix_saved_share",
                                  "queue_wait_mean_ms", "server_ttft_mean_ms"])
def test_a_reading_under_a_second_name_is_the_first_ones(run, name):
    """``session_<name>`` keeps no copy of the source: it reads ``<name>``."""
    with open(os.path.join(env.BENCH_DIR, "layer_metrics",
                           f"session_{name}.json")) as f:
        assert json.load(f)["source"] == {"type": "same_as", "metric": name}
    run.client["ttft_p90_ms"] = 281.0
    run.counters_end["serve_prefill_tokens_saved_total"] = counter(640)
    for fam in ("serve_gen_queue_seconds", "serve_gen_first_token_seconds"):
        run.counters_end[fam] = hist([[0.05, 4], ["+Inf", 0]], 0.1, 0.04)
    assert lm.read(run, f"session_{name}") == lm.read(run, name) is not None
    assert lm.read_declared(run, {"type": "same_as", "metric": "turn_host_ms"}) \
        is None                         # nothing to read: nothing, not 0


def test_every_metric_of_the_manifest_has_a_reader_and_agrees_with_it():
    manifest = env.load_json(env.MANIFEST)
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        base = os.path.join(env.BENCH_DIR, "layer_metrics", m["name"])
        assert os.path.exists(base + ".json") or os.path.exists(base + ".py"), m
        assert m["moves"] in e2e
        if os.path.exists(base + ".json"):
            with open(base + ".json") as f:
                d = json.load(f)
            assert (d["layer"], d["unit"], d["moves"]) == \
                (m["layer"], m["unit"], m["moves"]), m["name"]
