"""``BENCHMARK.json`` against the contract's limits that can be checked
without a chip, and against the files it names."""

import os
import re

import pytest

from harness import env

M = env.load_json(env.MANIFEST)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and M["command"][-1] == "benchmark/run.py"
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(env.MANIFEST) <= 64 * 1024
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_lines():
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for x in M[g]]
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in M[g]}) == len(M[g])
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(NAME.match(n) for n in names)
    for w in M["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for x in M["configs"] + M["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"] and "\t" not in x["why"]
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_cells_and_chips():
    cells = M["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in M["configs"]}


WIDTH = re.compile(r"(_dim|_rank|_size)$|^(n_embd|n_inner|n_head|head_dim|"
                   r"num_attention_heads|num_key_value_heads|"
                   r"num_experts_per_tok)$")


@pytest.mark.parametrize("c", M["configs"], ids=lambda c: c["name"])
def test_configs_keep_their_widths(c):
    """Each configuration against ITS OWN file: the source, the reference,
    what was reduced (never a width, each key with the published value
    beside it) and the program's build arguments against the file's sizes."""
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("benchmark/") and c["source"].startswith("https://")
    cfg = env.load_json(os.path.join(env.ROOT, c["file"]))
    assert cfg["source"] == c["source"] and cfg["name"] == c["name"]
    assert os.path.exists(os.path.join(env.BENCH_DIR, "references",
                                       cfg["reference"] + ".py"))
    for key in c["reduced"]:
        assert not WIDTH.search(key) and key != "vocab_size", key
        assert key in cfg
        said = {**cfg.get("published", {}), **cfg.get("cut", {})}
        assert key in said, f"{key}: the published value is not beside it"
    k = cfg["build"]["kwargs"]
    if cfg["reference"] == "gpt2_block":          # full depth, full widths
        assert c["reduced"] == []
        assert (cfg["n_layer"], cfg["n_embd"], cfg["n_head"], cfg["n_inner"]) \
            == (24, 2048, 16, 8192)
        assert (k["num_layers"], k["d_model"], k["num_heads"], k["vocab"],
                k["input_shape"][0]) == (cfg["n_layer"], cfg["n_embd"],
                                         cfg["n_head"], cfg["vocab_size"],
                                         cfg["n_positions"])
        assert (k.get("num_kv_heads") == 1) == bool(cfg["multi_query"])
    else:                                         # cut in depth, never in width
        assert "num_hidden_layers" in c["reduced"]
        assert (k["num_layers"], k["d_model"], k["vocab"]) == (
            cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"])
        assert k["top_k"] == cfg["num_experts_per_tok"]
        assert k["dtype"] == "bfloat16"           # held once, as published


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]       # every cell reports it
    cells = {w["name"] for w in M["workloads"]}
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert 1 <= len(m["layer"]) <= 200
        moved = e2e[m["moves"]]
        # a per-layer metric is reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for cell in cells:
        mine = [m for m in M["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_cells_load_with_their_files(cell):
    c = env.Cell(env.MANIFEST, cell)
    assert c.official and c.traffic["kind"] in ("serve_open", "serve_closed", "train")
    assert os.path.dirname(c.traffic_path) == os.path.join(env.BENCH_DIR, "traffic")
