"""chip_smoke.py's legs at a tiny width on the CPU (interpreted kernels).

The script itself only passes on a TPU at the 738M width; these tests keep
its control flow and its checks alive between chip runs: every leg runs
through the same entry points (``Trainer.fit``, ``ModelServer`` over HTTP,
the AOT store), two layers and d_model 64 wide.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = cs.Size(num_layers=2, d_model=64, num_heads=4, vocab=256, seq=64,
               batch=4, train_steps=6, kernel_head_dims=(16,), window=16,
               slots=4, capacity=64, prompt_lens=(5, 8, 13, 20, 30, 40),
               new_tokens=(4, 8), prefix_len=16)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Legs (b)-(e) once, in order, sharing the trained model and the store
    the way ``main`` does."""
    store = str(tmp_path_factory.mktemp("aot"))
    train, model = cs.leg_train(TINY)
    serve, plan = cs.leg_serve(TINY, model, store)
    agree = cs.leg_agree(TINY, model.params, plan)
    warm = cs.leg_warm_boot(TINY, model, store, plan[0], serve["aot"])
    return {"train": train, "serve": serve, "plan": plan, "agree": agree,
            "warm": warm, "model": model}


def test_leg_kernels_against_dense():
    rec = cs.leg_kernels(TINY)
    assert set(rec["cases"]) == {"D16_none", "D16_lengths", "D16_key_mask",
                                 "D16_window"}
    assert 0 < rec["worst_err"] <= cs.KERNEL_TOL
    # interpreted on the CPU: no Mosaic call, and the leg says so
    assert all(c["mosaic_calls"] == 0 for c in rec["cases"].values())


def test_leg_train_learns(run):
    t = run["train"]
    assert len(t["losses"]) == TINY.train_steps
    assert t["losses"][-1] < t["losses"][0]
    assert t["steady_step_seconds"] > 0 and t["compile_seconds"] > 0
    assert t["hbm"] == {}  # the CPU backend reports no allocator stats


def test_leg_serve_accounts_for_every_token(run):
    s, plan = run["serve"], run["plan"]
    assert s["requests"] == len(plan) == len(TINY.prompt_lens) + 2
    assert all(len(b["tokens"]) == b["max_new_tokens"] for b in plan)
    assert s["gen_tokens_total"] == s["tokens_received"]
    assert s["sheds"] == 0 and s["http_errors"] == 0
    assert s["prefix_cache_hits"] >= 1
    # cold boot: everything traced once, nothing loaded, nothing fell back
    assert s["aot"]["hits"] == 0 and s["aot"]["compile_misses"] > 0
    assert s["aot"]["fallbacks"] == {}
    assert s["decode_tick_seconds"]["count"] > 0


def test_leg_agree_has_power(run):
    a = run["agree"]
    assert a["max_gap"] <= cs.LOGIT_TOL and a["mean_gap"] <= cs.MEAN_GAP_TOL
    assert a["wrong_context_mean_gap"] \
        >= cs.AGREE_MIN_POWER * cs.MEAN_GAP_TOL


def test_leg_agree_rejects_a_wrong_token(run):
    """Tokens that did not come from the model fail the check."""
    import copy

    plan = copy.deepcopy(run["plan"])
    plan[0]["tokens"] = [(t + 1) % TINY.vocab for t in plan[0]["tokens"]]
    with pytest.raises(AssertionError, match="below the reference maximum"):
        cs.leg_agree(TINY, run["model"].params, plan)


def test_leg_warm_boot_loads_everything(run):
    w = run["warm"]
    assert w["aot"]["hits"] == run["serve"]["aot"]["compile_misses"]
    assert w["aot"]["compile_misses"] == 0 and w["aot"]["fallbacks"] == {}
    assert w["tokens_equal_cold"]


def test_leg_four_chip_on_virtual_devices(run):
    """The flash kernel under a mesh (shard_map over data and model axes):
    same first-step loss as one device, shards on all four devices."""
    rec = cs.leg_four_chip(TINY, run["train"]["losses"][0])
    assert set(rec) == {"data4", "data2_model2", "one_chip_first_loss"}
    assert rec["data4"]["batch"] == 2 * TINY.batch


def test_main_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode not in (0, None)
    assert "no TPU" in r.stderr
    # the device line is printed, a result is not
    assert "platform=cpu" in r.stdout
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_last_line_is_the_fixed_result_object(tmp_path, capsys):
    """The chip check reads the last line of stdout: exactly ``ok`` and
    ``device`` {platform, kind, count}. The full record is the line before
    it and the file."""
    out = {"ok": True, "versions": {"jax": "x"}, "legs": {}, "seconds": {},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    cs.report(out, str(tmp_path))
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": out["device"]}
    assert type(json.loads(lines[-1])["device"]["count"]) is int
    assert json.loads(lines[-2]) == out
    assert json.loads((tmp_path / "chip_smoke.json").read_text()) == out


class TestCompileCacheDir:
    def test_env_is_respected_and_nothing_else_set(self, monkeypatch):
        import jax

        from deeplearning4j_tpu.utils import compile_cache as cc

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert cc.use_compile_cache() == "/some/dir"
        assert calls == []  # JAX reads the variable itself

    def test_default_is_the_fixed_in_checkout_path(self, monkeypatch):
        import jax

        from deeplearning4j_tpu.utils import compile_cache as cc

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert cc.use_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))]
        assert cc.use_compile_cache() == cc.DEFAULT_DIR  # same every call
