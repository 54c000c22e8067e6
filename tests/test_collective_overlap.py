"""``parallel.sharding.collective_overlap_options``: a train step jitted over
a mesh of several TPU chips is compiled with the compiler's asynchronous
collectives, and every other step (no mesh, one device, a CPU mesh: every
mesh of this suite) is compiled exactly as before. What the options do to
the four-chip step's schedule is ``scripts/mesh_step_schedule.py``'s to show;
here: who gets them, and that nobody else's program changes."""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import (DATA_AXIS, DENSE_RULES, MODEL_AXIS,
                                         make_mesh, sharding)
from deeplearning4j_tpu.parallel.sharding import collective_overlap_options
from deeplearning4j_tpu.train import Trainer


def _stub_mesh(shape, platforms=("tpu",)):
    """What the function reads of a mesh, over devices that only report a
    platform (a described or attached TPU reports ``"tpu"``)."""
    n = int(np.prod(shape))
    devices = np.empty(n, object)
    for i in range(n):
        devices[i] = SimpleNamespace(id=i, platform=platforms[i % len(platforms)])
    return SimpleNamespace(devices=devices.reshape(shape), size=n)


def _cpu_mesh(axes):
    n = int(np.prod(list(axes.values())))
    return make_mesh(axes, jax.devices()[:n])


@pytest.mark.parametrize("mesh", [
    pytest.param(lambda: None, id="no-mesh"),
    pytest.param(lambda: _cpu_mesh({DATA_AXIS: 2, MODEL_AXIS: 2}), id="cpu-2x2"),
    pytest.param(lambda: _cpu_mesh({DATA_AXIS: 1}), id="cpu-one-device"),
    pytest.param(lambda: _stub_mesh((1,)), id="tpu-one-chip"),
    pytest.param(lambda: _stub_mesh((1, 1)), id="tpu-one-chip-two-axes"),
    pytest.param(lambda: _stub_mesh((2, 2), ("tpu", "cpu")), id="mixed-platforms"),
    pytest.param(lambda: _stub_mesh((4,), ("gpu",)), id="gpu-four"),
])
def test_nothing_where_there_is_nothing_to_overlap(mesh):
    assert collective_overlap_options(mesh()) == {}


@pytest.mark.parametrize("shape", [(2, 2), (4,), (1, 2), (2, 1, 2)],
                         ids=lambda s: "x".join(map(str, s)))
def test_a_mesh_of_tpu_chips_gets_compiler_options(shape):
    options = collective_overlap_options(_stub_mesh(shape))
    assert options
    for key, value in options.items():
        assert isinstance(key, str) and key.startswith("xla_")
        assert type(value) in (bool, int, str)
    # a fresh dict each time: a caller may add to its own
    options["mine"] = 1
    assert "mine" not in collective_overlap_options(_stub_mesh(shape))


def _mlp():
    from deeplearning4j_tpu.nn import NetConfig, SequentialBuilder
    from deeplearning4j_tpu.nn import layers as L

    return (SequentialBuilder(NetConfig(seed=7, updater={"type": "adam",
                                                         "learning_rate": 1e-2}))
            .input_shape(12)
            .layer(L.Dense(n_out=16, activation="relu"))
            .layer(L.Output(n_out=4, activation="softmax", loss="mcxent"))
            .build())


def _batches(steps=3, bs=8, d=12, c=4):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((steps * bs, d)).astype(np.float32)
    y = np.eye(c, dtype=np.float32)[rng.integers(0, c, steps * bs)]
    return x, y, bs


def _mesh_trainer():
    return Trainer(_mlp(), seed=3, mesh=_cpu_mesh({DATA_AXIS: 2, MODEL_AXIS: 2}),
                   rules=DENSE_RULES)


def _lowered_text(tr):
    x, y, bs = _batches()
    xb, yb = tr._place_batch(x[:bs], y[:bs])
    return tr._make_step().lower(tr.params, tr.opt_state, tr.state, xb, yb,
                                 jax.random.PRNGKey(0)).as_text()


def _losses(tr):
    from deeplearning4j_tpu.data import ArrayIterator
    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    x, y, bs = _batches()
    scores = CollectScoresListener()
    tr.fit(ArrayIterator(x, y, bs, shuffle=False), epochs=1, prefetch=False,
           listeners=[scores])
    return [float(s) for _, s in scores.scores]


def test_cpu_mesh_step_lowers_to_the_same_text(monkeypatch):
    mine = _lowered_text(_mesh_trainer())
    monkeypatch.setattr(sharding, "collective_overlap_options", lambda mesh: {})
    assert _lowered_text(_mesh_trainer()) == mine


def test_cpu_mesh_trains_to_the_same_losses(monkeypatch):
    mine = _losses(_mesh_trainer())
    assert len(mine) == 3 and np.all(np.isfinite(mine))
    monkeypatch.setattr(sharding, "collective_overlap_options", lambda mesh: {})
    assert _losses(_mesh_trainer()) == mine


@pytest.mark.parametrize("n_unpinned", [1, 2])
def test_no_mesh_and_cpu_mesh_pass_no_compiler_options(n_unpinned):
    _, kw = Trainer(_mlp(), seed=3)._mesh_jit_setup(n_unpinned)
    assert kw == {}
    _, kw = _mesh_trainer()._mesh_jit_setup(n_unpinned)
    assert set(kw) == {"out_shardings"}


@pytest.mark.parametrize("n_unpinned", [1, 2])
def test_every_mesh_step_is_handed_what_the_function_returns(monkeypatch,
                                                             n_unpinned):
    """The one jit site of the plain, multi-step and tBPTT steps."""
    asked = []

    def options(mesh):
        asked.append(mesh)
        return {"xla_made_up": True}

    monkeypatch.setattr(sharding, "collective_overlap_options", options)
    tr = _mesh_trainer()
    _, kw = tr._mesh_jit_setup(n_unpinned)
    assert kw["compiler_options"] == {"xla_made_up": True}
    assert asked == [tr.mesh]


# --- scripts/mesh_step_schedule.py: reading a compiled module's schedule ---

_HLO = """\
HloModule jit_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%fused_matmul (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
}

%fused_start (p0: bf16[8,8]) -> (bf16[8,8], bf16[8,8]) {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %all-reduce.9 = bf16[8,8]{1,0} all-reduce(%p0), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
  ROOT %t = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%p0, %all-reduce.9)
}

%async_collective_fusion.7 (p0: bf16[8,8], p1: bf16[8,8]) -> (bf16[8,8], bf16[8,8]) {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  %convolution.2 = bf16[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
  %all-reduce.10 = bf16[8,8]{1,0} all-reduce(%p0), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
  ROOT %t = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%convolution.2, %all-reduce.10)
}

%fused_done (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %all-reduce.11 = bf16[8,8]{1,0} all-reduce(%p0), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
}

ENTRY %main (a: bf16[8,8], b: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8]{1,0} parameter(0)
  %b = bf16[8,8]{1,0} parameter(1)
  %fusion.1 = bf16[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_matmul
  %all-reduce.1 = f32[8,8]{1,0} all-reduce(%fusion.1), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add
  %all-reduce-start.1 = bf16[8,8]{1,0} all-reduce-start(%fusion.1), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%add
  %fusion.2 = bf16[8,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_matmul
  %custom-call.1 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  %all-reduce-done.1 = bf16[8,8]{1,0} all-reduce-done(%all-reduce-start.1)
  %async-collective-start.4 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) fusion(%fusion.2), kind=kCustom, calls=%fused_start
  %fusion.3 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) fusion(%a, %b), kind=kOutput, calls=%async_collective_fusion.7
  %async-collective-done.4 = bf16[8,8]{1,0} fusion(%fusion.3), kind=kCustom, calls=%fused_done
  ROOT %add.1 = bf16[8,8]{1,0} add(%async-collective-done.4, %all-reduce-done.1)
}
"""


@pytest.fixture(scope="module")
def schedule_script():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "scripts", "mesh_step_schedule.py")
    spec = importlib.util.spec_from_file_location("mesh_step_schedule", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def rows(schedule_script):
    mesh = _stub_mesh((2, 2))
    mesh.axis_names = ("data", "model")
    out = schedule_script.schedule(_HLO, mesh)
    assert (out["heavy_total"], out["sync"], out["async"]) == (4, 1, 2)
    return {r["op"]: r for r in out["rows"]}


@pytest.mark.parametrize("op,kind,over,before,between,shape", [
    ("all-reduce.1", "sync", "data", 1, None, "f32[8,8]{1,0}"),
    ("all-reduce-start.1", "async", "data+model", 1, 2, "bf16[8,8]{1,0}"),
    # the fused pair: the collective is inside the called computation, and
    # the matmul between start and done carries its state
    ("async-collective-start.4", "async", "model", 3, 1, "bf16[8,8]{1,0}"),
])
def test_schedule_script_reads_each_kind_of_collective(rows, op, kind, over,
                                                       before, between, shape):
    r = rows[op]
    assert (r["kind"], r["over"], r["heavy_before"], r["shape"]) == \
        (kind, over, before, shape)
    assert r.get("heavy_between") == between


def test_schedule_script_groups_rows_alike(schedule_script):
    rows = [{"op": f"all-reduce.{i}", "kind": "sync", "over": "model",
             "shape": "bf16[2]", "heavy_before": 3 * i} for i in range(4)]
    (g,) = schedule_script.grouped(rows)
    assert (g["op"], g["n"], g["heavy_before"], g["last"]) == ("all-reduce", 4, 0, 9)
