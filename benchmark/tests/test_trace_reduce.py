"""The trace reduction on a two-device timeline laid out by hand
(``xplane_writer``: a real ``.xplane.pb`` read back through
``jax.profiler.ProfileData``), and on the small trace recorded on the chip
that is kept under ``data/``.

Device 0 (ns):  fusion.1 [0,400)  all-reduce.1 [400,600)  -gap-  while.1 [800,1400)
                  inside the while: fusion.2 [800,1000) all-gather.3 [1000,1300)
                  and, overlapping the all-gather, copy.4 [1100,1200)
Device 1 (ns):  fusion.1 [0,500)  all-reduce.1 [500,600)  fusion.9 [1900,2000)
Programs:       jit_step(1) [0,600) on both; jit_step(1) [800,1400) on device 0;
                jit_other(2) [1900,2000) on device 1
"""

import os

import numpy as np
import pytest

import xplane_writer as xw
from harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def devices(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("trace") / "two.xplane.pb")
    d0 = xw.plane(1, "/device:TPU:0", {
        "XLA Ops": [("%fusion.1 = f32[8]", 0, 400), ("all-reduce.1", 400, 200),
                    ("while.1", 800, 600), ("fusion.2", 800, 200),
                    ("all-gather.3", 1000, 300), ("copy.4", 1100, 100)],
        "XLA Modules": [("jit_step(1)", 0, 600), ("jit_step(1)", 800, 600)],
        "Steps": [("0", 0, 1400)]})
    d1 = xw.plane(2, "/device:TPU:1", {
        "XLA Ops": [("%fusion.1 = f32[8]", 0, 500), ("all-reduce.1", 500, 100),
                    ("fusion.9", 1900, 100)],
        "XLA Modules": [("jit_step(1)", 0, 600), ("jit_other(2)", 1900, 100)]})
    host = xw.plane(3, "/host:CPU", {"python": [("work", 0, 5000)]})
    xw.write(path, [d0, d1, host])
    return tr.load(path)


def test_only_device_planes_are_read(devices):
    assert sorted(devices) == [0, 1]
    assert len(devices[0].ops) == 6 and len(devices[0].modules) == 2


def test_busy_window_and_idle(devices):
    assert tr.window_ns(devices) == (0, 2000)
    busy = tr.busy_s(devices)
    assert busy[0] == pytest.approx(1200e-9)     # [0,600) + [800,1400)
    assert busy[1] == pytest.approx(700e-9)      # [0,600) + [1900,2000)
    s = tr.summary(devices)
    assert s["window_s"] == pytest.approx(2000e-9)
    assert s["busy_s"] == pytest.approx(950e-9)  # the mean over devices
    assert s["idle_share_worst"] == pytest.approx(1 - 700 / 2000)


def test_self_time_does_not_count_a_while_body_twice(devices):
    ops = devices[0].ops
    self_ns = dict(zip(ops.names, tr.self_ns(ops)))
    assert self_ns["while.1"] == 600 - 200 - 300        # its direct children
    assert self_ns["all-gather.3"] == 300 - 100         # copy.4 nested in it
    assert self_ns["copy.4"] == 100
    top = tr.top_ops(devices, 3)                        # the busiest device: 0
    assert [n for n, _ in top] == ["fusion.1", "all-reduce.1", "fusion.2"]
    assert top[0][1] == pytest.approx(400e-9)
    assert sum(tr.self_ns(ops)) == 1200                 # adds up to busy


def test_collectives_and_their_exposed_part(devices):
    c = tr.collectives(devices)
    # device 0: all-reduce [400,600) + all-gather [1000,1300) = 500 ns; copy.4
    # runs during 100 ns of the all-gather, so 400 ns are exposed
    assert c[0]["collective_s"] == pytest.approx(500e-9)
    assert c[0]["exposed_s"] == pytest.approx(400e-9)
    assert c[0]["busy_s"] == pytest.approx(1200e-9)
    assert c[1]["collective_s"] == pytest.approx(100e-9)
    assert c[1]["exposed_s"] == pytest.approx(100e-9)
    assert c[0]["window_s"] == c[1]["window_s"] == pytest.approx(2000e-9)


def test_program_busy_time_and_op_matching(devices):
    ms = sorted(tr.module_busy_ms(devices, r"jit_step"))
    assert ms == pytest.approx([600e-6, 600e-6, 600e-6])
    assert tr.module_busy_ms(devices, r"jit_other") == pytest.approx([100e-6])
    assert tr.module_busy_ms(devices, r"nothing") == []
    hit = tr.op_self_s(devices, r"^%?fusion")
    assert hit[0] == (pytest.approx(600e-9), 2)
    assert hit[1] == (pytest.approx(600e-9), 2)


def test_idle_gaps_are_named_after_the_program_before_them(devices):
    assert tr.idle_gaps(devices) == [["after:jit_step", pytest.approx(200e-9)]]
    named = tr.idle_gaps(devices, label=lambda a, b: "between_fit_calls")
    assert named == [["between_fit_calls", pytest.approx(200e-9)]]


def test_interval_helpers():
    s, e = tr.union(np.array([0, 5, 10, 30]), np.array([20, 8, 25, 40]))
    assert s.tolist() == [0, 30] and e.tolist() == [25, 40]
    a = (np.array([0, 100]), np.array([50, 150]))
    b = (np.array([40, 120]), np.array([110, 130]))
    assert tr.overlap(a, b) == 10 + 10 + 10
    assert tr.op_name("%all-reduce.5 = f32[2]{0} all-reduce(...)") == "all-reduce.5"
    assert tr.COLLECTIVE.search("%psum_invariant.7 = bf16[512,512]{1,0} all-reduce(bf16[512,512] %fusion)")
    assert tr.COLLECTIVE.search("%ar = (f32[8]) all-reduce-start(f32[8] %x)")
    assert not tr.COLLECTIVE.search("%fusion.465 = f32[2048,50257] fusion(f32[2] %all-reduce.3)")
    assert tr.module_name("jit__decode_paged_fn(123456789)") == "jit__decode_paged_fn"


RECORDED = os.path.join(DATA, "two_chip_v5e.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace recorded on the chip is kept yet")
def test_recorded_two_chip_trace():
    """A matmul and a psum over two chips of the v5e host, recorded on the
    chip in PR 22 (benchmark/tools/record_small_trace.py, my chip run 4):
    four executions of ``jit_body`` on each chip, each a fusion and an
    all-reduce that the trace names ``%psum_invariant.7`` — found by its
    opcode, not its name. What is pinned is structure, not speed."""
    devices = tr.load(RECORDED)
    assert sorted(devices) == [0, 1]
    s = tr.summary(devices)
    assert 0 < s["busy_s"] <= s["window_s"]
    for d in tr.collectives(devices).values():
        assert 0 < d["exposed_s"] <= d["collective_s"] <= d["busy_s"] <= d["window_s"]
        assert d["collective_s"] > 0.8 * d["busy_s"]     # a psum of 512 KB and little else
    assert len(tr.module_busy_ms(devices, "jit_body")) == 8
    assert [n for n, _ in tr.top_ops(devices, 2)] == ["psum_invariant.7", "fusion"]
    assert tr.idle_gaps(devices)[0][0] == "after:jit_body"
    for n, dev in devices.items():
        assert int(tr.self_ns(dev.ops).sum()) == round(tr.busy_s(devices)[n] * 1e9)
