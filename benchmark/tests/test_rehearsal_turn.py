"""The clock's per-layer metrics end to end on the CPU, through ``run.py`` as
the driver calls it: a traced run of a serving cell prints all eight
``turn_*_ms``, every ``stall_*`` and both ``write_lag_*``; the seven phase
metrics add up to ``turn_host_ms`` + ``turn_readback_ms``.
``data/BENCHMARK.turn.test.json`` is ``BENCHMARK.test.json`` with the new
metrics appended as ``BENCHMARK.json`` has them (counts and sums of the host's
clock on a CPU: never device numbers)."""

import os

import pytest

from test_rehearsal import last_line, run_cell

MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "BENCHMARK.turn.test.json")
PHASES = ("turn_prepare_ms", "turn_dispatch_ms", "turn_readback_ms",
          "turn_publish_ms", "turn_admit_ms", "turn_prefill_ms", "turn_self_ms")
STALLS = ("stall_count", "stall_share", "stall_max_ms", "stall_readback_share",
          "stall_offcpu_share", "stall_proc_cpu_share")


@pytest.mark.parametrize("workload", ["tiny-sessions", "tiny-bursts"])
def test_a_traced_serving_run_prints_the_clocks_metrics(workload):
    out = last_line(run_cell(workload, trace=1, manifest=MANIFEST))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for name in PHASES + ("turn_host_ms", "write_lag_p50_ms",
                          "write_lag_p99_ms") + STALLS:
        assert name in m, name    # the driver refuses a line that lacks one
    assert all(m[n] >= 0 for n in PHASES)
    assert m["turn_host_ms"] + m["turn_readback_ms"] \
        == pytest.approx(sum(m[n] for n in PHASES), rel=1e-9)
    assert m["turn_dispatch_ms"] > 0 and m["turn_readback_ms"] > 0
    assert 0 < m["write_lag_p50_ms"] <= m["write_lag_p99_ms"]
    assert out["metrics"]["turn_host_ms"]["unit"] == "ms"
    if m["stall_count"] == 0:       # a loaded machine may well stall
        assert all(m[n] == 0 for n in STALLS)
    else:
        assert m["stall_max_ms"] > 100 and m["stall_share"] > 0
    # serve_gen_decode_seconds times ONE step from its own dispatch to the
    # return of its own readback; since the loop runs a step ahead (PR 41)
    # the next step's enqueue lies in between, so it reads over a step's own
    # dispatch and wait and up to two of the tick's periods (a chunk beside)
    period = m["turn_host_ms"] + m["turn_readback_ms"]
    assert m["turn_dispatch_ms"] + m["turn_readback_ms"] <= m["tick_mean_ms"] \
        <= 2.5 * period
