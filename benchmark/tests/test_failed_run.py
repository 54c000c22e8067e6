"""A run made to fail says which check failed and by how much. The whole of
``run.py`` is driven in this process (only the look for a chip is the
rehearsal manifest's, which takes the CPU) with the timed path broken
underneath: a token altered where it is produced. ``correct`` comes out
false, ``failed_checks`` names the agreement gate's numbers, and the last
key of the result's line holds each number compared beside its limit, as do
the last lines of standard error."""

import json
import os

import pytest

import run as bench_run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CASES = {"dense": ("BENCHMARK.test.json", "tiny-sessions"),
         "experts": ("BENCHMARK.olmoe.test.json", "tiny-olmoe-sessions")}


def drive(capfd, manifest, workload):
    rc = bench_run.main(["--workload", workload, "--seed", "5", "--seconds", "3",
                         "--trace", "0", "--manifest",
                         os.path.join(DATA, manifest)])
    out, err = capfd.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_token_altered_where_it_is_produced_is_not_correct(case, capfd,
                                                            monkeypatch):
    from deeplearning4j_tpu.serve import programs

    real = programs.GenPrograms.decode
    steps = [0]

    def altered(self, *args, **kw):
        nxt = real(self, *args, **kw)
        steps[0] += 1
        if steps[0] % 2:
            return nxt
        # every second step's tokens come out as their neighbours: what is
        # STREAMED is altered, the cache and the device's carry are not
        return nxt.at[:self.slots].set(nxt[:self.slots] ^ 1)

    monkeypatch.setattr(programs.GenPrograms, "decode", altered)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out, err = drive(capfd, *CASES[case])
    assert out["correct"] is False
    assert out["checks"]["agreement"] is False
    # every other check holds: the requests were served and counted
    assert [k for k in out["failed_checks"] if "." not in k] == ["agreement"]
    assert "agreement.big_gap_share" in out["failed_checks"]
    # the comparison that refused the run, number beside limit, last in the line
    assert list(out)[-1] == "compared"
    value, limit = out["compared"]["agreement.big_gap_share"]
    assert value > limit == out["agreement"]["big_gap_share_limit"]
    assert value == out["agreement"]["big_gap_share"] > 0.15
    assert out["agreement"]["flip_share"] >= value   # reported beside it
    for name in ("agreement.max_gap_rel",
                 "agreement.left_out_share", "agreement.control_flip_share_min",
                 "requests_failed", "checked_missing", "tokens_unaccounted"):
        assert len(out["compared"][name]) == 2
    tail = err.strip().splitlines()[-len(out["compared"]) - 1:]
    assert tail[-1].startswith("[bench] checks failed: ['agreement'")
    assert any(line.startswith("[bench] compared agreement.big_gap_share = ")
               and " limit " in line for line in tail)
