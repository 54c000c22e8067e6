"""The generator's copy: byte-identical per seed, different across seeds, and
a fixed amount of work whatever the seed."""

import os
import subprocess
import sys

import pytest

from harness import env, trafficgen

TRAFFIC = os.path.join(env.BENCH_DIR, "traffic")
FILES = sorted(f for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def spec(name, fixed_schedule=True):
    s = env.load_json(os.path.join(TRAFFIC, name))
    if not fixed_schedule:
        s.pop("schedule_seed", None)    # let --seed draw the schedule too
    return s


@pytest.mark.parametrize("name", FILES)
def test_same_seed_same_bytes_other_seed_other_bytes(name):
    s = spec(name)
    a = trafficgen.plan_bytes(s, 7, 45.0)
    assert a == trafficgen.plan_bytes(s, 7, 45.0)
    assert a != trafficgen.plan_bytes(s, 8, 45.0)
    assert a.startswith(b"# bench-traffic-v1 kind=" + s["kind"].encode())


def test_bytes_are_stable_across_processes():
    """No ``hash()``, no dict order: a fresh interpreter with another
    PYTHONHASHSEED expands the same bytes."""
    code = ("import sys, json, hashlib; sys.path.insert(0, %r); import trafficgen;"
            "s = json.load(open(%r));"
            "print(hashlib.sha256(trafficgen.plan_bytes(s, 3, 30.0)).hexdigest())"
            % (os.path.join(env.BENCH_DIR, "harness"),
               os.path.join(TRAFFIC, "short-chat-bursts.json")))
    outs = {subprocess.run([sys.executable, "-c", code], check=True, text=True,
                           capture_output=True,
                           env={**os.environ, "PYTHONHASHSEED": h}).stdout
            for h in ("1", "2")}
    assert len(outs) == 1


def test_a_fixed_schedule_is_the_cells_and_the_seed_draws_the_contents():
    s = spec("short-chat-bursts.json")
    a, b = (trafficgen.open_loop_plan(s, seed, 45.0) for seed in (1, 2))
    shape = lambda plan: [(r.due_us, r.fresh_len, r.max_new_tokens,
                           r.temperature, r.top_k) for r in plan]
    assert shape(a) == shape(b)
    assert all(x.seed != y.seed for x, y in zip(a, b))
    c = spec("longctx-sessions.json")
    sa, sb = (trafficgen.session_plan(c, seed) for seed in (1, 2))
    assert [x.context_len for x in sa] == [x.context_len for x in sb]
    assert [[t.fresh_len for t in x.turns] for x in sa] == \
        [[t.fresh_len for t in x.turns] for x in sb]
    assert all(x.context_seed != y.context_seed for x, y in zip(sa, sb))


def test_open_loop_offers_the_same_work_whatever_the_seed():
    s = spec("short-chat-bursts.json", fixed_schedule=False)
    rate, seconds = s["arrivals"]["mean_rate_rps"], 45.0
    plans = [trafficgen.open_loop_plan(s, seed, seconds) for seed in range(6)]
    for plan in plans:
        assert len(plan) == round(rate * seconds)
        dues = [r.due_us for r in plan]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < seconds * 1e6
        assert all(8 <= r.fresh_len <= 768 and 8 <= r.max_new_tokens <= 256
                   and r.fresh_len + r.max_new_tokens <= 1024 for r in plan)
        greedy = sum(1 for r in plan if r.temperature == 0.0)
        assert abs(greedy - len(plan) / 2) <= 1
    out_tokens = [sum(r.max_new_tokens for r in p) for p in plans]
    in_tokens = [sum(r.fresh_len for r in p) for p in plans]
    # stratified draws: totals agree to a few percent (independent draws of a
    # Pareto with alpha 1.8 would swing by tens of percent)
    assert max(out_tokens) / min(out_tokens) < 1.03
    assert max(in_tokens) / min(in_tokens) < 1.03


def test_bursts_fill_a_fixed_share_of_the_window():
    import random

    s = spec("short-chat-bursts.json", fixed_schedule=False)
    for seed in range(5):
        w = trafficgen.burst_windows(s, random.Random(seed), 45.0)
        assert len(w) == 5                      # 4 whole cycles and a half
        assert sum(b - a for a, b in w) == pytest.approx(9.0)   # 45 x 2/10
        assert all(a2 >= b1 for (_, b1), (a2, _) in zip(w, w[1:]))
    plan = trafficgen.open_loop_plan(s, 1, 45.0)
    w = trafficgen.burst_windows(
        s, random.Random(trafficgen._sub_seed(1, "open")), 45.0)
    inside = sum(1 for r in plan if any(a <= r.due_us / 1e6 < b for a, b in w))
    # 9 s at x3 against 36 s at x1: 27/63 of the arrivals, give or take
    assert abs(inside / len(plan) - 27 / 63) < 0.08


def test_sessions_are_multi_turn_and_fit_the_server():
    s = spec("longctx-sessions.json", fixed_schedule=False)
    sessions = trafficgen.session_plan(s, 4)
    assert len(sessions) == 16
    assert all(2048 <= x.context_len <= 5632 for x in sessions)
    assert sorted(x.context_len for x in sessions) != \
        sorted(x.context_len for x in trafficgen.session_plan(s, 5))
    cap = s["server"]["gen_capacity"]
    for x in sessions:
        assert len(x.turns) == 48
        first = x.turns[0]
        assert x.context_len + first.fresh_len + first.max_new_tokens <= cap
        assert all(64 <= t.fresh_len <= 256 and 128 <= t.max_new_tokens <= 384
                   for t in x.turns)
    # contexts: log-uniform strata, so their sum barely moves with the seed
    sums = [sum(x.context_len for x in trafficgen.session_plan(s, k))
            for k in range(6)]
    assert max(sums) / min(sums) < 1.05


def test_tokens_are_seeded_and_in_vocabulary():
    a = trafficgen.tokens(11, 300, 49152)
    assert a == trafficgen.tokens(11, 300, 49152) != trafficgen.tokens(12, 300, 49152)
    assert len(a) == 300 and all(0 <= t < 49152 for t in a)


def test_warmup_touches_every_chunk_bucket():
    lens = sorted(len(r["prompt"]) for r in trafficgen.warmup_requests(64, 1024, 500))
    assert lens == [5, 13, 29, 64, 133]   # buckets 8, 16, 32, 64, then 3 chunks
    assert all(r["max_new_tokens"] == 4 for r in trafficgen.warmup_requests(64, 1024, 500))


def test_length_distributions_invert_their_cdf():
    d = trafficgen.LengthDist.from_dict(
        {"kind": "lognormal", "p1": 96, "p2": 0.9, "min": 8, "max": 768})
    assert d.at(0.5) == 96
    assert d.at(0.999999) == 768 and d.at(1e-7) == 8
    p = trafficgen.LengthDist.from_dict(
        {"kind": "pareto", "p1": 24, "p2": 1.8, "min": 8, "max": 256})
    assert p.at(0.0) == 24 and p.at(0.5) == round(24 * 2 ** (1 / 1.8))
    u = trafficgen.LengthDist.from_dict(
        {"kind": "loguniform", "p1": 2048, "p2": 5632, "min": 2048, "max": 5632})
    assert u.at(0.5) == round((2048 * 5632) ** 0.5)
