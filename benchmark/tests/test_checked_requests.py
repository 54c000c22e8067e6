"""Which answers are compared with the reference is the SCHEDULE's choice:
the same requests whatever the seed and whatever finished first, spread over
the prompt lengths the cell sends; one that did not complete is reported as
missing, never replaced by another."""

import os

import pytest

from harness import env, loadgen, trafficgen

TRAFFIC = os.path.join(env.BENCH_DIR, "traffic")
SERVING = sorted(f for f in os.listdir(TRAFFIC) if f.endswith(".json")
                 and env.load_json(os.path.join(TRAFFIC, f))["kind"] != "train")


def spec(name):
    return env.load_json(os.path.join(TRAFFIC, name))


def prompt_lens(s, seed, seconds=45.0):
    """{seq: (prompt length, temperature, tokens streamed before it)}."""
    out = {}
    if s["kind"] == "serve_open":
        return {r.seq: (r.fresh_len, r.temperature, 0)
                for r in trafficgen.open_loop_plan(s, seed, seconds)}
    cap = s["server"]["gen_capacity"]
    for ses in trafficgen.session_plan(s, seed):
        history, streamed = ses.context_len, 0
        for r in ses.turns:
            if trafficgen.starts_over(history, r.fresh_len, r.max_new_tokens, cap):
                history = ses.context_len
            out[r.seq] = (history + r.fresh_len, r.temperature, streamed)
            history += r.fresh_len + r.max_new_tokens
            streamed += r.max_new_tokens
    return out


@pytest.mark.parametrize("name", SERVING)
def test_the_schedule_marks_them_whatever_the_seed(name):
    s = spec(name)
    assert "schedule_seed" in s and "checked" in s     # the marking is data
    a = trafficgen.checked_seqs(s, 7, 45.0)
    assert a == trafficgen.checked_seqs(s, 2147490133, 45.0) == sorted(set(a))
    assert len(a) == s["checked"]["count"] and 6 <= len(a) <= 16
    lens = prompt_lens(s, 7)
    greedy = sorted(n for n, t, before in lens.values() if t == 0.0
                    and before <= s["checked"].get("after_tokens", 1e9))
    mine = sorted(lens[q][0] for q in a)
    assert all(lens[q][1] == 0.0 for q in a)           # greedy only
    # spread over the lengths the cell sends: its shortest, its longest, and
    # a median near the candidates' median
    assert mine[0] == greedy[0] and mine[-1] == greedy[-1]
    mid = greedy[len(greedy) // 2]
    assert min(abs(n - mid) for n in mine) <= 0.25 * (greedy[-1] - greedy[0])


@pytest.mark.parametrize("name", [f for f in SERVING
                                  if spec(f)["kind"] == "serve_closed"])
def test_a_slow_server_is_still_sent_them_inside_the_window(name):
    """A checked turn follows at most ``after_tokens`` streamed tokens of its
    session: at 60 ms a token (half again the slowest cell) it is sent
    before the 45 s window closes, and then read to its end."""
    s = spec(name)
    lens = prompt_lens(s, 7)
    stagger = s["sessions"]["start_stagger_s"] * s["sessions"]["count"]
    for q in trafficgen.checked_seqs(s, 7, 45.0):
        assert lens[q][2] <= s["checked"]["after_tokens"]
        assert stagger + 0.060 * lens[q][2] < 45.0


def test_without_a_fixed_schedule_the_seed_draws_them():
    s = spec("chat-sessions-1k.json")
    s.pop("schedule_seed")
    assert trafficgen.checked_seqs(s, 1, 45.0) != trafficgen.checked_seqs(s, 2, 45.0)
    assert trafficgen.checked_seqs(s, 1, 45.0) == trafficgen.checked_seqs(s, 1, 45.0)


def stream(seq, checked, n_prompt, ok=True, done_at=1.0):
    s = loadgen.Stream(0, {"prompt": list(range(n_prompt)), "max_new_tokens": 3,
                           "temperature": 0.0}, ref_t=0.0, seq=seq,
                       checked=checked)
    s.status, s.done, s.consistent = 200, ok, ok
    s.tokens = [seq, seq + 1, seq + 2] if ok else [seq]
    s.token_t = [done_at - 0.2, done_at - 0.1, done_at][:len(s.tokens)]
    return s


def test_two_completion_orders_give_the_same_checked():
    """Same seed, the streams finished in another order and OTHER unchecked
    greedy requests completed: ``checked`` is the same list. The old rule
    (shortest, longest and middle of whatever completed) gave another."""
    marked = [5, 9, 12]
    a = [stream(5, True, 40), stream(9, True, 10), stream(12, True, 90),
         stream(3, False, 500), stream(4, False, 2)]
    b = [stream(12, True, 90, done_at=0.5), stream(4, False, 2),
         stream(9, True, 10, done_at=3.0), stream(5, True, 40, done_at=2.0),
         stream(7, False, 700), stream(8, False, 1)]
    ra, rb = (loadgen.reduce(x, 0.0, 5.0, "serve_closed", marked) for x in (a, b))
    assert ra["checked"] == rb["checked"]
    assert [c["seq"] for c in ra["checked"]] == marked
    assert [len(c["prompt"]) for c in ra["checked"]] == [40, 10, 90]
    assert ra["checked_planned"] == 3 and ra["checked_missing"] == []


def test_one_that_did_not_complete_is_missing_and_never_replaced():
    marked = [5, 9, 12]
    streams = [stream(5, True, 40), stream(9, True, 10, ok=False),
               stream(3, False, 500), stream(4, False, 2)]     # 12 never sent
    r = loadgen.reduce(streams, 0.0, 5.0, "serve_closed", marked)
    assert [c["seq"] for c in r["checked"]] == [5]
    assert r["checked_missing"] == [[9, "short"], [12, "not sent"]]
    assert r["checked_planned"] == 3
