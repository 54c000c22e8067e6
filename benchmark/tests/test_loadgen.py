"""The load generator's reduction from token times to numbers: the bins, the
median second, and the stalls that medians do not see."""

from harness import loadgen


def stream(times, ref_t=0.0):
    s = loadgen.Stream(0, {"prompt": [1], "max_new_tokens": len(times)}, ref_t)
    s.token_t = list(times)
    s.tokens = [0] * len(times)
    return s


def test_seconds_are_whole_and_start_with_the_window():
    s = stream([9.9, 10.0, 10.2, 10.99, 11.0, 12.4, 12.6])
    # window 10.0 .. 12.5: two whole seconds; 12.4 is in neither, 9.9 before it
    assert loadgen.tokens_by_second([s], 10.0, 12.5) == [3, 1]
    assert loadgen.tokens_by_second([s, s], 10.0, 12.5) == [6, 2]
    assert loadgen.tokens_by_second([s], 10.0, 10.5) == []


def test_a_stall_moves_the_total_and_not_the_median_second():
    # four streams, a token every 40 ms for 20 s, and nothing for 2 s in the middle
    steady = [i * 0.04 for i in range(500)]
    stalled = [t for t in steady if not 9.0 <= t < 11.0]
    out = {}
    for name, times in (("steady", steady), ("stalled", stalled)):
        streams = [stream([t + 0.001 * k for t in times]) for k in range(4)]
        out[name] = loadgen.reduce(streams, 0.0, 20.0, "serve_closed")
    assert out["steady"]["tokens_per_s_p50"] == out["stalled"]["tokens_per_s_p50"] == 100
    assert out["stalled"]["tokens_in_window"] == 0.9 * out["steady"]["tokens_in_window"]
    assert sum(out["stalled"]["tokens_by_second"]) == out["stalled"]["tokens_in_window"]
    assert out["stalled"]["itl_max_ms"] > 2000 > out["steady"]["itl_max_ms"]
    # the four streams' long gaps are one stall, found where it ended
    (at, ms), second = out["stalled"]["longest_stalls"][:2]
    assert abs(at - 11.0) < 0.01 and ms == round(out["stalled"]["itl_max_ms"], 1)
    assert second[1] < 50
