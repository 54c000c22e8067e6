"""stall_max_ms (ms): the longest stall inside the window: quantile 1 of what
histogram ``serve_gen_stall_seconds`` observed between the window's two
snapshots (buckets 0.1 / 0.25 / 0.5 / 1 / 2.5 / 5 / 10 s; the reading is cut
at the series' max, so one stall reads as its own length). 0 in a window
without a stall: every traced run of a serving cell prints it, and the first
ledger line in which it passes 1000 is the one to read against ``PERF.md``
section 3's table. A stall is a gap between two published ticks, a slot
decoding throughout, longer than max(100 ms, 4 x the mean of the last 64 gaps),
caught by the worker's own clock over the client's whole window
(``obs/trace.py:PhaseClock``). A program without the histogram reads as
nothing. A reader of its own because the declarative ``histogram_quantile``
reads an empty window as nothing. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import layer_metrics

NAME = "serve_gen_stall_seconds"


def read(run):
    if NAME not in run.counters_end:
        return None
    longest = layer_metrics.histogram_quantile(
        run.counters_start, run.counters_end, NAME, 1.0)
    return 1000.0 * (longest or 0.0)
