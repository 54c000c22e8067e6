"""stall_readback_share (%): the part of the window's stall seconds that fell
in ``gen.tick.readback`` (counter ``serve_gen_stall_seconds_total``, the
series ``phase="gen.tick.readback"`` over all phases): the worker asleep in
the device readback, so the device or the runtime took that long. 0 in a
window without a stall (``stall_count`` 0 beside it says which zero it is):
every traced run of a serving cell prints it. A stall is a gap between two
published ticks, a slot decoding throughout, longer than max(100 ms, 4 x the
mean of the last 64 gaps), caught by the worker's own clock over the client's
whole window (``obs/trace.py:PhaseClock``). A program without the counter
reads as nothing. A reader of its own because a declarative ``ratio`` reads a
zero denominator as nothing. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import layer_metrics

STALLED = {"counter": "serve_gen_stall_seconds_total", "at": "window"}


def read(run):
    stalled = layer_metrics.term(run, STALLED)
    if stalled is None:
        return None
    if not stalled:
        return 0.0
    readback = layer_metrics.term(
        run, {**STALLED, "labels": {"phase": "gen.tick.readback"}})
    return 100.0 * readback / stalled
