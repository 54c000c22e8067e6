"""stall_offcpu_share (%): the part of the window's stall seconds during
which the generation worker's thread was NOT on a CPU: 100 x (1 - the
thread's own CPU seconds inside its stalls, counter
``serve_gen_stall_thread_cpu_seconds_total``, over the stalls' whole gaps,
``serve_gen_stall_seconds_total``). Near 100: the worker slept or was kept
off the CPU (``stall_readback_share`` and ``stall_proc_cpu_share`` say
which); near 0 with a stall counted: the stall was the worker's own work, and
the phase says which. 0 in a window without a stall (``stall_count`` 0 beside
it says which zero it is): every traced run of a serving cell prints it. A
stall is a gap between two published ticks, a slot decoding throughout, longer
than max(100 ms, 4 x the mean of the last 64 gaps), caught by the worker's own
clock over the client's whole window (``obs/trace.py:PhaseClock``). A program
without the counters reads as nothing. A reader of its own because the
declarative sources have no difference of two terms. Layer: generation
scheduler. Moves: itl_p50_ms."""

from harness import layer_metrics


def read(run):
    stalled = layer_metrics.term(
        run, {"counter": "serve_gen_stall_seconds_total", "at": "window"})
    cpu = layer_metrics.term(
        run, {"counter": "serve_gen_stall_thread_cpu_seconds_total",
              "at": "window"})
    if stalled is None or cpu is None:
        return None
    if not stalled:
        return 0.0
    return 100.0 * (1.0 - cpu / stalled)
