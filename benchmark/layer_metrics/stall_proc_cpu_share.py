"""stall_proc_cpu_share (%): the CPU seconds of the WHOLE process, every
thread, inside the worker's stalls (counter
``serve_gen_stall_process_cpu_seconds_total``, ``process_time_ns`` read once a
tick) over the stalls' whole gaps (``serve_gen_stall_seconds_total``). Beside
a worker that was off the CPU (``stall_offcpu_share`` near 100) it splits the
last two suspects: about 100 or more, another thread of the process ran, so
the worker waited for the interpreter lock; near 0, nobody in the process ran
and the machine stood still. It stands where ISSUE 40 asked for the thread's
run-queue wait (``serve_gen_stall_runqueue_seconds_total``), which the
machines with the chip cannot give: their kernel keeps no
``/proc/thread-self/schedstat``. 0 in a window without a stall
(``stall_count`` 0 beside it says which zero it is): every traced run of a
serving cell prints it. Several threads at once read above 100. A stall is a
gap between two published ticks, a slot decoding throughout, longer than
max(100 ms, 4 x the mean of the last 64 gaps), caught by the worker's own
clock over the client's whole window (``obs/trace.py:PhaseClock``). A program
without the counters reads as nothing. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import layer_metrics


def read(run):
    stalled = layer_metrics.term(
        run, {"counter": "serve_gen_stall_seconds_total", "at": "window"})
    cpu = layer_metrics.term(
        run, {"counter": "serve_gen_stall_process_cpu_seconds_total",
              "at": "window"})
    if stalled is None or cpu is None:
        return None
    if not stalled:
        return 0.0
    return 100.0 * cpu / stalled
