"""idle_turn_self_share (%): the part of the traced window in which the
busiest device ran nothing while the batcher's worker thread was inside
gen.turn but in none of its children: between the prefill chunks, the tick
and the loop's back edge. What runs there is the release of the leases and of
the step's device arrays as ``_tick`` returns, at which the interpreter lock
goes to the handler threads the tick has just woken (``host_spans.py``'s
report says how much of it lies under their ``http.stream_write``s). Read by
``harness/host_spans.py`` from the program's ``TraceAnnotation``s on the
``/host:CPU`` plane; the eight ``idle_*_share`` add up to
``device_idle_share`` on one chip. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import host_spans


def read(run):
    return host_spans.idle_share(run, host_spans.GEN_TURN)
