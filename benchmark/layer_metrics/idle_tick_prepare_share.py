"""idle_tick_prepare_share (%): the part of the traced window in which the busiest
device ran nothing while the batcher's worker thread was inside
gen.tick.prepare: the tick's first locked section (copy-on-write, block growth, table rows, host copies of slot state).
Read by ``harness/host_spans.py`` from the program's ``TraceAnnotation``s on
the ``/host:CPU`` plane; the eight ``idle_*_share`` add up to
``device_idle_share`` on one chip. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import host_spans


def read(run):
    return host_spans.idle_share(run, host_spans.GEN_TICK_PREPARE)
