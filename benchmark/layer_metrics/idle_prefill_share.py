"""idle_prefill_share (%): the part of the traced window in which the busiest
device ran nothing while the batcher's worker thread was inside
gen.prefill_chunk and gen.first_token: enqueueing a prefill chunk, and the first-token sample with its readback.
Read by ``harness/host_spans.py`` from the program's ``TraceAnnotation``s on
the ``/host:CPU`` plane; the eight ``idle_*_share`` add up to
``device_idle_share`` on one chip. Layer: generation scheduler. Moves:
itl_p50_ms."""

from harness import host_spans


def read(run):
    return host_spans.idle_share(run, host_spans.GEN_PREFILL_CHUNK, host_spans.GEN_FIRST_TOKEN)
