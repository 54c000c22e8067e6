"""paged_attn_busy_share (%): self time of the device events of the
paged-attention decode kernel (``ops/paged_attention.py``; the
``pallas_call``'s ``name="paged_attn_decode"`` is the HLO instruction's name,
so the trace's event name) over busy time, on the device where it is largest.
The counter that says the kernel engaged: above 0 where a decode step reads
the k/v pools in place, **0.0** in a traced run with no such event (the
gather at capacity is on the path: the parent of PR 46, or a cell whose
layers keep other parts). A reader of its own because the declarative
``trace_ops`` reads a trace without a match as nothing, and a traced line
that lacks a metric its cell lists is refused (PERF.md section 6, PR 40).
An untraced run reads as nothing. Layer: kernels ops/. Moves: itl_p50_ms."""

from harness import trace_reduce

MATCH = r"^%?paged_attn_decode[.\w]* = "


def read(run):
    if not run.trace:
        return None
    busy = trace_reduce.busy_s(run.trace)
    shares = [100.0 * s / busy[n] for n, (s, _)
              in trace_reduce.op_self_s(run.trace, MATCH).items()
              if busy.get(n)]
    return max(shares) if shares else None
