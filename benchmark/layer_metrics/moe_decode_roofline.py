"""moe_decode_roofline (%): the least time one chip could take to stream the
weights one decode step must read, over the device-busy time of one decode
step (the median ``decode_paged`` execution in the trace, as
``decode_step_device_ms`` reads it). The bytes (``harness/costs_olmoe.py``):
per layer the attention and router matrices plus the share of the experts'
weights the window's decode steps touched (the program's
``serve_moe_experts_touched_total`` over experts x ``serve_moe_layer_programs_total``,
``program="decode"``), plus the head, at the parameters' width; over the
chip's published memory rate (``harness/peaks``). The cache's gather, the
sampler and the activations are left out of the bytes and are in the time,
so this is a lower bound's share and cannot pass 100. Memory-bound by
construction: a decode step multiplies 32 rows. A program without the
counters (no experts) reads as nothing. Layer: model maths. Moves:
itl_p50_ms."""

import numpy as np

from harness import costs_olmoe, env, layer_metrics, trace_reduce


def _decode_steps(run, counter):
    return layer_metrics.term(run, {"counter": counter, "at": "window",
                                    "labels": {"program": "decode"}})


def read(run):
    if not run.trace or run.device["platform"] != "tpu":
        return None
    touched = _decode_steps(run, "serve_moe_experts_touched_total")
    programs = _decode_steps(run, "serve_moe_layer_programs_total")
    if not touched or not programs:
        return None
    ms = trace_reduce.module_busy_ms(run.trace, "decode_paged")
    if not ms:
        return None
    cfg = run.cell.config
    share = touched / (programs * int(cfg["num_experts"]))
    width = 2 if cfg["build"]["kwargs"].get("dtype") == "bfloat16" else 4
    nbytes = costs_olmoe.decode_step_bytes(cfg, share, width)
    least = nbytes / run.peak.hbm_bytes_s
    measured = float(np.median(ms)) / 1e3
    env.log(f"moe_decode_roofline: {len(ms)} steps, median "
            f"{measured * 1e3:.3f} ms, least {least * 1e3:.3f} ms for "
            f"{nbytes / 1e9:.3f} GB at touched share {share:.4f}")
    return 100.0 * least / measured
