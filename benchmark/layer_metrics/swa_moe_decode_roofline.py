"""swa_moe_decode_roofline (%): the least time one chip could take to stream
what one decode step of the window-and-full-attention expert model must read,
over the device-busy time of one decode step (the median ``decode_paged``
execution in the trace, as ``decode_step_device_ms`` reads it): the whole
step's share of its roofline. The bytes (``harness/costs_laguna.py``): per
layer the attention matrices at its kind's head count and the head-wise gate;
per expert layer the router, the shared expert and the share of the HELD
routed experts' weights the window's decode steps touched (the program's
``serve_moe_experts_touched_total`` over held experts x
``serve_moe_layer_programs_total``, ``program="decode"``); the dense layer;
the head; at the parameters' width; PLUS the live cache of both block groups
(the gauge ``serve_kv_live_bytes`` at the window's end: the full group's
contexts and the window group's rings); over the chip's published memory rate
(``harness/peaks``). The sampler, the activations and whatever a step reads
twice are left out of the bytes and are in the time, so this is a lower
bound's share and cannot pass 100. Memory-bound by construction: a decode step
multiplies 32 rows. A program without the counters (another model, or the
parent of the PR that brought this one) reads as nothing. Layer: model maths.
Moves: itl_p50_ms."""

import numpy as np

from harness import costs_laguna, env, layer_metrics, trace_reduce


def _decode_steps(run, counter):
    return layer_metrics.term(run, {"counter": counter, "at": "window",
                                    "labels": {"program": "decode"}})


def read(run):
    if not run.trace or run.device["platform"] != "tpu":
        return None
    cfg = run.cell.config
    if cfg.get("model_type") != "laguna":
        return None
    touched = _decode_steps(run, "serve_moe_experts_touched_total")
    programs = _decode_steps(run, "serve_moe_layer_programs_total")
    live = layer_metrics.term(run, {"counter": "serve_kv_live_bytes",
                                    "at": "end"})
    if not touched or not programs or live is None:
        return None
    ms = trace_reduce.module_busy_ms(run.trace, "decode_paged")
    if not ms:
        return None
    share = touched / (programs * costs_laguna.held_experts(cfg))
    width = 2 if cfg["build"]["kwargs"].get("dtype") == "bfloat16" else 4
    nbytes = costs_laguna.decode_step_bytes(cfg, share, live, width)
    least = nbytes / run.peak.hbm_bytes_s
    measured = float(np.median(ms)) / 1e3
    env.log(f"swa_moe_decode_roofline: {len(ms)} steps, median "
            f"{measured * 1e3:.3f} ms, least {least * 1e3:.3f} ms for "
            f"{nbytes / 1e9:.3f} GB (live cache {live / 1e9:.3f} GB) at "
            f"touched share {share:.4f}")
    return 100.0 * least / measured
