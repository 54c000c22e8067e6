"""sala_decode_roofline (%): the least time one chip could take to stream
what one decode step of the sparse-and-linear-attention model must read and
write, over the device-busy time of one decode step (the median
``decode_paged`` execution in the trace, as ``decode_step_device_ms`` reads
it): the whole step's share of its roofline. The bytes
(``harness/costs_minicpm_sala.py``): every layer's matrices and the head at
the parameters' width; the keys and values the sparse layers SELECTED and the
pooled keys they scored, from the program's two counters
(``serve_sparse_kv_positions_read_total`` and ``..._live_total`` over the
window's decode steps, ``serve_gen_decode_seconds``'s count); the live slots'
recurrent state once read and once written (the gauge
``serve_state_slot_bytes`` x the mean slots decoding,
``serve_gen_slot_occupancy``); over the chip's published memory rate
(``harness/peaks``). The sampler, the activations and whatever a step reads
twice are left out of the bytes and are in the time, so this is a lower
bound's share and cannot pass 100. Memory-bound by construction: a decode
step multiplies 16 rows. A program without the counters (another model, or the
parent of the PR that brought this one) reads as nothing. Layer: model maths.
Moves: itl_p50_ms."""

import numpy as np

from harness import costs_minicpm_sala as costs, env, layer_metrics, \
    trace_reduce


def _window(run, counter, field="value"):
    return layer_metrics.term(run, {"counter": counter, "field": field,
                                    "at": "window"})


def read(run):
    if not run.trace or run.device["platform"] != "tpu":
        return None
    cfg = run.cell.config
    if cfg.get("model_type") != "minicpm_sala":
        return None
    positions = _window(run, "serve_sparse_kv_positions_read_total")
    live = _window(run, "serve_sparse_kv_positions_live_total")
    steps = _window(run, "serve_gen_decode_seconds", "count")
    occupancy = _window(run, "serve_gen_slot_occupancy", "sum")
    slot_bytes = layer_metrics.term(run, {"counter": "serve_state_slot_bytes",
                                          "at": "end"})
    if not positions or not live or not steps or occupancy is None \
            or slot_bytes is None:
        return None
    ms = trace_reduce.module_busy_ms(run.trace, "decode_paged")
    if not ms:
        return None
    slots = float(run.cell.traffic["server"]["gen_slots"])
    width = 2 if cfg["build"]["kwargs"].get("dtype") == "bfloat16" else 4
    nbytes = costs.decode_step_bytes(
        cfg, positions / steps, live / steps, occupancy / steps * slots,
        slot_bytes, width)
    least = nbytes / run.peak.hbm_bytes_s
    measured = float(np.median(ms)) / 1e3
    env.log(f"sala_decode_roofline: {len(ms)} steps, median "
            f"{measured * 1e3:.3f} ms, least {least * 1e3:.3f} ms for "
            f"{nbytes / 1e9:.3f} GB ({positions / steps:.0f} positions read "
            f"of {live / steps:.0f} live a step, "
            f"{occupancy / steps * slots:.1f} slots)")
    return 100.0 * least / measured
