"""flash_fwd_roofline (%): the least time one chip could take for one forward
flash-attention call at the per-device shapes, over the median device time
of one ``flash_fwd`` event in the trace. Shapes: global batch over the mesh's
``data`` axis, heads over its ``model`` axis (``_flash_attend`` shards the
kernel that way), the job's ``seq_len``, the head dimension, causal, in the
compute dtype. Operations and bytes from ``harness/costs.flash_fwd_call``,
peaks from ``harness/peaks``; which of the two bounds it goes to stderr.
Layer: kernels. Moves: train_tokens_per_s."""

import numpy as np

from harness import costs, env, host_spans

KERNEL = r"^%?flash_fwd[.\w]* = "


def read(run):
    if not run.trace or run.device["platform"] != "tpu":
        return None
    ns = host_spans.op_durations_ns(run.trace, KERNEL)
    if not ns:
        return None
    cfg, job = run.cell.config, run.cell.traffic["job"]
    mesh = (cfg.get("layout") or {}).get("mesh", {})
    batch = int(job["global_batch"]) // int(mesh.get("data", 1))
    heads = int(cfg["n_head"]) // int(mesh.get("model", 1))
    dtype_bytes = 2 if cfg["build"].get("compute_dtype") == "bfloat16" else 4
    call = costs.flash_fwd_call(batch, heads, int(job["seq_len"]),
                                costs.head_dim(cfg), dtype_bytes, causal=True)
    least = costs.roofline_s(call["flops"], call["bytes"], run.peak)
    measured = float(np.median(ns)) / 1e9
    env.log(f"flash_fwd_roofline: {len(ns)} calls, median {measured * 1e6:.1f} us, "
            f"least {least['seconds'] * 1e6:.1f} us ({least['bound']}-bound) at "
            f"batch {batch} heads {heads}")
    return 100.0 * least["seconds"] / measured
