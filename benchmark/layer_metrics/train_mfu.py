"""train_mfu (%): tokens per second of the traced run, times the operations
the forward and backward passes need per token (from shapes:
``harness/costs.py``; recomputation under ``remat`` not counted), over chips
times the chip's published bf16 peak. Layer: training loop. Moves:
train_tokens_per_s."""

from harness import costs


def read(run):
    rate = run.result.get("train_tokens_per_s")
    if not rate or run.device["platform"] != "tpu":
        return None
    seq = int(run.cell.traffic["job"]["seq_len"])
    flops = costs.train_flops_per_token(run.cell.config, seq)
    return 100.0 * rate * flops / (run.result["chips"] * run.peak.bf16_flops)
