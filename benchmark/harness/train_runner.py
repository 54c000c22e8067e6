"""The training runner (traffic kind ``train``): ``Trainer.fit`` as a user
calls it, over the configuration's mesh, on a seeded set of packed batches
made on the host once and cycled until the window closes.

``train_tokens_per_s`` is tokens of the global batch times steps completed,
over the time from the call of ``fit`` to a final ``block_until_ready`` of
the parameters. Telemetry is off in the end-to-end run (it fences every
step); the traced run turns it on and reads ``train_step_seconds`` from it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from . import env, layer_metrics, model as modelmod, trace_reduce, trafficgen

# The first-step loss is a mean over the same tokens at the same seeded
# weights: the program computes it in bf16 over a mesh, the reference in f32
# at "highest" one sequence at a time. bf16 moves a logit by about a
# hundredth, which averages out over 16k tokens; what is left is the order of
# partial sums (on the four-chip host three layouts agreed to 1e-4 relative,
# PERF.md PR 21). A dropped layer, a wrong mask or a missing position table
# moves the loss by far more than a thousandth.
LOSS_RTOL = 1e-3


def packed_batches(job: dict, vocab: int, seed: int):
    """``batches`` x (global_batch, seq_len + 1) token ids: documents packed
    end to end with no cross-document mask, token frequencies Zipfian (rank
    r drawn with probability ~ 1/r, ranks mapped to ids by a seeded
    permutation), as real text is and uniform noise is not."""
    rng = np.random.default_rng(trafficgen.train_seed(seed))
    shape = (int(job["batches"]), int(job["global_batch"]),
             int(job["seq_len"]) + 1)
    ranks = np.minimum((vocab ** rng.random(shape)).astype(np.int64), vocab) - 1
    return rng.permutation(vocab)[ranks].astype(np.int32)


class CycledBatches:
    """The program's iterator protocol over the seeded batches: batch 0, 1,
    ..., cycling, until ``deadline`` (a ``perf_counter`` time) has passed or
    ``limit`` batches were served."""

    def __init__(self, ids: np.ndarray, deadline: float = float("inf"),
                 limit: int = 1 << 62):
        self.ids, self.deadline, self.limit = ids, deadline, limit
        self.batch_size = int(ids.shape[1])
        self.served = 0

    def __iter__(self):
        from deeplearning4j_tpu.data.iterators import DataSet

        while self.served < self.limit and time.perf_counter() < self.deadline:
            b = self.ids[self.served % len(self.ids)]
            self.served += 1
            yield DataSet(b[:, :-1], b[:, 1:])

    def reset(self):
        pass


def reference_loss(cell, seed: int, batch: np.ndarray) -> float:
    """Mean loss of the first batch at the seeded initial weights: the plain
    f32 reference, one sequence at a time, on one chip."""
    ref = modelmod.reference(cell.config)
    mdl = modelmod.build(cell.config)
    params, _ = modelmod.init_weights(mdl, seed)
    losses = [ref.loss(params, row[:-1], row[1:], cell.config) for row in batch]
    del params, mdl
    gc.collect()
    return float(np.mean(losses))


def run(cell, args, t_start: float, watch: env.CompileWatch, dirs: dict) -> dict:
    import jax

    from deeplearning4j_tpu.train import Trainer
    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    job = cell.traffic["job"]
    vocab = int(cell.config["vocab_size"])
    ids = packed_batches(job, vocab, args.seed)
    tokens_per_step = int(job["global_batch"]) * int(job["seq_len"])
    mdl = modelmod.build(cell.config)
    mesh, rules = modelmod.mesh_for(cell.config)
    n_chips = int(np.prod(list(mesh.shape.values()))) if mesh is not None else 1
    modelmod.init_weights(mdl, args.seed, mesh, rules)
    env.log(f"weights on device +{time.perf_counter() - t_start:.1f}s")
    kw = {"mesh": mesh, "rules": rules} if mesh is not None else {}
    tr = Trainer(mdl, seed=args.seed, **kw)
    if mesh is not None:
        mdl.params = None   # the trainer holds the placed copy
    env.log(f"trainer placed +{time.perf_counter() - t_start:.1f}s")
    scores = CollectScoresListener()
    tel = None
    if args.trace:
        from deeplearning4j_tpu.obs import StepTelemetry

        tel = StepTelemetry()
    # warm-up: two steps (batch 0 at the seeded weights, then batch 1 on the
    # step's own outputs), so that every program the window uses exists
    tr.fit(CycledBatches(ids, limit=2), epochs=1, listeners=[scores],
           telemetry=tel)
    jax.block_until_ready(tr.params)
    builds0, misses_setup = watch.builds, watch.misses
    warm_steps = len(scores.scores)
    setup_s = time.perf_counter() - t_start
    env.log(f"warm +{setup_s:.1f}s, losses {[float(s) for _, s in scores.scores]}")

    tracer = None
    if args.trace:
        tracer = env.trace_window(dirs["trace"], max(0.5, 0.4 * args.seconds),
                              min(5.0, args.seconds / 3.0))
    t0 = time.perf_counter()
    batches = CycledBatches(ids, deadline=t0 + args.seconds)
    batches.served = warm_steps          # go on where the warm-up stopped
    tr.fit(batches, epochs=1, listeners=[scores], telemetry=tel)
    jax.block_until_ready(tr.params)
    elapsed = time.perf_counter() - t0
    builds_in_window = watch.builds - builds0
    if tracer is not None:
        tracer.join(120)
    peak_bytes = env.memory_peak_bytes(n_chips)
    losses = [float(s) for _, s in scores.scores]
    steps = len(losses) - warm_steps
    spans = list(tel.tracer.events) if tel is not None else []
    del tr, mdl
    gc.collect()

    ref_loss = reference_loss(cell, args.seed, ids[0])
    tail = losses[-min(8, steps):] if steps else []
    checks = {
        "losses_finite": bool(steps > 0 and np.all(np.isfinite(losses))),
        "first_loss_matches_reference":
            abs(losses[0] - ref_loss) <= LOSS_RTOL * abs(ref_loss),
        "loss_fell": bool(tail and float(np.mean(tail)) < losses[0]),
        "no_compile_in_window": builds_in_window == 0,
    }
    compared = {
        "first_loss_rel_gap": [abs(losses[0] - ref_loss) / abs(ref_loss),
                               LOSS_RTOL],
        "compiles_in_window": [builds_in_window, 0],
        "last_losses_mean_less_first": [
            (float(np.mean(tail)) if tail else float("nan")) - losses[0], 0.0],
    }
    env.log(f"checks {checks}; first loss {losses[0]:.5f} reference {ref_loss:.5f} "
        f"last {tail[-1:]}; {steps} steps in {elapsed:.2f}s")
    result = {"train_tokens_per_s": steps * tokens_per_step / elapsed,
              "setup_s": setup_s, "steps": steps, "chips": n_chips,
              "tokens_per_step": tokens_per_step,
              "memory_peak_bytes": peak_bytes}
    out = {"correct": all(checks.values()), "attempted": steps,
           "failed": int(np.sum(~np.isfinite(losses[warm_steps:]))),
           "device": {**env.device_info(), "memory_peak_bytes": peak_bytes},
           "checks": checks,
           "losses": {"first": losses[0], "reference": ref_loss,
                      "last": tail[-1] if tail else None},
           "setup": {"xla_cache_misses": misses_setup},
           "compared": compared}
    if not args.trace:
        out["metrics"] = {
            m["name"]: {"value": float(result[m["name"]]), "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
        return out
    path = trace_reduce.find_xplane(dirs["trace"])
    trace = trace_reduce.load(path) if path else {}
    summary = trace_reduce.summary(trace)
    out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    run_ctx = layer_metrics.Run(cell, out["device"], result=result, trace=trace,
                                spans=spans)
    out["metrics"] = layer_metrics.read_all(run_ctx)
    out["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                        "idle_gaps": trace_reduce.idle_gaps(trace)}
    return out
