"""The serving runner (traffic kinds ``serve_open`` and ``serve_closed``):
boots the program's ``ModelServer`` in this process, which holds the chip,
lets the load generator (a child that never imports JAX) drive ``/generate``
over HTTP/SSE, and decides ``correct`` outside the window.

From the program it takes only the system under test (``ModelServer``, the
``AotStore``, the zoo model) and its counters (``server.metrics``). Times of
requests and tokens are the client's; the window is the client's too.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

from . import agreement, env, layer_metrics, model as modelmod, trace_reduce

# The agreement gate (which requests, which statistics, which bounds and the
# readings they were set from) is ``harness/agreement.py`` and the
# configuration's ``agreement`` group. What the server computes today: the
# dense configurations hold f32 weights and serve from ONE bf16 copy made
# per params generation (PR 24), the expert configurations hold bf16 once;
# activations are bf16 end to end (``olmoe-1b-7b``, ``starcoderbase-1b``) or
# f32 between exact products against bf16 weights and a bf16 cache
# (``glm-4.7-flash``, ``laguna-s-2.1``); the reference is float32 at
# "highest" throughout.


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, cell, args, port: int, vocab: int, out_path: str,
                 traffic_path: Optional[str] = None):
        here = os.path.dirname(os.path.abspath(__file__))
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(here, "loadgen.py"),
             "--port", str(port),
             "--traffic", traffic_path or cell.traffic_path,
             "--seed", str(args.seed), "--seconds", str(args.seconds),
             "--vocab", str(vocab),
             "--capacity", str(cell.traffic["server"]["gen_capacity"]),
             "--out", out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def expect(self, event: str) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"load generator ended before {event!r} "
                               f"(exit {self.proc.wait()})")
        msg = json.loads(line)
        if msg.get("event") != event:
            raise RuntimeError(f"load generator said {msg}, expected {event!r}")
        return msg

    def go(self) -> None:
        self.proc.stdin.write("go\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for pipe in (self.proc.stdin, self.proc.stdout):
            if pipe is not None:
                pipe.close()


def total(snap: dict, name: str, **labels) -> float:
    return layer_metrics.family_total(snap, name, "value", labels) or 0.0


def boot(cell, seed: int, dirs: dict, t_start: float):
    """Seeded weights on the device and the program's server on a free
    port, its generation stack built. Returns (server, model)."""
    import jax

    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.serve.http import ModelServer

    mdl = modelmod.build(cell.config)
    params, _ = modelmod.init_weights(mdl, seed)
    jax.block_until_ready(params)
    env.log(f"weights on device +{time.perf_counter() - t_start:.1f}s")
    # no cell calls /predict: one batch bucket, the shortest length bucket
    server = ModelServer(mdl, port=0, input_dtype=np.int32,
                         batch_buckets=(1,), length_buckets=(8,),
                         aot_store=AotStore(dirs["aot"]),
                         **cell.traffic["server"]).start()
    try:
        server.batcher()   # the generation stack, not left to the first request
    except BaseException:
        server.stop(drain=False)
        raise
    env.log(f"server booted +{time.perf_counter() - t_start:.1f}s")
    return server, mdl


def drive(cell, args, server, watch: env.CompileWatch, dirs: dict,
          t_start: float, traffic_path: Optional[str] = None,
          in_window=None) -> dict:
    """One load-generator child against a booted server: set-up traffic, the
    window, the drain. Returns the client's results and the program's
    counters around the window. ``in_window()`` runs on this thread while
    the window is open (the knee sweep samples the queue there)."""
    out_path = os.path.join(dirs["tmp"], "client.json")
    child = Child(cell, args, server.port, int(cell.config["vocab_size"]),
                  out_path, traffic_path)
    tracer = None
    try:
        ready = child.expect("ready")
        env.log(f"set-up traffic done +{time.perf_counter() - t_start:.1f}s: {ready}")
        time.sleep(0.1)    # handlers record their metrics after replying
        rec = {"counters_start": server.metrics.snapshot(),
               "misses_setup": watch.misses,
               "setup_s": time.perf_counter() - t_start}
        builds0 = watch.builds
        child.go()
        child.expect("window_start")
        if args.trace:
            tracer = env.trace_window(dirs["trace"], max(0.5, 0.4 * args.seconds),
                                  min(5.0, args.seconds / 3.0))
        if in_window is not None:
            in_window()
        child.expect("window_end")
        rec["counters_end"] = server.metrics.snapshot()
        rec["builds_in_window"] = watch.builds - builds0
        rec["peak_bytes"] = env.memory_peak_bytes(1)
        child.expect("done")
        if tracer is not None:
            tracer.join(120)
        time.sleep(0.2)
        rec["counters_final"] = server.metrics.snapshot()
    finally:
        child.close()
    with open(out_path) as f:
        rec["client"] = json.load(f)
    return rec


def run(cell, args, t_start: float, watch: env.CompileWatch, dirs: dict) -> dict:
    server, mdl = boot(cell, args.seed, dirs, t_start)
    try:
        counters_boot = server.metrics.snapshot()
        rec = drive(cell, args, server, watch, dirs, t_start)
    finally:
        server.stop(drain=False)
    del server
    client, mdl_params = rec["client"], mdl.params
    counters_start, counters_end = rec["counters_start"], rec["counters_end"]
    counters_final, peak_bytes = rec["counters_final"], rec["peak_bytes"]
    builds_in_window, setup_s = rec["builds_in_window"], rec["setup_s"]
    misses_setup = rec["misses_setup"]

    served = total(counters_final, "serve_gen_tokens_total") \
        - total(counters_start, "serve_gen_tokens_total")
    slack = 8 * client["cut"]    # tokens decoded for a client that hung up
    accounting = 0 <= served - client["tokens_received"] <= slack
    compiles = (total(counters_end, "serve_compile_misses_total")
                - total(counters_start, "serve_compile_misses_total"))
    compared = {
        "setup_failures": [len(client["setup_failures"]), 0],
        "tokens_unaccounted": [served - client["tokens_received"], slack],
        "compiles_in_window": [compiles + builds_in_window, 0],
        "requests_failed": [client["failed"], 0],
        "checked_missing": [len(client["checked_missing"]), 0],
    }
    if client["checked"]:
        agree, judged = agreement.check(
            cell, mdl_params, client["checked"],
            os.path.join(dirs["tmp"], "agreement_positions.json"))
        compared.update({"agreement." + k: [c["value"], c["limit"]]
                         for k, c in judged.items()})
    else:
        agree = {"ok": False, "failed": ["nothing to check"]}
    checks = {
        "setup_failures": not client["setup_failures"],
        "token_accounting": bool(accounting),
        "no_compile_in_window": compiles == 0 and builds_in_window == 0,
        "agreement": agree["ok"],
        "requests": client["failed"] == 0 and client["completed"] > 0,
        # every request the schedule marked was answered (late counts)
        "checked_requests": (not client["checked_missing"]
                             and client["checked_planned"] > 0),
    }
    failed_checks = sorted(k for k, v in checks.items() if not v) + [
        "agreement." + k for k in agree["failed"]]
    env.log(f"served {served} received {client['tokens_received']} "
            f"cut {client['cut']}; failures {client['failures']}; "
            f"missing {client['checked_missing']}; agreement {agree}")

    result = {
        "window_tokens_per_s": client["tokens_in_window"] / client["window_s"],
        "ttft_p90_ms": client["ttft_p90_ms"],
        "ttft_p50_ms": client["ttft_p50_ms"],
        "itl_p50_ms": client["itl_p50_ms"],
        "setup_s": setup_s,
        "memory_peak_bytes": peak_bytes,
    }
    out = {"correct": all(checks.values()),
           "attempted": client["attempted"], "failed": client["failed"],
           "device": {**env.device_info(), "memory_peak_bytes": peak_bytes},
           "checks": checks, "failed_checks": failed_checks,
           "agreement": agree,
           "setup": {"xla_cache_misses": misses_setup,
                     "aot_hits": total(counters_boot, "serve_aot_hits_total"),
                     "aot_misses": total(counters_boot, "serve_aot_misses_total")},
           "client": {k: v for k, v in client.items() if k != "checked"}}
    if not args.trace:
        out["metrics"] = {
            m["name"]: {"value": float(result[m["name"]]), "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
            if result.get(m["name"]) is not None}
        out["compared"] = compared      # last: each number beside its limit
        return out
    path = trace_reduce.find_xplane(dirs["trace"])
    trace = trace_reduce.load(path) if path else {}
    summary = trace_reduce.summary(trace)
    out["device"].update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    run_ctx = layer_metrics.Run(
        cell, out["device"], counters_boot=counters_boot,
        counters_start=counters_start, counters_end=counters_end,
        client=client, result=result, trace=trace)
    out["metrics"] = layer_metrics.read_all(run_ctx)
    out["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                        "idle_gaps": trace_reduce.idle_gaps(trace)}
    out["compared"] = compared          # last: each number beside its limit
    return out
