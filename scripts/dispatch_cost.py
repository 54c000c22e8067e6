#!/usr/bin/env python
"""What the host pays to enqueue one decode step through the executable
store's wrapper, split in three, on any host and with no weights.

    JAX_PLATFORMS=cpu python scripts/dispatch_cost.py
    JAX_PLATFORMS=cpu python scripts/dispatch_cost.py \
        --config benchmark/configs/olmoe-1b-7b.json --slots 32

From the configuration file's ``build`` block the model is built and its
parameters are taken as abstract shapes (``jax.eval_shape``: nothing is
initialised). The decode step's operand list (``GenPrograms.signatures``:
parameters, state, tokens, pools, tables and the eight slot vectors) is then
made with every leaf cut to ONE element: the costs below are a leaf's, not a
byte's, so the tree and the ranks are the configuration's and the widths are
not (nor the compute dtype: the parameters keep the dtype they are made in).
Over that list, microseconds a call (median):

- ``lookup_us``: ``aot.keys.structural_key`` and the dict lookup, what
  ``AotFunction.__call__`` does before it calls its executable;
- ``signature_us``: ``aot.keys.call_signature``, the string the store's key
  hashes, built once an executable acquired (on every call before PR 50);
- ``executable_call_us``: the loaded executable's own call on a stand-in
  program that takes the same operands, donates the pools and returns what
  the decode step returns (its arithmetic is one add: the enqueue is timed,
  not the device);
- ``wrapped_call_us``: the same call through an ``AotFunction`` over a
  scratch store, the whole of what the scheduler's ``decode`` pays the
  wrapper.

Host times of THIS machine's CPU: they say how the enqueue divides, never how
long a step takes on a chip. ``PERF.md`` reads them beside the chip's
``turn_dispatch_ms``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))   # the harness package


def decode_operands(config: dict, slots: int, block_size: int):
    """The decode step's operands for ``config``'s model as
    ``GenPrograms.signatures`` lists them, every leaf one element."""
    import jax
    import jax.numpy as jnp
    from harness import model as modelmod

    from deeplearning4j_tpu.serve.paged import STATE, cache_groups
    from deeplearning4j_tpu.serve.programs import GenPrograms

    model = modelmod.build(config)
    params, state = jax.eval_shape(model.init, jnp.uint32(0))
    groups = [g.name for g in cache_groups(model)]
    blocks = [g for g in groups if g != STATE]

    def a_group(n):
        return {g: n for g in blocks} if len(blocks) > 1 else n

    # two blocks a pool (the trash block and one more), tables one wide:
    # the programs are built and never traced
    programs = GenPrograms(
        model, slots=slots, table_blocks=a_group(1),
        vocab=int(config["vocab_size"]), kv_blocks=a_group(2),
        block_size=block_size, chunk_buckets=(), metrics=None,
        compile_counter=None)
    (operands,) = programs.signatures(params, state)["gen_decode_paged"]
    return jax.tree.map(
        lambda leaf: jnp.zeros((1,) * len(leaf.shape), leaf.dtype), operands)


def _stand_in(params, state, toks, pools, tables, pos, keys, temps, tks,
              fresh, set_toks, set_pos, set_keys):
    return toks + 1, pools, pos + 1, keys


def _median_us(call, calls: int) -> float:
    call()                                       # first use of each path
    times = []
    for _ in range(calls):
        t0 = time.perf_counter_ns()
        call()
        times.append(time.perf_counter_ns() - t0)
    return statistics.median(times) / 1e3


def measure(operands, calls: int) -> dict:
    import jax

    from deeplearning4j_tpu.aot import AotFunction, AotStore
    from deeplearning4j_tpu.aot.keys import call_signature, structural_key

    held = {structural_key(operands): None}
    jitted = jax.jit(_stand_in, donate_argnums=(3,))
    ops = list(operands)

    def through(fn):
        def call():
            out = fn(*ops)
            ops[3] = out[1]                      # the donated pools, back
            return out
        return call

    with tempfile.TemporaryDirectory() as scratch:
        wrapped = AotFunction(jitted, tag="dispatch_cost", arch="stand-in",
                              store=AotStore(scratch), donate_argnums=(3,))
        wrapped.warm(*ops)
        (exe,) = wrapped.executables.values()
        out = {
            "leaves": len(jax.tree.leaves(operands)),
            "lookup_us": _median_us(
                lambda: held.get(structural_key(ops)), calls),
            "signature_us": _median_us(lambda: call_signature(ops), calls),
            "executable_call_us": _median_us(through(exe), calls),
            "wrapped_call_us": _median_us(through(wrapped), calls),
        }
        jax.block_until_ready(ops[3])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmark", "configs", "starcoderbase-1b.json"))
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--calls", type=int, default=300)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    import jax

    out = {"config": config["name"], "platform": jax.devices()[0].platform,
           **measure(decode_operands(config, args.slots, args.block_size),
                     args.calls)}
    for name, value in out.items():
        shown = round(value, 1) if isinstance(value, float) else value
        print(f"{name:>20}  {shown}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
