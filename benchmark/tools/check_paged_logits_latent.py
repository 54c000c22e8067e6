#!/usr/bin/env python3
"""``check_paged_logits.py`` for a model whose cache is not keys and values:
prefill, then decode, through the paged cache against the plain reference,
LOGITS and not tokens, at the configuration's real size, outside any timed
window (model-configs guide, section 3.3).

    python3 benchmark/tools/check_paged_logits_latent.py --workload glm47f-longchat-decode --seeds 3,4

The older tool builds ``k`` and ``v`` pools by hand from ``cache_spec``'s
triples. This one takes its pools from the program's own
``serve.paged.build_pools`` and its cache dictionaries from
``nn.generation.as_paged``, so it runs whatever parts a layer's spec names
(``glm-4.7-flash``: a latent and a rope key a token, no heads). The rest is
the older tool's: for each seed, seeded weights as the benchmark makes them,
one sequence of ``--length`` random tokens; the first ``length - last`` go
through ``decode_forward`` in chunks of 64 into pool blocks handed out in a
scrambled order, into one slot of the cell's ``gen_slots`` (the others idle on
the trash block); the last ``--last`` positions are single decode steps at the
cell's batch. Their logits are compared with the reference's full forward of
the whole sequence, as the largest absolute error in standard deviations of
the reference's logits at that position.

Positions whose routing is a tie in the reference are left out, as the
agreement gate leaves them out (``harness/agreement.py``: the margin is the
configuration file's ``agreement.tie_margin``). The limit is the older
tool's 0.15, and so is the control: the reference fed
the weights rounded to 8 bits (float8_e4m3's 3 mantissa bits) has to come out
over it. Readings for ``glm47f-longchat-decode`` are in PERF.md (PR 31). Exit
code 1 where a seed is over the limit or the 8-bit reading under it. The last
line of stdout is one JSON record.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from check_paged_logits import CHUNK, LIMIT  # noqa: E402  (the older tool's)

BLOCK = 16


def run_seed(cell, seed: int, length: int, last: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.generation import (as_paged, cache_parts,
                                                  decode_forward, paged_parts)
    from deeplearning4j_tpu.serve.paged import block_bytes, build_pools
    from harness import agreement, model as modelmod

    mdl = modelmod.build(cell.config)
    params, state = modelmod.init_weights(mdl, seed)
    ref = modelmod.reference(cell.config)
    server = cell.traffic["server"]
    slots, cap = int(server["gen_slots"]), int(server["gen_capacity"])
    maxb = cap // BLOCK
    ids = np.random.default_rng(seed).integers(
        0, int(cell.config["vocab_size"]), length).astype(np.int32)

    n_blocks = slots * maxb + 1
    names = {lk: tuple(parts) for lk, parts in cache_parts(mdl)}
    pools = build_pools(mdl, n_blocks, BLOCK, mdl.dtype)
    slot = slots // 3
    order = np.random.default_rng(seed + 1).permutation(
        np.arange(1, n_blocks))[:maxb].astype(np.int32)
    tables = np.zeros((slots, maxb), np.int32)
    tables[slot] = order

    def caches(pools, tables):
        return {lk: as_paged(pools[lk], tables) for lk in names}

    def back(c):
        return {lk: paged_parts(c[lk], names[lk]) for lk in names}

    # the pools are donated, as the batcher donates them: a model that
    # fills the chip has no room for two
    @functools.partial(jax.jit, donate_argnums=(2,))
    def chunk(params, ids, pools, row, pos):
        _, c = decode_forward(mdl, params, state, ids, caches(pools, row), pos)
        return back(c)

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(params, toks, pools, tables, pos):
        lg, c = decode_forward(mdl, params, state, toks[:, None],
                               caches(pools, tables), pos)
        return lg[:, 0], back(c)

    first = length - last
    for lo in range(0, first, CHUNK):
        hi = min(lo + CHUNK, first)
        buf = np.zeros((1, CHUNK), np.int32)
        buf[0, :hi - lo] = ids[lo:hi]        # right-padded, as the batcher pads
        pools = chunk(params, jnp.asarray(buf), pools,
                      jnp.asarray(tables[slot:slot + 1]),
                      jnp.asarray([lo], jnp.int32))
    got = []
    for t in range(first, length):
        # fresh arrays every step: on the CPU jnp.asarray may alias a numpy
        # buffer, and the step it was handed to runs asynchronously
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        toks[slot], pos[slot] = ids[t], t
        lg, pools = step(params, jnp.asarray(toks), pools,
                         jnp.asarray(tables), jnp.asarray(pos))
        got.append(lg[slot])
    got = np.asarray(jnp.stack(got), np.float32)
    del pools

    def ref_logits(p):
        h, margin = ref.hidden_and_margin(p, ids, cell.config)
        return (np.asarray(ref.logits(p, h[first:], cell.config), np.float32),
                np.asarray(margin[first:]))

    # positions whose routing is a tie in the reference are not judged (the
    # reference's note): there the model itself is undefined to rounding
    want, margin = ref_logits(params)
    judged = margin >= agreement.tie_margin(cell.config)
    got, want = got[judged], want[judged]
    spread = want.std(axis=-1)
    err = np.abs(got - want).max(axis=-1) / spread
    # the weights rounded to 8 bits IN PLACE (donated): two trees of a model
    # that fills the chip do not fit, and the program's run is over.
    # reduce_precision, not a cast to float8 and back, which the TPU's
    # compiler folds away
    eight = jax.jit(lambda t: jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                           mantissa_bits=3), t),
        donate_argnums=0)(params)
    low = np.abs(ref_logits(eight)[0][judged] - want).max(axis=-1) / spread
    return {"seed": seed, "positions": int(err.size),
            "ties_left_out": int((~judged).sum()),
            "cache_parts": {lk: list(parts) for lk, parts in names.items()},
            "cache_token_bytes": block_bytes(mdl, BLOCK, mdl.dtype) // BLOCK,
            "max_err_rel": float(err.max()), "mean_err_rel": float(err.mean()),
            "argmax_agree": float((got.argmax(-1) == want.argmax(-1)).mean()),
            "eight_bit_max_err_rel": float(low.max()),
            "eight_bit_mean_err_rel": float(low.mean())}


def main(argv=None) -> int:
    from harness import env

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="3")
    ap.add_argument("--length", type=int, default=1000)
    ap.add_argument("--last", type=int, default=256)
    ap.add_argument("--manifest", default=env.MANIFEST)
    args = ap.parse_args(argv)
    cell = env.Cell(args.manifest, args.workload)
    env.use_compile_cache(env.cache_dirs(cell.name)["xla"])
    recs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        recs.append(run_seed(cell, seed, args.length, args.last))
        env.log(f"{recs[-1]}")
    ok = all(r["max_err_rel"] <= LIMIT < r["eight_bit_max_err_rel"]
             for r in recs)
    print(json.dumps({"ok": ok, "limit": LIMIT, "device": env.device_info(),
                      "seeds": recs}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
