"""The agreement gate of the serving cells: served greedy tokens against the
configuration's plain float32 reference, outside the window.

**What is compared.** The load generator hands back the requests that the
traffic's SCHEDULE marked as checked (``trafficgen.checked_seqs``: the same
requests in every run of a cell, whatever the timing), each with its prompt
and the tokens the server streamed. The reference runs once over prompt +
tokens; at each of the last ``CHECK_LAST`` generated positions

    gap = (largest reference logit) - (reference logit of the served token)

in units of the standard deviation of the reference's logits at that
position (``gap_rel``): 0 where the served token is the reference's first
choice. The logits of seeded random weights are flat, so close candidates
swap under bf16 rounding; what must hold is that the swaps are SMALL.

**Three rules, stated once, for every configuration.**

1. *What is stored is given.* A configuration whose file states
   ``cache_dtype`` has its reference round what a deployment's cache stores
   to that width once, where it is produced, and compute everything from it
   in float32 (the reference reads the key; the record says which width was
   given). What is computed is compared; what is stored is an input.
2. *A routing tie is not judged.* A reference of a model with a router
   returns, beside the hidden states, each position's smallest selection
   margin over its expert layers (``hidden_and_margin``: the k-th score minus
   the (k+1)-th; infinite for a dense model). Where that margin is under the
   configuration's ``tie_margin``, which expert runs is undefined to
   rounding and the position is left out: counted (``positions_left_out``,
   ``left_out_share``) and capped (``left_out_share_max``), so the rule
   cannot empty the check. It reads the REFERENCE's scores only.
3. *A share over a stated size, not a mean.* The mean gap is a sum of rare
   events (0 almost everywhere, 0.01-0.04 at a few dozen swaps): on ONE
   program it ranged twentyfold from seed to seed (PERF.md section 6, PR
   41), so no bound on it tells a sound program from a faulty one. Judged
   instead: ``big_gap_share``, the share of judged positions whose gap
   passes the configuration's ``big_gap``, and ``max_gap_rel`` against the
   configuration's ``gap_backstop``. ``flip_share`` (judged positions whose
   served token is not the reference's first choice) and the mean are
   reported and NOT judged: in the two configurations that compute in bf16
   end to end the 8-bit control flips as few as 7% of positions where a
   sound run flips up to 5.1%, so no limit on the NUMBER of swaps stands
   between the two; their SIZE does (PERF.md section 2).

**Where the bounds live, and the rule that sets them.** In the
configuration's file, group ``agreement``, each beside the two readings it
was set from (``readings``, and as numbers ``sound_max`` and
``control_8bit_min``): the standing tree on the chip over a dozen seeds or
more gives the largest reading of each number, the 8-bit control (below) the
smallest. A number is compared only where the control's smallest reading
is ``SEPARATION`` times the sound runs' largest or more. ``big_gap`` is the
largest single judged gap read when the bounds were set, so the share over
it reads 0 there and its limit is no configuration's but one constant,
``big_gap_share_limit`` (``SHARE_FLOOR``, and never fewer than
``BIG_GAPS_FLOOR`` positions); the control reads 18 to 370 times that.
``gap_backstop`` is ``BACKSTOP_MULTIPLE`` times the largest gap that one
swapped expert or one sound run was read to give, and never over
``CONTROL_SHARE`` of the control's smallest largest gap: every limit stays
under what the control reads. It is ``null`` where the largest gap does not
separate (``olmoe-1b-7b``: 2.6 times), and ``max_gap_rel`` is then reported
without a limit. The same rule sets the rehearsal configurations' bounds
from CPU readings (``benchmark/tests``), where the gate is shown to fail for
both controls, and ``benchmark/tests/test_agreement.py`` holds every
configuration's limits to its two readings through ``judge``.

**The gate must be able to fail.** (i) In every run the same gaps are taken
again with each prompt replaced by one token repeated (the served tokens
kept): a cache that returned the same wrong rows everywhere. Its
``flip_share`` must come out at ``CONTROL_MIN`` or more (random OTHER
prompts are too weak a control: seeded random weights attend almost
uniformly, PERF.md section 7). (ii) Outside the runs
(``tools/agreement_readings.py --control``, ``benchmark/tests``): the
reference itself, fed the weights rounded to 8 bits (3 mantissa bits,
float8_e4m3's, the nearest precision below bf16), put in the program's
place: at each judged position the token IT puts first is read under the
float32 reference like a served one, and it must fail a bound.
"""

from __future__ import annotations

import functools
import time

import numpy as np

CHECK_LAST = 256          # generated positions checked per request, from its end
SEPARATION = 3.0          # compared: the control's smallest over the sound runs' largest
SHARE_FLOOR = 0.002       # the share over big_gap: 3 positions in 1500,
BIG_GAPS_FLOOR = 3        # and, in a short run, never fewer than this many
BACKSTOP_MULTIPLE = 4.0   # the single-gap backstop over the largest gap read,
CONTROL_SHARE = 0.6       # and never over this share of the 8-bit control's smallest
CONTROL_MIN = 0.5         # the repeated-token control: half of its tokens flip
#   or more. Between what a gate blind to the prompt would read (the served
#   tokens' own flip_share, at most 0.051) and what sound runs read on the
#   chip, 0.849-1.0 (PR 44; 0.849 and 0.866 twice, in the dense cells' short
#   answers: a floor of 0.9 refused two sound runs of 53, so the floor is no
#   nearer the sound side)
RULE_KEYS = ("tie_margin", "left_out_share_max", "big_gap", "gap_backstop")


def rules(config: dict) -> dict:
    """The configuration's ``agreement`` group: its bounds as numbers
    (``gap_backstop`` may be null: the largest gap is then not compared)."""
    group = config.get("agreement")
    if not isinstance(group, dict):
        raise SystemExit(
            f"configuration {config.get('name')!r} states no `agreement` "
            f"group: {', '.join(RULE_KEYS)} (harness/agreement.py)")
    missing = [k for k in RULE_KEYS if k not in group]
    if missing:
        raise SystemExit(f"configuration {config.get('name')!r}: `agreement` "
                         f"lacks {missing}")
    return {k: None if k == "gap_backstop" and group[k] is None   # not compared
            else float(group[k]) for k in RULE_KEYS}


def big_gap_share_limit(positions: int) -> float:
    """The one limit on the share of judged positions over ``big_gap``."""
    return max(SHARE_FLOOR, BIG_GAPS_FLOOR / max(1, positions))


def tie_margin(config: dict) -> float:
    """The routing margin under which a position is not judged (0 for a
    configuration without a router)."""
    return rules(config)["tie_margin"]


@functools.lru_cache(maxsize=None)
def _gap_rel_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(lg, nxt):
        got = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        return (jnp.max(lg, axis=-1) - got) / jnp.std(lg, axis=-1)

    return f


def _gap_rel(lg, nxt):
    """How far each row's token ``nxt`` sits below the row's largest logit,
    in standard deviations of the row's logits."""
    return np.asarray(_gap_rel_fn()(lg, nxt))


def logits_rows(ref, params, prompt, tokens, cfg: dict, pad_to: int,
                last: int = CHECK_LAST):
    """The reference over prompt + tokens, right-padded to ``pad_to`` so that
    every request of a cell shares one compiled program (causal: the padding
    cannot reach back; routing is per token). Returns the logits of the last
    ``last`` positions that predict a generated token, the token each
    predicts, and the routing margin there: ``(lg, nxt, margin, keep)`` with
    ``keep`` the slice of rows that are generated positions."""
    import jax
    import jax.numpy as jnp

    seq = list(prompt) + list(tokens)
    pad_to = max(pad_to, len(seq), last)
    ids = np.zeros(pad_to, np.int32)
    ids[:len(seq)] = seq
    h, margin = ref.hidden_and_margin(params, ids, cfg)
    lo = max(0, len(seq) - 1 - last)          # row j is position lo + j
    rows = jax.lax.dynamic_slice_in_dim(h, lo, last, axis=0)
    nxt = np.zeros(last, np.int32)            # position t predicts token t+1
    upto = min(last, len(seq) - 1 - lo)
    nxt[:upto] = seq[lo + 1:lo + 1 + upto]
    keep = slice(max(0, len(prompt) - 1 - lo), upto)
    margin = np.asarray(margin)[lo:lo + last]
    return ref.logits(params, rows, cfg), jnp.asarray(nxt), margin, keep


def positions(ref, params, prompt, tokens, cfg: dict, pad_to: int,
              last: int = CHECK_LAST):
    """``(gap_rel, margin)`` of one served request's judged positions."""
    lg, nxt, margin, keep = logits_rows(ref, params, prompt, tokens, cfg,
                                        pad_to, last)
    return _gap_rel(lg, nxt)[keep], margin[keep]


def eight_bit(params):
    """``params`` rounded to 8 bits IN PLACE (donated: two trees of a model
    do not fit a chip): ``reduce_precision`` to float8_e4m3's 3 mantissa
    bits (a cast to float8 and back is folded away by the TPU's compiler);
    the exponent stays the leaf's own, as a scaled 8-bit format's would."""
    import jax
    import jax.numpy as jnp

    def low(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return jax.lax.reduce_precision(
            a, exponent_bits=jnp.finfo(a.dtype).nexp, mantissa_bits=3)

    return jax.jit(lambda t: jax.tree.map(low, t), donate_argnums=0)(params)


def control(cell, params, checked: list, dump_path: str = None) -> dict:
    """The lower-precision control over one run's checked requests: the
    reference fed the weights rounded to 8 bits takes the program's place. At
    each judged position the token IT puts first is read under the float32
    reference like a served one (it need not decode). ``params`` is donated.
    Returns the ``shares`` the gate would have judged."""
    import jax.numpy as jnp

    from . import model as modelmod

    cfg, rule = cell.config, rules(cell.config)
    ref = modelmod.reference(cfg)
    pad_to = int(cell.traffic["server"]["gen_capacity"])
    rows = []
    for req in checked:          # the float32 reference's logits, to the host
        lg, _, margin, keep = logits_rows(ref, params, req["prompt"],
                                          req["tokens"], cfg, pad_to)
        rows.append((np.asarray(lg), margin, keep))
    low = eight_bit(params)
    per_request = []
    for req, (lg, margin, keep) in zip(checked, rows):
        low_lg, _, _, _ = logits_rows(ref, low, req["prompt"], req["tokens"],
                                      cfg, pad_to)
        first = jnp.argmax(low_lg, axis=-1).astype(jnp.int32)
        per_request.append({"seq": req.get("seq"),
                            "prompt_len": len(req["prompt"]),
                            "gap_rel": _gap_rel(jnp.asarray(lg), first)[keep],
                            "margin": margin[keep]})
    _dump(per_request, dump_path)
    return _shares_of(per_request, rule)


def _shares_of(per_request: list, rule: dict) -> dict:
    return shares(np.concatenate([r["gap_rel"] for r in per_request]),
                  np.concatenate([r["margin"] for r in per_request]), rule)


def _dump(per_request: list, path: str = None) -> None:
    """Every judged position's gap and routing margin, request by request:
    what a bound or a tie margin is set from."""
    import json

    if path:
        with open(path, "w") as f:
            json.dump([{**r, "gap_rel": [float(x) for x in r["gap_rel"]],
                        "margin": [float(min(x, 1e30)) for x in r["margin"]]}
                       for r in per_request], f)


def shares(gap_rel, margin, rule: dict) -> dict:
    """The judged statistics of one set of positions."""
    gap_rel, margin = np.asarray(gap_rel, np.float64), np.asarray(margin)
    judged = margin >= rule["tie_margin"]
    g = gap_rel[judged]
    n = int(g.size)
    return {
        "positions": n,
        "positions_left_out": int((~judged).sum()),
        "left_out_share": float((~judged).mean()) if judged.size else 0.0,
        "argmax_flips": int((g > 0).sum()),
        "flip_share": float((g > 0).mean()) if n else 0.0,
        "big_gap_share": float((g > rule["big_gap"]).mean()) if n else 0.0,
        "max_gap_rel": float(g.max()) if n else 0.0,
        "mean_gap_rel": float(g.mean()) if n else 0.0,
        "finite": bool(np.isfinite(gap_rel).all()),
    }


def judge(true: dict, control: dict, rule: dict) -> dict:
    """Each judged number beside its limit, and which of them failed.
    ``true`` and ``control`` are ``shares`` of the served tokens and of the
    repeated-token control."""
    compared = {
        "positions_min": (true["positions"], ">=", 1),
        "left_out_share": (true["left_out_share"], "<=",
                           rule["left_out_share_max"]),
        "big_gap_share": (true["big_gap_share"], "<=",
                          big_gap_share_limit(true["positions"])),
    }
    if rule["gap_backstop"] is not None:     # null: reported, not compared
        compared["max_gap_rel"] = (true["max_gap_rel"], "<=",
                                   rule["gap_backstop"])
    compared["control_flip_share_min"] = (control["flip_share"], ">=",
                                          CONTROL_MIN)
    failed = [k for k, (v, op, lim) in compared.items()
              if not (np.isfinite(v) and (v <= lim if op == "<=" else v >= lim))]
    if not true["finite"]:
        failed.append("finite")
    return {"compared": {k: {"value": v, "limit": lim}
                         for k, (v, op, lim) in compared.items()},
            "failed": failed}


def check(cell, params, checked: list, dump_path: str = None):
    """The gate over one run's checked requests. Returns the record that
    the result's line carries (``ok``, every judged number with its
    ``*_limit`` beside it, ``failed``: the names of those over their limit)
    and ``{name: {"value", "limit"}}`` of what was compared."""
    from . import model as modelmod

    t0 = time.perf_counter()
    cfg = cell.config
    rule = rules(cfg)
    ref = modelmod.reference(cfg)
    pad_to = int(cell.traffic["server"]["gen_capacity"])
    vocab = int(cfg["vocab_size"])
    rng = np.random.default_rng(2)
    per_request, repeated = [], []
    for req in checked:
        gap, margin = positions(ref, params, req["prompt"], req["tokens"],
                                cfg, pad_to)
        per_request.append({"seq": req.get("seq"), "prompt_len": len(req["prompt"]),
                            "gap_rel": gap, "margin": margin})
        other = [int(rng.integers(0, vocab))] * len(req["prompt"])
        gap, margin = positions(ref, params, other, req["tokens"], cfg, pad_to)
        repeated.append({"gap_rel": gap, "margin": margin})
    true = _shares_of(per_request, rule)
    control = _shares_of(repeated, rule)
    verdict = judge(true, control, rule)
    rec = {"sequences": len(checked),
           "prompt_lens": [r["prompt_len"] for r in per_request],
           "cache_dtype_given": cfg.get("cache_dtype"),
           "tie_margin": rule["tie_margin"], "big_gap": rule["big_gap"]}
    rec.update({k: v for k, v in true.items() if k != "finite"})
    rec["control_flip_share"] = control["flip_share"]
    rec["control_mean_gap_rel"] = control["mean_gap_rel"]
    for name, c in verdict["compared"].items():
        rec[name + "_limit"] = c["limit"]
    rec["seconds"] = time.perf_counter() - t0
    rec["failed"] = verdict["failed"]
    rec["ok"] = not verdict["failed"]
    _dump(per_request, dump_path)
    return rec, verdict["compared"]
