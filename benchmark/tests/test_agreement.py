"""The agreement gate (``harness/agreement.py``): the statistics and their
limits, the routing-tie rule now the harness's, and the two demonstrations
that the gate can fail: the repeated-token prompt, and a computation in 8
bits put in the program's place, for a dense and an expert configuration,
under the bounds that the SAME rule set from the rehearsal's readings."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import agreement, env, model as modelmod

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELLS = {"dense": ("BENCHMARK.test.json", "tiny-sessions"),
         "olmoe": ("BENCHMARK.olmoe.test.json", "tiny-olmoe-sessions"),
         "glm": ("BENCHMARK.glm.test.json", "tiny-glm-longchat"),
         "laguna": ("BENCHMARK.laguna.test.json", "tiny-laguna-mixedctx")}
RULE = {"tie_margin": 1e-3, "left_out_share_max": 0.5, "big_gap": 0.02,
        "gap_backstop": 0.3}


@functools.lru_cache(maxsize=None)
def cell_and_params(kind, seed=3):
    manifest, name = CELLS[kind]
    cell = env.Cell(os.path.join(DATA, manifest), name)
    mdl = modelmod.build(cell.config)
    params, _ = modelmod.init_weights(mdl, seed)
    return cell, params


def greedy(ref, params, prompt, n, cfg, pad=96):
    """``n`` greedy tokens of the reference fed ``params``: the plain way, a
    full forward a token."""
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros(pad, np.int32)
        ids[:len(seq)] = seq
        h, _ = ref.hidden_and_margin(params, ids, cfg)
        seq.append(int(jnp.argmax(ref.logits(params, h[len(seq) - 1:len(seq)],
                                             cfg)[0])))
    return seq[len(prompt):]


def requests(cell, params, served_by, n_req=6, n_out=32):
    ref = modelmod.reference(cell.config)
    rng = np.random.default_rng(11)
    vocab = int(cell.config["vocab_size"])
    out = []
    for i in range(n_req):
        prompt = [int(t) for t in rng.integers(0, vocab, 20 + 9 * i)]
        out.append({"seq": i, "prompt": prompt,
                    "tokens": greedy(ref, served_by, prompt, n_out, cell.config)})
    return out


# ------------------------------------------------------------ the statistics
def test_shares_count_what_is_judged_and_what_is_left_out():
    gap = np.array([0.0, 0.0, 0.01, 0.05, 0.0, 0.2, 0.0, 0.0])
    margin = np.array([1, 1, 1, 1e-4, 1, 1, 5e-4, 1.0])
    s = agreement.shares(gap, margin, RULE)
    assert s["positions"] == 6 and s["positions_left_out"] == 2
    assert s["left_out_share"] == 0.25
    assert s["argmax_flips"] == 2 and s["flip_share"] == pytest.approx(2 / 6)
    assert s["big_gap_share"] == pytest.approx(1 / 6)    # 0.05 was a tie
    assert s["max_gap_rel"] == 0.2 and s["finite"]
    dense = agreement.shares(gap, np.full(8, np.inf), {**RULE, "tie_margin": 0.0})
    assert dense["positions_left_out"] == 0 and dense["argmax_flips"] == 3


def test_each_judged_number_stands_beside_its_limit_and_failures_are_named():
    good = agreement.shares(np.array([0.0] * 99 + [0.01]), np.ones(100), RULE)
    control = {"flip_share": 0.97}
    v = agreement.judge(good, control, RULE)
    assert v["failed"] == []
    assert v["compared"]["big_gap_share"] == {"value": 0.0, "limit": 0.03}
    assert v["compared"]["control_flip_share_min"]["limit"] == 0.5
    assert "flip_share" not in v["compared"]          # reported, not judged
    bad = agreement.shares(np.array([0.0] * 80 + [0.03] * 19 + [0.5]),
                           np.ones(100), RULE)
    v = agreement.judge(bad, control, RULE)
    assert v["failed"] == ["big_gap_share", "max_gap_rel"]
    # a configuration whose largest gap does not separate states no backstop
    v = agreement.judge(bad, control, {**RULE, "gap_backstop": None})
    assert v["failed"] == ["big_gap_share"] and "max_gap_rel" not in v["compared"]
    # a tie rule that empties the check fails; so does a control that passes
    ties = agreement.shares(np.zeros(10), np.array([1.0] * 4 + [0.0] * 6), RULE)
    assert agreement.judge(ties, control, RULE)["failed"] == ["left_out_share"]
    assert agreement.judge(good, {"flip_share": 0.2}, RULE)["failed"] \
        == ["control_flip_share_min"]
    nan = agreement.shares(np.array([0.0, np.nan]), np.ones(2), RULE)
    assert "finite" in agreement.judge(nan, control, RULE)["failed"]


def test_a_configuration_without_the_group_is_refused():
    with pytest.raises(SystemExit, match="agreement"):
        agreement.rules({"name": "x"})
    with pytest.raises(SystemExit, match="gap_backstop"):
        agreement.rules({"name": "x", "agreement": {
            k: 0 for k in agreement.RULE_KEYS if k != "gap_backstop"}})
    only = agreement.rules({"name": "x", "agreement": {
        **{k: 0 for k in agreement.RULE_KEYS}, "gap_backstop": None}})
    assert only["gap_backstop"] is None and only["big_gap"] == 0.0


@pytest.mark.parametrize("path", sorted(
    os.path.join(d, f) for d in (os.path.join(env.BENCH_DIR, "configs"),
                                 os.path.join(DATA, "configs"))
    for f in os.listdir(d)))
def test_every_served_configuration_states_its_bounds_and_their_readings(path):
    cfg = env.load_json(path)
    if "layout" in cfg:                       # the training configurations
        assert "agreement" not in cfg
        return
    rule = agreement.rules(cfg)
    assert len(cfg["agreement"]["readings"]) > 40      # what set them
    assert 0 < rule["big_gap"] < (rule["gap_backstop"] or 1.0)
    ref = modelmod.reference(cfg)
    if hasattr(ref, "ROUTING_TIE"):
        # the references keep a copy for tier-1's tests of them; one value
        assert rule["tie_margin"] == ref.ROUTING_TIE
        assert 0 < rule["left_out_share_max"] < 1
    if cfg["reference"] == "gpt2_block":       # no router: never a tie
        assert rule["tie_margin"] == 0.0 == rule["left_out_share_max"]


# ------------------ every limit between its two readings, through ``judge``
@pytest.mark.parametrize("name", ["starcoderbase-1b", "olmoe-1b-7b",
                                  "glm-4.7-flash", "laguna-s-2.1"])
def test_every_limit_stands_between_the_sound_runs_and_the_8_bit_control(name):
    """The configuration's file states, as numbers, the largest reading the
    standing tree gave on the chip (``sound_max``) and the smallest the 8-bit
    control gave at the cell's own size (``control_8bit_min``). Judged as a
    run would be: the first passes every bound, the second fails each number
    it is held against, and a number is compared only where the two readings
    stand ``SEPARATION`` apart."""
    group = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                       name + ".json"))["agreement"]
    rule = agreement.rules({"name": name, "agreement": group})
    sound, low = group["sound_max"], group["control_8bit_min"]

    def as_run(reading):
        return {"positions": group["positions_min"], "finite": True,
                "left_out_share": sound["left_out_share"], **reading}

    repeated = {"flip_share": group["repeated_token_min"]}
    assert agreement.judge(as_run(sound), repeated, rule)["failed"] == []
    failed = agreement.judge(as_run(low), repeated, rule)["failed"]
    separates = low["max_gap_rel"] >= agreement.SEPARATION * sound["max_gap_rel"]
    assert failed == ["big_gap_share"] + ["max_gap_rel"] * separates
    assert (rule["gap_backstop"] is not None) == separates
    if separates:
        # the rule's room: over every gap a sound run or one swapped expert
        # gave, and under CONTROL_SHARE of the control's smallest
        lower = max(sound["max_gap_rel"], group.get("swap_gap_max", 0.0))
        assert lower < rule["gap_backstop"] <= min(
            agreement.BACKSTOP_MULTIPLE * lower,
            agreement.CONTROL_SHARE * low["max_gap_rel"])
    limit = agreement.big_gap_share_limit(group["positions_min"])
    assert sound["big_gap_share"] <= limit / 3      # three positions' room
    assert low["big_gap_share"] >= agreement.SEPARATION * limit
    if name in ("starcoderbase-1b", "olmoe-1b-7b"):   # bf16 end to end: the
        # NUMBER of swaps does not separate, which is why it is not compared
        assert low["flip_share"] < agreement.SEPARATION * sound["flip_share"]


# --------------------------------- the tie rule is the harness's, unchanged
@pytest.mark.parametrize("kind", ["glm", "laguna"])
def test_the_tie_rule_in_the_harness_judges_what_the_references_judged(kind):
    """Position for position at the rehearsal's size: the harness's gaps with
    its own tie rule are the references' ``greedy_gaps`` (which tier-1 still
    pins), under the margin the configuration's file states."""
    cell, params = cell_and_params(kind)
    ref = modelmod.reference(cell.config)
    rule = agreement.rules(cell.config)
    rng = np.random.default_rng(5)
    vocab = int(cell.config["vocab_size"])
    left_out = 0
    for n_prompt, n_out in ((40, 30), (70, 12), (9, 60)):
        prompt = [int(t) for t in rng.integers(0, vocab, n_prompt)]
        tokens = [int(t) for t in rng.integers(0, vocab, n_out)]
        gap_rel, margin = agreement.positions(ref, params, prompt, tokens,
                                              cell.config, 128, last=40)
        assert len(gap_rel) == min(n_out, 40)
        judged = margin >= rule["tie_margin"]
        left_out += int((~judged).sum())
        gap, spread = ref.greedy_gaps(params, prompt, tokens, cell.config,
                                      128, 40)
        np.testing.assert_allclose(gap_rel[judged], gap / spread, rtol=1e-6)
    assert left_out > 0                     # the rule had something to do


@pytest.mark.parametrize("kind", ["dense", "olmoe"])
def test_the_older_references_answer_the_same_call(kind):
    cell, params = cell_and_params(kind)
    ref = modelmod.reference(cell.config)
    ids = np.arange(30, dtype=np.int32) % int(cell.config["vocab_size"])
    h, margin = ref.hidden_and_margin(params, ids, cell.config)
    np.testing.assert_array_equal(h, ref.hidden(params, ids, cell.config))
    margin = np.asarray(margin)
    assert margin.shape == (30,)
    if kind == "dense":
        assert np.isinf(margin).all()       # no router: never a tie
    else:
        assert (margin > 0).all() and (margin < 0.1).all()
    prompt, tokens = list(ids[:20]), list(ids[20:])
    gap_rel, _ = agreement.positions(ref, params, prompt, tokens, cell.config,
                                     64, last=10)
    gap, spread = ref.greedy_gaps(params, prompt, tokens, cell.config, 64, 10)
    np.testing.assert_allclose(gap_rel, gap / spread, rtol=1e-6)


# ------------------------------------------------ the gate is able to fail
@pytest.mark.parametrize("kind", ["dense", "olmoe"])
def test_the_gate_passes_the_reference_and_fails_an_8_bit_computation(kind):
    """The "served" tokens come from a copy of the reference: as it is (the
    gate must pass, no flip at all), and fed the weights rounded to 8 bits
    (it must fail a bound, and the record must say which and by how much).
    Never a switch in the program."""
    cell, params = cell_and_params(kind)
    sound, _ = agreement.check(cell, params, requests(cell, params, params))
    assert sound["ok"] and sound["argmax_flips"] == 0 and sound["failed"] == []
    assert sound["control_flip_share"] >= sound["control_flip_share_min_limit"]
    low = agreement.eight_bit(jax.tree.map(jnp.copy, params))
    rec, compared = agreement.check(cell, params, requests(cell, params, low))
    assert not rec["ok"]
    # by the size of the swaps, not their number
    assert {"big_gap_share", "max_gap_rel"} <= set(rec["failed"])
    for name in rec["failed"]:
        assert rec[name] > rec[name + "_limit"] == compared[name]["limit"]
    assert rec["max_gap_rel"] > 1.5 * rec["max_gap_rel_limit"] \
        or rec["big_gap_share"] > 2 * rec["big_gap_share_limit"]


@pytest.mark.parametrize("kind", ["dense", "olmoe"])
def test_the_8_bit_control_without_decoding_fails_too(kind):
    """What ``tools/agreement_readings.py --control 1`` reads on the chip at
    a cell's own size: at each judged position the token the 8-bit
    computation puts first, under the float32 reference."""
    cell, params = cell_and_params(kind)
    checked = requests(cell, params, params)
    ctl = agreement.control(cell, jax.tree.map(jnp.copy, params), checked)
    failed = agreement.judge(ctl, {"flip_share": 1.0},
                             agreement.rules(cell.config))["failed"]
    assert failed and "control_flip_share_min" not in failed


def test_the_repeated_token_prompt_fails_the_gate():
    """The control every run makes: the served tokens kept, each prompt
    replaced by one token repeated. Put in the served tokens' place it fails
    every share."""
    cell, params = cell_and_params("dense")
    ref = modelmod.reference(cell.config)
    rule = agreement.rules(cell.config)
    gaps = []
    for req in requests(cell, params, params):
        other = [7] * len(req["prompt"])
        gaps.append(agreement.positions(ref, params, other, req["tokens"],
                                        cell.config, 96)[0])
    wrong = agreement.shares(np.concatenate(gaps), np.ones(sum(map(len, gaps))),
                             rule)
    failed = agreement.judge(wrong, {"flip_share": 1.0}, rule)["failed"]
    assert {"big_gap_share", "max_gap_rel"} <= set(failed)
    assert wrong["flip_share"] >= 0.9
