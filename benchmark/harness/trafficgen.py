"""Seeded traffic for the benchmark's cells: one general generator, driven by
a traffic file of parameters (``benchmark/traffic/<name>.json``).

A trimmed copy of what is sound in ``deeplearning4j_tpu/sim/workload.py``
(stdlib only, ``random.Random(seed)``, integer microseconds, per-request
content seeds from sha256, a fixed line format whose bytes are the
determinism test), kept here so that no later PR can change the yardstick.
Left behind: tenants, model mixes, the diurnal curve, SLO classes. Added:
closed-loop multi-turn sessions, and low-variance draws —

- lengths come from the distribution's inverse CDF at stratified quantiles
  ``(perm[i] + u) / n``, so every run offers nearly the same number of
  tokens whatever the seed;
- an open-loop window holds exactly ``round(mean_rate * seconds)`` arrivals
  (a Poisson process conditioned on its count: sorted uniforms in cumulative
  intensity), and one burst of ``on_s`` seconds in every ``on_s + off_s``,
  at a seeded phase, so the share of the window spent in a burst is fixed.

The seed moves contents, order and phases; it does not move the amount of
work. A traffic file may go further and fix ``schedule_seed``: arrival times,
burst phases, lengths and the sampling mix are then the CELL's, the same in
every run, and ``--seed`` draws only what the tokens are (and the weights).
Queueing amplifies small differences in a schedule into large ones in a
tail, so a time to first token repeats within a few percent only on a fixed
schedule (PERF.md section 6, PR 22: 29-44% across six seeded schedules).

Never imports JAX or numpy: the load generator is a child process that must
not touch the chip its parent holds.
"""

from __future__ import annotations

import hashlib
import math
import random
from statistics import NormalDist
from typing import Dict, List, NamedTuple, Optional

SCHEMA = "bench-traffic-v1"


def _sub_seed(seed: int, *tags) -> int:
    """A stable child seed (sha256, never ``hash()``)."""
    text = ":".join(str(t) for t in (seed,) + tags)
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:8], "big")


class LengthDist(NamedTuple):
    """A token-length distribution read from a traffic file:
    ``{"kind", "p1", "p2", "min", "max"}``. ``p1``/``p2`` are (median, sigma)
    for ``lognormal``, (scale, alpha) for ``pareto``, (low, high) for
    ``uniform`` and ``loguniform``, (value, unused) for ``fixed``. Values are
    clipped to ``[min, max]``: the tail's mass stays at the cap."""

    kind: str
    p1: float
    p2: float
    lo: int
    hi: int

    @classmethod
    def from_dict(cls, d: dict) -> "LengthDist":
        return cls(str(d["kind"]), float(d["p1"]), float(d.get("p2", 0.0)),
                   int(d.get("min", 1)), int(d["max"]))

    def at(self, q: float) -> int:
        """The inverse CDF at quantile ``q`` in (0, 1), rounded and clipped."""
        q = min(max(q, 1e-9), 1.0 - 1e-9)
        if self.kind == "lognormal":
            v = self.p1 * math.exp(self.p2 * NormalDist().inv_cdf(q))
        elif self.kind == "pareto":
            v = self.p1 * (1.0 - q) ** (-1.0 / self.p2)
        elif self.kind == "uniform":
            v = self.p1 + q * (self.p2 - self.p1)
        elif self.kind == "loguniform":
            v = self.p1 * (self.p2 / self.p1) ** q
        elif self.kind == "fixed":
            v = self.p1
        else:
            raise ValueError(f"unknown length distribution {self.kind!r}")
        return max(self.lo, min(self.hi, int(round(v))))

    def draw(self, rng: random.Random, n: int) -> List[int]:
        """``n`` stratified draws: one from each of ``n`` equal-probability
        strata, in a seeded order."""
        order = list(range(n))
        rng.shuffle(order)
        return [self.at((k + rng.random()) / n) for k in order]


class Request(NamedTuple):
    """One request of a plan. ``due_us`` is microseconds from the window's
    start (open loop) or -1 (closed loop: sent when the session's previous
    turn ends). ``fresh`` prompt tokens are regenerated from ``seed``; a
    session turn's full prompt is its history plus those."""

    seq: int
    due_us: int
    session: int
    fresh_len: int
    max_new_tokens: int
    temperature: float
    top_k: int          # 0 = none
    seed: int

    def to_line(self) -> str:
        return (f"{self.seq} {self.due_us} {self.session} {self.fresh_len} "
                f"{self.max_new_tokens} {self.temperature:g} {self.top_k} "
                f"{self.seed}")

    def body(self, prompt: List[int]) -> dict:
        out = {"prompt": prompt, "max_new_tokens": self.max_new_tokens,
               "temperature": self.temperature}
        if self.top_k:
            out["top_k"] = self.top_k
        return out


def tokens(seed: int, n: int, vocab: int) -> List[int]:
    """``n`` token ids from a content seed."""
    return random.Random(seed).choices(range(max(2, int(vocab))), k=n)


def _sampling(spec: dict, rng: random.Random, n: int) -> List[dict]:
    """The traffic file's sampling mix dealt out in exact proportion:
    ``[{"weight", "temperature", "top_k"?}, ...]`` -> n entries, shuffled."""
    mix = spec.get("sampling") or [{"weight": 1.0, "temperature": 0.0}]
    total = sum(float(m.get("weight", 1.0)) for m in mix)
    out: List[dict] = []
    acc = 0.0
    for m in mix:
        acc += float(m.get("weight", 1.0)) / total
        while len(out) < round(acc * n):
            out.append(m)
    out += [mix[-1]] * (n - len(out))
    rng.shuffle(out)
    return out


def burst_windows(spec: dict, rng: random.Random, seconds: float) -> List[tuple]:
    """One burst of ``on_s`` in every cycle of ``on_s + off_s``, at a seeded
    phase inside its cycle; a last partial cycle gets its share."""
    arr = spec.get("arrivals", {})
    on, off = float(arr.get("burst_on_s", 0.0)), float(arr.get("burst_off_s", 0.0))
    if float(arr.get("burst_rate_mult", 1.0)) <= 1.0 or on <= 0 or off <= 0:
        return []
    out, t = [], 0.0
    while t < seconds:
        cycle = min(on + off, seconds - t)
        length = on * cycle / (on + off)
        start = t + rng.random() * (cycle - length)
        out.append((start, start + length))
        t += cycle
    return out


def open_loop_plan(spec: dict, seed: int, seconds: float) -> List[Request]:
    """The window's arrivals, ordered by due time."""
    arr = spec["arrivals"]
    rng = random.Random(_sub_seed(spec.get("schedule_seed", seed), "open"))
    mult = max(1.0, float(arr.get("burst_rate_mult", 1.0)))
    windows = burst_windows(spec, rng, seconds)
    n = max(1, int(round(float(arr["mean_rate_rps"]) * seconds)))
    # cumulative intensity, piecewise linear: weight `mult` inside a burst
    edges = sorted({0.0, seconds, *(e for w in windows for e in w)})
    pieces, total = [], 0.0
    for a, b in zip(edges, edges[1:]):
        w = mult if any(s <= a and b <= e for s, e in windows) else 1.0
        pieces.append((a, b, total, w))
        total += (b - a) * w
    dues = []
    for x in sorted(rng.random() * total for _ in range(n)):
        a, _, base, w = next(p for p in reversed(pieces) if p[2] <= x)
        dues.append(a + (x - base) / w)
    prompts = LengthDist.from_dict(spec["prompt_len"]).draw(rng, n)
    outputs = LengthDist.from_dict(spec["output_len"]).draw(rng, n)
    sampling = _sampling(spec, rng, n)
    return [Request(i, int(round(dues[i] * 1e6)), -1, prompts[i], outputs[i],
                    float(sampling[i].get("temperature", 0.0)),
                    int(sampling[i].get("top_k", 0) or 0),
                    _sub_seed(seed, "req", i)) for i in range(n)]


class Session(NamedTuple):
    """A closed-loop client: a context sent once in set-up, then turns."""

    index: int
    context_len: int
    context_seed: int
    turns: List[Request]


def session_plan(spec: dict, seed: int, turns: int = 48) -> List[Session]:
    """``sessions.count`` clients with ``turns`` planned turns each (more
    than any window uses; a client stops at the window's end)."""
    ses = spec["sessions"]
    n = int(ses["count"])
    rng = random.Random(_sub_seed(spec.get("schedule_seed", seed), "closed"))
    contexts = LengthDist.from_dict(ses["context_len"]).draw(rng, n)
    fresh = LengthDist.from_dict(ses["turn_prompt_len"]).draw(rng, n * turns)
    outs = LengthDist.from_dict(ses["turn_output_len"]).draw(rng, n * turns)
    sampling = _sampling(spec, rng, n * turns)
    out = []
    for s in range(n):
        reqs = [Request(s * turns + t, -1, s, fresh[s * turns + t],
                        outs[s * turns + t],
                        float(sampling[s * turns + t].get("temperature", 0.0)),
                        int(sampling[s * turns + t].get("top_k", 0) or 0),
                        _sub_seed(seed, "turn", s, t)) for t in range(turns)]
        out.append(Session(s, contexts[s], _sub_seed(seed, "ctx", s), reqs))
    return out


def starts_over(history_len: int, fresh_len: int, max_new_tokens: int,
                capacity: int) -> bool:
    """A session's turn starts over on its context when history, fresh
    prompt and answer would pass the slot's capacity."""
    return history_len + fresh_len + max_new_tokens > capacity


def _spread(cands: list, count: int) -> list:
    """``count`` of the ordered ``cands``, evenly over their order, the first
    and the last among them."""
    if count >= len(cands):
        return list(cands)
    if count <= 1:
        return cands[-1:]
    return [cands[round(i * (len(cands) - 1) / (count - 1))]
            for i in range(count)]


def checked_seqs(spec: dict, seed: int, seconds: float,
                 capacity: Optional[int] = None) -> List[int]:
    """The ``seq`` of the requests whose answers are compared with the
    reference: marked by the SCHEDULE (``schedule_seed`` where the file fixes
    one: lengths and the sampling mix), never by what a run happened to
    finish. ``spec["checked"]``: ``count`` requests (default 8), greedy ones,
    spread evenly over the prompt lengths the cell sends, the shortest and
    the longest among them. Closed loop: turns sent after at most
    ``after_tokens`` streamed tokens of their session (default 512), so that
    even a slow server is sent them inside the window; the client reads a
    checked answer to its end past the window's close. Open loop: any
    arrival of the window (every stream is drained)."""
    want = spec.get("checked", {})
    count = int(want.get("count", 8))
    cands = []                                   # (prompt length, seq)
    if spec["kind"] == "serve_open":
        cands = [(r.fresh_len, r.seq) for r in open_loop_plan(spec, seed, seconds)
                 if r.temperature == 0.0]
    elif spec["kind"] == "serve_closed":
        capacity = int(capacity or spec["server"]["gen_capacity"])
        after = int(want.get("after_tokens", 512))
        for ses in session_plan(spec, seed):
            history, streamed = ses.context_len, 0
            for r in ses.turns:
                if streamed > after:
                    break
                if starts_over(history, r.fresh_len, r.max_new_tokens, capacity):
                    history = ses.context_len
                if r.temperature == 0.0:
                    cands.append((history + r.fresh_len, r.seq))
                history += r.fresh_len + r.max_new_tokens
                streamed += r.max_new_tokens
    return sorted(seq for _, seq in _spread(sorted(cands), count))


def plan_bytes(spec: dict, seed: int, seconds: float) -> bytes:
    """The plan in a fixed line format: equal bytes mean equal traffic."""
    lines = [f"# {SCHEMA} kind={spec['kind']} seed={seed} seconds={seconds:g}"]
    if spec["kind"] == "serve_open":
        lines += [r.to_line() for r in open_loop_plan(spec, seed, seconds)]
    elif spec["kind"] == "serve_closed":
        for s in session_plan(spec, seed):
            lines.append(f"# session {s.index} {s.context_len} {s.context_seed}")
            lines += [r.to_line() for r in s.turns]
    elif spec["kind"] == "train":
        job = spec["job"]
        lines.append(f"# train {job['batches']}x{job['global_batch']}x"
                     f"{job['seq_len']} {_sub_seed(seed, 'train')}")
    else:
        raise ValueError(f"unknown traffic kind {spec['kind']!r}")
    return ("\n".join(lines) + "\n").encode()


def train_seed(seed: int) -> int:
    """The content seed of a training job's batches."""
    return _sub_seed(seed, "train") % (2 ** 32)


def warmup_requests(chunk: Optional[int], capacity: int, vocab: int) -> List[Dict]:
    """A few short greedy and sampled requests that touch every prefill
    chunk bucket (8, 16, ..., ``chunk``), the multi-chunk path, the decode
    step and the sampler before the window opens."""
    lens, b = [], 8
    top = min(chunk or capacity, capacity - 8)
    while b < top:
        lens.append(b - 3)
        b *= 2
    lens += [top, min(2 * top + 5, capacity - 8)]
    return [{"prompt": tokens(_sub_seed(0, "warm", i), n, vocab),
             "max_new_tokens": 4,
             **({"temperature": 0.0} if i % 2 else
                {"temperature": 0.8, "top_k": 40})}
            for i, n in enumerate(lens)]
