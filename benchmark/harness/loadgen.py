#!/usr/bin/env python3
"""The load generator: a child process that never imports JAX (its parent
holds the chip and runs ``ModelServer``). It speaks HTTP/SSE to ``/generate``,
times every token on its own monotonic clock, and reports how late it ran.

Protocol with the parent, one JSON object per line:

    child  -> {"event": "ready", ...}         set-up traffic done
    parent -> "go"
    child  -> {"event": "window_start"}
    child  -> {"event": "window_end"}
    child  -> {"event": "done"}                results written to --out

Open loop (``serve_open``): requests are sent at their due times whatever the
server does, timed from when they were DUE, and after the window every stream
is read to its end (at most ``DRAIN_S``). Closed loop (``serve_closed``): each
session sends its next turn when the previous one ends, timed from when it
was sent; at the window's end the clients hang up, and the turns in flight
count for the tokens they delivered inside the window.

Which answers are compared with the reference is the SCHEDULE's choice
(``trafficgen.checked_seqs``), the same requests in every run of a cell. A
checked turn still in flight at the window's end is read to its end (at most
``DRAIN_S``): an answer that comes late is late, not wrong. One that never
came, or was never sent, is reported as missing, never replaced by another.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import trafficgen  # noqa: E402  (stdlib only, like this file)

DRAIN_S = 60.0


class Stream:
    """One ``/generate`` call: status, token times, outcome."""

    def __init__(self, port: int, body: dict, ref_t: Optional[float] = None,
                 seq: int = -1, checked: bool = False):
        self.port, self.body = port, body
        self.ref_t = ref_t          # due time (open loop) or None: send time
        self.seq, self.checked = seq, checked   # the plan's number; compared?
        self.sent_t = 0.0
        self.status = 0
        self.token_t: List[float] = []
        self.tokens: List[int] = []
        self.done = False           # the closing ``done`` event arrived
        self.consistent = False     # ...and repeated exactly these tokens
        self.error: Optional[str] = None
        self._conn: Optional[http.client.HTTPConnection] = None
        self._hung_up = False       # set from another thread, read in run()

    def run(self) -> "Stream":
        self.sent_t = time.monotonic()
        if self.ref_t is None:
            self.ref_t = self.sent_t
        try:
            self._conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                                    timeout=120)
            self._conn.request("POST", "/generate", json.dumps(self.body),
                               {"Content-Type": "application/json"})
            resp = self._conn.getresponse()
            self.status = resp.status
            if resp.status != 200:
                self.error = f"status {resp.status}"
                resp.read()
                return self
            while not self._hung_up:
                line = resp.fp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                ev = json.loads(line[6:])
                if "token" in ev:
                    self.token_t.append(now)
                    self.tokens.append(int(ev["token"]))
                elif ev.get("done"):
                    self.done = True
                    self.consistent = ev.get("tokens") == self.tokens
                elif "error" in ev:
                    self.error = f"in-band {ev.get('cause')}"
            resp.close()    # hung up mid-stream: the server sees the client gone
        except (OSError, http.client.HTTPException, ValueError) as e:
            if not self._hung_up:
                self.error = f"{type(e).__name__}: {e}"
        finally:
            if self._conn is not None:
                self._conn.close()
        return self

    def hang_up(self) -> None:
        """Ask the reading thread to close the connection at its next event
        (a token gap away): the server then frees the slot. ``http.client``
        hands the socket of a ``Connection: close`` reply to the response, so
        it cannot be shut from here."""
        self._hung_up = True

    @property
    def ok(self) -> bool:
        want = int(self.body["max_new_tokens"])
        return (self.status == 200 and self.done and self.consistent
                and self.error is None and len(self.tokens) == want)


def say(**event) -> None:
    print(json.dumps(event), flush=True)


def run_parallel(streams: List[Stream]) -> None:
    threads = [threading.Thread(target=s.run, daemon=True) for s in streams]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def setup_traffic(args, spec) -> dict:
    """Warm-up requests, then (closed loop) every session's context, sent
    once so that its blocks sit in the prefix cache."""
    server = spec.get("server", {})
    warm = [Stream(args.port, b) for b in trafficgen.warmup_requests(
        server.get("gen_prefill_chunk", 64), args.capacity, args.vocab)]
    run_parallel(warm)
    bad = [s.error or "short" for s in warm if not s.ok]
    sessions, ctx_tokens = [], 0
    if spec["kind"] == "serve_closed":
        sessions = trafficgen.session_plan(spec, args.seed)
        ctx = [Stream(args.port, {
            "prompt": trafficgen.tokens(s.context_seed, s.context_len,
                                        args.vocab),
            "max_new_tokens": 1, "temperature": 0.0}) for s in sessions]
        run_parallel(ctx)
        bad += [s.error or "short" for s in ctx if not s.ok]
        ctx_tokens = sum(s.context_len for s in sessions)
    return {"sessions": sessions, "warmup_requests": len(warm),
            "context_tokens": ctx_tokens, "setup_failures": bad}


def open_loop(args, spec, checked):
    plan = trafficgen.open_loop_plan(spec, args.seed, args.seconds)
    bodies = [r.body(trafficgen.tokens(r.seed, r.fresh_len, args.vocab))
              for r in plan]
    say(event="window_start", planned=len(plan))
    t0 = time.monotonic()
    streams, threads = [], []
    for r, body in zip(plan, bodies):
        due = t0 + r.due_us / 1e6
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        s = Stream(args.port, body, ref_t=due, seq=r.seq,
                   checked=r.seq in checked)
        t = threading.Thread(target=s.run, daemon=True)
        t.start()
        streams.append(s)
        threads.append(t)
    rest = t0 + args.seconds - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    t1 = time.monotonic()
    say(event="window_end")
    deadline = t1 + DRAIN_S
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    for s, t in zip(streams, threads):
        if t.is_alive():
            s.error = s.error or "undrained"
            s.hang_up()
    for t in threads:
        t.join(5)
    return streams, t0, t1


def closed_loop(args, spec, sessions, checked):
    streams: List[Stream] = []
    lock = threading.Lock()
    stop = threading.Event()
    live: dict = {}

    stagger = float(spec["sessions"].get("start_stagger_s", 0.0))

    def client(ses):
        base = trafficgen.tokens(ses.context_seed, ses.context_len, args.vocab)
        history = list(base)
        if stop.wait(ses.index * stagger):   # independent users do not start as one
            return
        for r in ses.turns:
            if stop.is_set():
                return
            fresh = trafficgen.tokens(r.seed, r.fresh_len, args.vocab)
            if trafficgen.starts_over(len(history), len(fresh),
                                      r.max_new_tokens, args.capacity):
                history = list(base)   # the session starts over on its context
            prompt = history + fresh
            s = Stream(args.port, r.body(prompt), seq=r.seq,
                       checked=r.seq in checked)
            with lock:
                streams.append(s)
                live[ses.index] = s
            s.run()
            if not s.ok:
                return                  # failed, or hung up at the window's end
            history = prompt + s.tokens

    say(event="window_start", planned=len(sessions))
    t0 = time.monotonic()
    threads = [threading.Thread(target=client, args=(ses,), daemon=True)
               for ses in sessions]
    for t in threads:
        t.start()
    time.sleep(args.seconds)
    t1 = time.monotonic()
    say(event="window_end")
    stop.set()
    time.sleep(0.2)   # the parent reads its counters before anyone hangs up
    with lock:
        flying = [s for s in live.values() if not s.done and s.error is None]
    cut = [s for s in flying if not s.checked]
    for s in cut:
        s.hang_up()
    # a checked answer in flight is read to its end: late is not wrong
    deadline = time.monotonic() + (DRAIN_S if len(cut) < len(flying) else 10)
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    for s in flying:
        if s.checked and not s.done and s.error is None:
            s.error = "undrained"
            s.hang_up()
    for s in cut:
        s.cut = True
    return streams, t0, t1


def quantile(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    xs = sorted(xs)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tokens_by_second(streams: List[Stream], t0: float, t1: float) -> List[int]:
    """Tokens received in each whole second of the window, from its start;
    what is left over at the end is in no second."""
    counts = [0] * int(t1 - t0)
    for s in streams:
        for t in s.token_t:
            if t0 <= t < t0 + len(counts):
                counts[int(t - t0)] += 1
    return counts


def reduce(streams: List[Stream], t0: float, t1: float, kind: str,
           checked: List[int] = ()) -> dict:
    """Everything the parent needs, from the client's side of the wire.
    ``checked`` are the plan's numbers of the requests to compare."""
    in_window = [s for s in streams if t0 <= s.ref_t < t1]
    ttft = [(s.token_t[0] - s.ref_t) * 1e3 for s in in_window if s.token_t]
    timed_gaps = [(b - t0, (b - a) * 1e3) for s in streams
                  for a, b in zip(s.token_t, s.token_t[1:]) if t0 <= b < t1]
    gaps = [g for _, g in timed_gaps]
    tokens_in = sum(1 for s in streams for t in s.token_t if t0 <= t < t1)
    per_second = tokens_by_second(streams, t0, t1)
    stalls: List[List[float]] = []
    for at, g in sorted(timed_gaps, key=lambda x: -x[1]):
        if all(abs(at - s[0]) > 0.05 for s in stalls):
            stalls.append([round(at, 3), round(g, 1)])
            if len(stalls) == 8:
                break
    late = [(s.sent_t - s.ref_t) * 1e3 for s in in_window]
    cut = [s for s in streams if getattr(s, "cut", False)]
    failed = [s for s in streams if not s.ok and s not in cut]
    shed = [s for s in streams if s.status in (429, 503)]
    answered = {s.seq: s for s in streams if s.checked and s.ok}
    sent = {s.seq: s for s in streams if s.checked}
    missing = [[q, (sent[q].error or "short") if q in sent else "not sent"]
               for q in checked if q not in answered]
    return {
        "kind": kind, "window_s": t1 - t0,
        "attempted": len(streams), "failed": len(failed), "cut": len(cut),
        "shed": len(shed), "completed": sum(1 for s in streams if s.ok),
        "failures": sorted({s.error or "short" for s in failed})[:8],
        "tokens_in_window": tokens_in,
        # per whole second of the window, and the median second: what a
        # full server delivers between stalls (reported, never judged)
        "tokens_by_second": per_second,
        "tokens_per_s_p50": quantile(per_second, 0.5),
        "tokens_received": sum(len(s.tokens) for s in streams),
        "prompt_tokens_sent": sum(len(s.body["prompt"]) for s in streams),
        "ttft_count": len(ttft), "gaps_count": len(gaps),
        "ttft_p50_ms": quantile(ttft, 0.5), "ttft_p90_ms": quantile(ttft, 0.9),
        "itl_p50_ms": quantile(gaps, 0.5), "itl_p99_ms": quantile(gaps, 0.99),
        "itl_max_ms": max(gaps, default=None),
        # [seconds into the window, ms], one entry for gaps that end within
        # 50 ms of each other: whether every stream stood still at once
        "longest_stalls": stalls,
        "late_p99_ms": quantile(late, 0.99), "late_max_ms": max(late, default=0.0),
        # exactly the requests the schedule marked; one that did not
        # complete is named in `checked_missing`, never replaced
        "checked": [{"seq": q, "prompt": answered[q].body["prompt"],
                     "tokens": answered[q].tokens}
                    for q in checked if q in answered],
        "checked_planned": len(checked), "checked_missing": missing,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--capacity", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        spec = json.load(f)
    prep = setup_traffic(args, spec)
    say(event="ready", warmup_requests=prep["warmup_requests"],
        context_tokens=prep["context_tokens"],
        setup_failures=prep["setup_failures"])
    if sys.stdin.readline().strip() != "go":
        return 3
    checked = trafficgen.checked_seqs(spec, args.seed, args.seconds,
                                      args.capacity)
    if spec["kind"] == "serve_open":
        streams, t0, t1 = open_loop(args, spec, set(checked))
    else:
        streams, t0, t1 = closed_loop(args, spec, prep["sessions"],
                                      set(checked))
    out = reduce(streams, t0, t1, spec["kind"], checked)
    out["context_tokens"] = prep["context_tokens"]
    out["setup_failures"] = prep["setup_failures"]
    with open(args.out, "w") as f:
        json.dump(out, f)
    say(event="done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
