#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, no arguments needed. Drives the train -> serve
path once through the entry points a user calls, at the full width of the
738M ``CausalLM`` (12 layers, d_model 2048, 32 heads, vocab 32000, T 1024,
bf16 compute / f32 params, flash attention), with seeded random weights:

  a. kernels   the Pallas flash kernel, compiled, forward and both
               backwards, against dense attention
  b. train     ``Trainer(model).fit`` for a few steps on one repeated batch
  c. serve     ``ModelServer`` over HTTP on localhost: paged KV, chunked
               prefill, prefix cache, AOT store
  d. agree     served greedy tokens against the plain f32 full forward
  e. warm      a second server booted from the first one's AOT store
  f. four      the train leg over a {data: 4} and a {data: 2, model: 2} mesh
               (skipped with fewer than four devices)

It exits non-zero unless JAX's backend is a TPU, and any failed check
raises: nothing is caught and continued. Times it prints are smoke
observations of one run, not benchmark numbers. The last line of stdout is
the result, ``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX
reports the device; the line before it is the full record (versions, per-leg
seconds, compile seconds, HBM), which also goes to
``chiprun_out/chip_smoke.json``. A run that fails prints neither.

    python chip_smoke.py              # every leg
    python chip_smoke.py --legs f     # chosen legs (a leg pulls in what it needs)
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")

# --- tolerances, each with its reason -------------------------------------
# Leg (a). Inputs and outputs are bf16 (8 significand bits: one rounding is
# up to 2^-9 = 0.2% of a value). The kernel rounds three times on the way —
# p (forward) or p/ds (backward) to bf16 before the second matmul, and the
# result to bf16 — while the reference runs the same bf16 inputs in f32 at
# "highest" precision. Errors are taken relative to the largest magnitude in
# the reference tensor. Three independent roundings stay well inside 2% of
# that scale (the worst of the 56 comparisons on the v5e was 0.0055, PR 21);
# a dropped block, a wrong mask or a missing 1/sqrt(D) is wrong by the scale
# itself.
KERNEL_TOL = 2e-2
# Leg (d). The server computes in bf16 end to end (weights re-cast to bf16,
# 12 blocks at d_model 2048), the reference in f32 at "highest". At each
# generated position take gap = max(ref_logits) - ref_logits[served token]:
# zero when the served greedy token is the reference's argmax. The logits of
# these nearly random weights are flat (spread ~0.36, the top two often
# within 0.1 of each other) and bf16 moves one by about a hundredth, so close
# candidates swap: on the v5e, 6 swaps in 233 positions, the largest gap
# 0.0102, the mean gap 0.00013 (PR 21, one run). The bounds sit 5x and 15x
# above those — and an 8-bit computation, with 16x bf16's rounding error,
# would break both:
LOGIT_TOL = 0.05      # no single gap above this
MEAN_GAP_TOL = 0.002  # nor the mean over all positions above this
# ...and the check must be able to fail: with each prompt swapped for other
# random tokens (the generated tokens kept) the mean gap has to come out at
# least this many times MEAN_GAP_TOL. It was 0.199 on the v5e — a thousand
# times the true mean. (Six AdamW steps at the model's default 3e-4 took the
# 738M model's greedy token to one that ignores its context, and the check
# then passed with any prompt: hence Size.learning_rate.)
AGREE_MIN_POWER = 25.0
# Leg (f). The first-step loss is a mean over the same tokens at the same
# seeded weights; only the order of partial sums differs between one chip
# and a mesh (on the four-chip v5e host all three were 10.4318, PR 21).
MESH_LOSS_RTOL = 1e-3


@dataclasses.dataclass(frozen=True)
class Size:
    """One model configuration plus the traffic the smoke sends it. The
    default is the 738M model; tests shrink every field."""

    num_layers: int = 12
    d_model: int = 2048
    num_heads: int = 32
    vocab: int = 32000
    seq: int = 1024
    batch: int = 4
    train_steps: int = 6
    # a thirtieth of the model's default: enough for the loss to fall on a
    # repeated batch, little enough that the served weights still read their
    # context (see AGREE_MIN_POWER)
    learning_rate: float = 1e-5
    kernel_head_dims: tuple = (64, 128)
    window: int = 256
    slots: int = 16
    capacity: int = 1024  # the learned position table is max(seq, 512) long
    # concurrent wave: prompt lengths below, at and far above the 64-token
    # prefill chunk (one chunk bucket each of 8/16/32/64, then multi-chunk),
    # more requests than slots so some wait in the queue
    prompt_lens: tuple = (8, 13, 27, 50, 64, 65, 100, 128, 150, 200, 256,
                          300, 350, 400, 450, 512, 600, 640, 700, 777, 850,
                          900)
    new_tokens: tuple = (32, 96)
    prefix_len: int = 256


FULL = Size()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.perf_counter()


def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        out["libtpu"] = None
    return out


def hbm(device=None) -> dict:
    """Allocator statistics of one device; empty where the backend keeps
    none (CPU)."""
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


class HbmSampler(threading.Thread):
    """Largest ``bytes_in_use`` seen while a leg runs. The allocator's own
    peak is a maximum since the process began, so after the train leg it
    says nothing about a later leg; polling does, to within what happens
    between two polls."""

    def __init__(self, period_s: float = 0.02):
        super().__init__(daemon=True, name="chip-smoke-hbm")
        self.period_s = period_s
        self.max_bytes: Optional[int] = None
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            b = hbm().get("bytes_in_use")
            if b is not None:
                self.max_bytes = max(self.max_bytes or 0, b)
            self._halt.wait(self.period_s)

    def finish(self) -> Optional[int]:
        self._halt.set()
        self.join(5)
        return self.max_bytes


def build_model(size: Size, *, flash: bool = True,
                compute_dtype: Optional[str] = "bfloat16"):
    from deeplearning4j_tpu.models import CausalLM

    model = CausalLM(seed=0, input_shape=(size.seq,),
                     num_layers=size.num_layers, d_model=size.d_model,
                     num_heads=size.num_heads, vocab=size.vocab,
                     flash=flash).build()
    model.config.compute_dtype = compute_dtype
    model.config.updater = {**model.config.updater,
                            "learning_rate": size.learning_rate}
    return model


# ---------------------------------------------------------------- (a) kernels
def _kernel_case(q, k, v, w, aux, *, kind: str, window: int):
    """Flash forward + both backwards and the dense oracle for one mask
    kind; returns errors relative to each reference tensor's scale."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    T = q.shape[1]
    kw = {"causal": True}
    pos = jnp.arange(T)
    dense = (pos[None, :] <= pos[:, None])[None, None]      # (1, 1, Tq, Tk)
    if kind == "lengths":
        kw["lengths"] = aux
        dense = dense & (pos[None, :] < aux[:, None])[:, None, None, :]
    elif kind == "key_mask":
        kw["key_mask"] = aux
        dense = dense & aux[:, None, None, :]
    elif kind == "window":
        kw["window"] = window
        dense = dense & (pos[:, None] - pos[None, :] < window)[None, None]

    def flash_loss(backward):
        return lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, backward=backward, **kw)
            .astype(jnp.float32) * w)

    o = flash_attention(q, k, v, **kw)
    g_xla = jax.grad(flash_loss("xla"), argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(flash_loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
        o_ref = dot_product_attention(qf, kf, vf, mask=dense)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, mask=dense) * w),
            argnums=(0, 1, 2))(qf, kf, vf)

    def err(a, ref):
        return (jnp.max(jnp.abs(a.astype(jnp.float32) - ref))
                / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))

    out = {"fwd": err(o, o_ref)}
    for name, g in (("xla", g_xla), ("pallas", g_pal)):
        for part, a, ref in zip(("dq", "dk", "dv"), g, g_ref):
            out[f"{name}_{part}"] = err(a, ref)
    return out


def leg_kernels(size: Size) -> dict:
    import jax
    import jax.numpy as jnp

    compiled = jax.default_backend() == "tpu"  # CPU tests interpret
    B, T = size.batch, size.seq
    rows, worst, compile_s = {}, 0.0, 0.0
    for D in size.kernel_head_dims:
        H = size.d_model // D
        ks = jax.random.split(jax.random.PRNGKey(D), 5)
        q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
                   .astype(jnp.bfloat16) for kk in ks[:3])
        w = jax.random.normal(ks[3], (B, T, H, D), jnp.float32)
        lengths = jnp.asarray(np.linspace(T, max(T // 16, 1), B), jnp.int32)
        # key 0 stays visible: a causal row whose every key is masked is 0
        # from the kernel and mean(v) from the dense softmax, by contract
        kmask = jax.random.bernoulli(ks[4], 0.8, (B, T)).at[:, 0].set(True)
        for kind, aux in (("none", None), ("lengths", lengths),
                          ("key_mask", kmask), ("window", None)):
            fn = jax.jit(functools.partial(_kernel_case, kind=kind,
                                           window=size.window))
            lowered = fn.lower(q, k, v, w, aux)
            calls = lowered.as_text().count("tpu_custom_call")
            if compiled and not calls:
                raise AssertionError(
                    f"kernel D={D} {kind}: no Mosaic custom call in the "
                    f"lowered program — the kernel was interpreted")
            t0 = time.perf_counter()
            exe = lowered.compile()
            compile_s += time.perf_counter() - t0
            errs = {n: float(e) for n, e in exe(q, k, v, w, aux).items()}
            bad = {n: e for n, e in errs.items()
                   if not np.isfinite(e) or e > KERNEL_TOL}
            if bad:
                raise AssertionError(
                    f"kernel D={D} {kind}: error over {KERNEL_TOL}: {bad}")
            worst = max(worst, *errs.values())
            rows[f"D{D}_{kind}"] = {"mosaic_calls": calls,
                                    "max_err": round(max(errs.values()), 5)}
            log(f"a. kernel B{B} T{T} H{H} D{D} {kind:8s} mosaic_calls={calls} "
                f"max_err={max(errs.values()):.2e}")
    return {"cases": rows, "worst_err": round(worst, 5), "tol": KERNEL_TOL,
            "compile_seconds": round(compile_s, 2)}


# ------------------------------------------------------------------ (b) train
def train_batch(size: Size, batch: Optional[int] = None):
    """One seeded batch of token ids and next-token targets; ``batch`` rows
    repeat the ``size.batch`` seeded rows, so a larger global batch has the
    same mean loss."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, size.vocab, (size.batch, size.seq + 1))
    reps = (batch or size.batch) // size.batch
    ids = np.tile(ids, (reps, 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


def leg_train(size: Size, *, steps: Optional[int] = None, mesh_axes=None,
              batch: Optional[int] = None):
    """``Trainer(model).fit`` on one repeated batch. Returns (record, model);
    the model carries the trained params."""
    import jax

    from deeplearning4j_tpu.data import ArrayIterator
    from deeplearning4j_tpu.obs import StepTelemetry
    from deeplearning4j_tpu.train import Trainer
    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    steps = steps or size.train_steps
    x, y = train_batch(size, batch)
    model = build_model(size)
    model.init()
    kw = {}
    if mesh_axes is not None:
        from deeplearning4j_tpu.parallel.mesh import make_mesh
        from deeplearning4j_tpu.parallel.sharding import TRANSFORMER_RULES

        n_dev = int(np.prod(list(mesh_axes.values())))
        kw = {"mesh": make_mesh(mesh_axes, jax.devices()[:n_dev]),
              "rules": TRANSFORMER_RULES}
    tr = Trainer(model, seed=0, **kw)
    if mesh_axes is not None:
        model.params = None  # the trainer holds the placed copy; free device 0's
    tel, scores = StepTelemetry(), CollectScoresListener()
    # one batch per epoch, `steps` epochs: the same batch every step
    tr.fit(ArrayIterator(x, y, batch_size=x.shape[0]), epochs=steps,
           listeners=[scores], telemetry=tel)
    losses = [float(s) for _, s in scores.scores]
    if len(losses) != steps or not np.all(np.isfinite(losses)):
        raise AssertionError(f"train: losses not finite: {losses}")
    if steps > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    # StepTelemetry fences every step with block_until_ready
    step_s = [e["dur"] / 1e6 for e in tel.tracer.events
              if e["name"] == "train_step"]
    steady = float(np.median(step_s[1:])) if steps > 1 else None
    mosaic = tr._step_fn.lower(
        tr.params, tr.opt_state, tr.state, *tr._place_batch(x, y),
        jax.random.PRNGKey(0), None, None).as_text().count("tpu_custom_call")
    if jax.default_backend() == "tpu" and not mosaic:
        raise AssertionError(
            "train: no Mosaic custom call in the lowered step — attention "
            "ran interpreted or on the dense branch")
    rec = {"steps": steps, "batch": int(x.shape[0]), "seq": size.seq,
           "params": int(sum(np.prod(a.shape)
                             for a in jax.tree.leaves(tr.params))),
           "losses": [round(l, 4) for l in losses],
           "first_step_seconds": round(step_s[0], 3),
           "steady_step_seconds": (round(steady, 4) if steady else None),
           "compile_seconds": round(step_s[0] - (steady or 0.0), 2),
           "mosaic_calls": mosaic, "hbm": hbm()}
    if mesh_axes is not None:
        for name, tree in (("params", tr.params), ("opt_state", tr.opt_state)):
            used = {s.device.id for a in jax.tree.leaves(tree)
                    for s in a.addressable_shards}
            if len(used) != n_dev:
                raise AssertionError(
                    f"train {mesh_axes}: {name} live on devices "
                    f"{sorted(used)}, expected {n_dev}")
        rec["mesh"] = dict(mesh_axes)
        rec["hbm_per_device"] = [hbm(d) for d in jax.devices()[:n_dev]]
    log(f"train{'' if mesh_axes is None else ' ' + str(dict(mesh_axes))}: "
        f"losses {rec['losses']}, first step {rec['first_step_seconds']} s, "
        f"steady {rec['steady_step_seconds']} s, hbm {rec['hbm']}")
    return rec, model


# ------------------------------------------------------------------ (c) serve
def plan_requests(size: Size) -> list:
    """The seeded request list: [first, *wave, last]. ``first`` and ``last``
    share a ``prefix_len``-token prefix; ``first`` runs alone (it also
    builds the generation stack), the wave runs all at once, ``last`` runs
    after the wave so the prefix it shares is in the cache."""
    rng = np.random.default_rng(1)
    lo, hi = size.new_tokens

    def req(prompt, greedy):
        new = int(rng.integers(lo, hi + 1))
        new = min(new, size.capacity - len(prompt))
        body = {"prompt": [int(t) for t in prompt], "max_new_tokens": new}
        body.update({"temperature": 0.0} if greedy
                    else {"temperature": 0.8, "top_k": 40})
        return body

    prefix = rng.integers(0, size.vocab, size.prefix_len)
    tail = max(size.prefix_len // 8, 4)
    first = req(np.concatenate([prefix, rng.integers(0, size.vocab, tail)]),
                True)
    last = req(np.concatenate([prefix, rng.integers(0, size.vocab, tail)]),
               True)
    wave = [req(rng.integers(0, size.vocab, n), greedy=i % 2 == 0)
            for i, n in enumerate(size.prompt_lens)]
    return [first, *wave, last]


def _generate(port: int, body: dict, stream: bool) -> list:
    """POST /generate; any status but 200 raises. Streams parse the SSE
    body and must end in a ``done`` event repeating every token."""
    url = f"http://127.0.0.1:{port}/generate"
    data = json.dumps(body if stream else {**body, "stream": False}).encode()
    req = urllib.request.Request(url, data=data,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as r:
        if r.status != 200:
            raise AssertionError(f"/generate answered {r.status}")
        raw = r.read().decode()
    if not stream:
        return json.loads(raw)["tokens"]
    events = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ")]
    if not events or not events[-1].get("done"):
        raise AssertionError(f"stream ended without done: {events[-1:]}")
    toks = [e["token"] for e in events[:-1]]
    if toks != events[-1]["tokens"]:
        raise AssertionError("streamed tokens differ from the done event")
    return toks


def _get(port: int, path: str) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=60) as r:
        if r.status != 200:
            raise AssertionError(f"{path} answered {r.status}")
        return json.loads(r.read())


def _metric(snap: dict, name: str) -> float:
    """Sum over a counter family's series."""
    return float(sum(s["value"]
                     for s in snap.get(name, {}).get("series", [])))


def _hist(snap: dict, name: str) -> dict:
    series = snap.get(name, {}).get("series", [])
    if not series or not series[0]["count"]:
        return {}
    s = series[0]
    return {"count": s["count"], "mean": round(s["sum"] / s["count"], 5),
            "max": round(s["max"], 5),
            **{k: round(v, 5) for k, v in s["quantiles"].items()}}


def boot_server(size: Size, model, store_dir: str):
    from deeplearning4j_tpu.aot import AotStore
    from deeplearning4j_tpu.serve.http import ModelServer

    t0 = time.perf_counter()
    # watchdog_s stays None: a first compile of a 738M bucket reads as a stall
    server = ModelServer(model, port=0, input_dtype=np.int32,
                         gen_slots=size.slots, gen_capacity=size.capacity,
                         aot_store=AotStore(store_dir)).start()
    boot_s = time.perf_counter() - t0
    if _get(server.port, "/ready").get("status") != "ready":
        raise AssertionError("server not ready after boot")
    return server, boot_s


def _aot_counts(snap: dict) -> dict:
    fallbacks = {s["labels"].get("cause", "?"): s["value"]
                 for s in snap.get("serve_aot_fallback_total", {})
                 .get("series", []) if s["value"]}
    return {"hits": _metric(snap, "serve_aot_hits_total"),
            "misses": _metric(snap, "serve_aot_misses_total"),
            "compile_misses": _metric(snap, "serve_compile_misses_total"),
            "fallbacks": fallbacks}


def leg_serve(size: Size, model, store_dir: str):
    """Cold boot on an empty store, then the request plan over HTTP.
    Returns (record, requests-with-their-tokens)."""
    plan = plan_requests(size)
    sampler = HbmSampler()
    sampler.start()
    server, boot_s = boot_server(size, model, store_dir)
    try:
        t0 = time.perf_counter()
        plan[0]["tokens"] = _generate(server.port, plan[0], stream=True)
        first_s = time.perf_counter() - t0
        time.sleep(0.1)  # handlers record their metrics after replying
        after_first = _aot_counts(server.metrics.snapshot())
        log(f"c. boot {boot_s:.1f} s, first /generate (builds the generation "
            f"stack) {first_s:.1f} s, compiles {after_first['compile_misses']:.0f}")

        wave = plan[1:-1]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(wave)) as pool:
            # greedy/sampled alternate; streamed/buffered alternate in pairs
            futures = [pool.submit(_generate, server.port, body,
                                   (i // 2) % 2 == 0)
                       for i, body in enumerate(wave)]
            for body, fut in zip(wave, futures):
                body["tokens"] = fut.result()
        wave_s = time.perf_counter() - t0
        plan[-1]["tokens"] = _generate(server.port, plan[-1], stream=False)
        time.sleep(0.1)
        snap = server.metrics.snapshot()
        kv = _get(server.port, "/models").get("kv", {})
    finally:
        server.stop()
        hbm_max = sampler.finish()

    for i, body in enumerate(plan):
        toks = body["tokens"]
        if len(toks) != body["max_new_tokens"] or not all(
                isinstance(t, int) and 0 <= t < size.vocab for t in toks):
            raise AssertionError(
                f"request {i}: got {len(toks)} tokens, asked "
                f"{body['max_new_tokens']}")
    received = sum(len(b["tokens"]) for b in plan)
    counts = _aot_counts(snap)
    checks = {
        "sheds": _metric(snap, "serve_shed_total"),
        "http_errors": _metric(snap, "serve_http_errors_total"),
        "prefix_cache_hits": _metric(snap, "serve_prefix_cache_hits_total"),
        "gen_tokens_total": _metric(snap, "serve_gen_tokens_total"),
        "tokens_received": received,
        "completed": _metric(snap, "serve_gen_completed_total"),
    }
    if checks["sheds"] or checks["http_errors"]:
        raise AssertionError(f"serve: sheds/errors: {checks}")
    if checks["prefix_cache_hits"] < 1:
        raise AssertionError("serve: the shared prefix never hit the cache")
    if checks["gen_tokens_total"] != received \
            or checks["completed"] != len(plan):
        raise AssertionError(f"serve: token accounting: {checks}")
    if counts["compile_misses"] != after_first["compile_misses"]:
        raise AssertionError(
            f"serve: compiles after the first request: "
            f"{after_first['compile_misses']} -> {counts['compile_misses']}")
    if counts["fallbacks"]:
        raise AssertionError(f"serve: AOT fallbacks {counts['fallbacks']}")
    rec = {"requests": len(plan), "concurrent": len(wave),
           "boot_seconds": round(boot_s, 2),
           "first_generate_seconds": round(first_s, 2),
           "wave_seconds": round(wave_s, 2), **checks, "aot": counts,
           "decode_tick_seconds": _hist(snap, "serve_gen_decode_seconds"),
           "prefill_chunk_seconds": _hist(snap, "serve_gen_prefill_seconds"),
           "kv": {k: kv.get(k) for k in ("blocks_total", "block_size")},
           "hbm_sampled_max_bytes": hbm_max, "hbm": hbm()}
    log(f"c. {len(plan)} requests, {received} tokens, wave of {len(wave)} in "
        f"{wave_s:.1f} s, decode tick {rec['decode_tick_seconds']}, prefill "
        f"chunk {rec['prefill_chunk_seconds']}, hbm sampled max {hbm_max}")
    return rec, plan


# ------------------------------------------------------------------ (d) agree
def leg_agree(size: Size, params, plan: list) -> dict:
    """Three greedy requests (the first, and the shortest and longest greedy
    prompts of the wave): the whole sequence through the plain full forward
    — dense attention, f32, "highest" precision — and at every generated
    position the served token's logit within LOGIT_TOL of the maximum.

    The same gaps are then taken with each prompt swapped for other random
    tokens. There the mean must come out far ABOVE the tolerance: a model
    whose next token ignored its context (one that has only learnt the
    unigram bias, say) would pass the first check with a broken cache too."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.model import _layer_key

    greedy = sorted((b for b in plan[1:-1] if b["temperature"] == 0.0),
                    key=lambda b: len(b["prompt"]))
    picked = [plan[0], greedy[0], greedy[-1]]
    ref = build_model(size, flash=False, compute_dtype=None)
    n = len(ref.layers)
    head = ref.layers[-1]
    head_key = _layer_key(n - 1, head)

    @jax.jit
    def ref_gaps(params, ids):
        """Per position t: how far the NEXT token's logit sits below the
        maximum, and the spread of the logits there."""
        with jax.default_matmul_precision("highest"):
            h, _ = ref.forward(params, {}, ids, training=False, up_to=n - 1)
            logits = head.preactivation(params[head_key], h)
        nxt = jnp.take_along_axis(logits[:, :-1], ids[:, 1:, None], axis=-1)
        return (jnp.max(logits[:, :-1], axis=-1) - nxt[..., 0],
                jnp.std(logits[:, :-1], axis=-1))

    rng = np.random.default_rng(2)
    ids = np.zeros((2 * len(picked), size.capacity), np.int32)
    for i, b in enumerate(picked):
        seq = b["prompt"] + b["tokens"]
        ids[i, :len(seq)] = seq  # causal: right padding cannot reach back
        ids[len(picked) + i] = ids[i]
        ids[len(picked) + i, :len(b["prompt"])] = rng.integers(
            0, size.vocab, len(b["prompt"]))
    gap, spread = (np.asarray(a) for a in ref_gaps(params, jnp.asarray(ids)))

    def gaps(offset):  # position t - 1 predicts token t
        return np.concatenate([
            gap[offset + i, len(b["prompt"]) - 1:
                len(b["prompt"]) + len(b["tokens"]) - 1]
            for i, b in enumerate(picked)])

    true, wrong = gaps(0), gaps(len(picked))
    rec = {"sequences": len(picked), "positions": int(true.size),
           "prompt_lens": [len(b["prompt"]) for b in picked],
           "argmax_flips": int((true > 0).sum()),
           "max_gap": round(float(true.max()), 4),
           "mean_gap": round(float(true.mean()), 5),
           "wrong_context_mean_gap": round(float(wrong.mean()), 4),
           "wrong_context_median_gap": round(float(np.median(wrong)), 4),
           "logit_std": round(float(spread[0, len(picked[0]["prompt"])]), 3),
           "tol": {"max": LOGIT_TOL, "mean": MEAN_GAP_TOL}}
    log(f"d. {rec}")
    if not np.isfinite(true).all() or true.max() > LOGIT_TOL \
            or true.mean() > MEAN_GAP_TOL:
        raise AssertionError(
            f"agree: served tokens sit below the reference maximum by "
            f"{true.max():.3f} at worst (tolerance {LOGIT_TOL}), "
            f"{true.mean():.4f} on average (tolerance {MEAN_GAP_TOL})")
    if wrong.mean() < AGREE_MIN_POWER * MEAN_GAP_TOL:
        raise AssertionError(
            f"agree: with the prompts swapped the mean gap is only "
            f"{wrong.mean():.4f} — the check could not see a wrong cache")
    return rec


# ------------------------------------------------------------------- (e) warm
def leg_warm_boot(size: Size, model, store_dir: str, first: dict,
                  cold_aot: dict) -> dict:
    """A second server on the first one's store: everything loads, nothing
    compiles, nothing falls back, and the first request repeats exactly."""
    server, boot_s = boot_server(size, model, store_dir)
    try:
        t0 = time.perf_counter()
        toks = _generate(server.port, {k: v for k, v in first.items()
                                       if k != "tokens"}, stream=True)
        first_s = time.perf_counter() - t0
        time.sleep(0.1)
        counts = _aot_counts(server.metrics.snapshot())
    finally:
        server.stop()
    if counts["hits"] <= 0 or counts["compile_misses"] != 0:
        raise AssertionError(f"warm boot compiled or missed: {counts}")
    if counts["fallbacks"] or cold_aot["fallbacks"]:
        raise AssertionError(
            f"AOT fallbacks: cold {cold_aot['fallbacks']}, "
            f"warm {counts['fallbacks']}")
    if toks != first["tokens"]:
        raise AssertionError("warm boot: greedy tokens differ from cold boot")
    rec = {"boot_seconds": round(boot_s, 2),
           "first_generate_seconds": round(first_s, 2), "aot": counts,
           "tokens_equal_cold": True}
    log(f"e. {rec}")
    return rec


# ------------------------------------------------------------------- (f) four
def leg_four_chip(size: Size, one_chip_first_loss: float) -> dict:
    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    rec = {}
    for name, axes in (("data4", {DATA_AXIS: 4}),
                       ("data2_model2", {DATA_AXIS: 2, MODEL_AXIS: 2})):
        sub, model = leg_train(size, mesh_axes=axes, batch=2 * size.batch)
        del model  # and with it this mesh's params, before the next one's
        gc.collect()
        first = sub["losses"][0]
        if abs(first - one_chip_first_loss) \
                > MESH_LOSS_RTOL * abs(one_chip_first_loss):
            raise AssertionError(
                f"four_chip {name}: first-step loss {first} vs one chip "
                f"{one_chip_first_loss}")
        rec[name] = sub
    rec["one_chip_first_loss"] = one_chip_first_loss
    return rec


# ----------------------------------------------------------------------- main
LEGS = "abcdef"


def report(out: dict, out_dir: str) -> None:
    """The full record (versions, per-leg seconds, compile seconds, HBM) as
    one JSON line on stdout and in ``chip_smoke.json``; then, as the LAST
    line of stdout, the result in the fixed form the chip check reads:
    ``ok`` and ``device`` {platform, kind, count}, nothing else."""
    line = json.dumps(out)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    dev = out["device"]
    print(json.dumps({"ok": bool(out["ok"]),
                      "device": {"platform": str(dev["platform"]),
                                 "kind": str(dev["kind"]),
                                 "count": int(dev["count"])}}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=LEGS,
                    help="legs to run, e.g. 'ab' or 'f' (default: all); c-e "
                         "run the train leg first, f runs one one-chip step")
    legs = set(ap.parse_args(argv).legs.replace(",", ""))
    if not legs <= set(LEGS):
        ap.error(f"--legs takes letters of {LEGS!r}")

    from deeplearning4j_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    dev, ver = device_info(), versions()
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} jax={ver['jax']} jaxlib={ver['jaxlib']} "
        f"libtpu={ver['libtpu']} compile_cache={cache_dir}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU: JAX's backend is {dev['platform']!r} "
              f"({dev['kind']}). This script only passes on a TPU.",
              file=sys.stderr)
        return 2

    size = FULL
    store_dir = os.path.join(OUT_DIR, "chip_smoke_aot")
    shutil.rmtree(store_dir, ignore_errors=True)  # nothing pre-built serves
    os.makedirs(OUT_DIR, exist_ok=True)
    out = {"ok": False, "device": dev, "versions": ver,
           "compile_cache_dir": cache_dir, "legs": {}, "seconds": {}}

    def run(name, fn, *args, **kw):
        t0 = time.perf_counter()
        res = fn(*args, **kw)
        out["seconds"][name] = round(time.perf_counter() - t0, 1)
        log(f"leg {name} passed in {out['seconds'][name]} s")
        return res

    try:
        if "a" in legs:
            out["legs"]["kernels"] = run("kernels", leg_kernels, size)
        model = first_loss = None
        if legs & set("bcde"):
            out["legs"]["train"], model = run("train", leg_train, size)
            first_loss = out["legs"]["train"]["losses"][0]
        if legs & set("cde"):
            gc.collect()  # the trainer (Adam state, ~6 GB) is gone by now
            cold, plan = run("serve", leg_serve, size, model, store_dir)
            out["legs"]["serve"] = cold
            if "d" in legs:
                out["legs"]["agree"] = run("agree", leg_agree, size,
                                           model.params, plan)
            if "e" in legs:
                out["legs"]["warm_boot"] = run(
                    "warm_boot", leg_warm_boot, size, model, store_dir,
                    plan[0], cold["aot"])
        if "f" in legs and dev["count"] < 4:
            log(f"four_chip: skipped ({dev['count']} device)")
            out["legs"]["four_chip"] = f"skipped ({dev['count']} device)"
        elif "f" in legs:
            if first_loss is None:
                one, model = run("one_chip_step", leg_train, size, steps=1)
                first_loss = one["losses"][0]
            del model  # device 0 holds its params; the meshes need the room
            gc.collect()
            out["legs"]["four_chip"] = run("four_chip", leg_four_chip, size,
                                           first_loss)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    out["ok"] = True
    # where compilation shows: small on a second run in the same command
    out["compile_seconds"] = {
        f"{leg}.{key}": out["legs"][leg][key]
        for leg, key in (("kernels", "compile_seconds"),
                         ("train", "compile_seconds"),
                         ("serve", "boot_seconds"),
                         ("serve", "first_generate_seconds"))
        if leg in out["legs"]}
    out["seconds"]["total"] = round(time.perf_counter() - _T0, 1)
    report(out, OUT_DIR)
    return 0


if __name__ == "__main__":
    sys.exit(main())
