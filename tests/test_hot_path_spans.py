"""Hot-path spans (ISSUE 23): the one seam in ``obs/trace.py`` whose spans land
in the JAX profiler's own trace, the counters at the same boundaries, and the
names on the device side.

- with a profiler session open around real SSE traffic, every name of the
  table is on ``/host:CPU`` and the decode tick's children nest in order;
- with no session nothing in ``obs/`` is called that was not called before;
- ``serve_gen_queue_seconds`` / ``serve_gen_first_token_seconds`` are
  observed once per admitted request, with or without ``reqtrace``;
- ``ModelServer`` owns exactly one ``gc.callbacks`` entry from ``start()`` to
  ``stop()``;
- the flash kernels carry their names.
"""

import gc
import json
import os
import re
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.obs import metrics as obs_metrics
from deeplearning4j_tpu.obs import reqtrace as reqtrace_mod
from deeplearning4j_tpu.obs import step as obs_step
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.metrics import MetricsRegistry
from deeplearning4j_tpu.obs.reqtrace import RequestTracer
from deeplearning4j_tpu.obs.trace import Tracer

TICK_CHILDREN = (obs_trace.GEN_TICK_PREPARE, obs_trace.GEN_TICK_DISPATCH,
                 obs_trace.GEN_TICK_READBACK, obs_trace.GEN_TICK_PUBLISH)


def _lm():
    from deeplearning4j_tpu.models import CausalLM

    lm = CausalLM(seed=0, input_shape=(64,), num_layers=2, d_model=32,
                  num_heads=4, vocab=50).build()
    lm.init()
    return lm


def _stream(port, prompt, n):
    """One SSE /generate call; returns the token events."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": n,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    with urllib.request.urlopen(req, timeout=120) as r:
        for line in r:
            if line.startswith(b"data: "):
                events.append(json.loads(line[len(b"data: "):]))
    assert events[-1].get("done") is True, events[-1:]
    return events


def _total(snap, name, field, **labels):
    return sum(s[field] for s in snap[name]["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


# ------------------------------------------------- (a) under a profiler session
@pytest.fixture(scope="module")
def host_lines(tmp_path_factory):
    """``{line index: [(name, start_ns, end_ns, stats)]}`` of ``/host:CPU``
    from a session opened around two SSE generations and one forced full
    collection against a small paged ``ModelServer``."""
    from jax.profiler import ProfileData

    from deeplearning4j_tpu.serve.http import ModelServer

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    srv = ModelServer(_lm(), port=0, input_dtype=np.int32, gen_slots=2,
                      gen_capacity=32, gen_prefill_chunk=8).start()
    try:
        _stream(srv.port, [1, 2, 3, 4], 3)          # every program compiled
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1               # as the benchmark traces
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            _stream(srv.port, list(range(1, 13)), 6)    # two chunks, six tokens
            _stream(srv.port, [5, 6, 7], 4)
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        srv.stop()
    found = [os.path.join(base, f) for base, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    assert len(found) == 1, found
    host = [p for p in ProfileData.from_file(found[0]).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    return {i: [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                 dict(e.stats)) for e in line.events]
            for i, line in enumerate(host[0].lines)}


@pytest.mark.parametrize("name", obs_trace.SPAN_NAMES)
def test_every_span_of_the_table_is_on_the_host_plane(host_lines, name):
    hits = [e for events in host_lines.values() for e in events if e[0] == name]
    assert hits, f"{name} not in the profiler's trace"
    assert all(end >= start for _, start, end, _ in hits)


def _worker_line(host_lines):
    """The batcher worker's line: the one that holds ``gen.tick`` events."""
    lines = [ev for ev in host_lines.values()
             if any(e[0] == obs_trace.GEN_TICK for e in ev)]
    assert len(lines) == 1, "gen.tick on more than one thread"
    return lines[0]


def test_tick_children_nest_in_order(host_lines):
    """Since PR 41 a tick enqueues the NEXT step (prepare, dispatch) and then
    reads back and publishes the one before it: the four children in that
    order where a step runs on both sides, the first two alone for a
    request's first step (nothing to read yet), prepare and the last two for
    its last (nothing more to enqueue)."""
    worker = _worker_line(host_lines)
    ticks = [e for e in worker if e[0] == obs_trace.GEN_TICK]
    P, D, R, U = TICK_CHILDREN
    full = published = 0
    for _, t0, t1, _ in ticks:
        inside = sorted((e for e in worker if e[0] in TICK_CHILDREN
                         and t0 <= e[1] and e[2] <= t1), key=lambda e: e[1])
        names = tuple(e[0] for e in inside)
        assert names in ((P,), (P, D), (P, R, U), TICK_CHILDREN), names
        full += names == TICK_CHILDREN
        published += U in names
        for (_, _, end, _), (_, start, _, _) in zip(inside, inside[1:]):
            assert end <= start     # dispatch ends before readback starts
    assert published >= 8         # 6 + 4 tokens, the first of each from prefill
    assert full >= 5              # all but a request's first and last step
    # no publish outside a tick; a readback outside one is the slack's wait
    # for the running step, and lies in the turn
    for e in worker:
        if e[0] in (P, D, U):
            assert any(t0 <= e[1] and e[2] <= t1 for _, t0, t1, _ in ticks)


def test_tick_carries_active_and_nothing_else_takes_arguments(host_lines):
    worker = _worker_line(host_lines)
    actives = [e[3].get("active") for e in worker
               if e[0] == obs_trace.GEN_TICK and "active" in e[3]]
    assert actives and all(int(a) == 1 for a in actives)
    for events in host_lines.values():
        for name, _, _, stats in events:
            if name == obs_trace.GC_PAUSE:
                assert int(stats["generation"]) in (0, 1, 2)
            elif name in obs_trace.SPAN_NAMES and name != obs_trace.GEN_TICK:
                assert not stats, (name, stats)


def test_prefill_and_first_token_are_siblings_on_the_worker(host_lines):
    worker = _worker_line(host_lines)
    chunks = [e for e in worker if e[0] == obs_trace.GEN_PREFILL_CHUNK]
    firsts = [e for e in worker if e[0] == obs_trace.GEN_FIRST_TOKEN]
    assert len(chunks) == 3 and len(firsts) == 2    # 12 tokens = 8 + 4; then 3
    for _, f0, f1, _ in firsts:
        assert not any(c0 < f1 and f0 < c1 for _, c0, c1, _ in chunks)
    # SSE writes happen on handler threads, never on the worker's line
    assert not any(e[0] == obs_trace.HTTP_STREAM_WRITE for e in worker)


def test_every_chunk_and_tick_lies_in_a_turn_and_admission_outside(host_lines):
    """``gen.turn`` covers the worker from admission's end to the loop's back
    edge: the chunks, first tokens and ticks nest in it, ``gen.admit`` never
    does, and what is left between two turns is the next admission."""
    worker = _worker_line(host_lines)
    turns = sorted((e[1], e[2]) for e in worker if e[0] == obs_trace.GEN_TURN)
    assert len(turns) >= 8
    for (_, end), (start, _) in zip(turns, turns[1:]):
        assert end <= start
    inner = (obs_trace.GEN_TICK, obs_trace.GEN_PREFILL_CHUNK,
             obs_trace.GEN_FIRST_TOKEN)
    for name, t0, t1, _ in worker:
        covered = any(a <= t0 and t1 <= b for a, b in turns)
        if name in inner:
            assert covered, name
        elif name == obs_trace.GEN_ADMIT:
            assert not any(a < t1 and t0 < b for a, b in turns)


def test_step_telemetry_spans_reach_the_profiler(tmp_path):
    """``Tracer.span`` opens the same annotation beside its own record."""
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("train_step", kind="single"):
            with tr.span("dispatch"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
            for f in fs if f.endswith(".xplane.pb")][0]
    names = {e.name for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines for e in line.events}
    assert {"train_step", "dispatch"} <= names
    assert [e["name"] for e in tr.events if e["ph"] == "X"] \
        == ["dispatch", "train_step"]       # its own record, as before


# ------------------------------------------------------ (b) with no session
def test_no_session_no_tracer_and_no_request_context_calls(monkeypatch):
    from deeplearning4j_tpu.serve import ContinuousBatcher

    def boom(*a, **k):
        raise AssertionError("obs touched on the default serving path")

    for meth in ("span", "instant", "async_event", "_add"):
        monkeypatch.setattr(Tracer, meth, boom)
    for meth in ("add_stage", "stage", "decode_begin", "decode_tick",
                 "finish_work", "finish", "annotate"):
        monkeypatch.setattr(reqtrace_mod.RequestContext, meth, boom)
    monkeypatch.setattr(reqtrace_mod.RequestTracer, "begin", boom)
    monkeypatch.setattr(obs_trace.GcPauses, "__call__", boom)
    assert reqtrace_mod.ACTIVE is None
    cb = ContinuousBatcher(_lm(), slots=2, capacity=16, seed=0)
    try:
        assert len(cb.generate(np.arange(1, 5, dtype=np.int32), 4,
                               temperature=0.0)) == 4
    finally:
        cb.shutdown()


def test_fit_without_telemetry_makes_zero_obs_calls(monkeypatch):
    from deeplearning4j_tpu.data import ArrayIterator
    from deeplearning4j_tpu.nn.layers import Dense, Output
    from deeplearning4j_tpu.nn.model import NetConfig, Sequential
    from deeplearning4j_tpu.train import Trainer

    calls = []

    def spy(name):
        def f(*a, **k):
            calls.append(name)
            raise AssertionError(f"obs call {name} on a plain fit")
        return f

    monkeypatch.setattr(obs_trace, "span", spy("trace.span"))
    monkeypatch.setattr(Tracer, "span", spy("Tracer.span"))
    monkeypatch.setattr(obs_step.StepTelemetry, "step", spy("step"))
    monkeypatch.setattr(obs_metrics.Histogram, "observe", spy("observe"))
    monkeypatch.setattr(obs_metrics.Counter, "inc", spy("inc"))
    model = Sequential(
        NetConfig(updater={"type": "sgd", "learning_rate": 0.1}),
        [Dense(n_out=8, activation="relu"),
         Output(n_out=3, loss="mcxent", activation="softmax")], (5,))
    rng = np.random.RandomState(0)
    it = ArrayIterator(rng.rand(32, 5).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.randint(0, 3, 32)],
                       batch_size=16)
    Trainer(model).fit(it, epochs=1, telemetry=None)
    assert calls == []


def test_span_without_jax_is_the_null_span(monkeypatch):
    """In a process that never loaded JAX the seam does not import it."""
    monkeypatch.setattr(obs_trace, "_ANNOTATION", None)
    monkeypatch.delitem(sys.modules, "jax")
    s = obs_trace.span(obs_trace.GEN_TICK, active=1)
    assert s is obs_trace._NULL_SPAN
    with s as entered:
        entered.set_metadata(active=2)
    assert "jax" not in sys.modules


def test_trace_annotation_lives_behind_the_one_seam():
    import deeplearning4j_tpu

    root = os.path.dirname(deeplearning4j_tpu.__file__)
    users = []
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    if "TraceAnnotation" in fh.read():
                        users.append(os.path.relpath(os.path.join(base, f), root))
    assert users == [os.path.join("obs", "trace.py")]


# ------------------------------------------ (c) counters at the same boundaries
@pytest.mark.parametrize("prefill_chunk", [4, None],
                         ids=["chunks-of-4", "whole-prompt"])
@pytest.mark.parametrize("traced", [False, True], ids=["plain", "reqtrace"])
def test_queue_and_first_token_counted_once_per_admitted_request(
        prefill_chunk, traced):
    """Both prefill modes: a prompt of up to three chunks closes its queue
    wait at the first one only, and a whole-prompt prefill at its one."""
    from deeplearning4j_tpu.serve import ContinuousBatcher

    reg = MetricsRegistry()
    rt = RequestTracer(tracer=Tracer()) if traced else None
    cb = ContinuousBatcher(_lm(), slots=2, capacity=32, seed=0,
                           prefill_chunk=prefill_chunk, metrics=reg)
    rng = np.random.RandomState(1)
    try:
        ctxs = [rt.begin("generate") if rt else None for _ in range(5)]
        reqs = [cb.submit(rng.randint(1, 50, (int(rng.randint(2, 12)),)),
                          int(rng.randint(1, 5)), temperature=0.0, ctx=c)
                for c in ctxs]                      # five requests, two slots
        for r in reqs:
            r.wait()
    finally:
        cb.shutdown()
    snap = reg.snapshot()
    admitted = _total(snap, "serve_gen_admitted_total", "value")
    assert admitted == 5
    assert _total(snap, "serve_gen_queue_seconds", "count") == admitted
    assert _total(snap, "serve_gen_first_token_seconds", "count") == admitted
    waits = [r.disp_t - r.enq_t for r in reqs]
    firsts = [r.first_t - r.enq_t for r in reqs]
    assert all(0 <= w <= f for w, f in zip(waits, firsts))
    assert _total(snap, "serve_gen_queue_seconds", "sum") \
        == pytest.approx(sum(waits), abs=1e-9)
    assert _total(snap, "serve_gen_first_token_seconds", "sum") \
        == pytest.approx(sum(firsts), abs=1e-9)
    if traced:      # reqtrace's queue stage is the same stamp, not a second one
        for ctx, wait in zip(ctxs, waits):
            queue = [s for s in ctx.stages if s["name"] == "queue"]
            assert len(queue) == 1
            assert queue[0]["dur_ms"] / 1e3 == pytest.approx(wait, abs=2e-9)


# ------------------------------------------------------- (d) collector pauses
def test_server_owns_one_gc_callback_from_start_to_stop():
    from deeplearning4j_tpu.serve.http import ModelServer

    before = list(gc.callbacks)
    srv = ModelServer(_lm(), port=0, input_dtype=np.int32, gen_slots=2,
                      gen_capacity=16)
    assert gc.callbacks == before                   # not before start()
    srv.start()
    try:
        added = [c for c in gc.callbacks if c not in before]
        assert len(added) == 1 and isinstance(added[0], obs_trace.GcPauses)
        full0 = _total(srv.metrics.snapshot(), "process_gc_pause_seconds",
                       "count", generation="2")
        gc.collect()
        snap = srv.metrics.snapshot()
        assert _total(snap, "process_gc_pause_seconds", "count",
                      generation="2") == full0 + 1
        assert _total(snap, "process_gc_pause_seconds", "sum",
                      generation="2") > 0
        gc.collect(0)
        assert _total(srv.metrics.snapshot(), "process_gc_pause_seconds",
                      "count", generation="0") >= 1
    finally:
        srv.stop()
    assert gc.callbacks == before
    srv.stop()                                      # idempotent


def test_a_collection_inside_a_snapshot_does_not_deadlock():
    """The hook observes on whichever thread tripped the collector, which
    may be holding the histogram's own lock (a snapshot copying counts)."""
    hook = obs_trace.GcPauses(MetricsRegistry())
    hist = hook._hist[2]
    with hist._lock:
        hook("start", {"generation": 2})
        hook("stop", {"generation": 2})
    assert hist.count == 1


# ---------------------------------------------------- (e) names on the kernels
@pytest.mark.parametrize("name", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"])
def test_flash_kernels_carry_their_names_and_still_agree(name):
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 48, 2, 16)), jnp.float32)
               for _ in range(3))
    mask = jnp.tril(jnp.ones((48, 48), bool))[None, None]

    def flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, backward="pallas")))

    def dense(q, k, v):
        return jnp.sum(jnp.sin(dot_product_attention(q, k, v, mask=mask)))

    fn = flash if name == "flash_fwd" else jax.grad(flash, argnums=(0, 1, 2))
    ref = dense if name == "flash_fwd" else jax.grad(dense, argnums=(0, 1, 2))
    kernels = set(re.findall(r"name=(flash_\w+)", str(jax.make_jaxpr(fn)(q, k, v))))
    assert name in kernels
    for got, want in zip(jax.tree.leaves(fn(q, k, v)),
                         jax.tree.leaves(ref(q, k, v))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.fixture(scope="module")
def served_program_texts():
    """The lowered texts, with locations, of a paged batcher's one decode
    program and of its first prefill-chunk program, at a bf16 compute
    dtype: ``{"decode": ..., "chunk": ...}``."""
    from deeplearning4j_tpu.serve.continuous import ContinuousBatcher

    lm = _lm()
    lm.config.compute_dtype = "bfloat16"
    cb = ContinuousBatcher(lm, slots=2, capacity=16, seed=0)
    try:
        snap = cb.registry.current()
        decode = cb._programs._decode.lower(
            snap.params, snap.state, jnp.zeros((2,), jnp.int32),
            cb._programs.pools,
            jnp.asarray(cb._tables_np), jnp.zeros((2,), jnp.int32),
            jnp.asarray(cb._keys), jnp.asarray(cb._temps),
            jnp.asarray(cb._topks), jnp.zeros((2,), bool),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.asarray(cb._keys)).as_text(debug_info=True)
        chunk = cb._programs._prefill_chunk.lower(
            *cb._programs.signatures(snap.params, snap.state)
            ["gen_prefill_chunk"][0]).as_text(debug_info=True)
        return {"decode": decode, "chunk": chunk}
    finally:
        cb.shutdown()


@pytest.mark.parametrize("scope", ["attention", "cache_read", "mlp", "head",
                                   "weight_cast", "sample"])
def test_named_scopes_reach_the_decode_program(served_program_texts, scope):
    """Scopes are metadata: they are on the path (``op_name``) of the lowered
    program's operations, where a device trace's reader finds them
    (``benchmark/harness/op_scopes.py``), and change no result (the serve
    tests hold the tokens). ``cache_read``, the gather at capacity, left the
    decode step in PR 46 (a full k/v pool is read in place by the kernel of
    ``ops/paged_attention.py``): it names the gather of a prefill chunk, and
    no operation of the decode step."""
    if scope == "cache_read":       # the gather lies inside attention
        assert re.search(r'"[^"]*/attention/cache_read/',
                         served_program_texts["chunk"])
        assert "cache_read" not in served_program_texts["decode"]
        return
    assert re.search(rf'"[^"]*[/(]{scope}[/)]',
                     served_program_texts["decode"]), scope


@pytest.mark.parametrize("scope", ["attention", "mlp", "head", "loss", "optimizer"])
def test_named_scopes_reach_the_training_step(scope):
    from deeplearning4j_tpu.train import Trainer

    lm = _lm()
    trainer = Trainer(lm)
    x = jnp.zeros((2, 64), jnp.int32)
    text = trainer._make_step().lower(
        lm.params, trainer.opt_state, lm.state, x, x,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert re.search(rf'"[^"]*[/(]{scope}[/)]', text), scope
