"""``harness/host_spans.py`` and ``harness/op_scopes.py`` on traces laid out
by hand, whose readings can be worked out on paper, and the new serving
metrics end to end in the CPU rehearsal (a scratch copy, as ``test_extend.py``
makes one: the benchmark's own test manifest is not edited).

The timeline (ns; the device's busy intervals are the four decode programs):

    device   busy [0,100) [300,400) [700,800) [1000,1100)   window 1100, idle 700
    gaps     A [100,300)          B [400,700)           C [800,1000)
    worker   turn [15,208):  tick [20,205): prepare [20,30) dispatch [30,40)
                             readback [40,150) publish [150,200)
             admit [210,260)
             turn [262,655): tick [270,560): prepare [270,280) dispatch [280,300)
                             readback [300,400) publish [400,550)
                             prefill_chunk [560,600)  first_token [600,650)
             admit [790,830)
             turn [830,1108): tick [830,1105): prepare [830,850)
                              dispatch [850,900) readback [900,1100)
    handler  http.stream_write [100,300), and a stray gen.admit [650,700)
    xla      DoEnqueueProgram of run 0..3 at 0, 275, 695, 990

    A: readback 50, publish 50, tick-self 5, turn 3, none 2, admit 50, none 2,
       turn 8, prepare 10, dispatch 20     (split across spans)
    B: publish 150 (innermost under gen.tick), tick-self 10, prefill 40 + 50,
       turn 5, none 45 (the handler's gen.admit is another thread's)
    C: admit 30, prepare 20, dispatch 50, readback 100
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import xplane_writer as xw
from harness import (env, host_spans as hs, layer_metrics as lm, op_scopes,
                     trace_reduce)

ON_PAPER = {hs.GEN_TICK_PREPARE: 30, hs.GEN_TICK_DISPATCH: 70,
            hs.GEN_TICK_READBACK: 150, hs.GEN_TICK_PUBLISH: 200,
            hs.GEN_ADMIT: 80, hs.GEN_PREFILL_CHUNK: 40, hs.GEN_FIRST_TOKEN: 50,
            hs.GEN_TURN: 16, hs.UNATTRIBUTED: 64}
IDLE_SHARES = ["idle_tick_prepare_share", "idle_tick_dispatch_share",
               "idle_tick_readback_share", "idle_tick_publish_share",
               "idle_admit_share", "idle_prefill_share", "idle_turn_self_share",
               "idle_unattributed_share"]
BUSY = [(0, 100), (300, 100), (700, 100), (1000, 100)]
LEADS = (0, 25, 5, 10)      # ns between an enqueue and the program's start
WORKER = [(hs.GEN_TURN, 15, 193),
          (hs.GEN_TICK, 20, 185), (hs.GEN_TICK_PREPARE, 20, 10),
          (hs.GEN_TICK_DISPATCH, 30, 10), (hs.GEN_TICK_READBACK, 40, 110),
          (hs.GEN_TICK_PUBLISH, 150, 50), (hs.GEN_ADMIT, 210, 50),
          (hs.GEN_TURN, 262, 393),
          (hs.GEN_TICK, 270, 290), (hs.GEN_TICK_PREPARE, 270, 10),
          (hs.GEN_TICK_DISPATCH, 280, 20), (hs.GEN_TICK_READBACK, 300, 100),
          (hs.GEN_TICK_PUBLISH, 400, 150), (hs.GEN_PREFILL_CHUNK, 560, 40),
          (hs.GEN_FIRST_TOKEN, 600, 50), (hs.GEN_ADMIT, 790, 40),
          (hs.GEN_TURN, 830, 278),
          (hs.GEN_TICK, 830, 275), (hs.GEN_TICK_PREPARE, 830, 20),
          (hs.GEN_TICK_DISPATCH, 850, 50), (hs.GEN_TICK_READBACK, 900, 200)]
HANDLER = [(hs.HTTP_STREAM_WRITE, 100, 200), (hs.GEN_ADMIT, 650, 50)]
FUSION = "%fusion.1 = f32[8]{0} fusion()"


def stat_plane(pid, name, lines, op_names=None):
    """As ``xplane_writer.plane``, with what the readers need beyond a name,
    a start and a duration. Events are (name, start, dur, run_id or None): one
    int64 stat ``run_id`` on the event, XEvent.stats = 4 {metadata_id = 1,
    int64_value = 4}. ``op_names`` {event name: (path, program id)} becomes
    two stats on the event's METADATA, where a TPU trace keeps an operation's
    JAX op_name and its program: XEventMetadata.stats = 5 {metadata_id = 2,
    str_value = 5} (``tf_op``) and {metadata_id = 3, uint64_value = 3}
    (``program_id``). XPlane.stat_metadata = 5 {id = 1, name = 2}."""
    meta, body = {}, xw._int(1, pid) + xw._bytes(2, name.encode())
    for lid, (lname, events) in enumerate(lines.items(), 1):
        lb = xw._int(1, lid) + xw._bytes(2, lname.encode()) + xw._int(3, 0)
        for ename, start, dur, rid in events:
            mid = meta.setdefault(ename, len(meta) + 1)
            ev = xw._int(1, mid) + xw._int(2, start * 1000) + xw._int(3, dur * 1000)
            if rid is not None:
                ev += xw._bytes(4, xw._int(1, 1) + xw._int(4, rid))
            lb += xw._bytes(4, ev)
        body += xw._bytes(3, lb)
    for ename, mid in meta.items():
        record = xw._int(1, mid) + xw._bytes(2, ename.split("#")[0].encode())
        if op_names and ename in op_names:
            op, program = op_names[ename]
            record += xw._bytes(5, xw._int(1, 2) + xw._bytes(5, op.encode()))
            record += xw._bytes(5, xw._int(1, 3) + xw._int(3, program))
        body += xw._bytes(4, xw._int(1, mid) + xw._bytes(2, record))
    for sid, sname in ((1, b"run_id"), (2, b"tf_op"), (3, b"program_id")):
        body += xw._bytes(5, xw._int(1, sid) + xw._bytes(
            2, xw._int(1, sid) + xw._bytes(2, sname)))
    return body


def write_trace(path, base=0, device_early=0, run_ids=True, device=True):
    """The timeline at ``base``; the device's clock ``device_early`` ns
    behind the host's; without ``run_ids`` the two planes name no execution
    in common."""
    dev = base - device_early
    rid = (lambda i: i) if run_ids else (lambda i: None)
    planes = [stat_plane(2, "/host:CPU", {
        "python": [(n, base + s, d, None) for n, s, d in WORKER],
        "handler": [(n, base + s, d, None) for n, s, d in HANDLER],
        "xla": [(hs.ENQUEUED, base + s - lead, 5, rid(i))
                for i, ((s, _), lead) in enumerate(zip(BUSY, LEADS))]})]
    if device:
        planes.insert(0, stat_plane(1, "/device:TPU:0", {
            "XLA Ops": [(FUSION, dev + s, d, None) for s, d in BUSY],
            "XLA Modules": [("jit__decode_paged_fn(7)", dev + s, d, rid(i))
                            for i, (s, d) in enumerate(BUSY)]}))
    xw.write(path, planes)
    return path


class FakeCell:
    name = "cell"
    traffic = {"job": {"seq_len": 2048, "global_batch": 4}}
    config = env.load_json(os.path.join(env.BENCH_DIR, "configs",
                                        "cerebras-gpt-1.3b.json"))

    def metrics(self, group):
        return []


TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_attribution_matches_the_paper(tmp_path):
    path = write_trace(str(tmp_path / "t.xplane.pb"))
    host, devices = hs.load(path), trace_reduce.load(path)
    assert [s.name for s in host.lines[host.worker]].count(hs.GEN_TICK) == 3
    got = hs.attribute(host, devices)
    assert got["shift"] == {"pairs": 4, "lower_ns": 0, "upper_ns": 0}
    assert (got["window_ns"], got["idle_ns"]) == (1100, 700)
    assert got["by_span"] == got["by_span_upper"] == ON_PAPER
    # the eight shares are trace_reduce's idle share, split
    idle = 100.0 * trace_reduce.summary(devices)["idle_share_worst"]
    assert 100.0 * sum(got["by_span"].values()) / got["window_ns"] \
        == pytest.approx(idle)
    assert sum(g for _, g in trace_reduce.idle_gaps(devices)) * 1e9 \
        == pytest.approx(got["idle_ns"])


def test_the_readers_find_the_runs_trace_and_add_up(tmp_path, monkeypatch):
    path = write_trace(str(tmp_path / "t.xplane.pb"))
    monkeypatch.setattr(hs, "trace_path", lambda cell: path)
    run = lm.Run(FakeCell(), TPU, trace=trace_reduce.load(path))
    shares = {n: lm.read(run, n) for n in IDLE_SHARES}
    assert shares["idle_tick_publish_share"] == pytest.approx(100 * 200 / 1100)
    assert shares["idle_prefill_share"] == pytest.approx(100 * 90 / 1100)
    assert shares["idle_turn_self_share"] == pytest.approx(100 * 16 / 1100)
    assert shares["idle_unattributed_share"] == pytest.approx(100 * 64 / 1100)
    assert sum(shares.values()) == pytest.approx(
        lm.read_declared(run, {"type": "trace_idle"}))
    monkeypatch.setattr(hs, "trace_path", lambda cell: None)    # untraced
    assert all(lm.read(run, n) is None for n in IDLE_SHARES)


@pytest.mark.parametrize("lacks", ["spans", "run_ids", "device"])
def test_a_trace_that_lacks_a_side_reads_as_nothing(tmp_path, monkeypatch, lacks):
    """A program without the spans (the parent of the PR that added them), a
    runtime that names no execution on both planes (no estimate of the shift
    is guessed in its place), no device plane (the CPU rehearsal): nothing to
    read, nothing raised."""
    path = str(tmp_path / "t.xplane.pb")
    if lacks == "spans":
        xw.write(path, [xw.plane(1, "/device:TPU:0", {
            "XLA Ops": [(FUSION, s, d) for s, d in BUSY]})])
    else:
        write_trace(path, run_ids=lacks != "run_ids", device=lacks != "device")
    host, devices = hs.load(path), trace_reduce.load(path)
    assert hs.attribute(host, devices) is None
    monkeypatch.setattr(hs, "trace_path", lambda cell: path)
    run = lm.Run(FakeCell(), TPU, trace=devices)
    assert all(lm.read(run, n) is None for n in IDLE_SHARES)
    rep = hs.report(path)
    assert "shares" not in rep and rep["devices"] == (lacks != "device")


def test_a_device_clock_that_runs_behind_is_found_from_the_run_ids(tmp_path):
    """Enqueue on the host and execution on the device name the same
    ``run_id``: the largest enqueue-minus-start is the shift, and the
    readbacks bound it from the other side."""
    path = write_trace(str(tmp_path / "t.xplane.pb"), base=50_000,
                       device_early=1_400)
    host = hs.load(path)
    info = hs.clock_shift_ns(host)
    assert (info["pairs"], info["lower_ns"]) == (4, 1_400)
    # (a readback's end pairs with the nearest decode end only where the lag
    # is under half a tick, as a millisecond is of 25: not in this timeline)
    assert info["upper_ns"] is not None
    got = hs.attribute(host, trace_reduce.load(path))
    assert got["by_span"] == ON_PAPER
    rep = hs.report(path)
    assert rep["shares"][hs.GEN_TURN] == pytest.approx(100 * 16 / 1100)
    assert set(rep["shares_at_upper_bound"]) <= set(rep["shares"])
    # on paper: the programs at 300 and 1000 begin inside readbacks
    assert rep["decode_starts_inside_dispatch_or_readback_shifted"] \
        == {"inside": 2, "of": 4}
    assert rep["decode_starts_inside_dispatch_or_readback_unshifted"]["inside"] == 0


def test_the_report_says_what_fills_the_turns_own_time(tmp_path):
    """gen.turn's own segments are [15,20) [205,208) [262,270) [650,655)
    [1105,1108): 24 ns in 3 turns, 3 + 8 of them under the handler's
    ``http.stream_write`` [100,300)."""
    rep = hs.report(write_trace(str(tmp_path / "t.xplane.pb")))
    assert rep["turn_own_time"]["mean_us_a_turn"] == pytest.approx(0.008)
    assert rep["turn_own_time"]["under_stream_write_share"] \
        == pytest.approx(100 * 11 / 24)
    # the longest gaps, each with the worker's spans over it and the writers
    json.dumps(rep)     # what the command line prints
    b, a, c = rep["longest_gaps"]
    assert (b["ms"], a["ms"], c["ms"]) == (300e-6, 200e-6, 200e-6)
    assert b["under_ms"] == pytest.approx({
        hs.GEN_TICK_PUBLISH: 150e-6, hs.GEN_TICK: 10e-6, hs.GEN_TURN: 5e-6,
        hs.GEN_PREFILL_CHUNK: 40e-6, hs.GEN_FIRST_TOKEN: 50e-6})
    assert (b["stream_writes"], a["stream_writes"]) == (0, 1)


def test_the_kernels_events_and_its_roofline(tmp_path, monkeypatch):
    """A Mosaic kernel is one ``XLA Ops`` event named after the
    ``pallas_call``: 100 us a call at the per-device shapes of the training
    cell is 87% of what one v5e could do, compute-bound."""
    path = str(tmp_path / "t.xplane.pb")
    call = "%flash_fwd.1 = (bf16[16,2048,128]{2,1,0}, f32[16,2048,1]{2,1,0}) custom-call()"
    xw.write(path, [xw.plane(1, "/device:TPU:0", {
        "XLA Ops": [(call, 0, 100_000), ("%fusion.2 = f32[8]{0} fusion()", 100_000, 300_000),
                    (call, 400_000, 100_000),
                    ("%flash_bwd_dq.3 = bf16[8]{0} custom-call()", 500_000, 100_000)]})])
    run = lm.Run(FakeCell(), {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
                 trace=trace_reduce.load(path))
    assert lm.read(run, "flash_fwd_busy_share") == pytest.approx(100 * 200 / 600)
    # 4 x 2 x 8 x 2048^2 x 128 / 2 = 17.18 GFLOP over 197 TFLOP/s = 87.2 us
    assert lm.read(run, "flash_fwd_roofline") == pytest.approx(87.2, rel=2e-3)
    run.trace = {}
    assert lm.read(run, "flash_fwd_roofline") is None


# --- device time by named scope -----------------------------------------------
# one decode step [100,1000): a while [100,500) whose body is two gathers (the
# while's self time is 400 - 300 = 100), an mlp matmul, a sort, the head's
# matmul and an async done without an op_name; then a prefill chunk whose
# weight cast is an instruction of the SAME text as the decode step's head
# matmul ("#2" tells the two metadata records apart here and is cut from the
# name written); then one training step
DECODE_ID, PREFILL_ID, STEP_ID = 7054026437570601022, 2246704867589849854, 31
HEAD = "%fusion.3 = bf16[8]{0} fusion()"
SCOPED = [  # (event name, start, dur, op_name, program)
    ("%while.1 = () while()", 100, 400, "jit(_decode_paged_fn)/attention/while", DECODE_ID),
    ("%fusion.17 = bf16[8]{0} fusion()", 100, 200,
     "jit(_decode_paged_fn)/attention/cache_read/gather:", DECODE_ID),
    ("%fusion.18 = bf16[8]{0} fusion()", 300, 100,
     "jit(_decode_paged_fn)/attention/cache_read/gather:", DECODE_ID),
    ("%fusion.2 = bf16[8]{0} fusion()", 500, 250,
     "jit(_decode_paged_fn)/mlp/dot_general:", DECODE_ID),
    ("%sort.5 = f32[8]{0} sort()", 750, 150,
     "jit(_decode_paged_fn)/vmap(sample)/jit(sort)/sort:", DECODE_ID),
    (HEAD, 900, 50, "jit(_decode_paged_fn)/head/dot_general:", DECODE_ID),
    ("%slice-done.1 = f32[8]{0} slice-done()", 950, 50, None, None),
    (HEAD + "#2", 1000, 300,
     "jit(_prefill_chunk_fn)/weight_cast/convert_element_type:", PREFILL_ID),
    ("%fusion.4 = bf16[8]{0} fusion()", 1300, 700,
     "jit(_prefill_chunk_fn)/mlp/dot_general:", PREFILL_ID),
    ("%flash_fwd.1 = bf16[8]{0} custom-call()", 2000, 100,
     "jit(step)/jvp(attention)/shard_map/flash_fwd/pallas_call:", STEP_ID),
    ("%fusion.5 = bf16[8]{0} fusion()", 2100, 200,
     "jit(step)/transpose(jvp(attention))/mul:", STEP_ID),
    ("%fusion.6 = bf16[8]{0} fusion()", 2300, 300,
     "jit(step)/transpose(jvp(mlp))/dot_general:", STEP_ID),
    ("%all-reduce.305 = f32[8]{0} all-reduce()", 2600, 100,
     "jit(step)/transpose(jvp(head))/dot_general:", STEP_ID),
    ("%fusion.7 = f32[8]{0} fusion()", 2700, 100,
     "jit(step)/jvp(loss)/jit(log_softmax)/exp:", STEP_ID),
    ("%fusion.8 = f32[8]{0} fusion()", 2800, 150, "jit(step)/optimizer/mul:", STEP_ID),
    ("%fusion.9 = f32[8]{0} fusion()", 2950, 50, "jit(step)/jvp()/add:", STEP_ID),
]
MODULES = [(f"jit__decode_paged_fn({DECODE_ID})", 100, 900, None),
           (f"jit__prefill_chunk_fn({PREFILL_ID})", 1000, 1000, None),
           (f"jit_step({STEP_ID})", 2000, 1000, None)]
PROGRAMS = ("jit(_decode_paged_fn)", "jit(_prefill_chunk_fn)", "jit(step)")
SCOPE_SHARES = {  # on paper: decode 900 with 850 named, prefill 1000, step 1000
    ("jit(_decode_paged_fn)", "attention"): 100 * 400 / 850,
    ("jit(_decode_paged_fn)", "cache_read"): 100 * 300 / 850,
    ("jit(_decode_paged_fn)", "mlp"): 100 * 250 / 850,
    ("jit(_decode_paged_fn)", "head"): 100 * 50 / 850,
    ("jit(_decode_paged_fn)", "sample"): 100 * 150 / 850,
    ("jit(_prefill_chunk_fn)", "weight_cast"): 30.0,
    ("jit(_prefill_chunk_fn)", "mlp"): 70.0,
    ("jit(step)", "attention"): 30.0, ("jit(step)", "mlp"): 30.0,
    ("jit(step)", "head"): 10.0, ("jit(step)", "loss"): 10.0,
    ("jit(step)", "optimizer"): 15.0}


def scoped_trace(path, op_names=True):
    xw.write(path, [stat_plane(
        1, "/device:TPU:0",
        {"XLA Ops": [(n, s, d, None) for n, s, d, _, _ in SCOPED],
         "XLA Modules": MODULES},
        {n: (op, prog) for n, _, _, op, prog in SCOPED if op} if op_names else None)])
    return path


@pytest.fixture(scope="module")
def scope_report(tmp_path_factory):
    path = scoped_trace(str(tmp_path_factory.mktemp("scoped") / "t.xplane.pb"))
    scopes = sorted({sc for _, sc in SCOPE_SHARES})
    return {row["program"]: row for row in op_scopes.report(path, scopes)}


@pytest.mark.parametrize("program,scope", sorted(SCOPE_SHARES))
def test_device_time_by_named_scope(scope_report, program, scope):
    row = scope_report[program]
    assert row["scopes"][scope] == pytest.approx(SCOPE_SHARES[program, scope])
    assert sum(row["outermost"].values()) == pytest.approx(100.0)
    if scope != "cache_read":       # nested in attention, which is the outermost
        assert row["outermost"][scope] == pytest.approx(row["scopes"][scope])


def test_a_trace_without_op_names_has_no_program_to_report(tmp_path):
    """A backend that writes no ``tf_op``: no table, nothing to print."""
    assert op_scopes.report(scoped_trace(str(tmp_path / "bare.xplane.pb"),
                                         op_names=False), ["mlp"]) == []


def test_scope_paths_and_outermost_scopes():
    assert op_scopes.scope_rx("mlp").search("jit(step)/transpose(jvp(mlp))/dot_general:")
    assert not op_scopes.scope_rx("head").search("jit(step)/jvp(multihead)/head_cast/mul")
    assert op_scopes.outermost("jit(f)/jit(main)/transpose(jvp(mlp))/dot_general:") == "mlp"
    assert op_scopes.outermost("jit(f)/jvp()/add:") == "add"
    assert op_scopes.outermost("jit(f)/attention/cache_read/gather:") == "attention"


# --- the serving metrics end to end, in a scratch copy ------------------------
DEVICE_ONLY = set(IDLE_SHARES)
SERVING = DEVICE_ONLY | {"gc_pause_share"}
ONLY = {"tiny-sessions": {"queue_wait_mean_ms", "server_ttft_mean_ms"},
        "tiny-bursts": {"open_queue_wait_mean_ms"}}


@pytest.fixture(scope="module")
def extended(tmp_path_factory):
    """A copy of the benchmark whose test manifest lists this PR's serving
    metrics for the rehearsal cells, as ``BENCHMARK.json`` lists them for
    the chip's."""
    copy = str(tmp_path_factory.mktemp("scratch") / "benchmark")
    shutil.copytree(env.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    data = os.path.join(copy, "tests", "data")
    manifest = env.load_json(os.path.join(data, "BENCHMARK.test.json"))
    official = {m["name"]: m for m in env.load_json(env.MANIFEST)["per_layer"]}
    for name in sorted(SERVING | ONLY["tiny-sessions"] | ONLY["tiny-bursts"]):
        cells = [c for c in ("tiny-sessions", "tiny-bursts")
                 if name in SERVING or name in ONLY[c]]
        manifest["per_layer"].append({**official[name], "workloads": cells})
    path = os.path.join(data, "BENCHMARK.spans.json")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return copy, path


@pytest.mark.parametrize("workload", ["tiny-bursts", "tiny-sessions"])
def test_traced_rehearsal_reads_the_counters_and_no_device_share(extended, workload):
    copy, manifest = extended
    proc = subprocess.run(
        [sys.executable, os.path.join(copy, "run.py"), "--workload", workload,
         "--seed", str(2**31 + 7), "--seconds", "3", "--trace", "1",
         "--manifest", manifest],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": env.ROOT},
        text=True, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    # see test_rehearsal.last_line for why agreement is not asserted here
    assert all(v for k, v in out["checks"].items() if k != "agreement"), out["checks"]
    metrics = out["metrics"]
    # what is read from counters is printed on any backend
    want = {"gc_pause_share"} | ONLY[workload]
    assert want <= set(metrics), sorted(want - set(metrics))
    assert not (ONLY["tiny-sessions"] | ONLY["tiny-bursts"]) - ONLY[workload] \
        & set(metrics)
    # a share of the device's time needs a device plane: declared, and
    # absent from a CPU's line without anything raised
    assert not DEVICE_ONLY & set(metrics), sorted(DEVICE_ONLY & set(metrics))
    assert 0 <= metrics["gc_pause_share"]["value"] < 50
    if workload == "tiny-sessions":
        assert 0 <= metrics["queue_wait_mean_ms"]["value"] \
            <= metrics["server_ttft_mean_ms"]["value"]
        # the server's clock starts after the client's and stops before it
        assert metrics["server_ttft_mean_ms"]["value"] \
            <= 1.5 * out["client"]["ttft_p90_ms"]
    # the trace holds the worker's line with its turns, and the writers'
    trace = trace_reduce.find_xplane(os.path.join(copy, ".cache", workload, "trace"))
    host = hs.load(trace)
    assert host.worker is not None
    names = {s.name for s in host.lines[host.worker]}
    assert {hs.GEN_TURN, hs.GEN_TICK, hs.GEN_ADMIT, hs.GEN_PREFILL_CHUNK} <= names
    assert any(s.name == hs.HTTP_STREAM_WRITE for i, ln in enumerate(host.lines)
               if i != host.worker for s in ln)
    rep = hs.report(trace)
    assert "shares" not in rep and rep["devices"] == 0
