#!/usr/bin/env python
"""Compile the benchmark's four-chip train step for a described ``v5e:2x2``
(no chip needed) and print where the compiler put its collectives.

    JAX_PLATFORMS=cpu python scripts/mesh_step_schedule.py            # parent and change
    JAX_PLATFORMS=cpu python scripts/mesh_step_schedule.py --layers 2 # a quick look
    JAX_PLATFORMS=cpu python scripts/mesh_step_schedule.py \
        --options xla_enable_async_all_reduce=true,...                # a candidate set

The step is the one ``Trainer._make_step`` builds for the configuration of
``cgpt1.3b-train-4chip`` (its zoo model, mesh, rules and batch, found as the
benchmark's harness finds them), given shapes in place of arrays because a
described device holds none. ``parent`` compiles it with no
compiler options, ``change`` with what
``parallel.sharding.collective_overlap_options`` returns for the mesh.

For each: every collective of the ENTRY computation in schedule order (the
order of a scheduled module's ENTRY is its schedule), synchronous or as a
start/done pair, with its shape, the number of heavy operations (fusions
that hold a matmul, and kernels) scheduled before it, and for a pair how
many lie between start and done; then ``memory_analysis()``. Nothing runs:
this says what the compiler scheduled, never how long anything takes.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))   # the harness package

_KINDS = "all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def abstract_step(cell, layers: int | None):
    """(options -> jitted step, abstract arguments, mesh) of the cell's
    Trainer."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import model as modelmod
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.parallel import sharding
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.train.trainer import Trainer, build_updater

    # the kernel asks jax.default_backend(), which is the CPU here: steer it
    # to its compiled branch, as on the chip
    flash = fa.flash_attention
    fa.flash_attention = lambda *a, **k: flash(*a, **{**k, "interpret": False})

    config, job = cell.config, cell.traffic["job"]
    if layers:
        config["build"]["kwargs"]["num_layers"] = layers
    model = modelmod.build(config)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(dict(config["layout"]["mesh"]), topo.devices)
    rules = getattr(sharding, config["layout"]["rules"])
    repl = NamedSharding(mesh, P())

    def shaped(a, sh):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    p_shapes, s_shapes = jax.eval_shape(model.init, jnp.uint32(0))
    like = jax.tree.map(
        lambda a: np.broadcast_to(np.zeros((), a.dtype), a.shape), p_shapes)
    params = jax.tree.map(shaped, p_shapes,
                          sharding.sharding_tree(like, mesh, rules))
    state = jax.tree.map(lambda a: shaped(a, repl), s_shapes)

    # the Trainer as its constructor leaves it, with shapes for arrays: the
    # moments lie as their parameters do, everything else replicated
    tr = Trainer.__new__(Trainer)
    tr.model, tr.mesh, tr.rules, tr.grad_accum = model, mesh, tuple(rules), 1
    tr.tx = build_updater(model)
    by_path = {jax.tree_util.keystr(k): v.sharding for k, v in
               jax.tree_util.tree_flatten_with_path(params)[0]}
    o_leaves, o_def = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(tr.tx.init, params))

    def moment_sharding(path):
        key = jax.tree_util.keystr(path)
        hit = [sh for p, sh in by_path.items() if key.endswith(p)]
        return hit[0] if hit else repl

    tr.params, tr.state = params, state
    tr.opt_state = jax.tree_util.tree_unflatten(
        o_def, [shaped(a, moment_sharding(k)) for k, a in o_leaves])

    def make_step(options: dict):
        # the Trainer asks this function by name at the time it builds a step
        asked = sharding.collective_overlap_options
        sharding.collective_overlap_options = lambda mesh: dict(options)
        try:
            return tr._make_step()
        finally:
            sharding.collective_overlap_options = asked

    ids = jax.ShapeDtypeStruct((int(job["global_batch"]), int(job["seq_len"])),
                               jnp.int32)
    ids = shaped(ids, sharding.batch_sharding(mesh, ids))
    rng = shaped(jax.eval_shape(lambda: jax.random.PRNGKey(0)), repl)
    return make_step, (params, tr.opt_state, state, ids, ids, rng), mesh


def computations(text: str) -> dict:
    """name -> lines, for every computation of an HLO module's text."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m and not line.startswith(" "):
            name = "ENTRY" if m.group(1) else m.group(2)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def replica_groups(line: str):
    """The groups of a collective's ``replica_groups=`` as a set of tuples,
    from the iota form (``[2,2]<=[2,2]T(1,0)``) or the explicit one."""
    import numpy as np

    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", line)
    if m:
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(d) for d in m.group(4).split(",")])
        return {tuple(g) for g in ids.reshape(int(m.group(1)), int(m.group(2))).tolist()}
    m = re.search(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}", line)
    if m:
        return {tuple(int(i) for i in g.split(",") if i)
                for g in re.findall(r"\{([\d,]*)\}", m.group(1))}
    return None


def axes_by_groups(mesh) -> dict:
    """frozenset of device-id groups -> the mesh axes reduced over."""
    import itertools

    import numpy as np

    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    names, out = mesh.axis_names, {}
    for r in range(1, len(names) + 1):
        for over in itertools.combinations(range(len(names)), r):
            keep = [i for i in range(len(names)) if i not in over]
            groups = ids.transpose(keep + list(over)).reshape(
                -1, int(np.prod([ids.shape[i] for i in over])))
            out[frozenset(tuple(sorted(g)) for g in groups.tolist())] = \
                "+".join(names[i] for i in over)
    return out


def schedule(text: str, mesh=None) -> dict:
    """The ENTRY computation's collectives in schedule order, and the counts.

    What the TPU compiler's text looks like (libtpu 0.0.34): a synchronous
    collective is an instruction whose opcode is the collective's, or a
    fusion around one; an asynchronous one is either the usual
    ``<kind>-start`` / ``<kind>-done`` pair or a pair of kCustom FUSIONS
    named ``%async-collective-start[.n]`` / ``%async-collective-done[.n]``
    (opcode ``fusion``: the collective is inside the called computation),
    and the matmul fusions scheduled between the two call computations named
    ``%async_collective_fusion.*`` that carry the collective's state along.
    """
    comps = computations(text)
    axes = axes_by_groups(mesh) if mesh is not None else {}
    coll = re.compile(rf"({_KINDS})(-start|-done)?")
    matmul = re.compile(r"convolution|dot")
    memo: dict = {}

    def first(comp: str, what: re.Pattern, seen=()):
        """The first instruction (opcode, line) under ``comp`` whose opcode
        matches, looking through called computations."""
        key = (comp, what.pattern)
        if key not in memo:
            memo[key] = None
            for line in comps.get(comp, ()):
                m = _INSTR.match(line)
                if m and what.fullmatch(m.group(3)):
                    memo[key] = (m.group(2), line)
                    break
                memo[key] = next(
                    (hit for c in _CALLS.findall(line) if c not in seen
                     and (hit := first(c, what, seen + (comp,)))), None)
                if memo[key]:
                    break
        return memo[key]

    def over(line: str) -> str:
        groups = replica_groups(line)
        if groups is None:
            return "pairs" if "source_target_pairs" in line else "?"
        return axes.get(frozenset(tuple(sorted(g)) for g in groups),
                        str(sorted(groups)))

    rows, heavy, open_at = [], 0, {}
    for line in comps["ENTRY"]:
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        called = _CALLS.findall(line) if op == "fusion" else []
        inner = next((hit for c in called if (hit := first(c, coll))), None)
        plain = bool(coll.fullmatch(op))
        # where the collective itself is written: this line, or inside
        about_shape, about_line = (shape, line) if plain or not inner else inner
        fused_start = name.startswith("async-collective-start")
        fused_done = name.startswith("async-collective-done")
        if fused_start or (plain and op.endswith("-start")):
            open_at[name.replace("-start", "") if fused_start else name] = len(rows)
            rows.append({"op": name, "kind": "async", "shape": about_shape,
                         "over": over(about_line), "heavy_before": heavy,
                         "heavy_between": None})
        elif fused_done or (plain and op.endswith("-done")):
            key = (name.replace("-done", "") if fused_done else
                   next((n for n in re.findall(r"%([\w.\-]+)", rest)
                         if n in open_at), None))
            if key in open_at:
                row = rows[open_at.pop(key)]
                row["heavy_between"] = heavy - row["heavy_before"]
        elif plain or (inner and not any(
                c.startswith("async_collective_fusion") for c in called)):
            rows.append({"op": name, "kind": "sync", "shape": about_shape,
                         "over": over(about_line), "heavy_before": heavy})
        elif (op == "custom-call" and "tpu_custom_call" in rest) or \
                op in ("convolution", "dot") or \
                any(first(c, matmul) for c in called):
            heavy += 1
    return {"heavy_total": heavy, "rows": rows,
            "sync": sum(r["kind"] == "sync" for r in rows),
            "async": sum(r["kind"] == "async" for r in rows)}


def grouped(rows: list) -> list:
    """Rows alike in everything but their place, as one row with a count and
    the first and last place, in order of first appearance."""
    out: dict = {}
    for r in rows:
        key = (r["kind"], re.sub(r"\.\d+$", "", r["op"]), r["over"], r["shape"],
               r.get("heavy_between"))
        g = out.setdefault(key, dict(r, op=key[1], n=0))
        g["n"] += 1
        g["last"] = r["heavy_before"]
    return list(out.values())


def report(label: str, compiled, mesh, seconds: float, dump: str | None,
           full: bool) -> dict:
    text = compiled.as_text()
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, f"{label}.hlo.txt"), "w") as f:
            f.write(text)
    sch = schedule(text, mesh)
    mem = compiled.memory_analysis()
    sizes = {k: getattr(mem, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes")}
    total = (sizes["argument_size_in_bytes"] + sizes["output_size_in_bytes"]
             - sizes["alias_size_in_bytes"] + sizes["temp_size_in_bytes"])
    print(f"== {label}: compiled in {seconds:.0f} s; {sch['heavy_total']} heavy "
          f"operations; collectives {sch['sync']} synchronous, {sch['async']} "
          f"asynchronous")
    for r in (sch["rows"] if full else grouped(sch["rows"])):
        shape = r["shape"] if len(r["shape"]) < 70 else r["shape"][:67] + "..."
        where = (f"after {r['heavy_before']:3d}" if "n" not in r else
                 f"x{r['n']:<3d} after {r['heavy_before']:3d}..{r['last']:3d}")
        if r["kind"] == "async":
            where += f", {r['heavy_between']} heavy before its done"
        print(f"  {r['kind']:5s} {r['op']:26s} {r['over']:6s} {where:46s} {shape}")
    print(f"  memory_analysis: arguments {sizes['argument_size_in_bytes'] / 1e9:.2f}"
          f" GB, temporaries {sizes['temp_size_in_bytes'] / 1e9:.2f} GB, "
          f"arguments + outputs - aliased + temporaries {total / 1e9:.2f} GB a chip")
    return {"label": label, "compile_s": seconds, **sch, **sizes,
            "total_bytes": total}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="cgpt1.3b-train-4chip",
                    help="a training cell of BENCHMARK.json with a 2x2 layout")
    ap.add_argument("--layers", type=int, help="fewer layers, for a quick look")
    ap.add_argument("--options", help="k=v,k=v: compile with these in place "
                    "of collective_overlap_options(mesh)")
    ap.add_argument("--only", choices=("parent", "change"))
    ap.add_argument("--full", action="store_true",
                    help="every collective on a line of its own")
    ap.add_argument("--dump", help="directory for the compiled text and a summary")
    args = ap.parse_args()

    # describe the chip, attach none: set before JAX is first imported
    for key, value in (("TPU_LOG_DIR", "disabled"),
                       ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                       ("TPU_WORKER_HOSTNAMES", "localhost"),
                       ("TPU_SKIP_MDS_QUERY", "1"), ("JAX_PLATFORMS", "cpu")):
        os.environ.setdefault(key, value)
    import jax

    from deeplearning4j_tpu.parallel.sharding import collective_overlap_options

    from harness import env

    make_step, abstract, mesh = abstract_step(
        env.Cell(env.MANIFEST, args.workload), args.layers)
    options = collective_overlap_options(mesh)
    if args.options:
        as_value = {"true": True, "false": False}
        options = {k: as_value.get(v.lower(), int(v) if v.lstrip("-").isdigit() else v)
                   for k, v in (kv.split("=", 1) for kv in args.options.split(","))}
    print(f"jax {jax.__version__}; mesh {dict(mesh.shape)} of "
          f"{mesh.devices.flat[0].device_kind}; change options {options}")
    out = []
    for label, opts in (("parent", {}), ("change", options)):
        if args.only not in (None, label):
            continue
        t0 = time.perf_counter()
        compiled = make_step(opts).lower(*abstract).compile()
        out.append(report(label, compiled, mesh, time.perf_counter() - t0, args.dump,
                          args.full))
    if args.dump:
        with open(os.path.join(args.dump, "summary.json"), "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
