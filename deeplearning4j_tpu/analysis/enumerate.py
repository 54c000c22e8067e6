"""Enumeration pass: compile-surface bounds -> concrete prebuild manifest.

The compile-surface pass (:mod:`.compilesurface`) proves each jit site's
executable cardinality as a *symbolic* product over bucket tables
(``|prompt_buckets|``, ``|batch_buckets|*|length_buckets|``, …). This
pass closes the loop to deployment: given one concrete serving config
(the same knobs a replica boots with), it resolves every symbolic factor
to its actual bucket table and expands each budgeted site into the
explicit list of ``(site, bucket-signature)`` pairs — the machine-readable
``prebuild_manifest.json`` that ``python -m deeplearning4j_tpu.aot
prebuild --from-surface`` compiles into the store and strict-mode replicas
verify against at boot.

Like the rest of ``analysis/``, this module is pure stdlib — it never
imports jax, numpy, or the serving code. The bucket-table derivations
(default prompt buckets, chunk buckets) are therefore *replicated* here
from ``serve/continuous.py``; ``tests/test_prebuild.py`` holds the two
implementations bit-identical so the manifest can never drift from what a
booted batcher actually warms.

Site -> AOT tag mapping lives in :data:`SITE_TAGS`: a budgeted serving
site the table does not name fails enumeration loudly (the manifest would
otherwise silently under-cover the surface), while non-serving sites
(training-side ``?`` bounds, helper jits with no store tag) are listed
under ``excluded`` with a reason, for human review.
"""

from __future__ import annotations

import hashlib
import itertools
import json
from typing import Dict, List, Optional, Tuple

from .compilesurface import _parse_bound

MANIFEST_VERSION = 1

#: site id -> (AotFunction tag, gate). The gate names which boot paths
#: build the executable: ``engine`` (always), ``gen`` (a batcher).
SITE_TAGS: Dict[str, Tuple[str, str]] = {
    "deeplearning4j_tpu.serve.engine:fwd":
        ("engine_forward", "engine"),
    "deeplearning4j_tpu.serve.programs:_sample_dynamic":
        ("gen_sample", "gen"),
    "deeplearning4j_tpu.serve.programs:_decode_paged_fn":
        ("gen_decode_paged", "gen"),
    "deeplearning4j_tpu.serve.programs:_prefill_chunk_fn":
        ("gen_prefill_chunk", "gen"),
}

_DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)
_DEFAULT_CAPACITY = 256
_DEFAULT_PREFILL_CHUNK = 64


def default_prompt_buckets(capacity: int) -> Tuple[int, ...]:
    """Pure replica of ``serve.continuous._default_prompt_buckets`` —
    powers of two from 8 up to (and including) the KV capacity. Held
    bit-identical to the serving code by a parity test."""
    buckets, b = [], 8
    while b < capacity:
        buckets.append(b)
        b *= 2
    buckets.append(capacity)
    return tuple(sorted(set(buckets)))


def chunk_buckets(prompt_buckets: Tuple[int, ...],
                  prefill_chunk: Optional[int]) -> Tuple[int, ...]:
    """Pure replica of the batcher's ``_chunk_buckets`` derivation: the
    prompt buckets a single prefill chunk can cover, plus the chunk width
    itself; ``prefill_chunk=None`` means whole-prompt prefill over the
    prompt buckets. Parity-tested against ``serve/continuous.py``."""
    if prefill_chunk is None:
        return tuple(prompt_buckets)
    return tuple(sorted(set(
        [b for b in prompt_buckets if b <= prefill_chunk]
        + [int(prefill_chunk)])))


def resolve_tables(config: dict) -> Dict[str, list]:
    """The concrete bucket tables one serving config boots with.

    ``config`` mirrors the knobs a replica passes to ``ServeEngine`` /
    ``ContinuousBatcher`` (``engine`` and ``gen`` groups, same key names
    as the tuned-config schema). ``length_buckets`` unset resolves to the
    one-entry table ``[None]`` — the model's native input shape — so the
    ``|batch_buckets|*|length_buckets|`` product stays well defined.
    """
    engine = dict(config.get("engine") or {})
    gen = dict(config.get("gen") or {})
    batch = [int(b) for b in sorted(set(
        engine.get("batch_buckets") or _DEFAULT_BATCH_BUCKETS))]
    length = engine.get("length_buckets")
    length = ([int(b) for b in sorted(set(length))] if length
              else [None])
    capacity = int(gen.get("capacity") or _DEFAULT_CAPACITY)
    prompt = gen.get("prompt_buckets") or default_prompt_buckets(capacity)
    # the constructor's normalization: ints, deduped, capped at capacity
    prompt = tuple(sorted(set(
        int(b) for b in prompt if int(b) <= capacity))) or (capacity,)
    prefill_chunk = gen.get("prefill_chunk", _DEFAULT_PREFILL_CHUNK)
    chunks = chunk_buckets(
        prompt, int(prefill_chunk) if prefill_chunk is not None else None)
    return {"batch_buckets": batch, "length_buckets": length,
            "prompt_buckets": list(prompt), "_chunk_buckets": list(chunks)}


def enumerate_surface(report: dict, budget: dict, config: dict) -> dict:
    """Expand the computed compile-surface ``report`` against one concrete
    serving ``config`` into a prebuild manifest.

    Every budgeted site is either *enumerated* — its symbolic factors
    resolved against the config's bucket tables, signatures = the cross
    product — or *excluded* with a machine-checkable reason (statically
    unknown bound, no call sites, not a serving executable, no generation
    stack). A serving-tagged site whose bound carries a factor the tables
    cannot resolve raises ``ValueError``: an unresolvable factor means the
    manifest would under-cover the surface, which is exactly the silent
    hole strict mode exists to forbid.
    """
    tables = resolve_tables(config)
    predict_only = bool(config.get("predict_only"))
    budgeted = budget.get("sites", {})
    sites_out: List[dict] = []
    excluded: List[dict] = []
    for row in sorted(report.get("sites", []), key=lambda r: r["site"]):
        site = row["site"]
        bound = row["bound"]
        reason = None
        tag = gate = ""
        factors: set = set()
        if budgeted.get(site) is None:
            reason = "no budget entry (the budget gate fails separately)"
        elif SITE_TAGS.get(site) is None:
            reason = "not a serving executable (no AOT store tag)"
        else:
            tag, gate = SITE_TAGS[site]
            unb, unk, factors, _numeric = _parse_bound(bound)
            if unb or unk:
                reason = f"bound {bound!r} is not statically enumerable"
            elif gate == "gen" and predict_only:
                reason = "predict-only config: no generation stack is built"
        if reason is not None:
            excluded.append({"site": site, "bound": bound,
                             "reason": reason})
            continue
        axes: List[Tuple[str, list]] = []
        for factor in sorted(factors):
            table_name = factor.strip("|")
            table = tables.get(table_name)
            if table is None:
                raise ValueError(
                    f"{site}: factor {factor} has no resolvable bucket "
                    f"table in the config (known: {sorted(tables)}) — "
                    "the manifest would under-cover the surface")
            axes.append((table_name, list(table)))
        signatures = [dict(zip([n for n, _ in axes], combo))
                      for combo in itertools.product(
                          *[vals for _, vals in axes])]
        sites_out.append({
            "site": site, "tag": tag, "path": row.get("path"),
            "line": row.get("line"), "bound": bound,
            "cardinality": len(signatures), "signatures": signatures,
        })
    manifest = {
        "version": MANIFEST_VERSION,
        "tool": "jaxlint-enumerate",
        "config": config,
        "tables": tables,
        "sites": sites_out,
        "excluded": excluded,
        "total_signatures": sum(s["cardinality"] for s in sites_out),
    }
    manifest["hash"] = manifest_hash(manifest)
    return manifest


def manifest_hash(manifest: dict) -> str:
    """Stable 16-hex digest over the manifest's canonical JSON (the
    ``hash`` field itself excluded) — one half of the coverage-record key
    ``(runtime fingerprint, manifest hash)``."""
    body = {k: v for k, v in manifest.items() if k != "hash"}
    canon = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


def load_serve_config(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        config = json.load(fh)
    if not isinstance(config, dict):
        raise ValueError("serve config must be a JSON object")
    return config


def write_manifest(manifest: dict, path: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh, indent=2)
        fh.write("\n")
