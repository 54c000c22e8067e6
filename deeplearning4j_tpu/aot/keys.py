"""Deterministic cache keys for persisted AOT executables.

An XLA executable is only reusable when *everything* that shaped its
compilation matches: the jax/jaxlib pair that lowered it, the backend and
device topology it was compiled for, the model architecture (param pytree
structure + leaf shapes/dtypes — values never matter, shapes always do),
the exact call signature (the bucket the serving tier padded to), and the
donation spec (donated operands change the executable's aliasing contract).
Every component lands in one SHA-256 so a mismatch in ANY of them is a
clean cache *miss* — never a crash, never a silently-wrong executable.
Changing jaxlib, moving from CPU smoke to a v5e slice, or publishing a
model with different head counts each simply re-keys the store.

Key strings are pure functions of their inputs (no timestamps, no paths),
so two processes on identical machines — or the same replica across
restarts, which is the whole point — compute identical keys.
"""

from __future__ import annotations

import hashlib
from typing import Any, Iterable, Optional, Sequence, Tuple

_SCHEMA = "aot-v1"  # bump to invalidate every existing key on format change


def runtime_fingerprint() -> dict:
    """jax/jaxlib versions + backend + device topology, as a stable dict.

    Device *kind* and count are what XLA specializes for; device ordinals
    are not (the same executable serves any chip of the slice).
    """
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": str(devices[0].device_kind),
        "device_count": len(devices),
        "process_count": jax.process_count(),
    }


def _leaf_key(leaf: Any) -> Any:
    """What of one pytree leaf shapes a compilation, as a value that
    hashes: arrays by ``(shape, dtype)``, python scalars by their type's
    name (their value is traced, not compiled in)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return tuple(shape), dtype
    return None if leaf is None else type(leaf).__name__


def _leaf_sig(leaf: Any) -> str:
    """:func:`_leaf_key` as a stable string."""
    key = _leaf_key(leaf)
    if isinstance(key, tuple):
        return f"{key[0]}:{key[1]}"
    return "none" if key is None else f"py:{key}"


def arch_fingerprint(params: Any, state: Any = None) -> str:
    """Model-architecture hash: param (+state) treedef and leaf
    shapes/dtypes. Two checkpoints of the same architecture share it; a
    resized layer, changed dtype, or restructured tree does not."""
    import jax

    parts = []
    for tag, tree in (("params", params), ("state", state)):
        leaves, treedef = jax.tree.flatten(tree)
        parts.append(f"{tag}|{str(treedef)}|" +
                     ";".join(_leaf_sig(leaf) for leaf in leaves))
    h = hashlib.sha256("\n".join(parts).encode())
    return h.hexdigest()[:16]


def call_signature(args: Sequence[Any]) -> Tuple[str, ...]:
    """The bucket signature of one call: flattened leaf shapes/dtypes plus
    the argument treedef. This is what the serving tier's shape buckets
    vary over — and exactly what a compiled executable is specialized to.
    A tuple of strings: what :func:`cache_key` hashes and the profiler
    reports. Formatting it costs milliseconds on a deep model, so it is
    built when an executable is acquired; the call path looks its
    executable up by :func:`structural_key`."""
    import jax

    leaves, treedef = jax.tree.flatten(tuple(args))
    return tuple(_leaf_sig(leaf) for leaf in leaves) + (str(treedef),)


def structural_key(args: Sequence[Any]) -> Tuple[Any, tuple]:
    """What :func:`call_signature` separates, from one flatten and no
    string: ``(treedef, (shape, dtype) or type name per leaf)``, the
    values the signature formats. Two operand lists have equal keys
    exactly when their signatures are equal (``PyTreeDef`` and numpy
    dtypes hash and compare by value), so an abstract
    ``jax.ShapeDtypeStruct`` keys as the array it stands for. The
    in-memory executable-map key."""
    import jax

    leaves, treedef = jax.tree.flatten(tuple(args))
    return treedef, tuple(map(_leaf_key, leaves))


def cache_key(tag: str, arch: str, sig: Iterable[str],
              donate: Sequence[int] = (),
              runtime: Optional[dict] = None,
              extra: str = "") -> str:
    """One SHA-256 hex key from every compilation-shaping component.

    ``tag`` names the function (``gen_decode``, ``engine_forward``, ...);
    two different programs with identical signatures must not collide.
    ``runtime`` defaults to :func:`runtime_fingerprint` — injectable so
    tests can simulate a jaxlib upgrade and assert it misses cleanly.
    """
    rt = runtime if runtime is not None else runtime_fingerprint()
    material = "\x1f".join([
        _SCHEMA, tag, arch,
        "|".join(f"{k}={rt[k]}" for k in sorted(rt)),
        "|".join(sig),
        "donate=" + ",".join(str(int(i)) for i in donate),
        extra,
    ])
    return hashlib.sha256(material.encode()).hexdigest()
