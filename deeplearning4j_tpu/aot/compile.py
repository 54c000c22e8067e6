"""AOT compile/serialize round-trip and the store-backed function wrapper.

The serving tier's contract is a *bounded executable set*; this module
makes that set *persistent*. :class:`AotFunction` wraps one jitted
function and resolves each call signature in order:

1. in-memory executable map (steady state: one flatten of the operands
   and one dict lookup by :func:`~.keys.structural_key`; the string
   signature is built once, with the executable it names),
2. the persistent :class:`~.store.AotStore` — ``deserialize_and_load`` of
   an executable some earlier process compiled (cold-start/hot-swap win),
3. live ``jit(...).lower(...).compile()`` — the normal tracing path,
   whose result is serialized back into the store for the next boot.

The hard rule: **every failure in (2) degrades to (3)** — a corrupt
entry, a jax/jaxlib version skew, an unpicklable payload, a store I/O
error. Each is counted on ``serve_aot_fallback_total{cause=...}`` and
costs one trace, exactly what the process would have paid with no store
at all. ``serve_aot_hits_total`` / ``serve_aot_misses_total`` make the
cold-start win measurable.

``warm()`` ensures an executable *exists* (store hit or fresh compile)
without executing it — safe for donated operands and abstract
``jax.ShapeDtypeStruct`` arguments — which is what lets
``ModelRegistry.publish`` precompile an incoming generation against every
live bucket signature *before* flipping traffic onto it.
"""

from __future__ import annotations

import pickle
import threading
import time
from typing import Any, Callable, Optional, Sequence, Tuple

from ..chaos.retry import RetryPolicy
from ..obs import profile as _prof
from ..obs import reqtrace as _rt
from .keys import arch_fingerprint, cache_key, call_signature, \
    runtime_fingerprint, structural_key
from .store import AotCorruptEntry, AotStore, AotStoreError, AotVersionError

_BLOB_SCHEMA = 2  # 2: device ids recorded beside the executable


def serialize_compiled(compiled) -> bytes:
    """One compiled executable -> portable bytes (payload + arg pytrees +
    the ids of the devices it was compiled for + the jax/jaxlib pair that
    built it, double-checked at load time)."""
    import jax
    import jaxlib
    from jax.experimental import serialize_executable as se

    payload, in_tree, out_tree = se.serialize(compiled)
    devices = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps({"schema": _BLOB_SCHEMA, "jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "devices": devices,
                         "exe": (payload, in_tree, out_tree)})


def deserialize_compiled(blob: bytes):
    """Bytes -> loaded executable. Raises :class:`AotVersionError` on a
    jax/jaxlib skew (the key scheme should already have missed; this is
    defense in depth), or whatever the unpickler raises on garbage — the
    caller maps every failure to a counted fallback."""
    import jax
    import jaxlib
    from jax.experimental import serialize_executable as se

    rec = pickle.loads(blob)
    if not isinstance(rec, dict) or rec.get("schema") != _BLOB_SCHEMA:
        raise AotStoreError("unrecognized AOT payload schema")
    if rec.get("jax") != jax.__version__ \
            or rec.get("jaxlib") != jaxlib.__version__:
        raise AotVersionError(
            f"executable built by jax {rec.get('jax')}/jaxlib "
            f"{rec.get('jaxlib')}, running {jax.__version__}/"
            f"{jaxlib.__version__}")
    # load onto the devices the executable was compiled for: left to its
    # default, deserialize_and_load targets EVERY local device, and a
    # one-device executable then rejects its arguments at call time
    # ("expected N shards") on any multi-device host
    by_id = {d.id: d for d in jax.devices()}
    try:
        devices = [by_id[i] for i in rec["devices"]]
    except KeyError as e:
        raise AotStoreError(
            f"executable compiled for device id {e} absent on this host")
    return se.deserialize_and_load(*rec["exe"], execution_devices=devices)


class AotFunction:
    """Store-backed drop-in for a jitted function.

    ``fn`` must expose ``.lower`` (a ``jax.jit`` result); anything else —
    e.g. a test's plain-python forward override — passes through untouched
    with the store disabled. ``donate_argnums`` only *keys* the cache (the
    aliasing contract is baked into ``fn`` itself); ``compile_counter`` is
    incremented on live traces only, so a warm boot reads as zero compile
    misses on the serving counters.

    ``strict=True`` inverts the degradation rule: a signature the store
    does not yield a loadable executable for (absent entry, corrupt blob,
    version skew, store I/O failure) raises a typed
    :class:`~..serve.errors.AotTraceError` instead of tracing — counted on
    ``serve_aot_strict_misses_total`` — so a replica deployed against a
    prebuilt store can never silently compile at request time.
    """

    def __init__(self, fn: Callable, *, tag: str,
                 store: Optional[AotStore] = None, metrics=None,
                 arch: str = "", component: str = "serve",
                 donate_argnums: Sequence[int] = (),
                 compile_counter=None, retry: Optional[RetryPolicy] = None,
                 strict: bool = False):
        self._fn = fn
        self.tag = tag
        self.store = store if hasattr(fn, "lower") else None
        self.arch = arch
        self.component = component
        self.donate = tuple(donate_argnums)
        self.strict = bool(strict) and self.store is not None
        if strict and self.store is None:
            raise ValueError(
                f"AotFunction(tag={tag!r}): strict mode requires a store "
                "and a lowerable (jitted) function")
        self._compile_counter = compile_counter
        # transient store-read failures (NFS hiccup, torn page cache) are
        # retried before falling back to a live trace; corrupt entries are
        # quarantined immediately — re-reading garbage can't help
        self._retry = retry if retry is not None else RetryPolicy(
            attempts=3, base_s=0.02, cap_s=0.5, metrics=metrics)
        self._runtime = None  # resolved lazily: jax may not be booted yet
        # structural key -> (executable, its string signature, store key)
        self._exes: dict = {}
        self._lock = threading.RLock()
        self._acquire_seconds = 0.0
        if metrics is not None and self.store is not None:
            labels = {"component": component}
            self._m_hits = metrics.counter(
                "serve_aot_hits_total", labels,
                help="executables loaded from the persistent AOT store")
            self._m_misses = metrics.counter(
                "serve_aot_misses_total", labels,
                help="AOT store lookups that found no entry")
            self._m_fallback = lambda cause: metrics.counter(
                "serve_aot_fallback_total", {**labels, "cause": cause},
                help="store entries abandoned for live tracing, by cause")
            self._m_strict = metrics.counter(
                "serve_aot_strict_misses_total", labels,
                help="signatures refused (typed 503) by strict AOT mode")
            self._m_sigs = metrics.counter(
                "serve_aot_signature_strings_total", {**labels, "tag": tag},
                help="string signatures built: one an executable acquired, "
                     "none a call")
        else:
            from ..obs.metrics import MetricsRegistry

            null = MetricsRegistry(enabled=False)
            # same label shape as the live registry above: a disabled
            # series is still part of the family's one-labelset contract
            labels = {"component": component}
            self._m_hits = null.counter("serve_aot_hits_total", labels)
            self._m_misses = null.counter("serve_aot_misses_total", labels)
            self._m_fallback = lambda cause: null.counter(
                "serve_aot_fallback_total", {**labels, "cause": cause})
            self._m_strict = null.counter(
                "serve_aot_strict_misses_total", labels)
            self._m_sigs = null.counter(
                "serve_aot_signature_strings_total", {**labels, "tag": tag})

    # ------------------------------------------------------------------ calls
    def __call__(self, *args):
        if self.store is None:
            return self._fn(*args)
        skey = structural_key(args)
        with self._lock:
            held = self._exes.get(skey)
        exe, sig, _ = held if held is not None else self._acquire(skey, args)
        # continuous-profiler seam (obs/profile): one attribute load + a
        # None check when profiling is off — the hot decode tick's cost
        prof = _prof.ACTIVE
        if prof is None:
            return exe(*args)
        return prof.dispatch(self, sig, exe, args)

    def warm(self, *args) -> bool:
        """Ensure the executable for this signature exists (store hit or
        fresh compile) WITHOUT executing it. Accepts
        ``jax.ShapeDtypeStruct`` leaves. Returns True when AOT-capable."""
        if self.store is None:
            return False
        self._acquire(structural_key(args), args)
        return True

    @property
    def executables(self) -> dict:
        """Signature -> loaded executable (diagnostic)."""
        with self._lock:
            return {sig: exe for exe, sig, _ in self._exes.values()}

    def store_key(self, sig: Tuple[str, ...]) -> str:
        """The store key of one acquired signature ("" before acquire) —
        how the profiler stamps its (component, tag, sig, key) identity."""
        with self._lock:
            return next((key for _, acquired, key in self._exes.values()
                         if acquired == sig), "")

    def warmed_keys(self) -> list:
        """Sorted store keys of every executable this wrapper acquired —
        the concrete coverage a prebuild run stamps into the store's
        coverage record (``aot/manifest.py``)."""
        with self._lock:
            return sorted({key for _, _, key in self._exes.values()})

    @property
    def acquire_seconds(self) -> float:
        """Cumulative wall time spent loading/compiling executables — the
        cold-start cost this wrapper exists to amortize."""
        with self._lock:
            return self._acquire_seconds

    # ---------------------------------------------------------------- acquire
    def _key(self, sig: Tuple[str, ...]) -> str:
        if self._runtime is None:
            self._runtime = runtime_fingerprint()
        return cache_key(self.tag, self.arch, sig, donate=self.donate,
                         runtime=self._runtime)

    def _acquire(self, skey, args: Sequence[Any]):
        """Store -> live trace, under the lock (a concurrent publish warm
        and the dispatch thread must not double-compile one signature).
        Returns the map's entry for ``skey``; the only place the string
        signature is built."""
        with self._lock:
            held = self._exes.get(skey)
            if held is not None:
                return held
            t0 = time.perf_counter()
            sig = call_signature(args)
            self._m_sigs.inc()
            key = self._key(sig)
            with _rt.span("aot.acquire", tag=self.tag):
                exe = self._load(key)
                if exe is None:
                    if self.strict:
                        # the deployment contract: every signature was
                        # prebuilt from the static surface — a miss is a
                        # typed 503, NEVER a trace
                        from ..serve.errors import AotTraceError

                        self._m_strict.inc()
                        raise AotTraceError(
                            f"strict AOT: no store executable for "
                            f"tag={self.tag!r} key={key[:16]}… — prebuild "
                            "the store from the compile-surface manifest "
                            "(aot prebuild --from-surface)")
                    with _rt.span("aot.trace", tag=self.tag):
                        exe = self._fn.lower(*args).compile()
                    if self._compile_counter is not None:
                        self._compile_counter.inc()  # a real trace happened
                    self._save(key, exe)
            held = self._exes[skey] = (exe, sig, key)
            self._acquire_seconds += time.perf_counter() - t0
            return held

    def _load(self, key: str):
        try:
            blob = self._retry.call(
                lambda: self.store.get(key), op="aot.store_read",
                retry_on=(AotStoreError,), give_up=(AotCorruptEntry,))
        except AotCorruptEntry:
            self._m_fallback("corrupt").inc()
            return None
        except AotStoreError:
            self._m_fallback("store_read").inc()
            return None
        if blob is None:
            self._m_misses.inc()
            return None
        try:
            exe = deserialize_compiled(blob)
        except AotVersionError:
            self._m_fallback("version").inc()
            return None
        except Exception:  # any bad payload degrades to tracing, never crashes  # jaxlint: disable=broad-except
            self._m_fallback("deserialize").inc()
            return None
        self._m_hits.inc()
        return exe

    def _save(self, key: str, exe) -> None:
        try:
            blob = serialize_compiled(exe)
        except Exception:  # unserializable backend/executable: serve live  # jaxlint: disable=broad-except
            self._m_fallback("serialize").inc()
            return
        if not self.store.put(key, blob,
                              meta={"tag": self.tag, "arch": self.arch}):
            self._m_fallback("store_write").inc()


def arch_of(params, state=None) -> str:
    """Convenience re-export: the model-architecture key component."""
    return arch_fingerprint(params, state)
