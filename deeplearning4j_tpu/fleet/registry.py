"""FleetRegistry — N named models multiplexed through one process.

Each :class:`FleetEntry` owns the full single-model serving stack when
resident — a :class:`~..serve.registry.ModelRegistry` (generations +
leases + hot-swap), a :class:`~..serve.engine.ServeEngine` (predict), and
a lazily-built :class:`~..serve.continuous.ContinuousBatcher` (generate)
— and shrinks to a host-side numpy weight copy when paged out. The
ground truth for a cold model is host RAM; activation is
``device_put`` + executable warm from the shared ``aot/`` store, so a
page-in costs seconds of transfer, not a recompile.

Generation numbers survive paging: deactivation records
``last generation + 1`` and the next activation's ModelRegistry starts
there (``start_generation``), so "which params answered this request" is
a total order per model across any number of page-out/page-in cycles —
the same purity contract hot-swap gives within one residency.

Request flow (:meth:`FleetRegistry.predict` / :meth:`~.generate`):
tenant admission first (:class:`~.tenants.TenantTable` — an over-quota
tenant is shed before any paging work), then ``pager.ensure`` (resident:
one lock; cold: LRU eviction + activation), then the entry's engine.
A request that loses the race with a concurrent eviction gets the
engine's typed ``ServerClosingError`` and simply retries through the
pager — bounded, because each retry pages the model back in.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, NamedTuple, Optional

import numpy as np

from ..obs.slo import SloBurn
from ..serve.continuous import ContinuousBatcher
from ..serve.engine import ServeEngine
from ..serve.errors import ServeError, ServerClosingError
from ..serve.health import Health
from ..serve.registry import ModelRegistry
from ..serve.watchdog import Watchdog
from .breaker import CircuitBreaker
from .pager import WeightPager
from .tenants import TenantTable

_EVICTION_RETRIES = 4

# ServeError causes that count against a model's circuit breaker: server-side
# breakage only. Quota/capacity/queue-full sheds and client deadlines are
# load signals, not path failures — tripping a breaker on them would turn an
# overload into an outage.
_BREAKER_CAUSES = frozenset({"internal", "page_in_failed", "worker_stall",
                             "worker_dead", "drain_timeout"})

# ServeError causes that do not consume error budget: the *client* (or its
# quota) failed, not our serving path. Everything else after admission —
# deadline misses included — is a bad event for the tenant's SLO class.
# "client_gone" is the client dropping its own socket mid-stream.
_SLO_EXCLUDED = frozenset({"quota", "over_capacity", "bad_request",
                           "client_gone"})


class UnknownModelError(ServeError):
    """No model with that name in the fleet (HTTP 404)."""

    cause = "unknown_model"
    http_status = 404


class FleetResult(NamedTuple):
    """One predict answer: the output rows and (when the request rode a
    single engine batch) the params generation that produced them."""

    output: np.ndarray
    generation: Optional[int]


def _tree_bytes(*trees) -> int:
    import jax

    return sum(int(leaf.nbytes) for tree in trees
               for leaf in jax.tree.leaves(tree))


class FleetEntry:
    """One named model: host weight copy + (when resident) serving stack."""

    def __init__(self, name: str, model, params, state=None, *,
                 version: str = "v0", input_dtype=np.float32, metrics=None,
                 aot_store=None, strict_aot: bool = False,
                 engine_opts: Optional[dict] = None,
                 gen_opts: Optional[dict] = None):
        import jax

        self.name = name
        self.model = model
        self.input_dtype = input_dtype
        self.metrics = metrics
        self.aot_store = aot_store
        # strict page-ins: activation loads executables from the prebuilt
        # store or fails typed (AotTraceError) — a paged-in model must
        # never trace its way back into residency
        self.strict_aot = bool(strict_aot)
        if self.strict_aot and aot_store is None:
            raise ValueError(f"model {name!r}: strict_aot=True requires "
                             "a shared aot_store")
        self.engine_opts = dict(engine_opts or {})
        self.gen_opts = dict(gen_opts or {})
        self.version = version
        # RLock held across the WHOLE of activate()/deactivate(): the pager
        # may start re-activating a victim (new traffic arrived) while its
        # drain is still completing — the lock serializes the lifecycles so
        # the new stack always starts from the drained host copy
        self._lock = threading.RLock()
        self._host_params = jax.tree.map(np.asarray, params)
        self._host_state = jax.tree.map(
            np.asarray, state if state is not None else {})
        self.weight_bytes = _tree_bytes(self._host_params, self._host_state)
        self._next_generation = 1
        self._registry: Optional[ModelRegistry] = None
        self._engine: Optional[ServeEngine] = None
        self._batcher: Optional[ContinuousBatcher] = None
        self._had_batcher = False

    # ------------------------------------------------------------- lifecycle
    @property
    def resident(self) -> bool:
        with self._lock:
            return self._engine is not None

    def activate(self) -> None:
        """Host copy -> device, registry/engine up, executables warmed.
        Called by the pager with residency bytes already reserved."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            if self._engine is not None:
                return
            params = jax.tree.map(jnp.asarray, self._host_params)
            state = jax.tree.map(jnp.asarray, self._host_state)
            self._registry = ModelRegistry(
                params, state, version=self.version, metrics=self.metrics,
                model=self.name, start_generation=self._next_generation)
            self._engine = ServeEngine(
                self.model, registry=self._registry, metrics=self.metrics,
                aot_store=self.aot_store, strict_aot=self.strict_aot,
                model_name=self.name, **self.engine_opts)
            if self.aot_store is not None:
                # store hit on every re-activation: page-in never re-traces
                # (strict: an uncovered signature fails the page-in typed)
                self._engine.warm(self.input_dtype)
            if self._had_batcher:
                # the model served generate traffic last residency; rebuild
                # eagerly so paged-in decode is warm before the next request
                self._build_batcher_locked()

    # Deliberate: the entry RLock is held across the whole drain (see the
    # __init__ comment) so a re-activation can never interleave with a
    # half-finished eviction. The join/wait inside shutdown(drain=True) is
    # the contract, not an accident — sanctioned, with eyes open.
    def deactivate(self) -> None:  # jaxlint: sanction=blocking-call-under-lock
        """Lease-drain, pull current weights to host, drop device refs.

        This is the hot-swap drain discipline applied to eviction:
        ``shutdown(drain=True)`` completes every admitted batch/generation
        against the old device params before they are released, so no
        in-flight work ever loses its params. The *current* registry
        snapshot (including any generations published while resident) is
        what survives as the host copy."""
        import jax

        with self._lock:
            if self._engine is None:
                return
            self._engine.shutdown(drain=True)
            if self._batcher is not None:
                self._batcher.shutdown(drain=True)
            snap = self._registry.current()
            self._host_params = jax.tree.map(np.asarray, snap.params)
            self._host_state = jax.tree.map(np.asarray, snap.state)
            self.weight_bytes = _tree_bytes(self._host_params,
                                            self._host_state)
            self.version = snap.version
            self._next_generation = snap.generation + 1
            self._registry = None
            self._engine = None
            self._batcher = None

    # --------------------------------------------------------------- serving
    # Sanctioned: "not resident" is an internal eviction-race signal — the
    # fleet facade's _EVICTION_RETRIES loop swallows it and pages the model
    # back in; only an exhausted retry escapes, and the HTTP boundary
    # counts that on fleet_http_errors_total{endpoint,code}. Counting at
    # the raise would overcount every won race.
    def engine(self) -> ServeEngine:  # jaxlint: sanction=uncounted-shed
        with self._lock:
            if self._engine is None:
                raise ServerClosingError(
                    f"model {self.name!r} is not resident")
            return self._engine

    def _build_batcher_locked(self) -> None:
        self._batcher = ContinuousBatcher(
            self.model, registry=self._registry, metrics=self.metrics,
            aot_store=self.aot_store, strict_aot=self.strict_aot,
            model_name=self.name, **self.gen_opts)
        self._had_batcher = True

    # Sanctioned: same eviction-race signal as engine() above.
    def batcher(self) -> ContinuousBatcher:  # jaxlint: sanction=uncounted-shed
        with self._lock:
            if self._engine is None:
                raise ServerClosingError(
                    f"model {self.name!r} is not resident")
            if self._batcher is None:
                self._build_batcher_locked()
            return self._batcher

    # Deliberate: publish-with-drain waits out in-flight leases while the
    # entry RLock serializes it against eviction/re-activation — same
    # lifecycle contract as deactivate(). Sanctioned, not overlooked.
    def publish(self, params, state=None, version: Optional[str] = None,  # jaxlint: sanction=blocking-call-under-lock
                drain: bool = True) -> int:
        """Hot-swap this model's weights; returns the new generation.
        Resident: the full registry publish (warmers precompile the
        candidate, atomic flip, lease drain). Cold: the host copy and
        generation counter advance so the next activation serves the new
        weights under the right generation number."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            if self._registry is not None:
                snap = self._registry.publish(
                    jax.tree.map(jnp.asarray, params),
                    state=(jax.tree.map(jnp.asarray, state)
                           if state is not None else None),
                    version=version, drain=drain)
                self.version = snap.version
                return snap.generation
            self._host_params = jax.tree.map(np.asarray, params)
            if state is not None:
                self._host_state = jax.tree.map(np.asarray, state)
            self.weight_bytes = _tree_bytes(self._host_params,
                                            self._host_state)
            gen = self._next_generation
            self.version = version if version is not None else f"v{gen - 1}"
            self._next_generation = gen + 1
            return gen

    def queue_depth(self) -> int:
        """Requests waiting in this entry's resident stack (0 when cold)."""
        with self._lock:
            if self._engine is None:
                return 0
            depth = self._engine.queue_depth()
            if self._batcher is not None:
                depth += self._batcher.queue_depth()
            return depth

    def kv_utilization(self) -> float:
        """Fraction of this entry's KV blocks in use — 0.0 when cold,
        predict-only, or the batcher runs the dense (non-paged) path."""
        with self._lock:
            if self._batcher is None:
                return 0.0
            stats = self._batcher.kv_block_stats()
        total = int(stats.get("blocks_total") or 0)
        return (int(stats.get("blocks_used") or 0) / total) if total else 0.0

    def components(self) -> list:
        """Watchdog view: ``(name, worker-owning component)`` pairs for the
        currently-resident serving stack (empty when paged out)."""
        with self._lock:
            if self._engine is None:
                return []
            comps = [(f"{self.name}.engine", self._engine)]
            if self._batcher is not None:
                comps.append((f"{self.name}.batcher", self._batcher))
            return comps

    def info(self) -> dict:
        with self._lock:
            resident = self._engine is not None
            out = {
                "resident": resident,
                "version": self.version,
                "generation": (self._registry.generation if resident
                               else self._next_generation - 1),
                "weight_bytes": int(self.weight_bytes),
                "generate_ready": self._batcher is not None,
            }
            batcher = self._batcher
        if batcher is not None:
            # sharing picture per tenant-facing model: block usage,
            # prefix-cache hit rates, CoW/fork counts (router placement
            # and dashboards read this off the heartbeat)
            out["kv"] = batcher.kv_block_stats()
        return out


class FleetRegistry:
    """Named models + tenant admission + weight paging, one front door.

    ``hbm_budget_bytes`` caps summed resident weights (None = unbounded);
    ``aot_store`` is shared across models (cache keys include the model's
    architecture fingerprint, so entries never collide). Per-model
    engine/batcher knobs ride in ``add(engine_opts=..., gen_opts=...)``.
    """

    def __init__(self, *, hbm_budget_bytes: Optional[int] = None,
                 metrics=None, aot_store=None, strict_aot: bool = False,
                 tenants: Optional[TenantTable] = None,
                 breaker_failures: Optional[int] = 5,
                 breaker_reset_s: float = 10.0, breaker_clock=None,
                 watchdog_s: Optional[float] = None,
                 tuned_for: Optional[str] = None):
        from ..obs.metrics import MetricsRegistry

        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.aot_store = aot_store
        # strict_aot applies fleet-wide: every entry's activation (and
        # every page-in after an eviction) must be served by the prebuilt
        # store or fail with a typed AotTraceError — never a trace
        self.strict_aot = bool(strict_aot)
        if self.strict_aot and aot_store is None:
            raise ValueError("strict_aot=True requires a shared aot_store")
        # tuned_for: a workload fingerprint (sim/workload.py). When set, the
        # boot resolves the autotuner's winning knob set for (this runtime,
        # that workload) from the AOT store — the same place the compiled
        # executables come from — and every add() starts from those knobs.
        # A miss (counted on sim_tuned_config_misses_total) means hand-picked
        # defaults, exactly as before.
        self.tuned_config: Optional[dict] = None
        if tuned_for is not None:
            from ..aot.tuned import get_tuned

            self.tuned_config = get_tuned(aot_store, tuned_for,
                                          metrics=self.metrics)
        self.tenants = tenants if tenants is not None \
            else TenantTable(metrics=self.metrics)
        self.pager = WeightPager(hbm_budget_bytes, metrics=self.metrics)
        self._lock = threading.Lock()
        self._entries: Dict[str, FleetEntry] = {}
        self._closing = False
        self.health = Health(metrics=self.metrics, component="fleet")
        # per (model, slo_class) error-budget burn; works with tracing off
        self.slo = SloBurn(metrics=self.metrics)
        # per-model circuit breakers; breaker_failures=None disables them
        self._breaker_failures = breaker_failures
        self._breaker_reset_s = float(breaker_reset_s)
        self._breaker_clock = breaker_clock
        self._breakers: Dict[str, CircuitBreaker] = {}
        self._watchdog: Optional[Watchdog] = None
        if watchdog_s is not None:
            self._watchdog = Watchdog(
                self._watch_components, deadline_s=watchdog_s,
                metrics=self.metrics, health=self.health).start()

    def _watch_components(self) -> list:
        with self._lock:
            entries = list(self._entries.values())
        comps: list = []
        for entry in entries:
            comps.extend(entry.components())
        return comps

    def _breaker(self, name: str) -> Optional[CircuitBreaker]:
        with self._lock:
            return self._breakers.get(name)

    # ------------------------------------------------------------ membership
    def add(self, name: str, model, params=None, state=None, *,
            version: str = "v0", input_dtype=np.float32,
            engine_opts: Optional[dict] = None,
            gen_opts: Optional[dict] = None,
            eager: bool = False) -> FleetEntry:
        """Register a model under ``name``. Weights default to the model's
        own initialized params. ``eager=True`` pages it in immediately;
        otherwise the first request does. With a resolved tuned config
        (``tuned_for=``), its engine/gen groups become the per-model
        defaults — explicit ``engine_opts``/``gen_opts`` keys still win."""
        if self.tuned_config is not None:
            from ..aot.tuned import tuned_group
            from ..serve.continuous import gen_opts_from_config
            from ..serve.engine import ENGINE_KNOBS

            tuned_engine = {
                k: v
                for k, v in tuned_group(self.tuned_config, "engine").items()
                if k in ENGINE_KNOBS}
            engine_opts = {**tuned_engine, **(engine_opts or {})}
            gen_opts = {**gen_opts_from_config(self.tuned_config),
                        **(gen_opts or {})}
        entry = FleetEntry(
            name, model,
            params if params is not None else model.params,
            state if state is not None else model.state,
            version=version, input_dtype=input_dtype, metrics=self.metrics,
            aot_store=self.aot_store, strict_aot=self.strict_aot,
            engine_opts=engine_opts, gen_opts=gen_opts)
        with self._lock:
            if name in self._entries:
                raise ValueError(f"model {name!r} already registered — "
                                 f"publish() hot-swaps weights in place")
            self._entries[name] = entry
            if self._breaker_failures is not None:
                kwargs = {}
                if self._breaker_clock is not None:
                    kwargs["clock"] = self._breaker_clock
                self._breakers[name] = CircuitBreaker(
                    failure_threshold=self._breaker_failures,
                    reset_s=self._breaker_reset_s, metrics=self.metrics,
                    model=name, health=self.health, **kwargs)
        if eager:
            self.pager.ensure(entry)
        return entry

    def remove(self, name: str) -> None:
        with self._lock:
            entry = self._entries.pop(name, None)
            self._breakers.pop(name, None)
        if entry is None:
            raise UnknownModelError(f"no model named {name!r}")
        # a removed model's open breaker must not keep readiness off
        self.health.clear(f"breaker_open:{name}")
        self.pager.drop(entry)

    def get(self, name: str) -> FleetEntry:
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise UnknownModelError(f"no model named {name!r}")
        return entry

    def names(self) -> list:
        with self._lock:
            return sorted(self._entries)

    def queue_depth(self) -> int:
        """Fleet-wide queued work (sum over resident models) — the load
        signal a replica self-reports on each cluster heartbeat."""
        with self._lock:
            entries = list(self._entries.values())
        return sum(e.queue_depth() for e in entries)

    def kv_pressure(self) -> float:
        """Worst KV-block utilization across resident models — the memory
        half of the load signal a replica self-reports on each cluster
        heartbeat (the autoscaler's KV-pressure input)."""
        with self._lock:
            entries = list(self._entries.values())
        return max((e.kv_utilization() for e in entries), default=0.0)

    def ensure(self, name: str) -> FleetEntry:
        """Page a model in without serving a request (prewarm)."""
        entry = self.get(name)
        self.pager.ensure(entry)
        return entry

    # --------------------------------------------------------------- serving
    def _admit(self, tenant: str, name: str,
               timeout_ms: Optional[float]) -> tuple:
        """Tenant admission; returns ``(deadline_ms, slo_class_name)``."""
        slo = self.tenants.admit(tenant, model=name)
        return (timeout_ms if timeout_ms is not None else slo.deadline_ms,
                slo.name)

    def _slo_record(self, name: str, slo_class: Optional[str],
                    exc: Optional[BaseException]) -> None:
        """One admitted request's outcome into the burn accounting.
        ``slo_class`` is None when admission itself refused (quota) —
        nothing to account."""
        if slo_class is None:
            return
        if isinstance(exc, ServeError) and exc.cause in _SLO_EXCLUDED:
            return
        self.slo.record(name, slo_class, good=exc is None)

    @staticmethod
    def _breaker_counts(exc: BaseException) -> bool:
        """Does this failure count against the model's breaker? Server-side
        breakage only — see ``_BREAKER_CAUSES``."""
        if isinstance(exc, ServeError):
            return exc.cause in _BREAKER_CAUSES
        return True

    def _observed(self, br: Optional[CircuitBreaker], fn):
        """Run one gated serving attempt, feeding its outcome back into the
        model's breaker. ``br.allow()`` already passed for this request."""
        if br is None:
            return fn()
        try:
            out = fn()
        except BaseException as e:
            if self._breaker_counts(e):
                br.record_failure()
            else:
                br.record_ignored()
            raise
        br.record_success()
        return out

    def predict(self, name: str, x, *, tenant: str = "anonymous",
                timeout_ms: Optional[float] = None, ctx=None) -> FleetResult:
        """Breaker gate -> tenant admission -> page-in -> engine predict.
        ``timeout_ms`` defaults to the tenant's SLO deadline."""
        entry = self.get(name)
        br = self._breaker(name)
        if br is not None:
            br.allow()  # open breaker refuses before quota/paging work
        slo_cls: list = [None]

        def _serve() -> FleetResult:
            nonlocal timeout_ms
            if ctx is None:
                timeout_ms, slo_cls[0] = self._admit(tenant, name,
                                                     timeout_ms)
            else:
                with ctx.stage("admit", model=name):
                    timeout_ms, slo_cls[0] = self._admit(tenant, name,
                                                         timeout_ms)
                ctx.tenant = tenant
                ctx.slo_class = slo_cls[0]
            x_ = np.asarray(x, entry.input_dtype)
            last: Optional[ServeError] = None
            for _ in range(_EVICTION_RETRIES):
                if ctx is None:
                    self.pager.ensure(entry)
                else:
                    with ctx.stage("page_in_wait", model=name):
                        self.pager.ensure(entry)
                try:
                    eng = entry.engine()
                    if x_.ndim > len(entry.model.input_shape) \
                            and x_.shape[0] <= eng.batch_buckets[-1]:
                        handle = eng.submit(x_, timeout_ms=timeout_ms,
                                            ctx=ctx)
                        return FleetResult(handle.wait(), handle.generation)
                    return FleetResult(
                        eng.predict(x_, timeout_ms=timeout_ms, ctx=ctx),
                        None)
                except ServerClosingError as e:
                    last = e  # lost the race with an eviction: page back in
            raise last

        try:
            out = self._observed(br, _serve)
        except BaseException as e:
            self._slo_record(name, slo_cls[0], e)
            raise
        self._slo_record(name, slo_cls[0], None)
        return out

    def submit_generate(self, name: str, prompt, max_new_tokens: int, *,
                        tenant: str = "anonymous", temperature: float = 1.0,
                        top_k: Optional[int] = None,
                        eos_id: Optional[int] = None,
                        timeout_ms: Optional[float] = None, ctx=None):
        """Admit one generation; returns the batcher's streamable handle.
        The breaker observes the *submission* path (paging + admission into
        the batcher) — a handle that later times out does not count."""
        entry = self.get(name)
        br = self._breaker(name)
        if br is not None:
            br.allow()
        slo_cls: list = [None]

        def _serve():
            nonlocal timeout_ms
            if ctx is None:
                timeout_ms, slo_cls[0] = self._admit(tenant, name,
                                                     timeout_ms)
            else:
                with ctx.stage("admit", model=name):
                    timeout_ms, slo_cls[0] = self._admit(tenant, name,
                                                         timeout_ms)
                ctx.tenant = tenant
                ctx.slo_class = slo_cls[0]
            prompt_ = np.asarray(prompt, np.int32)
            last: Optional[ServeError] = None
            for _ in range(_EVICTION_RETRIES):
                if ctx is None:
                    self.pager.ensure(entry)
                else:
                    with ctx.stage("page_in_wait", model=name):
                        self.pager.ensure(entry)
                try:
                    return entry.batcher().submit(
                        prompt_, max_new_tokens, temperature=temperature,
                        top_k=top_k, eos_id=eos_id, timeout_ms=timeout_ms,
                        ctx=ctx)
                except ServerClosingError as e:
                    last = e
            raise last

        try:
            handle = self._observed(br, _serve)
        except BaseException as e:
            # the submission path itself failed after admission: account it
            self._slo_record(name, slo_cls[0], e)
            raise
        # SLO outcome is decided when the batcher finishes the request —
        # possibly much later, on the decode/watchdog thread
        cls = slo_cls[0]
        handle.set_on_done(lambda r: self._slo_record(name, cls, r.error))
        return handle

    def cancel_generate(self, name: str, handle,
                        cause: str = "client_gone") -> bool:
        """Abandon one streamed generation whose consumer vanished — frees
        its decode slot and KV pages via the batcher's cancel path. Returns
        False when the request already finished (including via a racing
        page-out, which drains in-flight work)."""
        try:
            batcher = self.get(name).batcher()
        except ServeError:
            return False
        return batcher.cancel(handle, cause=cause)

    def generate(self, name: str, prompt, max_new_tokens: int, *,
                 tenant: str = "anonymous", temperature: float = 1.0,
                 top_k: Optional[int] = None, eos_id: Optional[int] = None,
                 timeout_ms: Optional[float] = None, ctx=None) -> np.ndarray:
        """Blocking generate; batch prompts fan out row-per-request like
        :meth:`ContinuousBatcher.generate`."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            return self.submit_generate(
                name, prompt, max_new_tokens, tenant=tenant,
                temperature=temperature, top_k=top_k, eos_id=eos_id,
                timeout_ms=timeout_ms, ctx=ctx).wait()
        handles = [self.submit_generate(
            name, p, max_new_tokens, tenant=tenant, temperature=temperature,
            top_k=top_k, eos_id=eos_id, timeout_ms=timeout_ms)
            for p in prompt]
        outs = [h.wait() for h in handles]
        width = max(o.shape[0] for o in outs)
        pad = eos_id if eos_id is not None else 0
        full = np.full((len(outs), width), pad, np.int32)
        for i, o in enumerate(outs):
            full[i, :o.shape[0]] = o
        return full

    # ----------------------------------------------------------------- admin
    def publish(self, name: str, params, state=None,
                version: Optional[str] = None, drain: bool = True) -> int:
        return self.get(name).publish(params, state=state, version=version,
                                      drain=drain)

    def status(self) -> dict:
        with self._lock:
            entries = dict(self._entries)
            breakers = dict(self._breakers)
        body: Dict[str, Any] = {
            "models": {n: e.info() for n, e in sorted(entries.items())},
            "pager": self.pager.stats(),
            "tenants": self.tenants.stats(),
            "health": self.health.snapshot(),
            "breakers": {n: b.snapshot() for n, b in sorted(breakers.items())},
            "slo": self.slo.snapshot(),
        }
        if self.aot_store is not None:
            body["aot_store"] = self.aot_store.stats()
        return body

    def shutdown(self) -> None:
        """Drain and deactivate every resident model."""
        if self._watchdog is not None:
            # stop the watchdog FIRST: a drain must not be mistaken for a
            # stall and "restarted" mid-teardown
            self._watchdog.stop()
        with self._lock:
            self._closing = True
            entries = list(self._entries.values())
        for entry in entries:
            self.pager.drop(entry)
