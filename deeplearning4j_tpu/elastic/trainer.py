"""ElasticTrainer — fault-tolerant data-parallel training on the serving
control plane.

The last ten PRs built membership, chaos, autoscaling, the AOT store and
the telemetry plane for inference; this module points all of it at the
repo's original training mandate. One :class:`ElasticTrainer` owns a
ladder of data-parallel widths (``dp_min .. dp_max``) and, per step:

1. supervises one virtual worker per replica through
   :class:`~..cluster.membership.Membership` on a **logical clock** (one
   tick per step — deterministic under test, wall-free by construction);
   a chaos-killed worker (``elastic.step`` injection point) stops
   beating, is swept ``alive -> suspect -> dead``, reaped, and the mesh
   resizes down the ladder;
2. runs one ZeRO-1 weight-update-sharded pstep (PAPERS.md arXiv
   2004.13336 — optimizer state sharded over the data axis via the
   shared :func:`~..parallel.sharding.zero_opt_spec` rule, the update
   computed 1/n per replica and all-gathered by GSPMD) resolved through
   an :class:`~..aot.compile.AotFunction` per ladder width, all of them
   warmed up front so **a resize never cold-traces**;
3. feeds the wall (or injected) step time into a
   :class:`~..autoscale.signals.StepTimeSignalReader` and asks the
   stock :class:`~..autoscale.policy.AutoscalePolicy` (unchanged —
   burn = step-time regression vs. the step-time budget) whether to
   grow or shrink the mesh.

Every resize boundary publishes an atomic checkpoint
(:mod:`.checkpoint`) before AND after the layout change, with the
redistribution planned by :mod:`.reshard` (arXiv 2112.01075 — only
non-resident bytes move) and executed as one ``jax.device_put`` onto
the new shardings. A worker dying mid-step, mid-resize
(``elastic.resize`` injection point) or mid-checkpoint resumes from the
last published consistent (step, mesh-shape, shard-layout) triple,
bit-identical under fixed seed to a run started fresh at that triple.
"""

from __future__ import annotations

import os
import time
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..aot.compile import AotFunction, arch_of
from ..aot.store import AotStore
from ..autoscale.policy import IN, OUT, AutoscalePolicy
from ..autoscale.signals import StepTimeSignalReader
from ..chaos import faults
from ..cluster.membership import DEAD, Membership
from ..obs import flight as _flight
from ..parallel.mesh import DATA_AXIS, make_mesh
from ..parallel.sharding import zero_opt_spec
from ..train.trainer import build_updater, check_not_donated
from .checkpoint import CheckpointInfo, latest, save_atomic
from .reshard import ReshardPlan, plan_reshard


class ElasticError(RuntimeError):
    """Base class for typed elastic-training failures."""


class QuorumLostError(ElasticError):
    """Fewer live workers remain than ``dp_min`` — training cannot
    continue at any ladder width; resume after capacity returns."""


class NoCheckpointError(ElasticError):
    """``resume()`` found no published checkpoint pointer in the workdir."""


class _TraceCounter:
    """Counts live pstep traces (AOT misses) — the number the acceptance
    drill pins at zero across a resize — and mirrors them onto the
    metrics registry."""

    __slots__ = ("n", "_m")

    def __init__(self, metric=None):
        self.n = 0
        self._m = metric

    def inc(self) -> None:
        self.n += 1
        if self._m is not None:
            self._m.inc()


class ElasticTrainer:
    """Membership-supervised elastic data-parallel trainer.

    ``dp`` is the starting width, ``dp_min``/``dp_max`` bound the ladder;
    every width in ``[dp_min, dp_max]`` gets its own mesh (a prefix of
    ``devices``), jitted ZeRO-sharded pstep, and AOT store entry. The
    global batch must divide evenly by every ladder width (e.g. 12 for a
    2..4 ladder) so a resize never changes the batch a model sees.

    All timing that steers control flow runs on the trainer's logical
    clock (1.0 per step): membership leases, policy sustain windows and
    cooldowns. Wall time is only *measured* (metrics, bench), never
    branched on, so a drill under fixed seed is bit-reproducible.
    """

    def __init__(self, model, *, workdir: str, dp: int = 4, dp_min: int = 2,
                 dp_max: Optional[int] = None, seed: int = 0,
                 store: Optional[AotStore] = None, metrics=None,
                 devices=None, suspect_after_steps: float = 1.5,
                 dead_after_steps: float = 2.5,
                 step_time_budget_s: Optional[float] = None,
                 policy: Optional[AutoscalePolicy] = None):
        dp, dp_min = int(dp), int(dp_min)
        dp_max = int(dp_max) if dp_max is not None else dp
        if not 1 <= dp_min <= dp <= dp_max:
            raise ValueError("need 1 <= dp_min <= dp <= dp_max")
        devices = list(devices if devices is not None else jax.devices())
        if dp_max > len(devices):
            raise ValueError(f"dp_max={dp_max} exceeds {len(devices)} devices")
        self.model = model
        self.tx = build_updater(model)
        if model.params is None:
            model.init()
        check_not_donated((model.params, model.state), "ElasticTrainer")
        self.workdir = os.path.abspath(workdir)
        self.dp = dp
        self.dp_min = dp_min
        self.dp_max = dp_max
        self.iteration = 0
        self._tick = 0.0          # the logical clock: 1.0 per step
        self._rng = jax.random.PRNGKey(int(seed))
        self._devices = devices
        self._ladder = tuple(range(dp_min, dp_max + 1))
        self._meshes = {d: make_mesh({DATA_AXIS: d}, devices[:d])
                        for d in self._ladder}
        self.store = store if store is not None else AotStore(
            os.path.join(self.workdir, "aot"))
        self._metrics = metrics
        self._init_metrics(metrics)

        # placement at the starting width: params/net-state replicated,
        # optimizer state ZeRO-sharded (eager init so moments exist before
        # the first pstep — same discipline as ParallelWrapper)
        mesh = self._meshes[dp]
        repl = NamedSharding(mesh, P())
        self.params = jax.device_put(model.params, repl)
        self.state = jax.device_put(model.state, repl)
        opt0 = self.tx.init(self.params)
        self.opt_state = jax.device_put(opt0, self._opt_shardings(dp, opt0))
        self._arch = arch_of(self.params, self.state)

        self._trace_counts = {d: _TraceCounter(self._m_traces(d))
                              for d in self._ladder}
        self._steps = {d: AotFunction(
            self._make_pstep(d), tag=f"elastic_pstep_dp{d}",
            store=self.store, metrics=metrics, arch=self._arch,
            component="elastic", donate_argnums=(0, 1, 2),
            compile_counter=self._trace_counts[d]) for d in self._ladder}
        self._warmed = False

        # one virtual worker per data-parallel replica, supervised on the
        # logical clock (thresholds are in steps, not seconds)
        self.membership = Membership(
            suspect_after_s=float(suspect_after_steps),
            dead_after_s=float(dead_after_steps),
            clock=lambda: self._tick, metrics=metrics)
        self._workers: List[str] = []
        self._crashed: set = set()
        self._next_worker = 0
        for _ in range(dp):
            self._spawn_worker()

        # step-time burn -> the stock AutoscalePolicy, unchanged: burn 1.0
        # means each step spends exactly its budget
        self.budget_s = (float(step_time_budget_s)
                         if step_time_budget_s is not None else None)
        self.signals = (StepTimeSignalReader(
            budget_s=self.budget_s, clock=lambda: self._tick)
            if self.budget_s is not None else None)
        self.policy = policy if policy is not None else (AutoscalePolicy(
            min_replicas=dp_min, max_replicas=dp_max,
            burn_out={"train": 1.0}, queue_high=1e9, queue_low=1e9,
            sustain_out_s=2.0, sustain_in_s=6.0,
            cooldown_out_s=4.0, cooldown_in_s=4.0)
            if self.budget_s is not None else None)

        self.last_loss = None            # device scalar (no per-step sync)
        self.last_resize: Optional[dict] = None
        self.resizes: List[dict] = []

    # ------------------------------------------------------------- metrics
    def _init_metrics(self, metrics) -> None:
        if metrics is None:
            from ..obs.metrics import MetricsRegistry

            metrics = MetricsRegistry(enabled=False)
        self._m_resizes = lambda cause: metrics.counter(
            "elastic_resizes_total", {"cause": cause},
            help="mesh resizes by trigger cause")
        self._m_step = metrics.histogram(
            "elastic_step_seconds", {},
            help="elastic pstep wall time (dispatch + device)")
        self._m_reshard = metrics.counter(
            "elastic_reshard_bytes_total", {},
            help="optimizer-state bytes moved by resize redistribution")
        self._m_ckpt = metrics.histogram(
            "elastic_checkpoint_seconds", {},
            help="atomic checkpoint publish wall time")
        self._m_resize_s = metrics.histogram(
            "elastic_resize_seconds", {},
            help="full resize wall time (checkpoints + reshard + resolve)")
        self._m_dp = metrics.gauge(
            "elastic_dp", {}, help="current data-parallel mesh width")
        self._m_dp.set(self.dp)
        self._m_traces = lambda d: metrics.counter(
            "elastic_pstep_traces_total", {"dp": str(d)},
            help="live pstep traces (AOT store misses) by mesh width")

    # ------------------------------------------------------------ plumbing
    def _opt_shardings(self, d: int, opt_tree):
        mesh = self._meshes[d]
        return jax.tree.map(
            lambda a: NamedSharding(mesh, zero_opt_spec(np.shape(a), d)),
            opt_tree)

    def _make_pstep(self, d: int):
        """One jitted ZeRO-1 train step bound to the width-``d`` mesh:
        params in/out replicated, optimizer state in/out sharded per the
        shared layout rule — GSPMD partitions the elementwise update
        across the ``data`` axis and all-gathers the applied params
        (bit-identical numerics, ~1/d optimizer memory per device)."""
        mesh = self._meshes[d]
        repl = NamedSharding(mesh, P())
        opt_sh = self._opt_shardings(d, self.opt_state)
        model, tx = self.model, self.tx

        # params/opt-state/net-state are loop-carried: donated, like the
        # plain Trainer's step (a store-loaded donating executable runs
        # clean on the installed jaxlib — tests/test_elastic.py drills it)
        @partial(jax.jit, donate_argnums=(0, 1, 2),
                 out_shardings=(repl, opt_sh, repl, repl))
        def pstep(params, opt_state, net_state, x, y, rng):
            def loss_fn(p):
                loss, new_state = model.score(p, net_state, x, y,
                                              training=True, rng=rng)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, new_state, loss

        return pstep

    def next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def trace_count(self) -> int:
        """Total live pstep traces across the ladder (0 after a fully
        store-warmed boot — the zero-compile-miss acceptance number)."""
        return sum(c.n for c in self._trace_counts.values())

    # ------------------------------------------------------------- workers
    def _spawn_worker(self) -> str:
        wid = f"w{self._next_worker}"
        self._next_worker += 1
        self.membership.add(wid, f"elastic://{wid}")
        self._workers.append(wid)
        return wid

    def _retire_worker(self) -> str:
        wid = self._workers.pop()
        self.membership.remove(wid)
        self._crashed.discard(wid)
        return wid

    def _supervise(self) -> None:
        """One supervision round: fire the per-worker ``elastic.step``
        chaos seam (an injected error = that worker crashed and stops
        beating), renew survivors' leases, sweep, and reap the dead —
        which is what triggers a worker-death resize."""
        fp = faults.ACTIVE
        for wid in list(self._workers):
            if wid in self._crashed:
                continue
            if fp is not None:
                try:
                    fp.hit("elastic.step", scope=wid)
                except (RuntimeError, OSError):
                    # the worker "process" died; its lease now ages out
                    self._crashed.add(wid)
                    continue
            self.membership.report(wid, {"queue_depth": 0,
                                         "kv_utilization": 0.0,
                                         "step": self.iteration})
        states = self.membership.sweep()
        dead = sorted(w for w, s in states.items() if s == DEAD)
        if not dead:
            return
        for wid in dead:
            self.membership.remove(wid)
            self._workers.remove(wid)
            self._crashed.discard(wid)
        alive = len(self._workers)
        if alive < self.dp_min:
            raise QuorumLostError(
                f"{alive} workers remain, dp_min={self.dp_min}; resume "
                f"from {self.workdir} once capacity returns")
        self._resize(min(alive, self.dp_max), cause="worker_death")

    def _autoscale(self) -> None:
        """Ask the unchanged AutoscalePolicy about the step-time burn
        window; actuate OUT by spawning a worker and climbing the ladder,
        IN by retiring one and stepping down. The cooldown only arms via
        ``commit`` after the resize actually happened."""
        decision = self.policy.decide(self.signals, current=self.dp,
                                      now=self._tick)
        if decision.direction == OUT:
            target = min(self.dp + decision.amount, self.dp_max)
            if target <= self.dp:
                return
            for _ in range(target - self.dp):
                self._spawn_worker()
            self._resize(target, cause="autoscale")
            self.policy.commit(decision, self._tick)
        elif decision.direction == IN:
            target = max(self.dp - decision.amount, self.dp_min)
            if target >= self.dp:
                return
            for _ in range(self.dp - target):
                self._retire_worker()
            self._resize(target, cause="autoscale")
            self.policy.commit(decision, self._tick)

    # -------------------------------------------------------------- resize
    def _checkpoint(self, cause: str) -> CheckpointInfo:
        t0 = time.perf_counter()
        info = save_atomic(self.workdir, self, step=self.iteration,
                           dp=self.dp, mesh_shape=((DATA_AXIS, self.dp),),
                           cause=cause)
        self._m_ckpt.observe(time.perf_counter() - t0)
        return info

    def _resize(self, dp_new: int, cause: str) -> ReshardPlan:
        """The resize sequence the failure-mode table documents:
        checkpoint at the OLD layout -> ``elastic.resize`` chaos seam
        (a death here resumes from that checkpoint) -> plan + execute the
        redistribution -> resolve the new width's pstep from the AOT
        store (never a trace) -> checkpoint at the NEW layout."""
        dp_old = self.dp
        t0 = time.perf_counter()
        self._m_resizes(cause).inc()
        self._checkpoint(cause=f"pre_resize_{cause}")
        fp = faults.ACTIVE
        if fp is not None:
            # a chaos error here simulates the coordinator dying mid-resize:
            # it propagates typed to the caller, and the pre-resize
            # checkpoint just published is the consistent resume point
            fp.hit("elastic.resize", scope=cause)
        plan = plan_reshard(self.opt_state, dp_old, dp_new)
        self._m_reshard.inc(plan.bytes_moved)
        mesh = self._meshes[dp_new]
        repl = NamedSharding(mesh, P())
        self.params = jax.device_put(self.params, repl)
        self.state = jax.device_put(self.state, repl)
        self.opt_state = jax.device_put(
            self.opt_state, self._opt_shardings(dp_new, self.opt_state))
        self.dp = dp_new
        self._m_dp.set(dp_new)
        self._checkpoint(cause=f"post_resize_{cause}")
        dt = time.perf_counter() - t0
        self._m_resize_s.observe(dt)
        self.last_resize = {"step": self.iteration, "from": dp_old,
                            "to": dp_new, "cause": cause,
                            "seconds": dt, **plan.summary()}
        self.resizes.append(self.last_resize)
        if _flight.ACTIVE is not None:
            _flight.ACTIVE.record_event("elastic", "resize", cause,
                                        dp_from=dp_old, dp_to=dp_new,
                                        bytes_moved=plan.bytes_moved)
        return plan

    # ---------------------------------------------------------------- warm
    def warm(self, x, y) -> None:
        """AOT-warm EVERY ladder width's pstep against this batch shape
        (abstract ShapeDtypeStructs — nothing executes). After this, a
        resize resolves its executable from memory or the store; a live
        trace at resize time can only mean the store was cold at boot."""
        x, y = np.asarray(x), np.asarray(y)
        for d in self._ladder:
            mesh = self._meshes[d]
            repl = NamedSharding(mesh, P())
            bsh = NamedSharding(mesh, P(DATA_AXIS))

            def sds(a, sh):
                return jax.ShapeDtypeStruct(np.shape(a),
                                            getattr(a, "dtype", np.float32),
                                            sharding=sh)

            self._steps[d].warm(
                jax.tree.map(lambda a, s=repl: sds(a, s), self.params),
                jax.tree.map(lambda a, s=mesh: jax.ShapeDtypeStruct(
                    np.shape(a), getattr(a, "dtype", np.float32),
                    sharding=NamedSharding(s, zero_opt_spec(np.shape(a),
                                                            d))),
                    self.opt_state),
                jax.tree.map(lambda a, s=repl: sds(a, s), self.state),
                sds(x, bsh), sds(y, bsh), sds(self._rng, repl))
        self._warmed = True

    # ----------------------------------------------------------------- fit
    def fit(self, batch_fn: Callable[[int], tuple], steps: int, *,
            step_time_fn: Optional[Callable[[int], float]] = None
            ) -> "ElasticTrainer":
        """Train until ``self.iteration == steps``. ``batch_fn(step)``
        must be a pure function of the step index returning host
        ``(x, y)`` with a global batch divisible by every ladder width —
        that purity is what makes a killed-and-resumed run replay the
        exact byte stream of an uninterrupted one. ``step_time_fn``
        overrides the observed step time (seconds) fed to the autoscale
        signal window — the deterministic handle drills use to stage a
        step-time regression."""
        x0, y0 = batch_fn(self.iteration)
        b = int(np.shape(x0)[0])
        for d in self._ladder:
            if b % d != 0:
                raise ValueError(
                    f"global batch {b} must divide by every ladder width "
                    f"{self._ladder} (got remainder at dp={d})")
        if not self._warmed:
            self.warm(x0, y0)
        while self.iteration < int(steps):
            self._supervise()
            x, y = batch_fn(self.iteration)
            mesh = self._meshes[self.dp]
            bsh = NamedSharding(mesh, P(DATA_AXIS))
            xd = jax.device_put(np.asarray(x), bsh)
            yd = jax.device_put(np.asarray(y), bsh)
            t0 = time.perf_counter()
            (self.params, self.opt_state, self.state,
             self.last_loss) = self._steps[self.dp](
                self.params, self.opt_state, self.state, xd, yd,
                self.next_rng())
            dt = time.perf_counter() - t0
            self._m_step.observe(dt)
            self.iteration += 1
            self._tick += 1.0
            if self.signals is not None:
                observed = (float(step_time_fn(self.iteration - 1))
                            if step_time_fn is not None else dt)
                self.signals.observe(observed, alive=self.dp)
                self._autoscale()
        self.model.params, self.model.state = self.params, self.state
        return self

    def final_loss(self) -> float:
        """The last step's loss as a host float (the ONE host sync the
        training loop ever pays, after fit returns)."""
        if self.last_loss is None:
            raise ElasticError("no step has run yet")
        return float(self.last_loss)

    # -------------------------------------------------------------- resume
    def checkpoint_now(self, cause: str = "manual") -> CheckpointInfo:
        """Publish an atomic checkpoint outside a resize boundary."""
        return self._checkpoint(cause=cause)

    @classmethod
    def resume(cls, workdir: str, *, dp: Optional[int] = None, model=None,
               **kwargs) -> "ElasticTrainer":
        """Rebuild a trainer from the workdir's last published consistent
        triple. ``dp`` may differ from the checkpoint's width — the
        restore itself redistributes onto the new layout (orbax places
        every leaf on the fresh trainer's shardings), which is how a
        replica that died mid-resize comes back at the post-resize width.
        """
        from ..train import orbax_io

        info = latest(workdir)
        if info is None:
            raise NoCheckpointError(f"no checkpoint pointer in {workdir}")
        if model is None:
            model = orbax_io.load_model_json(info.path)
        dp_new = int(dp) if dp is not None else info.dp
        t = cls(model, workdir=workdir, dp=dp_new, **kwargs)
        orbax_io.restore_trainer(info.path, t)
        t._tick = float(t.iteration)
        t.model.params, t.model.state = t.params, t.state
        if dp_new != info.dp:
            plan = plan_reshard(t.opt_state, info.dp, dp_new)
            t._m_reshard.inc(plan.bytes_moved)
            t.last_resize = {"step": t.iteration, "from": info.dp,
                             "to": dp_new, "cause": "resume",
                             **plan.summary()}
            t.resizes.append(t.last_resize)
        t._m_dp.set(t.dp)
        return t
