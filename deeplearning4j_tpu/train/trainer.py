"""Trainer — the L4 training loop (Solver/ConvexOptimizer/fit equivalents).

Reference call stack (SURVEY.md §3.1): MultiLayerNetwork.fit ->
Solver.optimize -> StochasticGradientDescent -> computeGradientAndScore ->
updater -> step. The TPU redesign collapses that stack into ONE jit-compiled
pure function::

    (params, opt_state, net_state, batch, rng) -> (params', opt_state', net_state', loss)

with buffer donation on (params, opt_state, net_state) — the functional
equivalent of DL4J's in-place flattened-param update (MultiLayerNetwork
flattenedParams :114) without the mutable aliasing. XLA compiles the entire
network + optimizer into a single fused program per batch shape; there is no
per-op dispatch (the reference's main perf weakness, SURVEY.md §3.1 note).

Per-layer updater overrides and Frozen layers map to optax.multi_transform
over a layer-name label tree (parity: per-layer IUpdater configs and
FrozenLayer's no-op updater).

tBPTT (BackpropType.TruncatedBPTT, MultiLayerNetwork.java:1309): sequences are
split into fixed chunks; RNN carries thread between chunk steps, gradients
stop at chunk boundaries — same semantics, expressed with explicit carries.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..nn.layers.special import Frozen
from ..nn.model import Graph, NetConfig, Sequential, _layer_key
from ..ops import updaters as upd
from .listeners import PerformanceListener, TrainingListener


def accum_supported(model, mask, label_mask) -> bool:
    """Whether ``grad_accum``'s microbatch accumulation is EXACT for this
    batch. Callers (Trainer, ParallelWrapper, MultiHostTrainer) run the
    plain step when False — one rule, three dispatch sites.

    - unmasked batches: always (equal masses reduce to the plain mean)
    - masked Sequential: yes via mass-weighted recombination
      (``score(with_mass=True)`` — one effective loss mask) — UNLESS the
      model carries aux losses (MoE load balancing): those are per-token
      over ALL positions and must not inherit the label-mask mass weighting
    - masked Graph: no (per-output label_masks would need per-output masses)
    """
    if mask is None and label_mask is None:
        return True
    if not isinstance(model, Sequential):
        return False
    return not any(getattr(l, "aux_loss_weight", None) is not None
                   for l in model.layers)


def _mesh_ctx(mesh):
    """Trace context for a mesh (activation constraints + ambient mesh for
    ring attention) or a no-op when mesh is None."""
    if mesh is None:
        import contextlib

        return contextlib.nullcontext
    from ..parallel.sharding import activation_sharding

    return lambda: activation_sharding(mesh)


def make_score_fn(model, mesh=None):
    """One jitted ``(params, state, x, y, mask) -> mean loss`` for a model —
    shared by Trainer / ParallelWrapper / MultiHostTrainer scoring paths so
    the Sequential-vs-Graph mask kwarg mapping lives in exactly one place.
    ``mesh``: trace under the mesh so mesh-aware layers (ring attention)
    keep their sharded path at scoring time too."""
    seq = isinstance(model, Sequential)
    ctx = _mesh_ctx(mesh)

    @jax.jit
    def score(params, state, x, y, mask=None, label_mask=None):
        kw = ({"mask": mask, "label_mask": label_mask} if seq
              else {"masks": mask, "label_masks": label_mask})
        with ctx():
            l, _ = model.score(params, state, x, y, training=False, **kw)
        return l

    return score


def make_infer_fn(model, mesh=None, out_sharding=None):
    """One jitted ``(params, state, x, mask) -> primary output`` forward for
    a model (Sequential or Graph, masks threaded either way) — shared by the
    evaluate paths of Trainer / ParallelWrapper / MultiHostTrainer. ``mesh``:
    see make_score_fn — without it a ring=True model would silently fall
    back to dense O(T^2) attention during evaluation. ``out_sharding`` pins
    the output placement (the global-mesh evaluate path pins predictions
    dp-sharded so every process can read back exactly its own rows)."""
    seq = isinstance(model, Sequential)
    ctx = _mesh_ctx(mesh)

    @partial(jax.jit, **({"out_shardings": out_sharding}
                         if out_sharding is not None else {}))
    def infer(params, state, x, mask=None):
        with ctx():
            if seq:
                y, _ = model.forward(params, state, x, training=False, mask=mask)
                return y
            ys, _ = model.forward(params, state, x, training=False, masks=mask)
            return ys[0]

    return infer


def model_output_width(model) -> int:
    """Width of the model's primary output (Sequential or Graph)."""
    return (model.output_shape[-1] if isinstance(model, Sequential)
            else model.output_shapes[0][-1])


def unpack_batch(model, ds):
    """(x, y, feature_mask, label_mask) from a DataSet OR a MultiDataSet
    (ComputationGraph.fit(MultiDataSetIterator) parity, SURVEY §3.2):
    MultiDataSet features map onto the Graph's named inputs by position,
    labels/label-masks stay positional lists matching ``outputs``."""
    from ..data.iterators import MultiDataSet

    if isinstance(ds, MultiDataSet):
        if not isinstance(model, Graph):
            raise TypeError("MultiDataSet batches require a Graph model")
        names = model.inputs
        if len(ds.features) != len(names):
            raise ValueError(f"MultiDataSet has {len(ds.features)} feature "
                             f"arrays; Graph expects inputs {names}")
        if ds.features_masks is not None and \
                len(ds.features_masks) != len(names):
            raise ValueError(f"MultiDataSet has {len(ds.features_masks)} "
                             f"feature masks; Graph expects inputs {names}")
        outs = model.outputs
        if len(ds.labels) != len(outs):
            raise ValueError(f"MultiDataSet has {len(ds.labels)} label "
                             f"arrays; Graph expects outputs {outs}")
        if ds.labels_masks is not None and len(ds.labels_masks) != len(outs):
            raise ValueError(f"MultiDataSet has {len(ds.labels_masks)} "
                             f"label masks; Graph expects outputs {outs}")
        if getattr(model.config, "tbptt_length", 0):
            raise ValueError(
                "tbptt_length is set but tBPTT is not supported for "
                "MultiDataSet/Graph fit — train full-BPTT "
                "(tbptt_length=0) or use a Sequential model")
        x = dict(zip(names, ds.features))
        y = list(ds.labels)
        fm = (dict(zip(names, ds.features_masks))
              if ds.features_masks is not None else None)
        lm = list(ds.labels_masks) if ds.labels_masks is not None else None
        return x, y, fm, lm
    return ds.features, ds.labels, ds.features_mask, ds.labels_mask


def evaluate_model(model, params, state, iterator, evaluation=None, *,
                   infer_fn=None, mesh=None):
    """Streaming evaluation over an iterator — the shared engine behind
    ``Trainer.evaluate`` and the Trainer-free ``net.evaluate`` sugar
    (no optimizer state is touched or allocated)."""
    if evaluation is None:
        evaluation = default_evaluation(model)
    infer = infer_fn if infer_fn is not None else make_infer_fn(model, mesh)
    for ds in iterator:
        x, y, fm, lm = unpack_batch(model, ds)
        preds = infer(params, state, x, fm)
        # multi-output graphs: evaluate the PRIMARY output (reference
        # SparkComputationGraph evaluation convention)
        if isinstance(y, list):
            y = y[0]
            lm = lm[0] if lm else None
        evaluation.eval(y, np.asarray(preds), mask=lm)
    if hasattr(iterator, "reset"):
        iterator.reset()
    return evaluation


def score_model(model, params, state, iterator, *, score_fn=None, mesh=None) -> float:
    """Average loss over an iterator (model.score(DataSetIterator) parity) —
    shared engine behind ``Trainer.score_iterator`` and the Trainer-free
    ``net.score_iterator`` sugar."""
    score = score_fn if score_fn is not None else make_score_fn(model, mesh)
    total, n = 0.0, 0
    for ds in iterator:
        x, y, fm, lm = unpack_batch(model, ds)
        total += float(score(params, state, x, y, fm, lm))
        n += 1
    if hasattr(iterator, "reset"):
        iterator.reset()
    return total / max(n, 1)


def default_evaluation(model):
    """Multiclass Evaluation sized to the model's primary output."""
    from ..eval import Evaluation

    return Evaluation(model_output_width(model))


def check_not_donated(tree, who: str = "Trainer"):
    """Raise a clear error when a params/state pytree holds buffers a previous
    donating train step already consumed (``donate_argnums``) — otherwise the
    failure surfaces as an opaque 'Array has been deleted' deep inside the
    next jit call (SURVEY.md §5 donation/aliasing asserts)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "is_deleted", lambda: False)():
            raise ValueError(
                f"{who}: the model holds donated (deleted) buffers — a "
                f"previous jitted train step consumed them via buffer "
                f"donation. Re-initialize (model.init()) or keep using the "
                f"trainer that owns the live params/state.")


def build_updater(model) -> optax.GradientTransformation:
    """Build the optax pipeline from NetConfig + per-layer overrides."""
    cfg: NetConfig = model.config

    def base_tx(updater_cfg):
        return upd.build(updater_cfg,
                         gradient_normalization=cfg.gradient_normalization,
                         gradient_normalization_threshold=cfg.gradient_normalization_threshold,
                         l1=cfg.l1, l2=cfg.l2)

    # collect per-layer overrides / frozen layers
    overrides: Dict[str, Any] = {}
    if isinstance(model, Sequential):
        named = [(_layer_key(i, l), l) for i, l in enumerate(model.layers)]
    else:
        named = [(n, model.nodes[n].spec) for n in model.topo_order if model.nodes[n].is_layer()]
    for name, layer in named:
        if isinstance(layer, Frozen):
            overrides[name] = "noop"
        elif getattr(layer, "updater", None) is not None:
            overrides[name] = layer.updater

    if not overrides:
        return base_tx(cfg.updater)

    transforms = {"__default__": base_tx(cfg.updater)}
    labels_by_name = {}
    for name, ov in overrides.items():
        if ov == "noop":
            transforms.setdefault("noop", optax.set_to_zero())
            labels_by_name[name] = "noop"
        else:
            lbl = f"override_{name}"
            transforms[lbl] = base_tx(ov)
            labels_by_name[name] = lbl

    def label_fn(params):
        return {k: jax.tree.map(lambda _: labels_by_name.get(k, "__default__"), v)
                for k, v in params.items()}

    return optax.multi_transform(transforms, label_fn)


class Trainer:
    """Owns (params, state, opt_state) and the jitted step — Solver parity.

    The one sharding API (SURVEY §7): pass ``mesh=`` (a jax.sharding.Mesh
    with any of the data/model/seq axes) and optionally ``rules=`` (path
    regex -> PartitionSpec, e.g. ``parallel.sharding.TRANSFORMER_RULES`` /
    ``DENSE_RULES`` / ``CNN_RULES``) and ANY Sequential/Graph trains
    dp x tp x sp: params are placed per rules, batches are dp(+sp)-sharded,
    activations carry with_sharding_constraints between layers, and GSPMD
    inserts the collectives. No rules = pure data parallelism. Replaces the
    reference's single-device-params restriction (SURVEY §2.4.5) rather than
    porting it."""

    def __init__(self, model, updater: Optional[optax.GradientTransformation] = None,
                 seed: int = 0, mesh=None, rules=None, grad_accum: int = 1):
        self.model = model
        # grad_accum=N: each fit batch is split into N sequential microbatches
        # inside ONE jitted step (lax.scan); grads are averaged and the
        # updater runs once. Activation memory scales with the microbatch,
        # optimizer HBM traffic (read m,v,params + write back — the dominant
        # per-step cost for 100M+ param models) is paid once per N
        # microbatches. Loss/grad semantics: microbatches recombine weighted
        # by their loss-reduction mass (ops.losses.reduction_mass), so the
        # result is EXACT vs the single big-batch masked mean even when mask
        # coverage varies across microbatches; Graph models with masks fall
        # back to the plain step (per-output masses not implemented).
        self.grad_accum = max(1, int(grad_accum))
        self.tx = updater if updater is not None else build_updater(model)
        if model.params is None:
            model.init()
        check_not_donated((model.params, model.state), "Trainer")
        self.mesh = mesh
        self.rules = tuple(rules) if rules is not None else ()
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.sharding import place_params

            self.params = place_params(model.params, mesh, self.rules)
            self.state = jax.device_put(model.state, NamedSharding(mesh, P()))
        else:
            self.params = model.params
            self.state = model.state
        # eager init on placed params: zeros_like/ones_like follow their
        # input's sharding, so adam moments land sharded like their params
        # (a jitted init would NOT propagate — constants get fresh layouts);
        # leaves with no param dependence (adam's step count) come out
        # single-device — re-place those replicated over the mesh
        self.opt_state = self.tx.init(self.params)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            self.opt_state = jax.tree.map(
                lambda a: a if getattr(getattr(a, "sharding", None), "mesh",
                                       None) == mesh
                else jax.device_put(a, repl), self.opt_state)
        self.iteration = 0
        self.epoch = 0
        self._rng = jax.random.PRNGKey(seed)
        self._step_fn = None
        self._multi_step_fn = None
        self._accum_step_fn = None
        self._tbptt_step_fn = None
        self._infer_fn = None

    def _place_batch(self, *arrays):
        """dp(+sp)-shard batch arrays when training over a mesh. Each element
        may be an array or a (Graph multi-input) dict/list of arrays."""
        if self.mesh is None:
            return arrays
        from ..parallel.sharding import batch_sharding

        def put(leaf):
            # keep device arrays on device (AsyncIterator may have
            # device_put them already — device_put reshards D2D, so no
            # blocking host roundtrip); only host data goes through numpy
            a = (leaf if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
                 else np.asarray(leaf))
            return jax.device_put(a, batch_sharding(self.mesh, a))

        return tuple(None if a is None else jax.tree.map(put, a)
                     for a in arrays)

    def _mesh_jit_setup(self, n_unpinned_outputs: int):
        """(act_ctx, jit kwargs) for a mesh-aware jitted step: the activation
        constraint context plus out_shardings pinning params/opt_state to
        their placed shardings — without the pin GSPMD may hand params back
        re-laid-out, drifting from the rules and forcing a retrace on the
        next step. ``n_unpinned_outputs`` outputs between opt_state and the
        loss stay unspecified (net_state — layers may add keys on the first
        training step — and tBPTT carries)."""
        if self.mesh is None:
            return _mesh_ctx(None), {}
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import collective_overlap_options

        jit_kw = {"out_shardings": (
            jax.tree.map(lambda a: a.sharding, self.params),
            jax.tree.map(lambda a: a.sharding, self.opt_state),
            *([None] * n_unpinned_outputs), NamedSharding(self.mesh, P()))}
        options = collective_overlap_options(self.mesh)
        if options:
            jit_kw["compiler_options"] = options
        return _mesh_ctx(self.mesh), jit_kw

    # --- the jitted train step ---
    def _step_math(self, act_ctx):
        """The one train-step body shared by :meth:`_make_step` and the
        ``steps_per_execution`` scan (:meth:`_make_multi_step`) — any change
        to step semantics lands in both paths by construction."""
        tx, model = self.tx, self.model
        seq = isinstance(model, Sequential)

        def one_step(params, opt_state, net_state, x, y, rng, mask, label_mask):
            if seq:
                mask_kw = {"mask": mask, "label_mask": label_mask}
            else:  # Graph: per-input mask dict / per-output label masks
                mask_kw = {"masks": mask, "label_masks": label_mask}

            def loss_fn(p):
                # the context wraps the TRACE: every layer output gets a
                # dp(+sp) sharding constraint when training over a mesh
                with act_ctx():
                    loss, new_state = model.score(p, net_state, x, y, training=True,
                                                  rng=rng, **mask_kw)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, new_state, loss

        return one_step

    def _make_step(self):
        act_ctx, jit_kw = self._mesh_jit_setup(n_unpinned_outputs=1)
        one_step = self._step_math(act_ctx)

        @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kw)
        def step(params, opt_state, net_state, x, y, rng, mask=None, label_mask=None):
            return one_step(params, opt_state, net_state, x, y, rng, mask, label_mask)

        return step

    def _make_accum_step(self):
        """One optimizer update from ``grad_accum`` sequential microbatches,
        compiled as a single program: ``lax.scan`` accumulates grads (and
        net_state carries through, so BN stats/dropout streams see every
        microbatch), then the updater applies the mean gradient ONCE.
        Inputs carry a leading (n_micro,) axis. Over a mesh, the shared
        strided program (parallel/sharding.make_mesh_accum_step) is used
        instead — it regroups the flat dp-sharded batch in-jit so no rows
        move between devices (an eager contiguous reshape would gather
        microbatch 0's rows from only dp/N of the devices every step)."""
        tx = self.tx
        n_micro = self.grad_accum
        act_ctx, jit_kw = self._mesh_jit_setup(n_unpinned_outputs=1)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.sharding import make_mesh_accum_step

            return make_mesh_accum_step(
                self.model, tx, self.mesh, n_micro, act_ctx,
                jax.tree.map(lambda a: a.sharding, self.params),
                jax.tree.map(lambda a: a.sharding, self.opt_state),
                NamedSharding(self.mesh, P()))
        model = self.model
        seq = isinstance(model, Sequential)

        @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kw)
        def step(params, opt_state, net_state, xs, ys, rngs, fms, lms):
            def one(carry, mb):
                g_acc, loss_acc, w_acc, net_state = carry
                x, y, rng, fm, lm = mb

                def loss_fn(p):
                    # mass-weighted recombination: each microbatch's
                    # masked-mean loss/grads weigh in by the reduction mass
                    # of the mask the loss ACTUALLY consumed (score's
                    # with_mass aux), so the combined result equals the
                    # single-step masked mean even when mask coverage varies
                    # across microbatches (padded RNN batches). Unmasked
                    # microbatches get equal masses — same as the plain
                    # mean. Graph models with masks never reach here
                    # (dispatch falls back — per-output mask masses).
                    with act_ctx():
                        if seq:
                            loss, ns, w = model.score(
                                p, net_state, x, y, training=True, rng=rng,
                                mask=fm, label_mask=lm, with_mass=True)
                        else:
                            loss, ns = model.score(
                                p, net_state, x, y, training=True, rng=rng,
                                masks=fm, label_masks=lm)
                            w = jnp.asarray(1.0, jnp.float32)
                    return loss * w, (ns, w)

                ((wloss, (ns, w)), g) = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                return (jax.tree.map(jnp.add, g_acc, g),
                        loss_acc + wloss, w_acc + w, ns), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (g, loss_sum, w_sum, net_state), _ = jax.lax.scan(
                one, (zeros, jnp.asarray(0.0, jnp.float32),
                      jnp.asarray(0.0, jnp.float32), net_state),
                (xs, ys, rngs, fms, lms))
            # clamp like losses._reduce: an all-masked batch yields 0, not NaN
            w_sum = jnp.maximum(w_sum, 1.0)
            g = jax.tree.map(lambda a: a / w_sum, g)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(g, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, net_state, loss_sum / w_sum

        return step

    def _make_multi_step(self):
        """K train steps as ONE compiled program: ``lax.scan`` over K stacked
        minibatches (the ``steps_per_execution`` fast path of :meth:`fit`).

        TPU-idiomatic replacement for per-iteration host dispatch: small
        models (LeNet-class, char-RNN) run in ~1-3 ms/step, where the
        host->device dispatch round-trip dominates the wall clock — one
        compiled K-step program amortizes that to 1/K. The reference has no
        equivalent (its per-op JNI dispatch makes every iteration host-driven,
        SURVEY §3.1); semantics match K sequential calls of the single step
        exactly (same step math by construction — :meth:`_step_math` — and
        same per-step rng stream), and listeners still observe every
        iteration in order."""
        act_ctx, jit_kw = self._mesh_jit_setup(n_unpinned_outputs=1)
        one_step = self._step_math(act_ctx)

        @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kw)
        def multi_step(params, opt_state, net_state, xs, ys, rngs, fms, lms):
            def one(carry, batch):
                x, y, rng, fm, lm = batch
                params, opt_state, net_state, loss = one_step(
                    *carry, x, y, rng, fm, lm)
                return (params, opt_state, net_state), loss

            (params, opt_state, net_state), losses = jax.lax.scan(
                one, (params, opt_state, net_state), (xs, ys, rngs, fms, lms))
            return params, opt_state, net_state, losses

        return multi_step

    def _make_tbptt_step(self):
        tx, model = self.tx, self.model
        assert isinstance(model, Sequential), "tBPTT fit targets Sequential RNNs"
        act_ctx, jit_kw = self._mesh_jit_setup(n_unpinned_outputs=2)

        @partial(jax.jit, donate_argnums=(0, 1, 2), **jit_kw)
        def step(params, opt_state, net_state, x, y, rng, carries, mask=None,
                 label_mask=None):
            """One tBPTT chunk: grads flow within the chunk; carries are
            stop-gradient at the boundary (DL4J doTruncatedBPTT parity)."""
            carries = jax.lax.stop_gradient(carries)

            def loss_fn(p):
                with act_ctx():
                    loss, new_state, new_carries = model.score_with_carry(
                        p, net_state, x, y, carries, training=True, rng=rng,
                        mask=mask, label_mask=label_mask)
                return loss, (new_state, new_carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, new_state, new_carries, loss

        return step

    def next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _unpack_batch(self, ds):
        return unpack_batch(self.model, ds)

    # --- fit (MultiLayerNetwork.fit :1262 / ComputationGraph.fit :1010) ---
    def fit(self, iterator, epochs: int = 1, listeners: Sequence[TrainingListener] = (),
            prefetch: bool = True, steps_per_execution: int = 1,
            telemetry=None) -> "Trainer":
        """Streaming hot loop: the loss readback for iteration k happens only
        AFTER iteration k+1 has been dispatched, so the device never idles
        waiting on the host (the reference keeps the device busy with its
        async prefetch thread, MultiLayerNetwork.java:1266-1268; a per-step
        ``float(loss)`` here would serialize dispatch with compute). Every
        iteration is still reported to listeners exactly once, in order —
        just one step late; epoch end flushes.

        ``steps_per_execution=K`` (K>1) compiles K train steps into ONE
        device program (:meth:`_make_multi_step`): minibatches are buffered
        K at a time, stacked on the host, and scanned on device — same math,
        same rng stream, every iteration still reported in order. Use it for
        small/fast models where per-step dispatch dominates (LeNet-class
        models run ~1-3 ms/step; one K-step program pays the dispatch cost
        once). Ignored for tBPTT fits, mesh-sharded trainers (their batches
        are placed per-minibatch), when any listener ``requires_sync``
        (e.g. divergence rollback — it must validate each iteration before
        the next runs), and when any listener ``snapshots_state``
        (checkpoint/evaluative — under a megastep iteration i would observe
        params up to K steps ahead); ragged tail batches fall back to the
        single step.

        ``telemetry``: an ``obs.StepTelemetry``-shaped object (duck-typed —
        this module never imports obs, so the default path stays obs-free by
        construction). When omitted, the first listener exposing a
        ``.telemetry`` attribute (``obs.TelemetryListener``) is adopted.
        Active telemetry times data-wait/dispatch/device-compute per step
        (fencing each step) and disables the megastep — K steps compiled
        into one program have no per-iteration boundaries to time."""
        from ..data.iterators import AsyncIterator
        from .listeners import DeferredScoreReporter

        if self._step_fn is None:
            self._step_fn = self._make_step()
        tbptt = getattr(self.model.config, "tbptt_length", 0)
        reporter = DeferredScoreReporter(self, listeners)
        tel = telemetry
        if tel is None:
            for lst in listeners:
                tel = getattr(lst, "telemetry", None)
                if tel is not None:
                    break
        spe = max(1, int(steps_per_execution))
        # requires_sync listeners (e.g. DivergenceListener rollback) need
        # every iteration validated before the next mutates trainer state —
        # a K-step program would run K steps past the first bad one.
        # snapshots_state listeners (checkpoint/evaluative) read trainer
        # params in iteration_done; under a megastep iteration i would see
        # params up to K steps ahead, so they too force the single step.
        # Telemetry also forces the single step: per-iteration phase timing
        # has nothing to clock inside one fused K-step program.
        use_mega = (spe > 1 and not tbptt and self.mesh is None
                    and self.grad_accum == 1 and tel is None
                    and not any(getattr(l, "requires_sync", False)
                                or getattr(l, "snapshots_state", False)
                                for l in listeners))
        buf: List[tuple] = []

        for epoch in range(epochs):
            self.epoch = epoch
            if tel is not None:
                tel.tracer.instant("epoch_start", epoch=epoch)
            for lst in listeners:
                lst.on_epoch_start(self, epoch)
            it = AsyncIterator(iterator) if prefetch else iterator
            if tel is not None:
                it = tel.wrap_iterator(it)
            for ds in it:
                bs = ds.num_examples
                xb, yb, fmb, lmb = self._unpack_batch(ds)
                if use_mega and self.iteration > 0:
                    # iteration 0 always runs the single step first: layers
                    # may add net_state keys on their first training step,
                    # and the scan carry needs a settled state structure
                    buf.append((xb, yb, fmb, lmb, bs))
                    if len(buf) == spe:
                        self._exec_megastep(buf, reporter, epoch, listeners)
                        buf.clear()
                    continue
                for lst in listeners:
                    if isinstance(lst, PerformanceListener):
                        lst.step_begin(bs)
                if self._step_fn is None:  # invalidated mid-fit (e.g. a
                    self._step_fn = self._make_step()  # rollback listener)
                xb_ndim = (getattr(xb, "ndim", None)  # no D2H just for rank
                           if not isinstance(xb, dict) else 0)
                if xb_ndim is None:
                    xb_ndim = np.asarray(xb).ndim
                if tbptt and xb_ndim >= 3:
                    if tel is not None:
                        loss = tel.step(
                            lambda: self._fit_tbptt_batch(ds, tbptt),
                            sig=self._batch_sig((xb, yb, fmb, lmb)),
                            batch_size=bs, kind="tbptt")
                    else:
                        loss = self._fit_tbptt_batch(ds, tbptt)
                elif tel is not None:
                    loss = tel.step(
                        lambda: self._dispatch_train_step(xb, yb, fmb, lmb),
                        sig=self._batch_sig((xb, yb, fmb, lmb)),
                        batch_size=bs)
                else:
                    loss = self._dispatch_train_step(xb, yb, fmb, lmb)
                reporter.report(self.iteration, epoch, loss)
                self.iteration += 1
            if buf:  # ragged tail: fewer than K buffered at epoch end
                self._exec_singles(buf, reporter, epoch, listeners)
                buf.clear()
            reporter.flush()
            if hasattr(iterator, "reset"):
                iterator.reset()
            for lst in listeners:
                lst.on_epoch_end(self, epoch)
        self.model.params, self.model.state = self.params, self.state
        return self

    def _dispatch_train_step(self, xb, yb, fmb, lmb):
        """Place one batch and run it through the plain step or, when
        ``grad_accum=N`` and the batch divides evenly, the microbatch-scan
        accumulation step (one optimizer update per batch either way).
        Returns the device loss scalar."""
        x, y, fm, lm = self._place_batch(xb, yb, fmb, lmb)
        if self.grad_accum > 1 and accum_supported(self.model, fm, lm):
            n = self.grad_accum
            first = next(iter(x.values())) if isinstance(x, dict) else x
            bs = int(first.shape[0])
            if self.mesh is not None:
                from ..parallel.mesh import DATA_AXIS

                dp = self.mesh.shape.get(DATA_AXIS, 1)
                if (bs // max(dp, 1)) % n == 0:
                    # shared strided program: flat batch, (n, 2) rng keys
                    if self._accum_step_fn is None:
                        self._accum_step_fn = self._make_accum_step()
                    rngs = jnp.stack([self.next_rng() for _ in range(n)])
                    (self.params, self.opt_state, self.state,
                     loss) = self._accum_step_fn(
                        self.params, self.opt_state, self.state,
                        x, y, rngs, fm, lm)
                    return loss
            elif bs % n == 0:
                def resh(t):
                    return None if t is None else jax.tree.map(
                        lambda a: a.reshape((n, bs // n) + a.shape[1:]), t)

                if self._accum_step_fn is None:
                    self._accum_step_fn = self._make_accum_step()
                rngs = jnp.stack([self.next_rng() for _ in range(n)])
                (self.params, self.opt_state, self.state,
                 loss) = self._accum_step_fn(
                    self.params, self.opt_state, self.state,
                    resh(x), resh(y), rngs, resh(fm), resh(lm))
                return loss
            # indivisible (ragged tail) batch: one plain step
        if self._step_fn is None:
            self._step_fn = self._make_step()
        self.params, self.opt_state, self.state, loss = self._step_fn(
            self.params, self.opt_state, self.state,
            x, y, self.next_rng(), fm, lm)
        return loss

    @staticmethod
    def _batch_sig(parts):
        """Structure+shape+dtype signature of an unpacked batch — megastep
        stacking requires every buffered batch to match exactly."""
        leaves, treedef = jax.tree_util.tree_flatten(parts)
        return (str(treedef),
                tuple((np.shape(l), str(getattr(l, "dtype", type(l))))
                      for l in leaves))

    def _exec_singles(self, buf, reporter, epoch, listeners):
        """Run buffered batches through the single-batch step path, in order."""
        for xb, yb, fmb, lmb, bs in buf:
            for lst in listeners:
                if isinstance(lst, PerformanceListener):
                    lst.step_begin(bs)
            loss = self._dispatch_train_step(xb, yb, fmb, lmb)
            reporter.report(self.iteration, epoch, loss)
            self.iteration += 1

    def _exec_megastep(self, buf, reporter, epoch, listeners):
        """Stack K buffered minibatches and run them as one compiled K-step
        program. Falls back to the single step when the batches don't agree
        on structure/shape (e.g. a ragged final batch or mask-presence
        change mid-epoch — stacking needs one common shape)."""
        if len({self._batch_sig(b[:4]) for b in buf}) > 1:
            self._exec_singles(buf, reporter, epoch, listeners)
            return
        if self._multi_step_fn is None:
            self._multi_step_fn = self._make_multi_step()
        # ONE step_begin with the window's total samples: K back-to-back
        # calls would zero the ETL metric for K-1 of every K iterations and
        # never bracket a real step (samples/sec over the window stays exact)
        for lst in listeners:
            if isinstance(lst, PerformanceListener):
                lst.step_begin(sum(b[-1] for b in buf))

        def stack(parts):
            if all(p is None for p in parts):
                return None

            def stack_leaves(*ls):
                # device arrays (AsyncIterator prefetch already H2D'd them)
                # stack on device — np.stack here would force a blocking
                # D2H round-trip of every batch
                if all(isinstance(l, jax.Array) for l in ls):
                    return jnp.stack(ls)
                return np.stack([np.asarray(l) for l in ls])

            return jax.tree.map(stack_leaves, *parts)

        xs, ys, fms, lms = (stack([b[i] for b in buf]) for i in range(4))
        rngs = jnp.stack([self.next_rng() for _ in buf])
        self.params, self.opt_state, self.state, losses = self._multi_step_fn(
            self.params, self.opt_state, self.state, xs, ys, rngs, fms, lms)
        for i in range(len(buf)):
            reporter.report(self.iteration, epoch, losses[i])
            self.iteration += 1

    def _fit_tbptt_batch(self, ds, chunk: int):
        """Per-batch tBPTT chunk loop. No host syncs inside: chunk losses
        accumulate on device and the mean comes back as one device scalar."""
        if self._tbptt_step_fn is None:
            self._tbptt_step_fn = self._make_tbptt_step()
        x = np.asarray(ds.features)
        y = np.asarray(ds.labels)
        fm = np.asarray(ds.features_mask) if ds.features_mask is not None else None
        lm = np.asarray(ds.labels_mask) if ds.labels_mask is not None else None
        B, T = x.shape[0], x.shape[1]
        carries = self.model.init_carries(B)
        loss = None
        n_chunks = 0
        for t0 in range(0, T, chunk):
            xc, yc = x[:, t0 : t0 + chunk], y[:, t0 : t0 + chunk]
            mc = fm[:, t0 : t0 + chunk] if fm is not None else None
            lmc = lm[:, t0 : t0 + chunk] if lm is not None else None
            if xc.shape[1] < chunk:  # ragged tail: pad + mask (static shapes for jit)
                pad = chunk - xc.shape[1]
                xc = np.pad(xc, [(0, 0), (0, pad)] + [(0, 0)] * (xc.ndim - 2))
                yc = np.pad(yc, [(0, 0), (0, pad)] + [(0, 0)] * (yc.ndim - 2))
                mc = np.pad(mc if mc is not None else np.ones((B, chunk - pad), np.float32),
                            [(0, 0), (0, pad)])
                if lmc is not None:
                    lmc = np.pad(lmc, [(0, 0), (0, pad)])
            xc, yc, mc, lmc = self._place_batch(xc, yc, mc, lmc)
            self.params, self.opt_state, self.state, carries, l = self._tbptt_step_fn(
                self.params, self.opt_state, self.state, xc, yc, self.next_rng(),
                carries, mc, lmc)
            loss = l if loss is None else loss + l
            n_chunks += 1
        return loss / max(n_chunks, 1)

    # --- pretraining (layerwise, AutoEncoder/VAE pretrain parity) ---
    def pretrain_layer(self, layer_index: int, iterator, epochs: int = 1,
                       learning_rate: float = 1e-2):
        """MultiLayerNetwork.pretrainLayer: unsupervised fit of one layer on the
        activations of the layers below it."""
        model = self.model
        assert isinstance(model, Sequential)
        layer = model.layers[layer_index]
        assert hasattr(layer, "pretrain_loss"), f"{type(layer).__name__} is not pretrainable"
        key = _layer_key(layer_index, layer)
        tx = optax.adam(learning_rate)
        lp = self.params[key]
        opt = tx.init(lp)

        @partial(jax.jit, donate_argnums=(0, 1))  # lp/opt are loop-carried
        def pstep(lp, opt, x, rng):
            def loss_fn(p):
                feats, _ = model.forward({**self.params, key: p}, self.state, x,
                                         training=False, up_to=layer_index)
                try:
                    return layer.pretrain_loss(p, feats, rng)
                except TypeError:
                    return layer.pretrain_loss(p, feats)

            loss, g = jax.value_and_grad(loss_fn)(lp)
            updates, opt = tx.update(g, opt, lp)
            return optax.apply_updates(lp, updates), opt, loss

        for _ in range(epochs):
            for ds in iterator:
                lp, opt, loss = pstep(lp, opt, ds.features, self.next_rng())
            if hasattr(iterator, "reset"):
                iterator.reset()
        self.params = {**self.params, key: lp}
        self.model.params = self.params
        return float(loss)

    # --- evaluation (streaming, Evaluation parity) ---
    def evaluate(self, iterator, evaluation=None):
        if self._infer_fn is None:
            self._infer_fn = make_infer_fn(self.model, self.mesh)
        return evaluate_model(self.model, self.params, self.state, iterator,
                              evaluation, infer_fn=self._infer_fn)

    def score_iterator(self, iterator) -> float:
        """Average loss over an iterator (model.score(DataSetIterator) parity)."""
        if getattr(self, "_score_fn", None) is None:  # cache: rebuilding the
            self._score_fn = make_score_fn(self.model, self.mesh)  # jit each
        return score_model(self.model, self.params, self.state, iterator,
                           score_fn=self._score_fn)  # call would recompile

    # --- checkpointing ---
    def save(self, path: str, normalizer=None):
        from .serialization import save_model

        save_model(path, self.model, params=self.params, state=self.state,
                   opt_state=self.opt_state, normalizer=normalizer)

    @classmethod
    def load(cls, path: str, seed: int = 0) -> "Trainer":
        from .serialization import load_model

        model, params, state, _, _ = load_model(path)
        t = cls(model, seed=seed)
        t.params, t.state = params, state
        # rebuild opt state with exact structure, then fill from file
        from .serialization import load_model as _lm

        _, _, _, opt_state, _ = _lm(path, opt_state_template=t.opt_state)
        if opt_state is not None:
            t.opt_state = opt_state
        model.params, model.state = params, state
        return t
