"""Orbax checkpoint bridge — sharded, multi-host-safe training checkpoints.

The zip format (``train/serialization.py`` — ModelSerializer.java parity)
gathers everything to one host: right for single-host models, wrong at
sharded scale. This bridge saves ``(params, opt_state, net_state)`` through
orbax (SURVEY.md §5 "orbax-style checkpoint with updater state"):

- sharded arrays are written per-shard by the process that owns them (no
  host gather, works under ``jax.distributed`` multi-host),
- restore places arrays back onto the SAME shardings as a live template
  (e.g. a freshly built trainer/wrapper), so a ``zero_sharded`` optimizer
  restores sharded,
- the model architecture travels as config JSON next to the arrays, so a
  checkpoint is self-describing like the zip format.

Retention/step management stays with ``CheckpointListener`` /
``orbax.CheckpointManager`` composition — this module is the (save, restore)
core.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional, Tuple

import jax


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def save_checkpoint(directory: str, model, *, params=None, state=None,
                    opt_state=None, extras=None) -> str:
    """Write a sharded checkpoint of (params, net_state, opt_state) plus the
    architecture JSON. ``directory`` must not already contain a checkpoint.
    Arrays are saved with their CURRENT shardings, per-process."""
    directory = os.path.abspath(directory)
    payload = {
        "params": params if params is not None else model.params,
        "net_state": state if state is not None else model.state,
        # always present so restore templates match; {} = "none saved"
        "opt_state": opt_state if opt_state is not None else {},
    }
    payload.update(extras or {})
    ckpt = _checkpointer()
    ckpt.save(os.path.join(directory, "arrays"), payload)
    ckpt.wait_until_finished()
    if jax.process_index() == 0:
        with open(os.path.join(directory, "model.json"), "w") as f:
            f.write(model.to_json())
    return directory


def restore_checkpoint(directory: str, template_payload) -> Any:
    """Restore arrays onto the structure AND shardings of
    ``template_payload`` (same dict layout save_checkpoint wrote: keys
    ``params``, ``net_state``, optionally ``opt_state``). Pass live arrays
    (e.g. a fresh trainer's pytrees) as the template — each leaf is restored
    with the template leaf's sharding."""
    directory = os.path.abspath(directory)
    ckpt = _checkpointer()
    return ckpt.restore(os.path.join(directory, "arrays"),
                        target=template_payload)


def load_model_json(directory: str):
    """Rebuild the architecture from the checkpoint's model.json."""
    from .serialization import model_from_json

    with open(os.path.join(os.path.abspath(directory), "model.json")) as f:
        return model_from_json(f.read())


def save_trainer(directory: str, trainer) -> str:
    """One-call save of a Trainer / ParallelWrapper / MultiHostTrainer.
    Includes the encoded_gradients error-feedback residual when the wrapper
    carries one, AND the trainer's rng stream + iteration counter — without
    them a crash-resume would replay already-consumed dropout keys and
    diverge from the uninterrupted run."""
    import numpy as np

    extras = {}
    residual = getattr(trainer, "residual", None)
    if residual is not None:
        extras["residual"] = residual
    if getattr(trainer, "_rng", None) is not None:
        extras["trainer_rng"] = np.asarray(trainer._rng)
        extras["iteration"] = np.asarray(getattr(trainer, "iteration", 0),
                                         np.int64)
    return save_checkpoint(directory, trainer.model, params=trainer.params,
                           state=trainer.state, opt_state=trainer.opt_state,
                           extras=extras)


def restore_trainer(directory: str, trainer):
    """Restore a previously saved trainer IN PLACE: the trainer provides the
    live (sharded) template; its params/state/opt_state (and the
    encoded-gradients residual, when present on both sides) are replaced by
    the checkpoint contents placed on the same shardings. The underlying
    model's params/state are synced too, so inference/serialization work
    immediately after restore. Returns the trainer."""
    import numpy as np

    template = {"params": trainer.params, "net_state": trainer.state,
                "opt_state": trainer.opt_state}
    residual = getattr(trainer, "residual", None)
    if residual is not None:
        template["residual"] = residual
    if getattr(trainer, "_rng", None) is not None:
        template["trainer_rng"] = np.asarray(trainer._rng)
        template["iteration"] = np.asarray(getattr(trainer, "iteration", 0),
                                           np.int64)
    # shape the template to what the checkpoint actually contains (a plain
    # save_checkpoint(dir, model) writes opt_state={} and no residual) so a
    # genuinely corrupt checkpoint or structure mismatch surfaces as ITS OWN
    # error rather than a second, unrelated-looking retry failure
    saved = _checkpointer().metadata(
        os.path.join(os.path.abspath(directory), "arrays")
    ).item_metadata.tree
    if saved.get("opt_state") == {}:
        template["opt_state"] = {}
    for opt_key in ("residual", "trainer_rng", "iteration"):
        if opt_key not in saved:
            template.pop(opt_key, None)
    restored = restore_checkpoint(directory, template)
    trainer.params = restored["params"]
    trainer.state = restored["net_state"]
    if restored.get("opt_state"):  # {} = checkpoint saved without opt state
        trainer.opt_state = restored["opt_state"]
    if residual is not None and restored.get("residual") is not None:
        trainer.residual = restored["residual"]
    if restored.get("trainer_rng") is not None:
        import jax.numpy as jnp

        trainer._rng = jnp.asarray(np.asarray(restored["trainer_rng"]))
        trainer.iteration = int(np.asarray(restored["iteration"]))
    trainer.model.params = trainer.params
    trainer.model.state = trainer.state
    return trainer
