"""From a configuration file to the program's model, with seeded weights made
on the device in one jitted call, and to the configuration's plain
reference (``benchmark/references/<reference>.py``)."""

from __future__ import annotations

import importlib.util
import os

from . import env


def build(config: dict):
    """``config["build"]``: the zoo class of the program, its keyword
    arguments, and the compute dtype the cell serves or trains in."""
    from deeplearning4j_tpu import models

    spec = config["build"]
    kwargs = dict(spec["kwargs"])
    kwargs["input_shape"] = tuple(kwargs["input_shape"])
    model = getattr(models, spec["zoo"])(seed=0, **kwargs).build()
    model.config.compute_dtype = spec.get("compute_dtype")
    return model


def mesh_for(config: dict):
    """The mesh of ``config["layout"]["mesh"]`` and the program's sharding
    rules it names, or (None, None) for a one-chip configuration."""
    layout = config.get("layout")
    if not layout:
        return None, None
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel import sharding
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    n = int(np.prod(list(layout["mesh"].values())))
    return (make_mesh(dict(layout["mesh"]), jax.devices()[:n]),
            getattr(sharding, layout["rules"]))


def init_weights(model, seed: int, mesh=None, rules=None):
    """Seeded weights in the dtype the program keeps them in, made where
    they will live: one jitted call, sharded by the rules over a mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from deeplearning4j_tpu.parallel.sharding import sharding_tree

        shapes, state = jax.eval_shape(model.init, jnp.uint32(0))
        # the rules read a leaf's shape through numpy: a zero-stride view
        like = jax.tree.map(
            lambda a: np.broadcast_to(np.zeros((), a.dtype), a.shape), shapes)
        out_shardings = (
            sharding_tree(like, mesh, rules),
            jax.tree.map(lambda _: NamedSharding(mesh, PartitionSpec()), state))
    params, state = jax.jit(model.init, out_shardings=out_shardings)(
        jnp.uint32(seed))
    model.params, model.state = params, state   # init() left tracers there
    return params, state


def reference(config: dict):
    """The configuration's plain reference module."""
    path = os.path.join(env.BENCH_DIR, "references",
                        config["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
