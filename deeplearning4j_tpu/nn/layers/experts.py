"""What every dropless sparse expert layer here does once its router has
chosen: turn each token's ``k`` (gate, expert) pairs into one weight an
expert, count what routing did, and run the SwiGLU experts. The routers
differ (``layers/olmoe.py``: softmax, the k largest probabilities as they
are; ``layers/glm4_moe_lite.py``: sigmoid, a selection bias, the chosen
gates renormalised and scaled) and stay in their blocks; everything from the
``(gate, idx)`` pairs on is here, so that a change to how experts stream is
judged on every configuration that has experts.

Shapes are static: every expert runs on every row and the rows an expert
was not chosen for are weighted 0, which makes the down projection ONE
matmul contracting over (expert, width). At serving shapes (32-64 rows) the
layer is bound by streaming the experts' weights, which this reads once;
PERF.md section 6 (PR 25) has the measurement against the sorted
``ragged_dot`` form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what a block's ``decode`` reports under "routing": three int32 sums over
# the rows marked live, in this order (the batcher's
# serve_moe_<field>_total counters)
ROUTING_FIELDS = {
    "assignments": "token-expert pairs of real tokens",
    "experts_touched": "experts with at least one real token, summed over "
                       "layers",
    "max_load": "rows of the fullest expert, summed over layers",
}
# a layer that HOLDS a share of its experts (``held``: the others live on
# other chips) counts the three sums above over the held experts only, and
# reports a fourth
ELSEWHERE_FIELDS = {
    "assignments_elsewhere": "token-expert pairs of real tokens whose expert "
                             "this layer does not hold",
}


def wide_einsum(spec: str, a, w):
    """``einsum(spec, a, w)`` accumulated in f32. Where the activations
    ``a`` are wider than what they multiply (an f32 stream against weights,
    or a cache, held in bf16) the product is exact in the wider type: a
    TPU's default matmul would first round ``a`` to bf16. Operands of one
    dtype multiply as they always did."""
    precision = jax.lax.Precision.HIGHEST if a.dtype != w.dtype else None
    return jnp.einsum(spec, a, w, precision=precision,
                      preferred_element_type=jnp.float32)


def assign(gate, idx, num_experts: int, live, rows_shape, held=None):
    """``gate``/``idx`` (N, k): each token's gates and the experts they
    belong to. Returns the weight of every expert for every token (N, E),
    0 where it was not chosen, and what routing did to the rows ``live``
    marks (broadcastable to ``rows_shape``, the token axes before they were
    flattened to N; None: nobody asked) as ROUTING_FIELDS' three sums.

    ``held = (first, count)``: the layer holds experts ``first .. first +
    count - 1`` of the ``num_experts`` the router chose among. The weights
    come back (N, count), a pair whose expert lives elsewhere weighs
    nothing here, the three sums count the held experts, and a fourth
    (ELSEWHERE_FIELDS) the pairs that left."""
    if held is None:
        chosen = jax.nn.one_hot(idx, num_experts, dtype=jnp.int32)  # (N, k, E)
    else:
        first, count = held
        # an index outside 0..count-1 is a row of zeros
        chosen = jax.nn.one_hot(idx - first, count, dtype=jnp.int32)
    # sums, not matmuls: a TPU's default matmul would round the gates to
    # bf16 on the way
    weight = jnp.sum(gate[:, :, None] * chosen, axis=1)          # (N, E)
    routing = None
    if live is not None:
        rows = jnp.broadcast_to(live, rows_shape).reshape(-1)
        load = jnp.sum(chosen * rows[:, None, None], axis=(0, 1))
        sums = [jnp.sum(load), jnp.sum(load > 0), jnp.max(load)]
        if held is not None:
            sums.append(jnp.sum(rows) * idx.shape[1] - jnp.sum(load))
        routing = jnp.stack(sums).astype(jnp.int32)
    return weight, routing


def swiglu_experts(h, w_gate, w_up, w_down, weight):
    """``sum_e weight[n, e] * (silu(h Wg_e) * (h Wu_e)) Wd_e`` for rows
    ``h`` (N, d), experts' weights (E, d, f), (E, d, f), (E, f, d) and
    ``weight`` (N, E); in ``h``'s dtype."""
    g = wide_einsum("nd,edf->nef", h, w_gate)
    u = wide_einsum("nd,edf->nef", h, w_up)
    a = (jax.nn.silu(g) * u * weight[:, :, None]).astype(h.dtype)
    return wide_einsum("nef,efd->nd", a, w_down).astype(h.dtype)
