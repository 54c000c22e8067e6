"""Flash attention — Pallas TPU kernel for the attention hot path.

The reference has no attention at all (DL4J 0.9 predates it; SURVEY.md §5);
this kernel serves the framework's transformer/long-context families, where
attention is the dominant non-matmul cost. Design per the Pallas TPU
playbook (/opt/skills/guides/pallas_guide.md):

- forward: ONE kernel, grid (B·H, T/bq, T/bk) with the key-block dimension
  innermost (sequential on TPU), streaming-softmax accumulators (m, l, acc)
  in VMEM scratch that persist across key blocks — O(T·block) memory, never
  a (T, T) score tensor in HBM
- scores accumulate in f32 regardless of input dtype (bf16-safe softmax,
  same contract as ``dot_product_attention``)
- backward: custom_vjp with the standard flash recomputation — the forward
  saves only (o, logsumexp); gradients are rebuilt q-block-by-q-block in a
  ``lax.scan`` (pure JAX: XLA already fuses the per-block matmul chain well,
  and the scan bounds memory the same way the kernel does)
- ``interpret=True`` automatically on the CPU backend, so the same code path
  is testable on the CPU mesh (pl.pallas_call interpreter mode); any other
  non-TPU backend raises

Causal masking and right-padded sequences (T not a multiple of the block)
are handled with compile-time index masks.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, lens_ref, kmask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                bq: int, bk: int, t_actual: int, has_lens: bool,
                has_kmask: bool, window: int = 0):
    """Mosaic-friendly layout notes: the (m, l) running stats live in
    (bq, 128) lane-replicated VMEM scratch (TPU vectors are (8, 128) tiles —
    1-D per-row scalars don't lower); lse is written as a (bq, 1) column so
    the HBM output can be (BH, T, 1) with a legal (1, bq, 1) block.

    ``has_lens`` (static): per-example ragged lengths — keys at positions
    >= lens_ref's value are masked out (right-padded batches). The
    interior-block specialization stays: blocks fully inside the length
    run unmasked under a runtime predicate; blocks fully beyond it are
    skipped at runtime.

    ``has_kmask`` (static): exact arbitrary (B, T) key mask — every block
    takes the masked path (no contiguity to exploit), and p is masked
    directly (an all-masked block must contribute nothing, which the
    s=NEG_INF trick alone does not guarantee: exp(NEG_INF - NEG_INF)=1)."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    L = lens_ref[0, 0, 0] if has_lens else t_actual

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate(masked: bool):
        q = q_ref[0].astype(jnp.float32)         # (bq, D)
        k = k_ref[0].astype(jnp.float32)         # (bk, D)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

        if masked:
            q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = k_pos < t_actual             # right-padding mask
            if has_lens:
                valid = valid & (k_pos < L)      # ragged example length
            if has_kmask:
                valid = valid & (kmask_ref[0] != 0)      # (1, bk) row
            if causal:
                valid = valid & (k_pos <= q_pos)
            if window:  # sliding window: q attends [q-window+1, q]
                valid = valid & (q_pos - k_pos < window)
            s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                      # (bq, 128) replicated
        l_prev = l_scr[...]
        row_max = jnp.max(s, axis=1, keepdims=True)          # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.broadcast_to(row_max, m_prev.shape))
        alpha = jnp.exp(m_prev - m_cur)                      # (bq, 128)
        rep = m_cur.shape[1]  # scratch lane width (128 compiled; bq interp)
        if bk == rep:
            m_bk = m_cur
        elif bk > rep and bk % rep == 0:  # replicate per-row max across lanes
            m_bk = pltpu.repeat(m_cur, bk // rep, axis=1)
        else:  # interpret mode (tiny or odd blocks): plain broadcast works
            m_bk = jnp.broadcast_to(m_cur[:, :1], (m_cur.shape[0], bk))
        p = jnp.exp(s - m_bk)                                # (bq, bk)
        if masked:
            # a row whose every key so far is masked has m == NEG_INF, where
            # exp(s - m) = exp(0) = 1 for masked entries — zero p explicitly
            # (reachable with kmask, and with window x lengths on padding
            # rows whose window lies wholly beyond the example length)
            p = jnp.where(valid, p, 0.0)
        l_scr[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        # p is in [0, 1]: bf16 is plenty for the PV matmul operand (f32
        # accumulation via preferred_element_type) and halves MXU feed cost
        pv = lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = (acc_scr[...]
                        * jnp.broadcast_to(alpha[:, :1], acc_scr.shape) + pv)
        m_scr[...] = m_cur

    # Block-level specialization: interior blocks (fully below the causal
    # diagonal, no right-padding, fully inside the ragged length) skip the
    # iota/compare/where mask entirely — the masked path only runs on
    # diagonal and tail blocks, saving ~1/3 of the VPU work that dominates
    # flash attention on TPU. With ragged lengths the interior test gains a
    # runtime predicate and blocks fully beyond the length are skipped.
    k_end = (ik + 1) * bk
    interior = (k_end <= t_actual) & (not has_kmask)  # kmask: no interior
    run = True
    if has_lens:
        interior = interior & (k_end <= L)
        run = ik * bk < L  # key block fully beyond this example: skip
    if causal:
        on_diag = k_end - 1 > iq * bq  # any k_pos could exceed some q_pos
        interior = interior & jnp.logical_not(on_diag)
        reachable = (ik * bk <= (iq + 1) * bq - 1) & run  # skip above-diagonal
        if window:
            # skip key blocks entirely behind every q row's window; a block
            # is interior only if its OLDEST (q, k) pair is still in-window
            reachable = reachable & (k_end - 1 >= iq * bq - (window - 1))
            interior = interior & ((iq + 1) * bq - 1 - ik * bk <= window - 1)
        pl.when(reachable & interior)(lambda: _accumulate(False))
        pl.when(reachable & jnp.logical_not(interior))(lambda: _accumulate(True))
    else:
        pl.when(run & interior)(lambda: _accumulate(False))
        pl.when(run & jnp.logical_not(interior))(lambda: _accumulate(True))

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...][:, :1], 1e-30)            # (bq, 1)
        o_ref[0] = (acc_scr[...] / jnp.broadcast_to(l, acc_scr.shape)
                    ).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...][:, :1] + jnp.log(l)


def _mask_operands(lens, kmask, BH, tp, pad):
    """(lens3, km3) pallas operands shared by the forward and backward
    calls — dummies when absent, so both directions keep ONE pallas_call
    signature and can never desynchronize their masking inputs."""
    if lens is None:
        lens = jnp.zeros((BH,), jnp.int32)
    lens3 = lens.reshape(BH, 1, 1)
    if kmask is None:
        km3 = jnp.zeros((BH, 1, tp), jnp.int8)
    else:
        km3 = jnp.pad(kmask.astype(jnp.int8), ((0, 0), (0, pad))
                      ).reshape(BH, 1, tp)
    return lens3, km3


def _flash_fwd(q, k, v, lens, kmask, scale: float, causal: bool, bq: int,
               bk: int, interpret: bool, has_lens: bool, has_kmask: bool,
               window: int = 0):
    import math

    BH, T, D = q.shape
    pad = (-T) % math.lcm(bq, bk)  # both grids must tile the padded length
    tp = T + pad
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    nq, nk = tp // bq, tp // bk
    lens3, km3 = _mask_operands(lens, kmask, BH, tp, pad)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, t_actual=T, has_lens=has_lens,
                               has_kmask=has_kmask, window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, iq, ik: (bh, 0, 0)),   # lens
            pl.BlockSpec((1, 1, bk), lambda bh, iq, ik: (bh, 0, ik)),  # kmask
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, tp, D), q.dtype),
            jax.ShapeDtypeStruct((BH, tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max m (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # running sum l (lane-replicated)
            pltpu.VMEM((bq, D), jnp.float32),    # unnormalized output acc
        ],
        # default scoped-VMEM budget is 16MB; large (512+) blocks with the
        # masked/unmasked branch specialization need a bit more headroom
        # (v5e has 128MB VMEM)
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name="flash_fwd",   # stable name in HLO and in a device trace
    )(q, k, v, lens3, km3)
    return o[:, :T], lse[:, :T, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, lens, kmask, scale, causal, bq, bk, interpret, backward,
           window):
    o, _ = _flash_fwd(q, k, v, lens, kmask, scale, causal, bq, bk, interpret,
                      lens is not None, kmask is not None, window)
    return o


def _flash_vjp_fwd(q, k, v, lens, kmask, scale, causal, bq, bk, interpret,
                   backward, window):
    o, lse = _flash_fwd(q, k, v, lens, kmask, scale, causal, bq, bk,
                        interpret, lens is not None, kmask is not None,
                        window)
    return o, (q, k, v, lens, kmask, o, lse)


# Block cap for the Mosaic backward kernels (the backward keeps more live
# tiles than the forward, so its VMEM-optimal block is smaller; 512 was the
# best of one v5e sweep at T<=4096 in 2026-07 — not re-measured since).
BWD_BLOCK_CAP = 512


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
              scale, causal, masked, iq, ik, bq, bk, t_actual, L=None,
              kmask_row=None, window=0):
    """Shared FlashAttention-2 backward recomputation for both passes:
    returns (p, ds) with p = exp(s - lse) (masked) and
    ds = p * (do @ v^T - delta) * scale. ``L`` (traced scalar): ragged
    example length — keys >= L are masked like the forward. ``kmask_row``
    ((1, bk) traced): exact key mask block, same forward parity."""
    q = q_ref[0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0].astype(jnp.float32)          # (bk, D)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    lse = lse_ref[0]                          # (bq, 1) f32
    p = jnp.exp(s - jnp.broadcast_to(lse, s.shape))
    if masked:
        q_pos = iq * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = k_pos < t_actual
        if L is not None:
            valid = valid & (k_pos < L)
        if kmask_row is not None:
            valid = valid & (kmask_row != 0)
        if causal:
            valid = valid & (k_pos <= q_pos)
        if window:
            valid = valid & (q_pos - k_pos < window)
        p = jnp.where(valid, p, 0.0)
    do = do_ref[0].astype(jnp.float32)        # (bq, D)
    dp = lax.dot_general(do, v_ref[0].astype(jnp.float32),
                         (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)  # (bq, bk)
    ds = p * (dp - jnp.broadcast_to(delta_ref[0], dp.shape)) * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
                   kmask_ref, dq_ref, dq_scr, *, scale: float, causal: bool,
                   bq: int, bk: int, t_actual: int, has_lens: bool,
                   has_kmask: bool, window: int = 0):
    """dQ pass: grid (BH, T/bq, T/bk), key blocks innermost sequential.
    Standard FlashAttention-2 recomputation: p = exp(s - lse);
    ds = p * (dp - delta) * scale; dq += ds @ k — accumulated in VMEM."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    L = lens_ref[0, 0, 0] if has_lens else None

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _accumulate(masked: bool):
        _, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          scale=scale, causal=causal, masked=masked,
                          iq=iq, ik=ik, bq=bq, bk=bk, t_actual=t_actual,
                          L=L if masked else None,
                          kmask_row=(kmask_ref[0]
                                     if masked and has_kmask else None),
                          window=window if masked else 0)
        dq_scr[...] += lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    k_end = (ik + 1) * bk
    interior = (k_end <= t_actual) & (not has_kmask)
    run = True
    if has_lens:
        interior = interior & (k_end <= L)
        run = ik * bk < L  # key block fully beyond the length: dq += 0
    if causal:
        on_diag = k_end - 1 > iq * bq
        interior = interior & jnp.logical_not(on_diag)
        reachable = (ik * bk <= (iq + 1) * bq - 1) & run
        if window:
            reachable = reachable & (k_end - 1 >= iq * bq - (window - 1))
            interior = interior & ((iq + 1) * bq - 1 - ik * bk <= window - 1)
        pl.when(reachable & interior)(lambda: _accumulate(False))
        pl.when(reachable & jnp.logical_not(interior))(lambda: _accumulate(True))
    else:
        pl.when(run & interior)(lambda: _accumulate(False))
        pl.when(run & jnp.logical_not(interior))(lambda: _accumulate(True))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, lens_ref,
                    kmask_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale: float, causal: bool, bq: int, bk: int,
                    t_actual: int, has_lens: bool, has_kmask: bool,
                    window: int = 0):
    """dK/dV pass: grid (BH, T/bk, T/bq), query blocks innermost sequential.
    dv += p^T @ do; dk += ds^T @ q — both accumulated in VMEM. With ragged
    lengths, a key block fully beyond the length skips every accumulate, so
    its dk/dv finalize as the zeros _init wrote (padded keys get 0 grad —
    matching the dense key-masked oracle); a key block straddling the
    length forces the masked path regardless of the q block."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    L = lens_ref[0, 0, 0] if has_lens else None

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accumulate(masked: bool):
        p, ds = _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          scale=scale, causal=causal, masked=masked,
                          iq=iq, ik=ik, bq=bq, bk=bk, t_actual=t_actual,
                          L=L if masked else None,
                          kmask_row=(kmask_ref[0]
                                     if masked and has_kmask else None),
                          window=window if masked else 0)
        # dv += p^T @ do ((bk, bq) @ (bq, D)); p in [0,1] — bf16 operand ok
        dv_scr[...] += lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    q_end = (iq + 1) * bq
    interior = (q_end <= t_actual) & (not has_kmask)
    run = True
    if has_lens:
        interior = interior & ((ik + 1) * bk <= L)  # key tail must mask
        run = ik * bk < L  # whole key block beyond length: keep zeros
    if causal:
        # diagonal touches this (ik, iq) pair unless the k block is fully
        # below every q row in the block
        on_diag = (ik + 1) * bk - 1 > iq * bq
        interior = interior & jnp.logical_not(on_diag)
        reachable = (q_end - 1 >= ik * bk) & run  # some q row sees this k
        if window:
            # some (q, k) pair still in-window for this block pair; interior
            # additionally needs the OLDEST pair in-window
            reachable = reachable & (iq * bq <= (ik + 1) * bk - 1 + window - 1)
            interior = interior & (q_end - 1 - ik * bk <= window - 1)
        pl.when(reachable & interior)(lambda: _accumulate(False))
        pl.when(reachable & jnp.logical_not(interior))(lambda: _accumulate(True))
    else:
        pl.when(run & interior)(lambda: _accumulate(False))
        pl.when(run & jnp.logical_not(interior))(lambda: _accumulate(True))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, lens, kmask, o, lse, do, scale, causal, bq, bk,
                      interpret, window=0):
    """Kernel-based flash backward (FlashAttention-2 decomposition): one
    pallas_call for dq (k innermost), one for dk/dv (q innermost)."""
    import math

    BH, T, D = q.shape
    # more live tiles than the forward (q, k, v, do + p/ds): cap blocks to
    # stay inside VMEM
    bq, bk = min(bq, BWD_BLOCK_CAP), min(bk, BWD_BLOCK_CAP)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)       # (BH, T, 1)
    lse3 = lse[..., None]                          # (BH, T, 1)

    pad = (-T) % math.lcm(bq, bk)
    tp = T + pad
    if pad:
        zpad = ((0, 0), (0, pad), (0, 0))
        q, k, v, do = (jnp.pad(a, zpad) for a in (q, k, v, do))
        delta = jnp.pad(delta, zpad)
        lse3 = jnp.pad(lse3, zpad)
    nq, nk = tp // bq, tp // bk
    has_lens = lens is not None
    has_kmask = kmask is not None
    lens3, km3 = _mask_operands(lens, kmask, BH, tp, pad)

    common = dict(scale=scale, causal=causal, bq=bq, bk=bk, t_actual=T,
                  has_lens=has_lens, has_kmask=has_kmask, window=window)
    vmem = pltpu.CompilerParams(vmem_limit_bytes=96 * 1024 * 1024)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),   # q
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),   # k
            pl.BlockSpec((1, bk, D), lambda bh, iq, ik: (bh, ik, 0)),   # v
            pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),   # do
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),   # lse
            pl.BlockSpec((1, bq, 1), lambda bh, iq, ik: (bh, iq, 0)),   # delta
            pl.BlockSpec((1, 1, 1), lambda bh, iq, ik: (bh, 0, 0)),     # lens
            pl.BlockSpec((1, 1, bk), lambda bh, iq, ik: (bh, 0, ik)),   # kmask
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, tp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=vmem,
        interpret=interpret,
        name="flash_bwd_dq",   # stable name in HLO and in a device trace
    )(q, k, v, do, lse3, delta, lens3, km3)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),   # q
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),   # k
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),   # v
            pl.BlockSpec((1, bq, D), lambda bh, ik, iq: (bh, iq, 0)),   # do
            pl.BlockSpec((1, bq, 1), lambda bh, ik, iq: (bh, iq, 0)),   # lse
            pl.BlockSpec((1, bq, 1), lambda bh, ik, iq: (bh, iq, 0)),   # delta
            pl.BlockSpec((1, 1, 1), lambda bh, ik, iq: (bh, 0, 0)),     # lens
            pl.BlockSpec((1, 1, bk), lambda bh, ik, iq: (bh, 0, ik)),   # kmask
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, tp, D), k.dtype),
            jax.ShapeDtypeStruct((BH, tp, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=vmem,
        interpret=interpret,
        name="flash_bwd_dkv",   # stable name in HLO and in a device trace
    )(q, k, v, do, lse3, delta, lens3, km3)
    return dq[:, :T], dk[:, :T], dv[:, :T]


# Default backward implementation: "pallas" = the Mosaic kernels above,
# "xla" = the pure-JAX scan recomputation. The per-call ``backward=`` arg of
# ``flash_attention`` overrides this (and, being a nondiff static arg, keys
# the jit cache correctly — mutating the global alone cannot retrace an
# already-compiled function). Default stays "xla" until the Mosaic lowering
# of the backward kernels is validated on a real chip (interpret-mode tests
# prove numerics, not lowering) — flip after the on-chip A/B in PERF.md.
BACKWARD = "xla"


def _flash_vjp_bwd(scale, causal, bq, bk, interpret, backward, window, res,
                   do):
    if backward == "pallas":
        q, k, v, lens, kmask, o, lse = res
        dq, dk, dv = _flash_bwd_pallas(q, k, v, lens, kmask, o, lse, do,
                                       scale, causal, bq, bk, interpret,
                                       window)
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                _lens_ct(lens), _lens_ct(kmask))
    return _flash_vjp_bwd_xla(scale, causal, bq, bk, interpret, window, res,
                              do)


def _flash_vjp_bwd_xla(scale, causal, bq, bk, interpret, window, res, do):
    """Flash backward: recompute probabilities per q block from (q, k, lse);
    scan over q blocks carrying (dk, dv) accumulators — peak memory
    O(bq·T), never (T, T)."""
    q, k, v, lens, kmask, o, lse = res
    BH, T, D = q.shape
    # Decoupled from the forward kernel's block width: the bwd is pure JAX
    # (XLA-fused, far less sensitive to block size than Mosaic) and its
    # per-step score tensor is O(BH·bq·T) — a 1024-wide fwd block would grow
    # bwd peak memory 8x over 128 and can OOM a backward whose forward fits.
    bq = min(bq, 256)
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (BH, T)

    pad = (-T) % bq
    tp = T + pad
    nq = tp // bq
    qp = jnp.pad(qf, ((0, 0), (0, pad), (0, 0))).reshape(BH, nq, bq, D)
    dop = jnp.pad(dof, ((0, 0), (0, pad), (0, 0))).reshape(BH, nq, bq, D)
    lsep = jnp.pad(lse, ((0, 0), (0, pad)), constant_values=1.0).reshape(BH, nq, bq)
    deltap = jnp.pad(delta, ((0, 0), (0, pad))).reshape(BH, nq, bq)

    k_pos = jnp.arange(T)[None, :]                       # (1, T)

    def per_block(carry, xs):
        dk_acc, dv_acc = carry
        qb, dob, lseb, deltab, iq = xs                    # (BH, bq, D) ...
        s = jnp.einsum("bqd,bkd->bqk", qb, kf) * scale    # (BH, bq, T)
        q_pos = iq * bq + jnp.arange(bq)[:, None]         # (bq, 1)
        valid = jnp.broadcast_to(k_pos <= q_pos if causal
                                 else jnp.ones((bq, T), bool), (bq, T))[None]
        if lens is not None:  # ragged: keys >= example length masked out
            valid = valid & (k_pos[None] < lens[:, None, None])
        if kmask is not None:  # exact (BH, T) key mask
            valid = valid & (kmask != 0)[:, None, :]
        if window:  # sliding window: q attends [q-window+1, q]
            valid = valid & (q_pos - k_pos < window)[None]
        # padded q rows (q_pos >= T) contribute nothing: their do is 0-padded
        p = jnp.where(valid, jnp.exp(s - lseb[..., None]), 0.0)
        dv_acc = dv_acc + jnp.einsum("bqk,bqd->bkd", p, dob)
        dp = jnp.einsum("bqd,bkd->bqk", dob, vf)
        ds = p * (dp - deltab[..., None]) * scale
        dq_b = jnp.einsum("bqk,bkd->bqd", ds, kf)
        dk_acc = dk_acc + jnp.einsum("bqk,bqd->bkd", ds, qb)
        return (dk_acc, dv_acc), dq_b

    xs = (qp.transpose(1, 0, 2, 3), dop.transpose(1, 0, 2, 3),
          lsep.transpose(1, 0, 2), deltap.transpose(1, 0, 2),
          jnp.arange(nq))
    (dk, dv), dq_blocks = lax.scan(
        per_block, (jnp.zeros_like(kf), jnp.zeros_like(vf)), xs)
    dq = dq_blocks.transpose(1, 0, 2, 3).reshape(BH, tp, D)[:, :T]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            _lens_ct(lens), _lens_ct(kmask))


def _lens_ct(a):
    """Cotangent for an integer input (lengths / key mask): float0 zeros
    (ints have no tangent space), or None when the input was absent."""
    return None if a is None else np.zeros(a.shape, jax.dtypes.float0)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    backward: Optional[str] = None,
                    lengths=None, key_mask=None,
                    window: Optional[int] = None):
    """Memory-efficient exact attention. q, k, v: (B, T, H, D) (the layout of
    ``dot_product_attention``); returns (B, T, H, D).

    Differentiable (custom flash VJP). On the CPU backend the kernel runs in
    Pallas interpreter mode automatically, so CPU tests exercise the same
    code; a backend that is neither TPU nor CPU raises.

    ``lengths`` ((B,) int32, optional): ragged example lengths for
    RIGHT-PADDED batches — keys at positions >= lengths[b] are masked out
    for every query (the key-padding mask), forward and backward, without
    materializing a mask or falling back to dense attention. Equivalent to
    the dense path's 2-D key mask ``arange(T) < lengths[:, None]``. The
    fast ragged variant: blocks fully inside the length keep the unmasked
    specialization, blocks beyond it are skipped. ``lengths[b] == 0``
    (fully padded example) returns 0 for that row with zero gradients —
    the dense oracle's mean(v) for an all-masked softmax is equally
    meaningless there; mask the loss either way.

    ``key_mask`` ((B, T) bool/int, optional): EXACT arbitrary key mask —
    no contiguity assumption (left padding, mid-sequence holes). Every
    block takes the masked path, so prefer ``lengths`` when the batch is
    right-padded. Mutually exclusive with ``lengths``. Rows whose keys are
    ALL masked return 0 (the dense path returns mean(v) there — both are
    degenerate; mask the loss). Padded ROWS still emit (ignored) outputs.

    ``window`` (int, optional, causal only): sliding-window attention —
    query t attends keys [t-window+1, t]. Key blocks wholly behind the
    window are SKIPPED, so attention cost scales O(T·window) instead of
    O(T²/2): at T=64k with window=4k that is ~16x less attention work.
    Windowed calls default to ``backward="pallas"`` — the Mosaic backward
    skips out-of-window blocks too, while the XLA scan backward computes
    full-width scores and only masks (pass ``backward="xla"`` to override;
    correct, but no backward FLOPs saving). window >= T degrades to plain
    causal. Composes with lengths/key_mask.

    Default block sizes adapt to T, capped at 1024 — the measured optimum on
    v5e (T=4096 causal: ~21 TF/s at 1024x1024 or 2048x2048, 5x faster than
    dense attention and 4.5x faster than this kernel at its previous 128x128
    defaults; 4096-wide blocks spill VMEM and regress ~2x — see PERF.md).
    """
    B, T, H, D = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(f"q/k/v shapes must match, got {q.shape} {k.shape} {v.shape}")
    if lengths is not None and key_mask is not None:
        raise ValueError("pass lengths OR key_mask, not both")
    if window is not None:
        if not causal:
            raise ValueError("window= requires causal=True (sliding-window "
                             "attention is a causal-LM construct)")
        window = int(window)  # host-side hyperparameter  # jaxlint: disable=host-sync
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= T:
            window = None  # full causal attention; keep the fast path
    if lengths is not None:
        if lengths.shape != (B,):
            raise ValueError(f"lengths must be ({B},), got {lengths.shape}")
        # length 0 = fully padded example: every block is skipped, the row
        # outputs 0 and contributes zero gradients (same contract as an
        # all-masked key_mask row) — do NOT clamp to 1, which would
        # silently attend key 0 and diverge from the dense oracle
        lengths = jnp.clip(lengths.astype(jnp.int32), 0, T)
    if key_mask is not None:
        if key_mask.shape != (B, T):
            raise ValueError(f"key_mask must be ({B}, {T}), got {key_mask.shape}")
        key_mask = key_mask.astype(jnp.int8)
    if backward is not None:
        bw = backward
    elif window:
        # the O(T·window) claim needs block SKIPPING in the backward too;
        # the XLA scan backward computes full (bq, T) scores per q block
        # and only masks, so windowed calls default to the Mosaic backward
        # (both backwards are checked against dense attention on the chip
        # by chip_smoke.py leg (a), windowed case included)
        bw = "pallas"
    else:
        bw = BACKWARD
    if bw not in ("pallas", "xla"):
        raise ValueError(f"backward must be 'pallas' or 'xla', got {bw!r}")
    if interpret is None:
        # compiled on TPU; interpreted on the CPU backend (the test suite);
        # anywhere else there is no lowering for this kernel and silently
        # interpreting it would hide that
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise NotImplementedError(
                f"flash_attention has no {platform!r} lowering (TPU "
                f"compiles, CPU interprets); use dot_product_attention")
        interpret = platform == "cpu"
    # Python-float scale: embedded as an f32 scalar constant in the kernel —
    # an np.float64 here would silently promote the whole QK^T tree.
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)  # jaxlint: disable=host-sync
    if interpret:
        # interpreter mode has no tiling constraints: shrink blocks toward T
        # so CPU tests stay fast
        bq = min(block_q or 128, max(16, T))
        bk = min(block_k or 128, max(16, T))
    else:
        # compiled TPU path: 128-multiple block sizes; the lcm padding
        # absorbs odd T — Mosaic requires hardware-aligned (sublane x
        # 128-lane) block shapes, so never clamp to raw T
        t128 = -(-T // 128) * 128
        bq = block_q if block_q is not None else min(1024, t128)
        bk = block_k if block_k is not None else min(1024, t128)
        if bq % 128 or bk % 128:
            raise ValueError(f"block_q/block_k must be multiples of 128 on "
                             f"TPU, got {bq}/{bk}")

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    lens_bh = None if lengths is None else jnp.repeat(lengths, H)
    km_bh = None if key_mask is None else jnp.repeat(key_mask, H, axis=0)
    o = _flash(to_bh(q), to_bh(k), to_bh(v), lens_bh, km_bh, scale, causal,
               bq, bk, interpret, bw, window or 0)
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
