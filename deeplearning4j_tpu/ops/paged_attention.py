"""Paged-attention DECODE kernel: one query a slot attends over that slot's
LIVE cache blocks, read from the paged k/v pools where they lie.

``nn.generation.cache_gather`` copies ``pool[tables]`` into a ``(slots,
maxb * bs, Hkv, hd)`` array on every layer of every step, whatever is live,
and the einsums behind it read the copy again (and transpose it where there
is more than one KV head). This kernel takes the pools as they are
(``memory_space=ANY``: no copy), the block tables and the rows' positions as
scalar-prefetch operands, and for each slot walks ``pos // bs + 1`` table
entries — the live blocks only:

- A block ``(bs, Hkv, hd)`` is one contiguous ``(bs * Hkv, hd)`` slab of the
  pool (row ``r`` is position ``r // Hkv`` of KV head ``r % Hkv``), fetched
  into VMEM by one DMA. A CHUNK of blocks (about :data:`CHUNK_ROWS` rows) is in
  flight at once; two chunk buffers alternate, and the next chunk — the next
  slot's first, at a slot's end — is started before the current one is waited
  for. A chunk's copies share a semaphore and are waited for by size: one
  wait for each power of two in their number, not one a block.
- The slab is never transposed to heads: every query head is multiplied
  against every row of the chunk (``(H, hd) x (rows, hd)^T`` on the MXU, which
  a one-row decode leaves idle anyway) and the columns of other KV heads are
  masked with the positions past ``pos``. Their weights are exactly 0, so the
  same ``(H, rows) x (rows, hd)`` product over the v slab is the grouped
  attention's. MQA, MHA and GQA are one code path: what differs is ``Hkv``
  and the group ``H // Hkv``, read off the operands.
- Running maximum, sum and accumulator in f32 (online softmax). The pool
  holds garbage past ``pos`` in the last live block and in every block no
  table names: scores there are replaced before the maximum, and the last
  chunk's v rows there are zeroed in VMEM before the product (0 x NaN is
  NaN), so nothing of it can reach the output.
- Never narrower than the einsums it replaces. ``q`` and the pool in one
  dtype: the MXU's products with f32 accumulation, the weights rounded to the
  pool's dtype for the values' product, as ``attend_cached`` does (an f32
  stream over an f32 pool at the process's default precision, as its einsums;
  a query narrower than the pool is widened first, as they promote it). ``q``
  in f32 over a bf16 pool (``wide_einsum``'s ``Precision.HIGHEST``): ``q`` and
  the weights are split into three bf16 pieces whose products with the bf16
  pool are exact in f32, stacked into ONE product so that a tile of the pool
  is loaded once for the three — HIGHEST's exactness at about the cost of a
  single pass. Decided from the operands' dtypes, as ``wide_einsum`` does.

Compiled by Mosaic on a TPU, run in Pallas interpreter mode on the CPU
(chosen as ``flash_attention`` chooses), so the tests run this code.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30          # a masked score: finite, so exp(NEG - m) is 0, never NaN
# pool rows (positions x KV heads) fetched and multiplied at once: 128 blocks
# of one KV head, 8 of sixteen. On a v5e 2048 read 3 / 7 / 11% less time a
# call than 1024 at MQA's, MHA's and GQA's published widths, 512 8-25% more
# (PERF.md section 6, PR 46); two buffers of k and of v are 2 MB of VMEM
CHUNK_ROWS = 2048


def supports(q_dtype, pool_dtype) -> bool:
    """Whether the kernel multiplies these two dtypes as exactly as the
    einsums would: one dtype; a query narrower than the pool, which is
    widened to it first, as the einsums' promotion does (a bf16 stream's
    first layer over an f32 pool); or an f32 query over a bf16 pool."""
    q_dtype, pool_dtype = jnp.dtype(q_dtype), jnp.dtype(pool_dtype)
    return (q_dtype == pool_dtype or q_dtype.itemsize < pool_dtype.itemsize
            or (q_dtype == jnp.float32 and pool_dtype == jnp.bfloat16))


def _pieces(x, dtype, n: int):
    """``x`` (H, ...) as ``n`` addends of ``dtype``, stacked to (n * H, ...):
    the rounding, then the rounding of what is left, and so on. Three bf16
    pieces hold an f32 exactly. Stacked, they go through the MXU as ONE
    product (the pool's tile is loaded once for all three), and
    :func:`_summed` adds the three results."""
    out = []
    for i in range(n):
        out.append(x.astype(dtype))
        if i + 1 < n:
            x = x - out[-1].astype(x.dtype)
    return out[0] if n == 1 else jnp.concatenate(out, axis=0)


def _summed(y, n: int):
    """The ``n`` stacked results (n * H, ...) of :func:`_pieces` added up."""
    H = y.shape[0] // n
    return sum(y[i * H:(i + 1) * H] for i in range(n))


def _kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, bs: int, kv_heads: int, maxb: int, pages: int, scale: float,
            pieces: int):
    S, H, hd = q_ref.shape
    R = bs * kv_heads                  # rows of one block's slab
    rows = pages * R                   # rows of one chunk
    G = H // kv_heads
    dtype = kbuf.dtype
    # bf16 x bf16 is exact in one pass of the MXU whatever precision the
    # process asks of its matmuls by default, and Mosaic refuses the wider
    # passes on bf16 operands; wider operands multiply as the default says
    precision = lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None

    def last(s):
        """The query's position, held inside the table: a position the
        contract rules out must not become a DMA from a wild address."""
        return jnp.clip(pos_ref[s], 0, maxb * bs - 1)

    def n_chunks(s):
        return (last(s) // bs) // pages + 1

    def chunk_pages(s, c):
        """Live blocks in chunk ``c`` of slot ``s``."""
        return jnp.minimum(last(s) // bs + 1 - c * pages, pages)

    def copies(phys, b, row):
        dst = pl.ds(row, R)
        return (pltpu.make_async_copy(k_hbm.at[phys], kbuf.at[b, dst],
                                      sem.at[0, b]),
                pltpu.make_async_copy(v_hbm.at[phys], vbuf.at[b, dst],
                                      sem.at[1, b]))

    def start(s, c, b):
        def page(i, _):
            for cp in copies(tables_ref[s * maxb + c * pages + i], b,
                             pl.multiple_of(i * R, R)):
                cp.start()
            return 0

        lax.fori_loop(0, chunk_pages(s, c), page, 0)

    def wait(s, c, b):
        """Wait for the chunk's copies. A DMA semaphore counts what has
        arrived and a wait names a size, not a source: the pages are waited
        for by the powers of two that add up to their number, one wait each,
        whichever copies they were."""
        n = chunk_pages(s, c)
        for j in range(pages.bit_length()):
            part = pl.ds(0, R << j)

            @pl.when((n >> j) & 1 == 1)
            def _():
                for buf, i in ((kbuf, 0), (vbuf, 1)):
                    pltpu.make_async_copy(buf.at[b, part], buf.at[b, part],
                                          sem.at[i, b]).wait()

    # which columns of a chunk belong to a query head's own KV head: the
    # same for every chunk, so taken once
    col = lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    if kv_heads == 1:
        own = None
    else:
        head = lax.broadcasted_iota(jnp.int32, (H, rows), 0)
        own = lax.rem(col, kv_heads) == lax.div(head, G)

    def slot(s, b0):
        p = last(s)
        nc = n_chunks(s)
        qs = _pieces(q_ref[s], dtype, pieces)            # (pieces * H, hd)

        def chunk(c, carry):
            b, m, l, acc = carry
            nb = 1 - b

            # the next chunk into the other buffer before this one is waited
            # for: this slot's next, or at its end the next slot's first
            more = c + 1 < nc

            @pl.when(more | (s + 1 < S))
            def _():
                start(jnp.where(more, s, s + 1), jnp.where(more, c + 1, 0),
                      nb)

            wait(s, c, b)
            live = (p + 1 - c * (pages * bs)) * kv_heads   # live rows here

            @pl.when(c + 1 == nc)
            def _():
                row = lax.broadcasted_iota(jnp.int32, (rows, hd), 0)
                v32 = vbuf[b].astype(jnp.float32)
                vbuf[b] = jnp.where(row < live, v32, 0.0).astype(dtype)

            k, v = kbuf[b], vbuf[b]                        # (rows, hd)
            sc = _summed(lax.dot_general(
                qs, k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32), pieces) * scale  # (H, rows)
            valid = col < live
            if own is not None:
                valid = valid & own
            sc = jnp.where(valid, sc, NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            w = jnp.exp(sc - m_new)          # 0 where masked: m_new is real
            l = alpha * l + jnp.sum(w, axis=1, keepdims=True)
            pv = _summed(lax.dot_general(
                _pieces(w, dtype, pieces), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32),
                pieces)                                    # (H, hd)
            return nb, m_new, l, alpha * acc + pv

        b, _, l, acc = lax.fori_loop(
            0, nc, chunk,
            (b0, jnp.full((H, 1), NEG, jnp.float32),
             jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, hd), jnp.float32)))
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return b

    start(0, 0, 0)
    lax.fori_loop(0, S, slot, 0)


def paged_attention_decode(q, k_pool, v_pool, tables, pos, *, interpret=None):
    """One decode step's attention through a paged cache, read in place.

    ``q`` (S, H, hd): one query a slot, already rotated. ``k_pool`` /
    ``v_pool`` (N, bs, Hkv, hd): the layer's pools, AFTER this step's keys and
    values were written. ``tables`` (S, maxb) int32: each slot's physical
    blocks, logical block ``b`` in column ``b`` (a FULL table, not a ring).
    ``pos`` (S,) int32: the query's position; slot ``s`` attends positions
    ``0..pos[s]``. Returns (S, H, hd) in the wider of ``q``'s and the pool's
    dtype: what ``cache_gather`` + the grouped einsums of ``attend_cached``
    give, without the gathered copy. An idle slot (a zeroed table row, position 0) reads one
    block, the trash block, as it does there."""
    S, H, hd = q.shape
    N, bs, Hkv, _ = k_pool.shape
    maxb = tables.shape[1]
    if v_pool.shape != k_pool.shape or v_pool.dtype != k_pool.dtype:
        raise ValueError(f"k_pool {k_pool.shape} {k_pool.dtype} and v_pool "
                         f"{v_pool.shape} {v_pool.dtype} differ")
    if H % Hkv or k_pool.shape[3] != hd:
        raise ValueError(f"q {q.shape} does not fold onto pools "
                         f"{k_pool.shape}")
    if not supports(q.dtype, k_pool.dtype):
        raise ValueError(f"q {q.dtype} over a {k_pool.dtype} pool: cast, or "
                         f"gather and use the einsums")
    if q.dtype.itemsize < k_pool.dtype.itemsize:
        q = q.astype(k_pool.dtype)
    if interpret is None:
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise NotImplementedError(
                f"paged_attention_decode has no {platform!r} lowering (TPU "
                f"compiles, CPU interprets); gather and use the einsums")
        interpret = platform == "cpu"
    R = bs * Hkv
    pages = max(1, min(maxb, CHUNK_ROWS // R))
    # a block as one slab of rows: a bitcast of the pool (a single KV head's
    # size-1 axis is laid outermost; more heads tile (Hkv, hd) a position)
    slab = (N, R, hd)
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, kv_heads=Hkv, maxb=maxb,
                          pages=pages, scale=1.0 / math.sqrt(hd),
                          pieces=1 if q.dtype == k_pool.dtype else 3),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((2, pages * R, hd), k_pool.dtype),
                            pltpu.VMEM((2, pages * R, hd), k_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((S, H, hd), q.dtype),
        interpret=interpret,
        name="paged_attn_decode",   # stable name in HLO and in a device trace
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32), q,
      k_pool.reshape(slab), v_pool.reshape(slab))
