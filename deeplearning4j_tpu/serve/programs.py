"""The device programs of the generation loop.

``serve/continuous.py`` is the scheduler: requests, slots, block tables,
admission, params generations, the tick. This module is what a served
program IS, and everything only the programs need to know:

- the three traced functions — ``_sample_dynamic``, ``_prefill_chunk_fn``,
  ``_decode_paged_fn``; a device trace names its programs after them
  (``jit__decode_paged_fn``), so the names are part of the measurement
  (the first and the last sample through ``_sample_rows``);
- the cache pools they carry from call to call (donated every step): what
  each layer's spec names, ``k`` and ``v``, or ``latent`` and ``rope``; the
  format between the pools and ``decode_forward``'s per-layer cache
  dictionaries is the layout contract's (``nn/generation.py``: ``as_paged``);
- the block GROUPS of a model whose layers differ in how far back their
  cache reaches (``serve/paged.py:cache_groups``): each group has pools of
  its own length and tables of its own width, and the programs take the
  tables as ``{group: array}``; a model with one group takes one array, as
  it always did;
- how a model with expert layers reports what routing did: which rows are
  live, and the three sums that ride a decode step's tokens as ``(S + 3,)``
  (four where the layers hold a share of their experts); the sums a layer
  names itself (``decode_sums``) ride behind them the same way;
- the STATE group of a model whose layers keep a recurrent state
  (``serve/paged.py``): pools sized in slots, updated in place by both
  programs; the chunk program is told which slot its one row is and what the
  slot starts from (a snapshot, zeros, its own state);
- the persistent-store wrapping under the tags ``gen_sample``,
  ``gen_prefill_chunk``, ``gen_decode_paged``;
- each program's operand list, written down once as abstract shapes
  (:meth:`GenPrograms.signatures`) beside the one call that passes it, so
  that warming a params generation is one call (:meth:`GenPrograms.warm`).

The scheduler hands over host values (a table, a prompt chunk, the slot
vectors of the rows it sets) and gets device values back; what it reads
back, and when, stays its decision. What one decode step needs of the one
before it (tokens, positions, keys) is kept here, on the device, so that the
scheduler can enqueue a step before it has read the last one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from .paged import FULL, STATE, build_pools, cache_groups


def _index_pairs(pairs: List[tuple]):
    """``(src, dst)`` pairs as two device index vectors (the operands of an
    eager row copy: XLA's eager cache keys on shapes, not on values)."""
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.fromiter((p[i] for p in pairs), np.int32,
                                         len(pairs))) for i in (0, 1))


class GenPrograms:
    """The sampler, the prefill-chunk program and the decode step of one
    batcher, over ``slots`` rows, block tables ``table_blocks`` wide and
    pools of ``kv_blocks`` blocks (each one number, or ``{group: number}``
    for a model with more than one block group: then every ``tables`` /
    ``table_row`` below is ``{group: array}`` too). ``store`` (an ``AotStore``) makes every
    executable load from disk before it traces, ``strict`` refuses to
    trace at all; ``snapshot`` is the registry snapshot whose architecture
    keys the store."""

    def __init__(self, model, *, slots: int, table_blocks: int, vocab: int,
                 kv_blocks: int, block_size: int,
                 chunk_buckets: Sequence[int], metrics, compile_counter,
                 store=None, strict: bool = False, snapshot=None,
                 state_snapshots: int = 0):
        import jax
        import jax.numpy as jnp

        from ..nn.generation import (as_paged, decode_forward, paged_parts,
                                     says_how_it_decodes, top_k_threshold)
        from ..nn.model import _layer_key

        self.slots = int(slots)
        self.table_blocks = table_blocks
        self.vocab = int(vocab)
        self.chunk_buckets = tuple(chunk_buckets)
        mdl = model

        def _sample_rows(logits, keys, temps, tks):
            """Fully-traced sampler over rows: temperature 0 -> greedy,
            top_k a dynamic value per row (top_k == V disables the
            restriction). The thresholds are found for the batch at once
            (``top_k_threshold``); the draw stays per row, each with its
            own key."""
            with jax.named_scope("sample"):  # its name in a device trace
                scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
                kth = top_k_threshold(scaled, tks)

                def one(l, s, key, temp, kth):
                    masked = jnp.where(s >= kth, s, -1e30)
                    samp = jax.random.categorical(key, masked, axis=-1)
                    return jnp.where(temp <= 0.0, jnp.argmax(l, axis=-1),
                                     samp).astype(jnp.int32)

                return jax.vmap(one)(logits, scaled, keys, temps, kth)

        def _sample_dynamic(logits, key, temperature, top_k):
            """One row: the first token of a request."""
            return _sample_rows(logits[None], key[None], temperature[None],
                                top_k[None])[0]

        #: block group -> its layers; more than one BLOCK group makes every
        #: tables operand a dict (the state group has slots, no tables)
        self.group_layers = {g.name: g.layers for g in cache_groups(mdl)}
        stateful = self.group_layers.get(STATE, ())
        self.pools = build_pools(
            mdl, kv_blocks, block_size, mdl.dtype,
            **({"state_rows": (self.slots, int(state_snapshots))}
               if stateful else {}))
        # layer key -> the parts its pools hold (k and v; latent and rope)
        names = {lk: tuple(pool) for lk, pool in self.pools.items()}
        grouped = isinstance(table_blocks, dict)
        group_of = {lk: g for g, layers in self.group_layers.items()
                    for lk in layers}
        # layers with experts (layers/experts.py) report what routing did
        # to the rows marked live; the sums leave each program as three
        # int32 (nn.layers.experts.ROUTING_FIELDS). A model without such
        # layers builds the programs it always built
        routed = [_layer_key(i, layer)
                  for i, layer in enumerate(mdl.layers)
                  if says_how_it_decodes(layer)
                  and getattr(layer, "num_experts", 0)]
        #: expert layers a program runs; 0 for a model without experts
        self.routed = len(routed)
        # layers that ask which rows (a chunk: which tokens) are real
        # (``reads_live``), and layers whose decode steps report sums of
        # their own (``decode_sums``: nn.generation.says_how_it_decodes)
        lively, summed = [], []
        #: what those sums count, in order: ``{counter name: help}``
        self.sum_fields: Dict[str, str] = {}
        for i, layer in enumerate(mdl.layers):
            if not says_how_it_decodes(layer):
                continue
            if getattr(layer, "reads_live", False):
                lively.append(_layer_key(i, layer))
            fields = dict(getattr(layer, "decode_sums", None) or {})
            if fields:
                if self.sum_fields and fields != self.sum_fields:
                    raise ValueError(
                        f"{_layer_key(i, layer)} reports {list(fields)}, the "
                        f"layers before it {list(self.sum_fields)}: one list "
                        f"a model")
                self.sum_fields = fields
                summed.append(_layer_key(i, layer))
        self.stateful = bool(stateful)
        from ..nn.layers.experts import ELSEWHERE_FIELDS, ROUTING_FIELDS

        #: what the sums a program reports count, in order: a fourth where
        #: the expert layers hold a share of their experts
        self.routing_fields = dict(ROUTING_FIELDS)
        if any(getattr(layer, "held", None) for layer in mdl.layers):
            self.routing_fields.update(ELSEWHERE_FIELDS)
        # a chunk's sums stay on the device until the scheduler reads
        # something computed behind them (chunk_routing)
        self._routing_pending: List[Any] = []

        def _as_caches(pools, tables, live=None, slot=None, load=None):
            caches = {lk: as_paged(pools[lk], tables[group_of[lk]]
                                   if grouped else tables)
                      for lk in names if lk not in stateful}
            for lk in stateful:
                # no tables: row s is slot s (the decode step), or the one
                # row is ``slot`` and starts from ``load`` (a chunk)
                caches[lk] = {**{f"{n}_pool": a
                                 for n, a in pools[lk].items()},
                              "slot": slot, "load": load,
                              "every": block_size}
            for lk in routed + lively:
                caches[lk]["live"] = live
            return caches

        def _routing(caches):
            return sum(caches[lk]["routing"] for lk in routed)

        def _as_pools(caches):
            return {lk: paged_parts(caches[lk], parts)
                    for lk, parts in names.items()}

        def _prefill_chunk_fn(params, state, ids, pools, table_row, pos,
                              true_len, *slot_load):
            """One prompt chunk for one slot. ``ids`` (1, Tb)
            right-padded; ``pos`` (1,) chunk offset; pad garbage writes
            past the row's blocks land in the trash block. Logits are
            gathered at the last REAL token of the chunk. ``slot_load``
            (a model with a state group only): the slot (1,) the row is, and
            what its state starts from (1,): a snapshot's row, -1 zeros, -2
            the slot's own state (the chunk before this one left it)."""
            live = (jnp.arange(ids.shape[1]) < true_len)[None] \
                if routed or lively else None
            lg, caches = decode_forward(
                mdl, params, state, ids,
                _as_caches(pools, table_row, live, *slot_load), pos)
            last = jnp.take(lg, true_len - 1, axis=1)  # (1, V)
            if routed:
                return last, _as_pools(caches), _routing(caches)
            return last, _as_pools(caches)

        S = self.slots

        def _decode_paged_fn(params, state, toks, pools, tables, pos,
                             keys, temps, tks, fresh, set_toks, set_pos,
                             set_keys):
            """One token for every slot, batched over the slot axis
            against the shared pools — ONE executable for the server's
            lifetime (tables are a traced operand). Inactive slots
            carry zeroed table rows, so their writes land in the trash
            block and their sampled garbage is discarded host-side.

            ``toks``, ``pos`` and ``keys`` are what the step before this one
            returned (its ``next``, its positions + 1, its split keys): they
            never visit the host. A row the host (re)sets, at an admission
            or a fork, is marked in ``fresh`` and takes ``set_toks`` /
            ``set_pos`` / ``set_keys`` instead: integer selects, the
            arithmetic is the step's own."""
            # a live slot's first block is never the trash block (a ring's
            # first column may be: the full group's table says who is live)
            full = tables[FULL] if grouped else tables
            live = full[:, :1] != 0
            toks = jnp.where(fresh, set_toks, toks[:S])
            pos = jnp.where(fresh, set_pos, pos)
            keys = jnp.where(fresh[:, None], set_keys, keys)
            # a row that is not live reads position 0, as it always did
            # (its carried position may be anything)
            pos = jnp.where(live[:, 0], pos, 0)
            lg, caches = decode_forward(
                mdl, params, state, toks[:, None].astype(jnp.int32),
                _as_caches(pools, tables,
                           live if routed or lively else None), pos)

            new_keys, subs = jnp.moveaxis(
                jax.vmap(jax.random.split)(keys), 1, 0)
            nxt = _sample_rows(lg[:, 0], subs, temps, tks)
            if routed:
                # the three sums ride the tokens' readback: (S + 3,)
                nxt = jnp.concatenate([nxt, _routing(caches)])
            if summed:
                # and the sums the layers name themselves behind them
                nxt = jnp.concatenate(
                    [nxt, sum(caches[lk]["sums"] for lk in summed)])
            return nxt, _as_pools(caches), pos + 1, new_keys

        self._sample = jax.jit(_sample_dynamic)
        # pools are the loop-carried buffers: donated every step
        self._prefill_chunk = jax.jit(_prefill_chunk_fn, donate_argnums=(3,))
        self._decode = jax.jit(_decode_paged_fn, donate_argnums=(3,))

        self._pools_sig = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.pools)
        # what a decode step hands the next on the device: its tokens (the
        # routing sums behind them), positions and keys; then the sampling
        # vectors, uploaded when a row is set and not between; then the
        # operands of a step that sets no row, made once
        self._next_width = S + (len(self.routing_fields) if routed else 0) \
            + len(self.sum_fields)
        self._carry = (jnp.zeros((self._next_width,), jnp.int32),
                       jnp.zeros((S,), jnp.int32),
                       jnp.zeros((S, 2), jnp.uint32))
        self._sampling = (jnp.ones((S,), jnp.float32),
                          jnp.full((S,), self.vocab, jnp.int32))
        self._none_fresh = (jnp.zeros((S,), bool), jnp.zeros((S,), jnp.int32),
                            jnp.zeros((S,), jnp.int32),
                            jnp.zeros((S, 2), jnp.uint32))
        self._aot_fns: Dict[str, Any] = {}
        if store is not None:
            from ..aot import AotFunction, arch_fingerprint

            arch = arch_fingerprint(snapshot.params, snapshot.state)

            def _wrap(fn, tag, donate=()):
                wrapped = AotFunction(
                    fn, tag=tag, store=store, metrics=metrics, arch=arch,
                    component="generate", donate_argnums=donate,
                    compile_counter=compile_counter, strict=strict)
                self._aot_fns[tag] = wrapped
                return wrapped

            self._sample = _wrap(self._sample, "gen_sample")
            self._prefill_chunk = _wrap(self._prefill_chunk,
                                        "gen_prefill_chunk", (3,))
            self._decode = _wrap(self._decode, "gen_decode_paged", (3,))

    # ------------------------------------------------- operands, written once
    def signatures(self, params, state) -> Dict[str, List[tuple]]:
        """Store tag -> the positional operand list of each of its
        executables, as abstract shapes, for one params generation
        (``params`` as the programs read them: the compute-dtype copy).
        The three calls below pass exactly these; a test holds the two
        together through a strict store."""
        import jax

        S, B, V = self.slots, self.table_blocks, self.vocab
        sds = jax.ShapeDtypeStruct
        i32, f32, u32 = np.int32, np.float32, np.uint32
        pools = self._pools_sig

        def tables(rows):
            if isinstance(B, dict):
                return {g: sds((rows, b), i32) for g, b in B.items()}
            return sds((rows, B), i32)

        return {
            "gen_sample": [(sds((V,), f32), sds((2,), u32), sds((), f32),
                            sds((), i32))],
            "gen_decode_paged": [(params, state,
                                  sds((self._next_width,), i32), pools,
                                  tables(S), sds((S,), i32),
                                  sds((S, 2), u32), sds((S,), f32),
                                  sds((S,), i32), sds((S,), np.bool_),
                                  sds((S,), i32), sds((S,), i32),
                                  sds((S, 2), u32))],
            "gen_prefill_chunk": [(params, state, sds((1, b), i32), pools,
                                   tables(1), sds((1,), i32),
                                   sds((), i32))
                                  + ((sds((1,), i32),) * 2
                                     if self.stateful else ())
                                  for b in self.chunk_buckets],
        }

    def warm(self, params, state) -> None:
        """With a store, load-or-compile the full static executable set —
        the sampler, the lifetime decode step, every prefill bucket — for
        one params generation, from abstract shapes: nothing executes,
        nothing is donated. Without a store there is nothing to do."""
        if not self._aot_fns:
            return
        for tag, lists in self.signatures(params, state).items():
            for operands in lists:
                self._aot_fns[tag].warm(*operands)

    def sample(self, logits, key, temperature: float, top_k: int):
        """One token (a device scalar) from one row of logits."""
        return self._sample(logits, key, np.float32(temperature),
                            np.int32(top_k))

    def prefill_chunk(self, params, state, tokens: np.ndarray, bucket: int,
                      table_row, off: int, slot: int = 0, load: int = -2):
        """Run ``tokens``, a prompt chunk at offset ``off`` right-padded to
        ``bucket``, through the slot whose table row is ``table_row``
        ``(1, table_blocks)`` (``{group: row}`` with more than one group).
        With a state group: the row is slot ``slot`` and its state starts
        from snapshot row ``load``, from zeros (-1), or goes on from the
        slot's own (-2). Returns the logits (1, V) at its last real token, on
        the device."""
        import jax
        import jax.numpy as jnp

        true_len = tokens.shape[0]
        ids = np.zeros((1, bucket), np.int32)
        # bucket is a member of chunk_buckets: the scheduler's _plan_chunks
        # emits no other width
        ids[0, :true_len] = tokens  # jaxlint: shape=ids:(1, bucket(_chunk_buckets))
        last, self.pools, *routing = self._prefill_chunk(
            params, state, jnp.asarray(ids), self.pools,
            jax.tree.map(jnp.asarray, table_row),
            np.full((1,), off, np.int32),
            np.int32(true_len),
            *((np.full((1,), slot, np.int32), np.full((1,), load, np.int32))
              if self.stateful else ()))
        self._routing_pending += routing
        return last

    def decode(self, params, state, tables, fresh=None):
        """One token for every slot whose table row is not zero. Each row's
        token, position and key are what the step before left on the
        device, but for the rows the host sets: ``fresh`` is None, or
        ``(mask, toks, pos, keys, temps, tks, first)`` — the host's slot
        vectors, read where ``mask`` (S,) is set (``temps`` and ``tks``
        whole: they replace the sampling vectors), and ``first``, ``{slot:
        (token, key)}``, the device values an admission left: the first
        token as the sampler's scalar (None once the host has read it into
        ``toks``) and the slot's key. Returns the device value ``next``: the
        ``slots`` tokens (and behind them what :meth:`decode_routing` and
        :meth:`decode_sums` read). Nothing is read back here, and a step
        that sets no row uploads the tables and nothing else."""
        import jax
        import jax.numpy as jnp

        sets = self._none_fresh
        if fresh is not None:
            mask, set_toks, set_pos, set_keys, temps, tks, first = fresh
            set_toks, set_keys = jnp.asarray(set_toks), jnp.asarray(set_keys)
            for s, (tok0, key) in first.items():
                # eager indexed updates, as copy_blocks' are: no jit site
                if tok0 is not None:
                    set_toks = set_toks.at[s].set(tok0)
                set_keys = set_keys.at[s].set(key)
            sets = (jnp.asarray(mask), set_toks, jnp.asarray(set_pos),
                    set_keys)
            self._sampling = (jnp.asarray(temps), jnp.asarray(tks))
        # fixed at boot: the slots, and behind them the routing sums' fields
        toks, pos, keys = self._carry  # jaxlint: shape=toks:(config)
        temps, tks = self._sampling
        mask, set_toks, set_pos, set_keys = sets
        nxt, self.pools, pos, keys = self._decode(
            params, state, toks, self.pools,
            jax.tree.map(jnp.asarray, tables), pos, keys, temps, tks,
            mask, set_toks, set_pos, set_keys)
        self._carry = (nxt, pos, keys)
        return nxt

    # ------------------------------------------------------------- routing
    def decode_routing(self, nxt: np.ndarray) -> np.ndarray:
        """The routing sums (ROUTING_FIELDS) of the decode step whose
        ``next`` was read back as ``nxt``; only for a model with experts."""
        return nxt[self.slots:self._next_width - len(self.sum_fields)]

    def decode_sums(self, nxt: np.ndarray) -> np.ndarray:
        """The sums the layers of that step name themselves
        (``sum_fields``); empty for a model without such layers."""
        return nxt[self._next_width - len(self.sum_fields):]

    def chunk_routing(self, n: int) -> List[np.ndarray]:
        """The sums of the ``n`` oldest prefill chunks not read yet. Call it
        only behind a readback of something the device computed after them
        (a step's tokens, a first token), so reading them waits for
        nothing."""
        sums = [np.asarray(s) for s in self._routing_pending[:n]]
        del self._routing_pending[:n]
        return sums

    # ---------------------------------------------------------------- pools
    def copy_blocks(self, pairs: List[tuple], group: str = FULL) -> None:
        """Copy-on-write device work: duplicate each ``(src, dst)`` block
        row in every pool of every layer of ``group`` (block ids are a
        group's own). Eager indexed updates — deliberately
        NOT a jit site, so the committed compile-surface budget (decode ==
        one executable) is untouched; the indices ride as device operands,
        so XLA's eager cache reuses one executable per pool shape."""
        src, dst = _index_pairs(pairs)
        for lk in self.group_layers[group]:
            pool = self.pools[lk]
            for n in pool:
                pool[n] = pool[n].at[dst].set(pool[n][src])

    def copy_states(self, pairs: List[tuple], current: bool = True) -> None:
        """Copy rows of the state group's pools, ``(src, dst)`` each: of the
        snapshot pools (a slot's boundary state kept as a snapshot), and with
        ``current`` of the slots' states too (a fork: the child's slot takes
        the parent's state and boundary state). Eager indexed updates, as
        :meth:`copy_blocks`; nothing is read back."""
        src, dst = _index_pairs(pairs)
        for lk in self.group_layers[STATE]:
            pool = self.pools[lk]
            for n in pool:
                if current or n.endswith("_snap"):
                    pool[n] = pool[n].at[dst].set(pool[n][src])

    def aot_functions(self) -> dict:
        """Tag -> :class:`~..aot.AotFunction` for every store-backed
        executable ({} without a store)."""
        return dict(self._aot_fns)
