"""Sparse KV: row-indexed push/pull on mesh-sharded embedding tables.

Reference workload config 4 (BASELINE.json: "sparse push/pull: Wide-&-Deep on
Criteo (row-sparse embedding tables)"; SURVEY.md §3 row 3, §4c). The GPU
reference's protocol is: workers send (row_ids, row_grads) to the servers
owning those rows (range-sharded), servers segment-sum duplicate rows and
scatter-apply with per-row optimizer state, pulls gather rows back.

TPU-native translation (north star: "sparse embedding row push/pull maps to
``lax.all_to_all`` row exchange"):

- The table [V, D] is **row-range-sharded** over the mesh's data axis
  (``NamedSharding(P('data', None))``) — the literal key→server range
  partition, as mesh shards.
- **pull / lookup** = ``jnp.take`` on the sharded table; under GSPMD, XLA
  partitions the gather and moves only the needed rows over ICI. On one
  chip the fused step pulls as the reference's worker does, the distinct
  rows of a batch only and each once (``plan_pull`` / ``lookup_distinct``),
  and its push takes those rows back (``apply_held``) in place of a second
  gather.
- **push / apply** = a ``shard_map`` program: worker-local (ids, row_grads)
  are exchanged to owner shards, duplicate rows are scatter-summed
  (segment-sum via ``.at[].add``), then a lazy row-wise optimizer
  (ps_tpu/optim/rowwise.py) applies only to touched rows.

Exchange modes for the push:

- ``'gather'`` (default, lossless): all-gather the (ids, grads) lists; each
  shard filters and applies its own rows. Per-device ICI bytes
  ≈ N·(D+1)·4·(k-1)/k — simple and exact.
- ``'a2a'``: capacity-bounded ``lax.all_to_all`` — duplicates merge locally
  first (pre-exchange segment-sum: a hot row travels ONCE per worker shard,
  which is what makes this path survive Criteo-like zipf skew — measured in
  BASELINE.md), then each device routes its unique rows into
  per-destination buckets of capacity C = ceil(N_local/k · capacity_factor);
  per-device bytes drop to ≈ k·C·(D+1)·4·(k-1)/k. Rows overflowing a bucket
  are **dropped** (standard embedding-capacity semantics; observable via
  :attr:`SparseEmbedding.dropped_rows`; set capacity_factor=k for provably
  lossless routing). Tests cover the merge, lossless, and drop behaviors.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ps_tpu import obs
from ps_tpu.api import current_context
from ps_tpu.obs import phases
from ps_tpu.ops.sparse_apply import (
    RowPlan,
    apply_plan,
    fused_sparse_apply,
    pair_segments,
    pull_plan,
    resolve_tier,
    row_plan,
    rows_of_pairs,
    segment_sums,
)
from ps_tpu.optim.rowwise import make_rowwise
from ps_tpu.parallel.mesh import DATA_AXIS


class PullPlan(NamedTuple):
    """What a step knows of one id list before its loss
    (:meth:`SparseEmbedding.plan_pull`): tables handed the same list share
    it."""
    rows: RowPlan         # the list deduped, ids the table lacks as filler
    pair_seg: jax.Array   # [N]: pair j's slot among the distinct rows
    ok: jax.Array         # [N] bool: pair j names a row of the table


class SparseEmbedding:
    """A row-sharded embedding table with PS sparse push/pull semantics.

    Args:
      num_rows: logical vocabulary size (internally padded up to a multiple
        of the mesh axis so every shard is even — the pad rows are
        unreachable by valid ids).
      dim: embedding dimension.
      optimizer: 'sgd' | 'adagrad' | 'adam' (lazy, per-row state) or a
        RowwiseOptimizer.
      exchange: 'gather' (lossless) | 'a2a' (capacity-bounded all_to_all).
      capacity_factor: 'a2a' only — per-destination bucket capacity multiple.
      dtype: table dtype (f32 default; bf16 halves pull bytes).
      fused_apply: which apply tier the scatter-apply routes through
        (README "Sparse apply"): 'off' = the legacy masked full-table
        apply, 'jax' = the batch-sized fused gather→apply→scatter
        (ps_tpu/ops/sparse_apply.py), 'auto' = 'jax'. None (default)
        inherits the backend's ``Config.fused_apply`` (PS_FUSED_APPLY).
    """

    def __init__(self, num_rows: int, dim: int, optimizer="adagrad",
                 exchange: str = "gather", capacity_factor: float = 2.0,
                 dtype=jnp.float32, mesh=None, axis: str = DATA_AXIS,
                 fused_apply: Optional[str] = None,
                 **opt_kwargs):
        if exchange not in ("gather", "a2a"):
            raise ValueError("exchange must be 'gather' or 'a2a'")
        ctx = current_context()
        self.mesh = mesh if mesh is not None else ctx.mesh
        if self.mesh is None:
            raise RuntimeError(
                "SparseEmbedding needs the mesh backend; ps_tpu.init(backend='tpu')"
            )
        self.axis = axis
        self.k = self.mesh.shape[axis]
        self.num_rows = num_rows
        self.padded_rows = int(math.ceil(num_rows / self.k) * self.k)
        self.rows_per_shard = self.padded_rows // self.k
        self.dim = dim
        self.dtype = dtype
        self.exchange = exchange
        self.capacity_factor = capacity_factor
        self._opt = make_rowwise(optimizer, **opt_kwargs)
        # fused apply tier (README "Sparse apply"): explicit arg wins;
        # otherwise the backend's Config.fused_apply
        if fused_apply is None:
            fused_apply = ctx.config.fused_apply
        self.fused_tier = resolve_tier(fused_apply)
        self._table: Optional[jax.Array] = None
        self._state: Any = None
        self._jit_apply = None   # cached jit wrappers: a fresh jax.jit per
        self._jit_lookup = None  # call would retrace every push/pull

        self.bytes_pushed = 0
        self.bytes_pulled = 0
        self.collective_bytes = 0
        self.push_count = 0
        self.rows_pushed = 0  # raw (id, gradient) pairs, filler included
        # per-row change stamps for the conditional read path (README
        # "Read path"): row i's last-touching push, in push_count units —
        # the same version the serving layer stamps on READ replies, so
        # a caller's known version v selects the delta rows directly
        # (row_version[i] > v == "changed since the caller's copy").
        # Host-side np like the directory arrays; not checkpointed —
        # restore stamps everything at push_count (conservatively "all
        # changed"), which can only widen a delta, never lose a row.
        self.row_version = np.zeros((num_rows,), np.int64)
        # what a push reports beside its rows, ``[dropped, applied]`` (see
        # :meth:`apply`): device values accumulate sync-free; reading
        # .dropped_rows or .rows_applied materializes them (read at logging
        # boundaries)
        self._counts_base = np.zeros((2,), np.int64)
        self._counts_pending: list = []
        # the same of the pushes whose distinct rows the step's pull held
        # (:meth:`apply_held`): their ``applied`` is also :attr:`rows_pulled`
        self._held_pending: list = []
        self._rows_pulled = 0

    def record_counts(self, counts, held: bool = False) -> None:
        """Accumulate one push's (possibly device-resident) ``[dropped,
        applied]`` counts without forcing a host sync on the hot path.
        Pending counts fold into one device value periodically so a long
        run that never reads :attr:`dropped_rows` or :attr:`rows_applied`
        holds O(1) buffers, not one per step: one fold for both counts.
        ``held``: the push applied to the rows its step's pull gathered,
        so the count of the one is the count of the other."""
        pending = self._held_pending if held else self._counts_pending
        pending.append(counts)
        if len(pending) >= 32:
            if getattr(pending[0], "is_ready", lambda: True)():
                # 31 pushes old and computed: its copy to the host waits
                # for nothing, and the int32 fold on the device never
                # holds more than 32 pushes' rows
                self._settle(pending.pop(0), held)
            total = pending[0]
            for x in pending[1:]:
                total = total + x  # device-side adds: still no host sync
            pending[:] = [total]

    def _settle(self, counts, held: bool) -> None:
        counts = np.asarray(counts, np.int64)
        self._counts_base += counts
        if held:
            self._rows_pulled += int(counts[1])

    def _read_counts(self) -> np.ndarray:
        for pending, held in ((self._counts_pending, False),
                              (self._held_pending, True)):
            while pending:
                self._settle(pending.pop(0), held)
        return self._counts_base

    @property
    def dropped_rows(self) -> int:
        """Total RAW pushed updates lost to a2a bucket overflow (0 under
        gather) — same units as :attr:`rows_pushed`: a dropped merged row
        reports every duplicate it carried. Tune ``capacity_factor`` until
        the rate is acceptable; reading this syncs any pending device
        counts. (Checkpoints from before the r3 dedupe stored the count in
        routed-row units; counts resumed from them mix units.)"""
        return int(self._read_counts()[0])

    @property
    def rows_applied(self) -> int:
        """Total DISTINCT rows the pushes wrote: each push counts a row
        once however many of its :attr:`rows_pushed` pairs named it, so
        ``rows_applied / rows_pushed`` is the live share of the push's
        slots (what the fused apply's loop walks; ops/sparse_apply.py).
        Not the sparse server's STATS ``rows_applied``, which counts raw
        pairs as :attr:`rows_pushed` does. Reading this syncs any pending
        device counts."""
        return int(self._read_counts()[1])

    @property
    def rows_pulled(self) -> int:
        """Total rows the store's pulls gathered out of the table: every
        (id, slot) pair of a :meth:`pull` or :meth:`lookup`, and the
        DISTINCT rows of a step that pulls over a plan
        (:meth:`lookup_distinct`), read from the count its push reports.
        ``rows_pulled / rows_pushed`` is the share of a step's slots its
        one gather serves; :attr:`bytes_pulled` goes on counting the rows
        the worker receives. Reading this syncs any pending device
        counts."""
        self._read_counts()
        return self._rows_pulled

    @property
    def dropped_fraction(self) -> float:
        """dropped_rows / rows_pushed (0.0 before any push)."""
        n = self.rows_pushed
        return (self.dropped_rows / n) if n else 0.0

    # -- placement -----------------------------------------------------------

    def _row_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis, None))

    def init(self, rng_or_table, scale: float = 0.01) -> jax.Array:
        """Create (or adopt) the table and per-row optimizer state, sharded
        row-range over the mesh. Returns the placed table."""
        if self._table is not None:
            raise RuntimeError("SparseEmbedding.init already called")
        with obs.tracer().program_span(
                phases.SETUP_TABLE_INIT, rows=self.num_rows, dim=self.dim,
                nbytes=self.rows_nbytes(self.padded_rows)):
            return self._make_table(rng_or_table, scale)

    def _make_table(self, rng_or_table, scale: float) -> jax.Array:
        is_prng_key = isinstance(rng_or_table, jax.Array) and jnp.issubdtype(
            rng_or_table.dtype, jax.dtypes.prng_key
        )
        if not is_prng_key and isinstance(rng_or_table, (jax.Array, np.ndarray)):
            arr = np.asarray(rng_or_table)
            if arr.shape != (self.num_rows, self.dim):
                raise ValueError(
                    f"table shape {arr.shape} != ({self.num_rows}, {self.dim})"
                )
            pad = self.padded_rows - self.num_rows
            if pad:
                arr = np.concatenate([arr, np.zeros((pad, self.dim), arr.dtype)])
            table = jnp.asarray(arr, self.dtype)
        else:
            table = scale * jax.random.normal(
                rng_or_table, (self.padded_rows, self.dim), self.dtype
            )
        self._table = jax.device_put(table, self._row_sharding())
        shard_init = shard_map(
            self._opt.init, mesh=self.mesh,
            in_specs=P(self.axis, None), out_specs=self._state_specs(),
        )
        self._state = jax.jit(shard_init)(self._table)
        return self._table

    def _state_specs(self):
        """PartitionSpecs of the optimizer state (row-major leaves shard on
        the table axis)."""
        probe = self._opt.init(jnp.zeros((self.k, self.dim), self.dtype))
        return jax.tree_util.tree_map(
            lambda leaf: P(self.axis, None) if getattr(leaf, "ndim", 0) > 1 else P(self.axis),
            probe,
        )

    # -- functional pieces (usable inside a fused jitted step) ---------------

    def lookup(self, table: jax.Array, ids: jax.Array) -> jax.Array:
        """rows = table[ids] — GSPMD partitions the gather over row shards.

        One gather of every (id, slot) pair, duplicates and all: the eager
        :meth:`pull`, and the fused step's pull where it cannot know the
        distinct rows before the loss (see :attr:`pulls_distinct`; there
        the step calls :meth:`lookup_distinct`, which returns these rows
        bit for bit).

        Valid ids are the caller's contract (synthetic data guarantees
        it). For an id the table does not have, ``jnp.take``'s default
        mode answers: a negative id counts from the table's end, anything
        else reads NaN."""
        with jax.named_scope(phases.LOOKUP):
            return jnp.take(table, ids, axis=0)

    def apply(self, table: jax.Array, state: Any, ids: jax.Array,
              row_grads: jax.Array) -> Tuple[jax.Array, Any, jax.Array]:
        """Scatter-apply summed row grads onto owner shards (pure function).

        ``ids``: [N] int32 (duplicates allowed), sharded or replicated.
        ``row_grads``: [N, D] grads w.r.t. the *gathered rows* (the sparse
        push payload — never a dense table grad).

        Returns ``(table, state, counts)`` — ``counts`` is int32
        ``[dropped, applied]``, both global and replicated: the real rows
        lost to a2a bucket overflow this push (always 0 for the lossless
        gather exchange; the observable signal ``capacity_factor`` is
        tuned from), and the distinct rows this push wrote.

        Apply tier (README "Sparse apply"): with ``fused_tier`` 'off'
        the owner shard builds a TABLE-SIZED ``gsum``/``cnt`` and the
        optimizer updates the whole shard under a mask (three-plus full
        HBM passes per push); 'jax' routes through
        :func:`~ps_tpu.ops.sparse_apply.fused_sparse_apply` — dedupe at
        batch size, gather only the touched rows + state, apply the
        dense-rows rule, scatter back, over the distinct rows of the
        batch and no further — so apply cost is O(distinct ids), not
        O(rows_per_shard). Same math by the parity contract.

        The eager :meth:`push`, and the fused step's push wherever its
        pull looked every pair up. Where the step pulls distinct rows
        (:attr:`pulls_distinct`) it calls :meth:`apply_held`, which is
        this on one shard with the dedupe made before the loss and the
        table's rows taken from the pull.
        """
        rps, dim, axis, k = self.rows_per_shard, self.dim, self.axis, self.k
        opt, tier = self._opt, self.fused_tier

        def shard_apply(table_shard, state_shard, ids_loc, grads_loc):
            with jax.named_scope(phases.ROW_EXCHANGE):
                if self.exchange == "gather" or k == 1:
                    all_ids = jax.lax.all_gather(ids_loc, axis, tiled=True)
                    all_grads = jax.lax.all_gather(grads_loc, axis,
                                                   tiled=True)
                    dropped = jnp.int32(0)  # gather is lossless
                else:
                    all_ids, all_grads, dropped = _a2a_route(
                        ids_loc, grads_loc, k, axis, rps,
                        self.capacity_factor
                    )
            lo = jax.lax.axis_index(axis) * rps
            local = all_ids - lo
            ok = (local >= 0) & (local < rps)
            if tier == "off":
                slot = jnp.where(ok, local, rps)  # overflow slot, sliced off
                g = jnp.where(ok[:, None], all_grads, 0).astype(jnp.float32)
                gsum = jnp.zeros((rps + 1, dim),
                                 jnp.float32).at[slot].add(g)[:-1]
                cnt = jnp.zeros((rps + 1,), jnp.int32).at[slot].add(
                    ok.astype(jnp.int32))[:-1]
                new_table, new_state = opt.apply(
                    table_shard, state_shard, gsum, cnt > 0
                )
                applied = jnp.sum(cnt > 0, dtype=jnp.int32)
            else:
                ids_m = jnp.where(ok, local, -1)
                g = jnp.where(ok[:, None], all_grads, 0).astype(jnp.float32)
                new_table, new_state, applied = fused_sparse_apply(
                    table_shard, state_shard, ids_m, g, opt
                )
            # one reduction for both counts: global, replicated
            counts = jax.lax.psum(jnp.stack([dropped, applied]), axis)
            return new_table, new_state, counts

        state_specs = self._state_specs()
        fn = shard_map(
            shard_apply, mesh=self.mesh,
            in_specs=(P(axis, None), state_specs, P(axis), P(axis, None)),
            out_specs=(P(axis, None), state_specs, P()),
        )
        with jax.named_scope(phases.ROW_APPLY):
            return fn(table, state, ids, row_grads)

    # -- the same two over the distinct rows (the fused step on one chip) -----

    @property
    def pulls_distinct(self) -> bool:
        """Whether a fused step (ps_tpu/kv/fused.py) pulls each distinct row
        of a batch once — :meth:`plan_pull`, :meth:`lookup_distinct`,
        :meth:`apply_held` — in place of :meth:`lookup` + :meth:`apply`. Read
        from what the store is: on one shard the owner's distinct rows are
        the batch's, known from the ids alone; across shards they are known
        only after the row exchange, and the 'off' tier promises the legacy
        program."""
        return self.k == 1 and self.fused_tier != "off"

    def plan_pull(self, ids: jax.Array) -> PullPlan:
        """The dedupe of a step's ``ids`` (any shape), made before the loss:
        the reference's worker sorts its keys, pulls the distinct ones and
        pushes the same list. An id outside the table is filler to the plan,
        as it is to :meth:`apply`'s owner mask."""
        ids = ids.reshape(-1)
        with jax.named_scope(phases.ROW_DEDUPE):
            ok = (ids >= 0) & (ids < self.rows_per_shard)
            masked = jnp.where(ok, ids, -1)
        rows = row_plan(masked, self.rows_per_shard)
        return PullPlan(rows, pair_segments(rows)[:ids.shape[0]], ok)

    def lookup_distinct(self, table: jax.Array, ids: jax.Array,
                        plan: PullPlan) -> Tuple[jax.Array, jax.Array]:
        """:meth:`lookup`'s rows, each distinct row gathered from the table
        once: ``(rows, held)``, ``held`` being the distinct rows in the
        plan's order (:func:`~ps_tpu.ops.sparse_apply.pull_plan`), which
        :meth:`apply_held` takes back, and ``rows`` their expansion to the
        pairs, a gather out of that batch-sized buffer. ``plan`` is
        :meth:`plan_pull` of the same ``ids``."""
        held = pull_plan(table, plan.rows)
        with jax.named_scope(phases.LOOKUP):
            rows = rows_of_pairs(held, plan.pair_seg).reshape(
                ids.shape + (self.dim,))
            # an id the table lacks has no row among the distinct ones: the
            # loss reads for it what lookup reads, at lookup's price, and
            # only in a step that meets one
            rows = jax.lax.cond(
                jnp.all(plan.ok), lambda rows: rows,
                lambda rows: jnp.where(
                    plan.ok.reshape(ids.shape)[..., None], rows,
                    self.lookup(table, ids)), rows)
        return rows, held

    def apply_held(self, table: jax.Array, state: Any, plan: PullPlan,
                   held: jax.Array, row_grads: jax.Array
                   ) -> Tuple[jax.Array, Any, jax.Array]:
        """:meth:`apply` of the planned ids' ``row_grads`` [N, D] to the rows
        :meth:`lookup_distinct` holds: the same sums in the same order, the
        same rule and the same scatters, and no second gather of the table,
        which nothing has written in between. Returns what :meth:`apply`
        returns, bit for bit."""
        with jax.named_scope(phases.ROW_APPLY):
            g = jnp.where(plan.ok[:, None], row_grads, 0).astype(jnp.float32)
            table, state, applied = apply_plan(
                table, state, plan.rows, segment_sums(plan.rows, g),
                self._opt, held)
            # one shard and no exchange: nothing to drop, nothing to reduce
            return table, state, jnp.stack([jnp.int32(0), applied])

    # -- eager PS API (the reference's worker-side protocol surface) ---------

    @property
    def table(self) -> jax.Array:
        if self._table is None:
            raise RuntimeError("SparseEmbedding.init not called")
        return self._table

    def pull(self, ids) -> jax.Array:
        """Gather current rows for ids (the sparse pull)."""
        ids = jnp.asarray(ids, jnp.int32)
        if self._jit_lookup is None:
            self._jit_lookup = jax.jit(self.lookup)
        rows = self._jit_lookup(self.table, ids)
        self.count_pull(ids.size)
        return rows

    def rows_nbytes(self, n_ids: int) -> int:
        """Bytes of ``n_ids`` rows in the table's dtype: what a lookup
        returns, and what the gradient with respect to it weighs."""
        return n_ids * self.dim * np.dtype(self.dtype).itemsize

    def count_pull(self, n_ids: int, held: bool = False) -> None:
        """Count a lookup of ``n_ids`` rows: :meth:`pull`'s, or that of a
        program that gathers the rows itself (ps_tpu/kv/fused.py).
        ``held``: a :meth:`lookup_distinct`, whose gathered rows are counted
        when its push reports them (:meth:`adopt_push`)."""
        self.bytes_pulled += self.rows_nbytes(n_ids)
        if not held:
            self._rows_pulled += n_ids

    def push(self, ids, row_grads) -> None:
        """Send (ids, row_grads); server scatter-applies immediately."""
        # change stamps from the caller's raw id list (before padding):
        # every real row this push touches carries the post-increment
        # push_count — see row_version in __init__
        np_ids = np.asarray(ids, np.int64).reshape(-1)
        touched = np_ids[(np_ids >= 0) & (np_ids < self.num_rows)]
        ids = jnp.asarray(ids, jnp.int32)
        row_grads = jnp.asarray(row_grads)
        if row_grads.shape != (ids.shape[0], self.dim):
            raise ValueError(
                f"row_grads shape {row_grads.shape} != ({ids.shape[0]}, {self.dim})"
            )
        if ids.shape[0] % self.k:
            # shard_map shards the push list over the axis; pad to a multiple
            # with id -1, which the owner-shard ok-mask drops (same filler
            # convention as a2a overflow) so no real row is marked touched
            pad = self.k - ids.shape[0] % self.k
            ids = jnp.concatenate([ids, jnp.full((pad,), -1, jnp.int32)])
            row_grads = jnp.concatenate(
                [row_grads, jnp.zeros((pad, self.dim), row_grads.dtype)]
            )
        if self._jit_apply is None:
            # fused tiers donate like the fused step (ps_tpu/kv/fused.py):
            # the old table/state buffers die with the call, so the
            # batch-sized scatter is a true in-place update instead of a
            # full-table output copy (references from earlier pull()s are
            # row COPIES and stay valid; init()'s returned placement is
            # superseded by .table, as the composite step already assumes).
            # The 'off' tier does NOT donate: PS_FUSED_APPLY=off promises
            # today's exact behavior, buffer lifetimes included — a caller
            # holding .table across a push keeps a readable array there.
            donate = (0, 1) if self.fused_tier != "off" else ()
            self._jit_apply = jax.jit(self.apply, donate_argnums=donate)
        self.adopt_push(
            *self._jit_apply(self.table, self._state, ids, row_grads),
            ids.shape[0], row_grads.nbytes)
        self.row_version[touched] = self.push_count

    def adopt_push(self, table: jax.Array, state: Any, counts,
                   n_ids: int, nbytes: int, held: bool = False) -> None:
        """Take over what one :meth:`apply` (``held``: :meth:`apply_held`)
        of ``n_ids`` row gradients weighing ``nbytes`` returned, and count
        it: the tail of :meth:`push` and of the fused step
        (ps_tpu/kv/fused.py). ``counts`` may stay on the device.
        ``row_version`` is not stamped here: only :meth:`push` has the ids
        on the host."""
        self._table, self._state = table, state
        self.record_counts(counts, held)  # sync-free; read at log time
        self.bytes_pushed += nbytes
        self.push_count += 1
        # arithmetic only — each routed row is (id:int32 + dim f32 grads)
        self.rows_pushed += n_ids
        row_bytes = 4 * (self.dim + 1)
        if self.k <= 1:
            return
        if self.exchange == "gather":
            payload = n_ids * row_bytes
        else:
            cap = int(math.ceil(n_ids / self.k / self.k * self.capacity_factor))
            payload = self.k * cap * row_bytes
        self.collective_bytes += int(payload * (self.k - 1) / self.k)

    def state(self):
        return self._state

    # -- tiered row movement (ps_tpu/kv/tiered.py) ---------------------------

    def export_rows(self, slots) -> Tuple[np.ndarray, list]:
        """Copy ``slots``' rows AND their per-row optimizer state out to
        host memory — the demotion half of the what-moves-with-a-row
        contract (README "Tiered embedding storage"): a row never travels
        without its state. Returns ``(rows [n, D], state_leaves)`` with
        the leaves in ``jax.tree_util`` order, each sliced to ``slots``."""
        slots = jnp.asarray(slots, jnp.int32)
        rows = np.asarray(jnp.take(self.table, slots, axis=0))
        leaves = [np.asarray(jnp.take(leaf, slots, axis=0))
                  for leaf in jax.tree_util.tree_leaves(self._state)]
        return rows, leaves

    def adopt_rows(self, slots, rows, state_leaves) -> None:
        """Scatter host rows + their per-row optimizer state into
        ``slots`` — the promotion half of :meth:`export_rows`. The slab
        is batch-sized, so a promotion costs O(moved rows), not a table
        pass."""
        slots = jnp.asarray(slots, jnp.int32)
        self._table = self.table.at[slots].set(
            jnp.asarray(rows, self.dtype))
        flat, treedef = jax.tree_util.tree_flatten(self._state)
        flat = [leaf.at[slots].set(jnp.asarray(v, leaf.dtype))
                for leaf, v in zip(flat, state_leaves)]
        self._state = jax.tree_util.tree_unflatten(treedef, flat)

    def adopt_state(self, table: jax.Array, state: Any) -> None:
        """Adopt an externally restored (table, state) pair — the tiered
        checkpoint path restores both tiers from ONE atomic snapshot and
        hands the hot tier back through here."""
        if self._table is None:
            raise RuntimeError("SparseEmbedding.init must precede adopt_state")
        self._table, self._state = table, state

    # -- checkpoint/resume ---------------------------------------------------

    def save(self, path: str) -> None:
        """Checkpoint the row-sharded table + per-row optimizer state (the
        reference server's sparse-table state; SURVEY.md §6)."""
        from ps_tpu import checkpoint as ckpt

        arrays = {
            "table": self.table,
            "opt": ckpt.flatten_leaves(self._state),
        }
        meta = {
            "engine": "sparse",
            "num_rows": self.num_rows,
            "dim": self.dim,
            "dtype": jnp.dtype(self.dtype).name,
            "push_count": self.push_count,
            "bytes_pushed": self.bytes_pushed,
            "bytes_pulled": self.bytes_pulled,
            "collective_bytes": self.collective_bytes,
            "rows_pushed": self.rows_pushed,
            "dropped_rows": self.dropped_rows,
            "rows_applied": self.rows_applied,
            "rows_pulled": self.rows_pulled,
        }
        ckpt.save(path, arrays, meta)

    def restore(self, path: str) -> jax.Array:
        """Restore a checkpoint written by :meth:`save`. Call after ``init``
        (same num_rows/dim/optimizer/mesh) — the restored shards land
        directly on the live row sharding. Returns the restored table."""
        from ps_tpu import checkpoint as ckpt

        if self._table is None:
            raise RuntimeError("SparseEmbedding.init must be called before restore")
        meta = ckpt.read_meta(path)
        if meta.get("engine") != "sparse":
            raise ValueError(
                f"checkpoint was written by engine {meta.get('engine')!r}, "
                f"not a sparse table"
            )
        if (meta["num_rows"], meta["dim"]) != (self.num_rows, self.dim):
            raise ValueError(
                f"checkpoint table is ({meta['num_rows']}, {meta['dim']}), "
                f"this embedding is ({self.num_rows}, {self.dim})"
            )
        if meta["dtype"] != jnp.dtype(self.dtype).name:
            raise ValueError(
                f"checkpoint table dtype is {meta['dtype']}, this embedding "
                f"is {jnp.dtype(self.dtype).name} — restore would silently cast"
            )
        abstract = {
            "table": ckpt.abstract_like(self.table),
            "opt": ckpt.abstract_like(ckpt.flatten_leaves(self._state)),
        }
        arrays = ckpt.restore(path, abstract, meta)
        self._table = arrays["table"]
        self._state = ckpt.unflatten_like(self._state, arrays["opt"])
        self.push_count = int(meta["push_count"])
        # change stamps are not checkpointed: mark every row changed at
        # the restored version — a conditional reader's delta can only
        # widen to "everything", never miss a row
        self.row_version[:] = self.push_count
        self.bytes_pushed = int(meta["bytes_pushed"])
        self.bytes_pulled = int(meta["bytes_pulled"])
        self.collective_bytes = int(meta["collective_bytes"])
        self.rows_pushed = int(meta.get("rows_pushed", 0))
        self._counts_base = np.array(
            [meta.get("dropped_rows", 0), meta.get("rows_applied", 0)],
            np.int64)
        self._counts_pending, self._held_pending = [], []
        self._rows_pulled = int(meta.get("rows_pulled", 0))
        return self._table


def _dedupe_rows(ids, grads):
    """Per-worker pre-exchange dedupe: sum duplicate ids' grads into their
    first occurrence; duplicates become filler (-1, zero grad). Scatter-add
    is what the owner shard would do anyway (accumulated in f32 here like
    there; for sub-f32 transport dtypes the merged row is rounded ONCE back
    to the wire dtype — within one rounding of the gather path). Capacity
    then counts UNIQUE rows, which is what makes the a2a exchange survive
    skewed (Criteo/zipf) id distributions: the hot row that used to
    overflow its bucket N times now travels once (measured in BASELINE.md).

    Returns ``(ids_u, grads_u, counts_u)`` — ``counts_u`` is the number of
    RAW pushed rows each surviving unique row represents (0 on filler), so
    overflow accounting can report lost UPDATES in the same units as
    ``rows_pushed``."""
    if ids.shape[0] == 0:  # empty per-shard push: nothing to merge
        return ids, grads, jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(ids)
    ids_s, grads_s = ids[order], grads[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]]
    )
    seg = jnp.cumsum(first) - 1  # segment index per sorted row
    summed = jnp.zeros(grads_s.shape, jnp.float32).at[seg].add(
        grads_s.astype(jnp.float32)
    )
    seg_count = jnp.zeros(ids_s.shape, jnp.int32).at[seg].add(1)
    ids_u = jnp.where(first, ids_s, -1)
    grads_u = jnp.where(
        first[:, None], summed[seg], 0
    ).astype(grads.dtype)
    counts_u = jnp.where(first, seg_count[seg], 0)
    return ids_u, grads_u, counts_u


def _a2a_route(ids, grads, k: int, axis: str, rows_per_shard: int,
               capacity_factor: float):
    """Route (ids, grads) into capacity-bounded per-destination buckets and
    lax.all_to_all them to owner shards. Duplicates merge locally first
    (:func:`_dedupe_rows`); overflow rows are dropped (their bucket slots
    stay id=-1 / grad=0)."""
    ids, grads, counts = _dedupe_rows(ids, grads)
    n = ids.shape[0]
    cap = int(math.ceil(n / k * capacity_factor))
    # filler ids (-1: push padding and merged duplicates) go to overflow
    # destination k — the scatter's mode='drop' discards them — so they
    # never consume shard 0's bucket capacity
    dest = jnp.where(ids < 0, k, jnp.clip(ids // rows_per_shard, 0, k - 1))
    # slot of each row within its destination bucket = rank among same-dest rows
    order = jnp.argsort(dest)  # stable: groups rows by destination
    ids_s, grads_s, dest_s = ids[order], grads[order], dest[order]
    counts_s = counts[order]
    pos = jnp.arange(n) - jnp.searchsorted(dest_s, dest_s, side="left")
    keep = pos < cap
    # observability: RAW pushed updates whose merged row overflowed (filler
    # excluded; counts carry each unique row's multiplicity so the number
    # shares units with rows_pushed) — the visible signal capacity_factor
    # is tuned from (VERDICT r2 item 5)
    dropped = jnp.sum(
        jnp.where((~keep) & (dest_s < k), counts_s, 0)
    ).astype(jnp.int32)
    bucket_ids = jnp.full((k, cap), -1, ids.dtype)
    bucket_grads = jnp.zeros((k, cap) + grads.shape[1:], grads.dtype)
    bucket_ids = bucket_ids.at[dest_s, pos].set(
        jnp.where(keep, ids_s, -1), mode="drop")
    bucket_grads = bucket_grads.at[dest_s, pos].set(
        jnp.where(keep[:, None], grads_s, 0), mode="drop")
    # exchange: device d receives every device's bucket for destination d
    recv_ids = jax.lax.all_to_all(bucket_ids, axis, 0, 0, tiled=True)
    recv_grads = jax.lax.all_to_all(bucket_grads, axis, 0, 0, tiled=True)
    return (recv_ids.reshape(-1),
            recv_grads.reshape((-1,) + grads.shape[1:]),
            dropped)
