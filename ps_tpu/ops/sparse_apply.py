"""Fused sparse embedding update: gather → optimizer-apply → scatter,
batch-sized, in one HBM pass (ROADMAP item 6; SURVEY §8 P3).

Why: the legacy sparse apply (``ps_tpu/kv/sparse.py`` ``shard_apply``)
pays three-plus full-table HBM passes per push — two ``zeros().at[].add``
scatter-sums building a TABLE-SIZED ``gsum``/``cnt``, then the row-wise
optimizer updates the ENTIRE shard under a ``touched`` mask. Apply cost
is O(num_rows) even when a batch touches 0.1% of rows — exactly the
regime out-of-HBM tiered tables (ROADMAP item 3) will live in. This
module makes apply cost O(batch): dedupe/segment-sum the pushed ids at
BATCH size into a compact list, the distinct rows in front; gather only
those rows and their per-row optimizer state, apply the dense-rows rule
(``RowwiseOptimizer.apply_rows``), and scatter rows+state back, in a loop
that stops at the last distinct row: a slot that names no row (a
duplicate's, filler's) costs a gather and a scatter what a live one does
on the TPU, and two thirds of a Criteo-like batch's slots name none.

The dedupe needs the ids only (:func:`row_plan`); the gradients come in
with :func:`segment_sums`. A step that has the ids before its loss makes
the plan there and pulls with it (:func:`pull_plan`: the same walk, each
distinct row gathered once into a batch-sized buffer the loss reads through
:func:`pair_segments`), and its push (:func:`apply_plan`) reads the table's
rows out of that buffer: one gather a table serves the pull and the push
(``kv/fused.py``, on one chip). :func:`fused_sparse_apply` is the three in a
row for a push that stands alone.

Two tiers, selected by ``PS_FUSED_APPLY`` (``Config.fused_apply``,
``off|jax|auto``; README "Sparse apply"):

- ``jax`` — the batch-sized path: chunk by chunk over the distinct rows,
  take/gather rows + state, ``apply_rows``, ``.at[].set(mode='drop')``
  scatter, every index list stated sorted and unique (the last chunk's
  filler tail redirects out of range and drops). Traffic and slots
  O(distinct rows), XLA-scheduled.
- ``off`` — the legacy masked full-table path, byte-for-byte today's
  behavior (the caller keeps its own code path; this module is not
  involved).

A third, hand-written tier (a Pallas kernel DMA-ing one ``[1, D]`` row at
a time out of the HBM-resident table) was removed in PR 21: libtpu 0.0.34's
Mosaic lays an HBM operand out in 128-lane tiles and refuses a row slice
narrower than that ("Slice shape along dimension 1 must be aligned to
tiling (128), but is 16" — and 1 for the wide table), and padding D to
128 lanes would cost 8x (D=16) to 128x (D=1) the table's memory.

Numerical contract (tests/test_sparse_apply.py): the fused tier matches
the masked full-table apply bitwise for SGD/Adagrad where the duplicate
reduction order is fixed (stable-sorted segments sum duplicates in
arrival order — the same order the full path's scatter-add applies
them), and within 1e-6 relative for Adam, across dup-heavy / empty /
all-rows id distributions.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ps_tpu.obs import phases

TIERS = ("off", "jax")


def resolve_tier(requested: Optional[str]) -> str:
    """Normalize a ``PS_FUSED_APPLY`` value to a concrete tier.

    ``auto`` (or None) is ``jax`` on every platform: it is the only fused
    tier, and the one chip_smoke.py compiles and checks against 'off' on
    the TPU. Unknown values fail loudly: a typo'd knob must not silently
    select 'off'.
    """
    if requested is None or requested == "auto":
        return "jax"
    if requested not in TIERS:
        raise ValueError(
            f"unknown fused-apply tier {requested!r}; use "
            f"'off', 'jax' or 'auto'")
    return requested


class RowPlan(NamedTuple):
    """What a list of ids says about a push before any gradient exists
    (:func:`row_plan`). Every array has the list's padded length ``Np``."""
    order: jax.Array     # sorted slot -> the pair it holds (arrival position)
    seg: jax.Array       # sorted slot -> its segment: a distinct id's run
    cnt: jax.Array       # segment -> its real pairs, 0 from segment U on
    uids: jax.Array      # segment -> its id, ascending; -1 from U on
    n_unique: jax.Array  # U, int32 scalar
    idx: jax.Array       # what the loop gathers and scatters at: ``uids``,
    #                      filler sent to ``num_rows + position``
    chunk: int           # C, the slots of one iteration; divides Np


def row_plan(ids: jax.Array, num_rows: int) -> RowPlan:
    """Batch-sized dedupe of a push's ids, for a table of ``num_rows``: the
    half of the push that needs no gradient, so that a step can make it
    before its loss and gather only the distinct rows (:func:`pull_plan`).

    ``ids`` [N] int32, N > 0, with duplicates and negative filler (-1)
    allowed; padded here with filler to whole chunks of
    ``chunk_len(N)``, so that no slice of the loop is clamped onto
    another. Segments ``0..U-1`` are the distinct real ids in ascending
    order; every segment from ``U`` on is filler. The table never
    appears: this is the O(batch) twin of the legacy table-sized
    ``zeros(rps).at[slot].add`` build."""
    c = chunk_len(ids.shape[0])
    if ids.shape[0] % c:
        ids = jnp.pad(ids, (0, -ids.shape[0] % c), constant_values=-1)
    n = ids.shape[0]
    with jax.named_scope(phases.ROW_DEDUPE):
        # read as unsigned, filler sorts behind every real id: one sort
        # leaves the distinct real ids as segments 0..U-1. Stable, so a
        # row's duplicates keep arrival order.
        key_s, order = jax.lax.sort_key_val(
            jax.lax.bitcast_convert_type(ids, jnp.uint32),
            jnp.arange(n, dtype=jnp.int32))
        first = jnp.concatenate(
            [jnp.ones((1,), bool), key_s[1:] != key_s[:-1]])
        seg = jnp.cumsum(first) - 1
        real = key_s <= jnp.uint32(2**31 - 1)
        n_unique = jnp.sum(first & real, dtype=jnp.int32)
        # segment i's count lands in slot i: already compact
        cnt = jnp.zeros((n,), jnp.int32).at[seg].add(
            real.astype(jnp.int32), indices_are_sorted=True)
        # and its id: the first of each real run, everything else sent
        # behind them by a second sort of N keys (0.1 ms on the chip, where
        # a scatter of N ids costs 0.9)
        uids = jax.lax.bitcast_convert_type(
            jnp.sort(jnp.where(first & real, key_s, jnp.uint32(2**32 - 1))),
            jnp.int32)
        pos = jnp.arange(n, dtype=jnp.int32)
        uids = jnp.where(pos < n_unique, uids, -1)
        idx = jnp.where(pos < n_unique, uids, num_rows + pos)
    return RowPlan(order, seg, cnt, uids, n_unique, idx, c)


def segment_sums(plan: RowPlan, grads: jax.Array) -> jax.Array:
    """The half of the push that needs the gradients: ``grads`` [N, D] of
    the pairs ``plan`` was made of, summed by segment in f32 in
    stable-sorted arrival order (the fixed reduction order the bitwise
    parity contract names). [Np, D]: segment i's sum in slot i, the zero
    grads filler carries from ``U`` on."""
    n = plan.order.shape[0]
    if grads.shape[0] != n:
        grads = jnp.pad(grads, ((0, n - grads.shape[0]), (0, 0)))
    with jax.named_scope(phases.ROW_DEDUPE):
        grads_s = grads[plan.order].astype(jnp.float32)
        # The hint changes no bit of a sum (the chip, PR 31: both tables
        # of the cell).
        return jnp.zeros(grads_s.shape, jnp.float32).at[plan.seg].add(
            grads_s, indices_are_sorted=True)


def segment_sum_np(ids, grads):
    """Host twin of :func:`row_plan` + :func:`segment_sums` for the tiered cold path
    (ps_tpu/kv/tiered.py): dedupe a push's (ids, grads) on the CPU before
    gathering the touched rows from the DRAM arena. Same reduction
    discipline — duplicates sum in f32 in arrival order (``np.add.at``
    accumulates sequentially) — so a row's gsum is the number the device
    paths would have produced. Returns compact ``(uids [U], gsum [U, D]
    f32, cnt [U])`` with filler (-1) ids dropped entirely: the cold slab
    is sized by unique touched rows, nothing else."""
    import numpy as np

    ids = np.asarray(ids, np.int32).reshape(-1)
    grads = np.asarray(grads).reshape(ids.shape[0], -1)
    real = ids >= 0
    ids, grads = ids[real], grads[real]
    if ids.size == 0:
        return (ids, np.zeros((0, grads.shape[1]), np.float32),
                np.zeros((0,), np.int32))
    uids, inv, cnt = np.unique(ids, return_inverse=True,
                               return_counts=True)
    gsum = np.zeros((uids.size, grads.shape[1]), np.float32)
    np.add.at(gsum, inv, grads.astype(np.float32))
    return uids, gsum, cnt.astype(np.int32)


#: Chunk length of the apply loop, from the push's length N alone:
#: ``ceil(N / _CHUNK_PARTS)`` slots, at least ``_CHUNK_FLOOR``, in whole
#: sublanes of 8 and never more than N. Measured on the chip (PR 31, PERF.md
#: §6; f32[33800000,32] with Adagrad, N = 106,496): an iteration's fixed cost
#: is below what a run resolves (the same 36,864 slots as 18 chunks of 2,048
#: or 9 of 4,096: 9.005 against 9.004 ms a push), so a chunk only has to be
#: short against N: the filler tail of the last chunk is C / 2 slots on
#: average at 0.16 us a slot, under 2% of an all-distinct push at N / 32
#: (8.45 ms a push on the cell's ids at N / 32, 8.92 at N / 16, 9.56 at
#: N / 8). The floor keeps a short push (the eager ``push``, a rehearsal) in
#: one chunk or two: no chunk under 2,048 slots was measured.
_CHUNK_PARTS = 32
_CHUNK_FLOOR = 1024


def chunk_len(n: int) -> int:
    """Slots one iteration of :func:`fused_sparse_apply`'s loop applies,
    for a push of ``n`` (ids, grads) pairs."""
    c = max(_CHUNK_FLOOR, -(-n // _CHUNK_PARTS))
    return min(-(-c // 8) * 8, -(-n // 8) * 8)


_HINTS = dict(indices_are_sorted=True, unique_indices=True)


def _as_vector(leaf: jax.Array) -> jax.Array:
    """A leaf one scalar wide as the vector the TPU's compiler gathers from
    and scatters into anyway (:func:`fused_sparse_apply`)."""
    return leaf.reshape(-1) if leaf.shape[1:] == (1,) else leaf


def _walk(plan: RowPlan, chunk_fn, carry):
    """``chunk_fn(lo, at, carry)`` over the chunks of the plan's distinct
    rows, ``at`` being ``plan.idx[lo:lo + C]``: ``ceil(U / C)`` iterations,
    read from the data; a short list is one chunk and no loop to stop
    early."""
    c = plan.chunk

    def body(i, carry):
        return chunk_fn(i * c, jax.lax.dynamic_slice_in_dim(plan.idx, i * c, c),
                        carry)

    if plan.idx.shape[0] == c:
        return body(0, carry)
    return jax.lax.fori_loop(0, -(-plan.n_unique // c), body, carry)


def pull_plan(table: jax.Array, plan: RowPlan) -> jax.Array:
    """The distinct rows of ``plan`` out of ``table``, segment i's row in
    slot i of an ``[Np, D]`` buffer (``[Np]`` for a table one scalar wide):
    the sparse pull of a worker that sorted its keys first. The walk is the
    push's (:func:`fused_sparse_apply`): ``C`` rows a gather, ascending and
    distinct and stated so, and no gather past the last distinct row, so a
    slot that names none costs nothing (one ``take`` of all Np slots would
    pay a filler slot what it pays a live one). Slots past the last chunk
    walked are zero; the filler tail of that chunk holds the table's last
    row, which is what the push's own gather would read there."""
    leaf = _as_vector(table)
    with jax.named_scope(phases.LOOKUP):
        held = jnp.zeros(plan.idx.shape + leaf.shape[1:], leaf.dtype)

        def pull_chunk(lo, at, held):
            rows = jnp.take(leaf, at, axis=0, mode="clip", **_HINTS)
            return jax.lax.dynamic_update_slice_in_dim(held, rows, lo, 0)

        return _walk(plan, pull_chunk, held)


def pair_segments(plan: RowPlan) -> jax.Array:
    """``[Np]``: the segment of pair j, in arrival order: where
    :func:`pull_plan` put the row pair j names. The plan's sort undone by a
    sort (the permutation as keys), not by a scatter: a sort of N keys is
    0.1 ms on the chip, a scatter of N ids 0.9 (:func:`row_plan`)."""
    with jax.named_scope(phases.LOOKUP):
        return jax.lax.sort_key_val(plan.order, plan.seg)[1]


def rows_of_pairs(held: jax.Array, pair_seg: jax.Array) -> jax.Array:
    """``held[pair_seg]``: the rows :func:`pull_plan` holds, expanded to the
    pairs (:func:`pair_segments`), ``[N, D]``, or ``[N]`` out of a vector.
    A gather out of a batch-sized buffer: 2.4 ns a slot on the chip at
    D = 32 (0.26 ms for 106,496 pairs, where the same pairs out of the
    33.8M-row table cost 3.9; PR 55). Out of a vector the TPU's gather
    costs 8.8 ns a slot (0.94 ms), out of rows eight wide 2.6 (0.27): a
    vector is read as rows of one sublane, every lane the same value."""
    with jax.named_scope(phases.LOOKUP):
        if held.ndim == 1:
            return jnp.take(jnp.broadcast_to(held[:, None], held.shape + (8,)),
                            pair_seg, axis=0, mode="clip")[:, 0]
        return jnp.take(held, pair_seg, axis=0, mode="clip")


def fused_sparse_apply(table: jax.Array, state: Any, ids: jax.Array,
                       grads: jax.Array, opt
                       ) -> Tuple[jax.Array, Any, jax.Array]:
    """THE entry point every fused sparse apply routes through
    (``kv/sparse``'s shard_apply, and through it the remote sparse server
    and the mesh backend). ``ids`` [N] are SHARD-LOCAL row indices with -1
    filler (out-of-range/padding already masked by the caller), ``grads``
    [N, D] with filler rows zeroed. Returns the updated ``(table, state)``
    and ``U``, the number of distinct rows written (int32 scalar).

    :func:`row_plan`, :func:`segment_sums` and :func:`apply_plan`, one after
    the other. A step that makes the plan before its loss and pulls with it
    (:func:`pull_plan`; ``kv/fused.py`` on one chip) calls the three itself
    and hands ``apply_plan`` the rows it holds."""
    if ids.shape[0] == 0:  # empty push: nothing gathered, nothing written
        return table, state, jnp.int32(0)
    plan = row_plan(ids, table.shape[0])
    return apply_plan(table, state, plan, segment_sums(plan, grads), opt)


def apply_plan(table: jax.Array, state: Any, plan: RowPlan, gsum: jax.Array,
               opt, held: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Any, jax.Array]:
    """The push over a plan: ``gsum`` (:func:`segment_sums`) applied to the
    plan's distinct rows of ``table`` and ``state``. With ``held``
    (:func:`pull_plan`'s result, and no write to the table since) the
    table's rows are read out of it, a slice a chunk, and only the state is
    gathered again. Returns the updated ``(table, state)`` and ``U``.

    Only the touched rows' bytes move, and only their slots are paid for:
    :func:`row_plan` leaves the ``U`` distinct rows of the push in
    front, and a loop of ``ceil(U / C)`` iterations, read from the data,
    walks that prefix ``C = chunk_len(N)`` slots at a time: gather ->
    ``apply_rows`` -> scatter. Inside a chunk the ids are ascending and
    distinct, which the gathers and the scatters state; the filler tail of
    the last chunk is sent to ``num_rows + position`` (still ascending and
    distinct, out of range): its gathers clip to the last row and its
    scatters drop. There is no collective in the loop, so the shards of a
    ``shard_map`` may run different trip counts.

    Two things the TPU's compiler does to rows one scalar wide shape the
    walk (the chip, PR 31, 33.8M rows; PERF.md §6). It gathers from and
    scatters into ``[V, 1]`` as the vector ``[V]`` and re-lays the array out
    to get there, 2.1 ms an iteration if that happens inside the loop: so
    table and state leaves of width 1 are walked as vectors, reshaped once
    on either side. And its scatter into a vector costs a pass over the
    vector a call (0.40 ms) beside 5 ns a slot, where a scatter of wider
    rows costs 119 ns a slot and nothing a call: so the loop only collects
    the vector leaves' new values, and one scatter of all N slots after it
    writes them (the filler dropped), while wider leaves are written chunk
    by chunk."""
    c, idx, n_unique, cnt = plan.chunk, plan.idx, plan.n_unique, plan.cnt
    pos = jnp.arange(idx.shape[0], dtype=jnp.int32)

    leaves, treedef = jax.tree_util.tree_flatten((table, state))
    shapes = [leaf.shape for leaf in leaves]
    leaves = [_as_vector(leaf) for leaf in leaves]
    vector = [leaf.ndim == 1 for leaf in leaves]

    def apply_chunk(lo, at, carry):
        leaves, collected = carry
        live = jax.lax.dynamic_slice_in_dim(pos, lo, c) < n_unique
        with jax.named_scope(phases.ROW_GATHER):
            # the table is the first leaf: its rows are held, or gathered
            # as the state's are
            from_table = leaves if held is None else leaves[1:]
            chunk = ([] if held is None else
                     [jax.lax.dynamic_slice_in_dim(held, lo, c)]) + [
                jnp.take(leaf, at, axis=0, mode="clip", **_HINTS)
                for leaf in from_table]
            rows, state_rows = jax.tree_util.tree_unflatten(treedef, [
                x.reshape((c,) + shape[1:])
                for x, shape in zip(chunk, shapes)])
        with jax.named_scope(phases.ROW_UPDATE):
            # filler is untouched to the rule, as its contract says
            new = jax.tree_util.tree_leaves(opt.apply_rows(
                rows, state_rows,
                jnp.where(live[:, None],
                          jax.lax.dynamic_slice_in_dim(gsum, lo, c), 0.0),
                jnp.where(live, jax.lax.dynamic_slice_in_dim(cnt, lo, c), 0)))
        with jax.named_scope(phases.ROW_SCATTER):
            new = [x.astype(leaf.dtype).reshape((c,) + leaf.shape[1:])
                   for x, leaf in zip(new, leaves)]
            collected = [
                jax.lax.dynamic_update_slice_in_dim(buf, x, lo, 0) if v
                else None for buf, x, v in zip(collected, new, vector)]
            leaves = [leaf if v else leaf.at[at].set(x, mode="drop", **_HINTS)
                      for leaf, x, v in zip(leaves, new, vector)]
        return leaves, collected

    # a vector leaf's new values, N of them. Zeros made of ``cnt``: under
    # ``shard_map`` as varying as the loop's results are, which a loop's
    # carry has to be from the start
    leaves, collected = _walk(plan, apply_chunk, (
        leaves, [jnp.zeros_like(cnt, leaf.dtype) if v else None
                 for leaf, v in zip(leaves, vector)]))
    with jax.named_scope(phases.ROW_SCATTER):
        leaves = [leaf.at[idx].set(buf, mode="drop", **_HINTS) if v else leaf
                  for leaf, buf, v in zip(leaves, collected, vector)]
    table, state = jax.tree_util.tree_unflatten(treedef, [
        leaf.reshape(shape) for leaf, shape in zip(leaves, shapes)])
    return table, state, n_unique


# -- HBM traffic model -------------------------------------------------------


def hbm_bytes_model(num_rows: int, dim: int, batch_rows: int, opt,
                    table_dtype_bytes: int = 4) -> dict:
    """Arithmetic HBM bytes per apply under the two designs — the model
    ``bench.py``'s sparse leg records beside the measured rows/s so the
    ≥2x claim is a trajectory, not a log line. ``batch_rows`` = unique
    touched rows. Fused: read+write exactly those rows and their state,
    plus the batch-sized gsum/cnt build. Full-table: read+write every
    row and its state, build a table-sized gsum/cnt, plus the incoming
    batch read. Both are lower-bound models (no padding/layout slack)."""
    state_row = opt.state_scalars_per_row(dim) * 4
    row = dim * table_dtype_bytes + state_row
    grad_row = (dim + 1) * 4  # summed grads + count per row
    fused = batch_rows * (2 * row + 2 * grad_row)
    full = (num_rows * (2 * row + 2 * grad_row)
            + batch_rows * grad_row)
    return {"fused_bytes_per_apply": int(fused),
            "full_table_bytes_per_apply": int(full),
            "ratio": round(full / max(fused, 1), 2)}
