"""Fused sparse embedding update: gather → optimizer-apply → scatter,
batch-sized, in one HBM pass (ROADMAP item 6; SURVEY §8 P3).

Why: the legacy sparse apply (``ps_tpu/kv/sparse.py`` ``shard_apply``)
pays three-plus full-table HBM passes per push — two ``zeros().at[].add``
scatter-sums building a TABLE-SIZED ``gsum``/``cnt``, then the row-wise
optimizer updates the ENTIRE shard under a ``touched`` mask. Apply cost
is O(num_rows) even when a batch touches 0.1% of rows — exactly the
regime out-of-HBM tiered tables (ROADMAP item 3) will live in. This
module makes apply cost O(batch): dedupe/segment-sum the pushed ids at
BATCH size, gather only the touched rows and their per-row optimizer
state, apply the dense-rows rule (``RowwiseOptimizer.apply_rows``), and
scatter rows+state back.

Two tiers, selected by ``PS_FUSED_APPLY`` (``Config.fused_apply``,
``off|jax|auto``; README "Sparse apply"):

- ``jax`` — the batch-sized path: take/gather the touched rows + state,
  ``apply_rows``, ``.at[].set(mode='drop')`` scatter (filler ids redirect
  out of range and drop). O(batch) traffic, XLA-scheduled.
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

from typing import Any, Optional, Tuple

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


def batch_segment_sum(ids: jax.Array, grads: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batch-sized dedupe + segment sum of a push's (ids, grads).

    ``ids`` [N] int32 with duplicates and -1 filler allowed; ``grads``
    [N, D]. Returns ``(uids, gsum, cnt)`` all length N: each unique real
    id survives at one slot with its duplicates' grads summed (f32, in
    stable-sorted arrival order — the fixed reduction order the bitwise
    parity contract names), duplicates and filler become ``uid=-1,
    gsum=0, cnt=0``. The table never appears: this is the O(batch) twin
    of the legacy table-sized ``zeros(rps).at[slot].add`` build.
    """
    n = ids.shape[0]
    if n == 0:
        return ids, grads.astype(jnp.float32), jnp.zeros((0,), jnp.int32)
    order = jnp.argsort(ids)  # stable: duplicates keep arrival order
    ids_s = ids[order]
    grads_s = grads[order].astype(jnp.float32)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), ids_s[1:] != ids_s[:-1]])
    seg = jnp.cumsum(first) - 1
    summed = jnp.zeros(grads_s.shape, jnp.float32).at[seg].add(grads_s)
    seg_cnt = jnp.zeros((n,), jnp.int32).at[seg].add(
        (ids_s >= 0).astype(jnp.int32))
    real = first & (ids_s >= 0)
    uids = jnp.where(real, ids_s, -1)
    gsum = jnp.where(real[:, None], summed[seg], 0.0)
    cnt = jnp.where(real, seg_cnt[seg], 0)
    return uids, gsum, cnt


def segment_sum_np(ids, grads):
    """Host twin of :func:`batch_segment_sum` for the tiered cold path
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


def fused_sparse_apply(table: jax.Array, state: Any, ids: jax.Array,
                       grads: jax.Array, opt) -> Tuple[jax.Array, Any]:
    """THE entry point every fused sparse apply routes through
    (``kv/sparse``'s shard_apply, and through it the remote sparse server
    and the mesh backend). ``ids`` [N] are SHARD-LOCAL row indices with -1
    filler (out-of-range/padding already masked by the caller), ``grads``
    [N, D] with filler rows zeroed. Returns the updated (table, state);
    only touched rows' bytes move: batch-sized gather → apply_rows →
    scatter. Filler slots gather row 0 (harmless: cnt 0 and gsum 0 make
    apply_rows the identity for them) and scatter out of range
    (``mode='drop'``)."""
    if ids.shape[0] == 0:  # empty push: nothing gathered, nothing written
        return table, state
    with jax.named_scope(phases.ROW_DEDUPE):
        uids, gsum, cnt = batch_segment_sum(ids, grads)
    num_rows = table.shape[0]
    with jax.named_scope(phases.ROW_GATHER):
        slot = jnp.where(uids >= 0, uids, 0)
        rows = jnp.take(table, slot, axis=0)
        state_rows = jax.tree_util.tree_map(
            lambda leaf: jnp.take(leaf, slot, axis=0), state)
    with jax.named_scope(phases.ROW_UPDATE):
        new_rows, new_state_rows = opt.apply_rows(rows, state_rows, gsum, cnt)
    with jax.named_scope(phases.ROW_SCATTER):
        dst = jnp.where(uids >= 0, uids, num_rows)  # filler drops off the end
        new_table = table.at[dst].set(new_rows.astype(table.dtype),
                                      mode="drop")
        new_state = jax.tree_util.tree_map(
            lambda leaf, nrows: leaf.at[dst].set(nrows.astype(leaf.dtype),
                                                 mode="drop"),
            state, new_state_rows)
    return new_table, new_state


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
