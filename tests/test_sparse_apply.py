"""Fused sparse gather→apply→scatter — the parity drill and edge cases.

The contract (ISSUE 15 / README "Sparse apply"): the fused batch-sized
tier ('jax') must match the legacy masked full-table apply ('off') — bitwise for
SGD/Adagrad (the stable-sorted segment sum fixes the duplicate reduction
order to the full path's scatter-add order), and within 1e-6 relative
for Adam — across dup-heavy / empty / all-rows id distributions, through
the REAL ``SparseEmbedding.push`` path (exchange + shard_map included).

Plus the satellite edge cases: ``_dedupe_rows`` and ``_a2a_route`` under
empty pushes, all-duplicate ids, out-of-range ids riding ``mode='drop'``,
and a single-row table; the ``PS_FUSED_APPLY`` knob roundtrip; and the
sparse server's fused-tier observability surface (STATS ``fused`` dict,
``ps_sparse_apply_seconds``, ``sparse_rows_applied``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.config import Config
from ps_tpu.kv.sparse import SparseEmbedding, _dedupe_rows
from ps_tpu.ops.sparse_apply import (
    chunk_len,
    fused_sparse_apply,
    hbm_bytes_model,
    pair_segments,
    resolve_tier,
    row_plan,
    segment_sums,
)
from ps_tpu.optim.rowwise import make_rowwise

V, D = 96, 8


def _table0():
    return np.random.default_rng(0).normal(size=(V, D)).astype(np.float32)


def _push_through(tier, optimizer, pushes, mesh_shape=None, **kw):
    """Run a push sequence through SparseEmbedding at one tier; return
    the final (table, state) as numpy."""
    ps.init(backend="tpu", mesh_shape=mesh_shape)
    emb = SparseEmbedding(V, D, optimizer=optimizer, fused_apply=tier,
                          learning_rate=0.1, **kw)
    emb.init(_table0())
    for ids, grads in pushes:
        emb.push(ids, grads)
    table = np.asarray(emb.table)[:V]
    state = jax.tree_util.tree_map(np.asarray, emb.state())
    ps.shutdown()
    return table, state


#: the ISSUE-named id distributions, all against a V-row table
def _distributions():
    rng = np.random.default_rng(7)
    dup_heavy = np.array([3, 7, 3, 3, 7, 0, 95, 3] * 2, np.int32)
    all_rows = np.arange(V, dtype=np.int32)  # every row touched
    empty = np.zeros((0,), np.int32)
    single = np.array([42], np.int32)
    out = []
    for name, ids in (("dup_heavy", dup_heavy), ("all_rows", all_rows),
                      ("empty", empty), ("single", single)):
        grads = rng.normal(size=(ids.size, D)).astype(np.float32)
        out.append((name, ids, grads))
    return out


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
def test_fused_tier_parity_sweep(optimizer):
    """The acceptance drill: fused vs full-table over the real push path,
    every id distribution in one multi-push sequence (state carries
    across pushes, so drift would compound and show)."""
    pushes = [(ids, grads) for _, ids, grads in _distributions()]
    base_t, base_s = _push_through("off", optimizer, pushes)
    got_t, got_s = _push_through("jax", optimizer, pushes)
    if optimizer in ("sgd", "adagrad"):
        # fixed reduction order (stable-sorted segments) -> bitwise
        np.testing.assert_array_equal(got_t, base_t)
        jax.tree_util.tree_map(np.testing.assert_array_equal,
                               got_s, base_s)
    else:
        np.testing.assert_allclose(got_t, base_t, rtol=1e-6, atol=1e-7)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6,
                                                    atol=1e-7),
            got_s, base_s)


def test_fused_parity_sharded_a2a():
    """8-way mesh + the a2a exchange compose with the fused tier: the
    owner-shard apply sees routed (possibly capacity-clipped) id lists
    and must still match the 'off' tier bitwise."""
    rng = np.random.default_rng(3)
    ids = np.array([3, 7, 3, 95, 42, 3, 7, 0], np.int32)
    grads = rng.normal(size=(8, D)).astype(np.float32)
    kw = dict(exchange="a2a", capacity_factor=8.0)
    base_t, _ = _push_through("off", "adagrad", [(ids, grads)],
                              mesh_shape={"data": 8}, **kw)
    got_t, _ = _push_through("jax", "adagrad", [(ids, grads)],
                             mesh_shape={"data": 8}, **kw)
    np.testing.assert_array_equal(got_t, base_t)


def _plan_and_sums(ids, grads, num_rows=V):
    """``(uids, gsum, cnt, n_unique, idx, pair_seg)`` of a push's two
    halves, the plan of the ids and the sums of the gradients, as numpy."""
    plan = row_plan(ids, num_rows)
    return tuple(map(np.asarray, (
        plan.uids, segment_sums(plan, grads), plan.cnt, plan.n_unique,
        plan.idx, pair_segments(plan))))


def test_batch_segment_sum_orders_and_counts():
    """The compact contract: the U distinct real ids in front, ascending,
    each with its duplicates' sum and count; filler behind, the list
    padded with it to whole chunks (6 pairs: one chunk of 8)."""
    ids = jnp.asarray([5, -1, 2, 5, 5, 2], jnp.int32)
    grads = jnp.asarray(np.arange(6 * D, dtype=np.float32).reshape(6, D))
    uids, gsum, cnt, n_unique, idx, pair_seg = _plan_and_sums(ids, grads)
    assert n_unique == 2 and n_unique.dtype == np.int32
    assert uids.tolist() == [2, 5, -1, -1, -1, -1, -1, -1]
    assert cnt.tolist() == [2, 3, 0, 0, 0, 0, 0, 0]
    # what the loop gathers and scatters at: filler past the table's end
    assert idx.tolist() == [2, 5] + [V + i for i in range(2, 8)]
    # each pair's slot among the distinct rows; filler's is the slot after
    assert pair_seg.tolist() == [1, 2, 0, 1, 1, 0, 2, 2]
    g = np.asarray(grads)
    # duplicates summed in arrival order (the stable sort keeps it)
    np.testing.assert_array_equal(gsum[0], g[2] + g[5])
    np.testing.assert_array_equal(gsum[1], (g[0] + g[3]) + g[4])
    # the filler's slot carries the filler's own gradients and no count
    np.testing.assert_array_equal(gsum[2], g[1])
    assert np.all(gsum[3:] == 0)
    # no real id at all: nothing in front
    none = _plan_and_sums(jnp.full((4,), -1, jnp.int32),
                          jnp.zeros((4, D), jnp.float32))
    assert int(none[3]) == 0 and none[0].tolist() == [-1] * 8


# -- the loop over the distinct rows (ISSUE 31) ------------------------------

# The CPU backend contracts ``a * b + c`` into one FMA or not, fusion by
# fusion, and a loop body fuses unlike straight-line code: one rounding more
# or less in ``rows - lr * g`` and in ``sum(g * g)``. These tests are about
# which slots the loop applies, so they draw gradients in eighths and take a
# learning rate of 1/8: every product and every sum of them is exact in f32
# and both tiers round the same values. Rounding order with arbitrary values
# is ``test_fused_tier_parity_sweep``'s (and, on the chip,
# ``chip_smoke.py::_tier_parity``'s).
LR_EXACT = 0.125


def _eighths(rng, shape):
    return (rng.integers(-2, 3, size=shape) / 8).astype(np.float32)


#: a push long enough for the loop: three chunks of C
N_LOOP = 3 * 1024
C_LOOP = chunk_len(N_LOOP)
V_LOOP = 4096
N_FILLER = 500


def _push_of(n_unique, filler, rng, num_rows=V_LOOP, n=N_LOOP):
    """``n`` (id, gradient) pairs naming ``n_unique`` distinct rows, the
    table's last row among them, ``N_FILLER`` of them -1 with ``filler``
    (all of them where no row is named)."""
    n_real = (n - N_FILLER if filler else n) if n_unique else 0
    assert n_unique <= n_real
    ids = np.full((n,), -1, np.int32)
    if n_unique:
        rows = np.concatenate([
            rng.choice(num_rows - 1, n_unique - 1, replace=False),
            [num_rows - 1]]).astype(np.int32)
        real = np.concatenate(
            [rows, rng.choice(rows, n_real - n_unique)]).astype(np.int32)
        ids[rng.choice(n, n_real, replace=False)] = rng.permutation(real)
    grads = _eighths(rng, (n, D))
    grads[ids < 0] = 0
    return ids, grads


def _assert_tiers_agree(optimizer, got, want):
    if optimizer in ("sgd", "adagrad"):
        jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    else:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6,
                                                    atol=1e-7), got, want)


def _apply_once(tier, optimizer, table0, ids, grads, mesh_shape=None):
    """One ``SparseEmbedding.push`` at one tier: ``(table, state)`` as
    numpy, and the rows it counted."""
    ps.init(backend="tpu", mesh_shape=mesh_shape)
    emb = SparseEmbedding(table0.shape[0], D, optimizer=optimizer,
                          fused_apply=tier, learning_rate=LR_EXACT)
    emb.init(table0)
    emb.push(ids, grads)
    out = jax.tree_util.tree_map(np.asarray, (emb.table, emb.state()))
    applied = emb.rows_applied
    ps.shutdown()
    return out, applied


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
@pytest.mark.parametrize("n_unique,filler", [
    (0, True), (1, False), (1, True),
    (C_LOOP - 1, False), (C_LOOP - 1, True), (C_LOOP, False),
    (C_LOOP, True), (C_LOOP + 1, False), (C_LOOP + 1, True),
    (2 * C_LOOP, False), (2 * C_LOOP, True),
    (N_LOOP - N_FILLER, True), (N_LOOP, False)])
def test_fused_apply_stops_at_the_distinct_rows(optimizer, n_unique, filler):
    """The loop's edges: U distinct rows of N pairs at 0, 1, one either
    side of a chunk's end, two chunks and all N, with and without filler,
    the table's last row among them. Against the 'off' tier under the
    module's contract; the count is U."""
    assert N_LOOP > 2 * C_LOOP  # a loop, not the one-chunk path
    rng = np.random.default_rng(n_unique + filler)
    table0 = rng.normal(size=(V_LOOP, D)).astype(np.float32)
    ids, grads = _push_of(n_unique, filler, rng)
    assert np.unique(ids[ids >= 0]).size == n_unique
    one = {"data": 1}
    want, _ = _apply_once("off", optimizer, table0, ids, grads, one)
    got, applied = _apply_once("jax", optimizer, table0, ids, grads, one)
    _assert_tiers_agree(optimizer, got, want)
    assert applied == n_unique
    untouched = np.setdiff1d(np.arange(V_LOOP), ids)
    np.testing.assert_array_equal(got[0][untouched], table0[untouched])


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
def test_fused_apply_shards_stop_at_their_own_rows(optimizer):
    """Eight shards under ``shard_map``, each owning another number of
    the push's distinct rows (none on the first, two chunks and more on
    the last): every shard's loop stops at its own count."""
    k, rps = 8, 4096
    rng = np.random.default_rng(5)
    table0 = rng.normal(size=(k * rps, D)).astype(np.float32)
    per_shard = [0, 1, 200, C_LOOP - 1, C_LOOP, C_LOOP + 1, 300,
                 2 * C_LOOP + 7]
    rows = np.concatenate([
        s * rps + rng.choice(rps, u, replace=False)
        for s, u in enumerate(per_shard)]).astype(np.int32)
    n = 8192
    ids = rng.permutation(np.concatenate(
        [rows, rng.choice(rows, n - rows.size)]).astype(np.int32))
    grads = _eighths(rng, (n, D))
    mesh = {"data": k}
    want, _ = _apply_once("off", optimizer, table0, ids, grads, mesh)
    got, applied = _apply_once("jax", optimizer, table0, ids, grads, mesh)
    _assert_tiers_agree(optimizer, got, want)
    assert applied == sum(per_shard)


def test_lowered_apply_states_what_the_sort_proved():
    """Every gather and scatter of rows carries ``indices_are_sorted`` and
    ``unique_indices`` (Adagrad: the table's inside the loop, the
    accumulator vector's one scatter after it), the walk is a ``while``,
    and the donated table is updated in place: no table-shaped ``copy`` in
    the optimized HLO."""
    opt = make_rowwise("adagrad", learning_rate=0.1)
    table = jnp.zeros((V_LOOP, D), jnp.float32)
    ids = jnp.zeros((N_LOOP,), jnp.int32)
    grads = jnp.zeros((N_LOOP, D), jnp.float32)
    lowered = jax.jit(
        lambda t, s, i, g: fused_sparse_apply(t, s, i, g, opt),
        donate_argnums=(0, 1)).lower(table, opt.init(table), ids, grads)
    text = lowered.as_text()
    assert "stablehlo.while" in text
    # the dedupe's own scatter-adds come before the loop
    walk = text[text.index("stablehlo.while"):].splitlines()
    scatters = [ln for ln in walk if "stablehlo.scatter" in ln]
    gathers = [ln for ln in walk if "stablehlo.gather" in ln]
    assert len(scatters) == 2 and len(gathers) == 2  # table and state
    for ln in scatters:
        assert "indices_are_sorted = true" in ln, ln
        assert "unique_indices = true" in ln, ln
    for ln in gathers:
        assert "indices_are_sorted = true" in ln, ln
    compiled = lowered.compile().as_text()
    assert " while(" in compiled
    copies = [ln for ln in compiled.splitlines()
              if f"= f32[{V_LOOP},{D}]" in ln and " copy(" in ln]
    assert not copies, copies


def _unique_real(ids, num_rows):
    ids = np.asarray(ids).reshape(-1)
    return np.unique(ids[(ids >= 0) & (ids < num_rows)]).size


def test_rows_applied_counts_distinct_rows_eager():
    """``rows_applied`` is ``np.unique`` of each push's ids, summed over
    the pushes, through the fold of the pending device counts (40 pushes:
    past the 32nd) and with nothing read in between."""
    ps.init(backend="tpu", mesh_shape={"data": 8})
    emb = SparseEmbedding(V, D, optimizer="adagrad", learning_rate=0.1)
    emb.init(_table0())
    rng = np.random.default_rng(11)
    want = pushed = 0
    for step in range(40):
        ids = rng.integers(-1, V, size=(8 * (1 + step % 5),)).astype(np.int32)
        emb.push(ids, rng.normal(size=(ids.size, D)).astype(np.float32))
        want += _unique_real(ids, V)
        pushed += ids.size
        assert all(isinstance(x, jax.Array)
                   for x in emb._counts_pending)  # still on the device
    assert len(emb._counts_pending) < 32  # folded, not one buffer a push
    assert (emb.rows_applied, emb.rows_pushed) == (want, pushed)
    assert emb.dropped_rows == 0
    assert emb.rows_applied == want  # reading twice counts once
    ps.shutdown()


@pytest.mark.parametrize("tier", ["off", "jax"])
def test_rows_applied_through_composite_step(tier):
    """The fused step hands the count out as a device value beside the
    dropped count; both tiers count the same rows."""
    from ps_tpu.models.wide_deep import (WideDeep, WideDeepConfig,
                                         make_ids_fn, make_wide_deep_loss_fn)

    ps.init(backend="tpu", mesh_shape={"data": 8})
    cfg = WideDeepConfig(num_dense=4, num_sparse=3, per_feature_vocab=50,
                         embed_dim=D, mlp=(16,))
    model = WideDeep(cfg)
    shape = (2, cfg.num_sparse, cfg.embed_dim)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, cfg.num_dense)),
                        jnp.zeros(shape), jnp.zeros(shape[:2] + (1,))
                        )["params"]
    dense = ps.KVStore(optimizer="sgd", learning_rate=0.1)
    dense.init(params)
    tables = {}
    for i, (name, dim) in enumerate((("deep", cfg.embed_dim), ("wide", 1))):
        tables[name] = SparseEmbedding(cfg.total_rows, dim,
                                       optimizer="adagrad", fused_apply=tier)
        tables[name].init(jax.random.key(i + 1))
    step = ps.make_composite_step(dense, tables,
                                  make_wide_deep_loss_fn(model),
                                  make_ids_fn(cfg))
    rng = np.random.default_rng(3)
    want = 0
    for _ in range(5):
        sparse = rng.zipf(1.5, size=(16, cfg.num_sparse)) % 50
        batch = {"dense": rng.normal(size=(16, 4)).astype(np.float32),
                 "sparse": sparse.astype(np.int32),
                 "label": rng.integers(0, 2, 16).astype(np.float32)}
        step(dense.shard_batch(batch))
        want += _unique_real(cfg.global_ids(sparse), cfg.total_rows)
    for emb in tables.values():
        assert len(emb._counts_pending) == 5  # nothing read on the way
        assert (emb.rows_applied, emb.rows_pushed) == (want, 5 * 16 * 3)
        assert emb.dropped_rows == 0
    ps.shutdown()


# -- the step that pulls each distinct row once (ISSUE 55) --------------------

def _step_ids(case, rng):
    """The id arrays of the parity cases, 2-D as ``ids_fn`` hands them."""
    if case == "ragged":  # N no multiple of the chunk: padded with filler
        ids = (rng.zipf(1.2, size=(250, 10)) - 1) % V_LOOP
        assert ids.size % chunk_len(ids.size)
        return ids.astype(np.int32)
    shape = (N_LOOP // 12, 12)
    if case == "distinct":
        return rng.permutation(V_LOOP)[:N_LOOP].reshape(shape).astype(np.int32)
    if case == "same":
        return np.full(shape, 7, np.int32)
    ids = ((rng.zipf(1.2, size=shape) - 1) % V_LOOP).astype(np.int32)
    ids[0, 0] = V_LOOP - 1  # the table's last row, which a clipped slot reads
    if case == "filler":
        ids[rng.random(shape) < 0.1] = -1
    if case == "beyond":  # lookup reads NaN past the end, and wraps before 0
        ids[rng.random(shape) < 0.01] = V_LOOP + 3
        ids[rng.random(shape) < 0.01] = -5
        ids[3, 3] = -V_LOOP - 2
    return ids


@pytest.mark.parametrize("optimizer", ["sgd", "adagrad", "adam"])
@pytest.mark.parametrize("case", ["zipf", "distinct", "same", "filler",
                                  "ragged", "beyond"])
def test_distinct_pull_and_held_push_equal_lookup_and_apply(optimizer, case):
    """``plan_pull`` + ``lookup_distinct`` + ``apply_held`` against
    ``lookup`` + ``apply`` on the same ids, bit for bit: the rows the loss
    reads (its value and its gradient with respect to them say so), the
    table, the state and the counts. Values in eighths, so that no sum
    depends on how a backend fuses it (see ``LR_EXACT``)."""
    rng = np.random.default_rng(55)
    ids = _step_ids(case, rng)
    table0 = _eighths(rng, (V_LOOP, D))
    weight = jnp.asarray(_eighths(rng, ids.shape + (D,)))
    ps.init(backend="tpu", mesh_shape={"data": 1})
    emb = SparseEmbedding(V_LOOP, D, optimizer=optimizer,
                          learning_rate=LR_EXACT)
    emb.init(table0)
    assert emb.pulls_distinct

    def loss_fn(rows):
        return jnp.sum(weight * rows * rows)

    def by_pairs(table, state, ids):
        rows = emb.lookup(table, ids)
        loss, grads = jax.value_and_grad(loss_fn)(rows)
        return emb.apply(table, state, ids.reshape(-1),
                         grads.reshape(-1, D)) + (loss, grads, rows)

    def by_distinct_rows(table, state, ids):
        plan = emb.plan_pull(ids)
        rows, held = emb.lookup_distinct(table, ids, plan)
        loss, grads = jax.value_and_grad(loss_fn)(rows)
        return emb.apply_held(table, state, plan, held,
                              grads.reshape(-1, D)) + (loss, grads, rows)

    args = (emb.table, emb.state(), jnp.asarray(ids))
    want = jax.device_get(jax.jit(by_pairs)(*args))
    got = jax.device_get(jax.jit(by_distinct_rows)(*args))
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    real = ids[(ids >= 0) & (ids < V_LOOP)]
    assert got[2].tolist() == [0, np.unique(real).size]
    assert np.isnan(got[3]) == (case == "beyond")
    untouched = np.setdiff1d(np.arange(V_LOOP), real)
    np.testing.assert_array_equal(got[0][untouched], table0[untouched])
    if real.size:
        assert not np.array_equal(got[0], table0)
    ps.shutdown()


@pytest.mark.parametrize("devices,tier,distinct", [
    (1, "jax", True), (1, "off", False), (8, "jax", False)])
def test_rows_pulled_after_three_steps(devices, tier, distinct):
    """``rows_pulled`` counts what the store's pulls gathered out of the
    table: a step on one chip the distinct rows of its batch, from the
    count its push reports and with nothing read on the way; a step that
    looks every pair up, and the eager ``pull``, the pairs."""
    ps.init(backend="tpu", mesh_shape={"data": devices})
    dense = ps.KVStore(optimizer="sgd", learning_rate=0.1)
    dense.init({"w": jnp.ones((D, 1))})
    emb = SparseEmbedding(V, D, optimizer="adagrad", fused_apply=tier)
    emb.init(_table0())
    assert emb.pulls_distinct == distinct
    step = ps.make_composite_step(
        dense, {"emb": emb},
        lambda p, rows, b: jnp.mean((rows["emb"] @ p["w"]) ** 2),
        lambda b: {"emb": b["ids"]})
    rng = np.random.default_rng(9)
    pulled = 0
    for _ in range(3):
        ids = ((rng.zipf(1.5, size=(16, 3)) - 1) % V).astype(np.int32)
        step(dense.shard_batch({"ids": ids}))
        pulled += np.unique(ids).size if distinct else ids.size
    assert len(emb._held_pending) == (3 if distinct else 0)
    assert len(emb._counts_pending) == (0 if distinct else 3)
    assert emb.rows_pulled == pulled
    assert (emb.rows_pulled == emb.rows_applied) == distinct
    assert emb.rows_pushed == 3 * 48
    # the worker received every pair's row, however few were gathered
    assert emb.bytes_pulled == 3 * 48 * D * 4
    emb.pull(np.arange(5))
    assert emb.rows_pulled == pulled + 5
    assert emb.rows_pulled == pulled + 5  # reading twice counts once
    ps.shutdown()


# -- satellite: _dedupe_rows / _a2a_route edge cases -------------------------


def test_dedupe_rows_empty():
    ids = jnp.zeros((0,), jnp.int32)
    grads = jnp.zeros((0, D), jnp.float32)
    u, g, c = _dedupe_rows(ids, grads)
    assert u.shape == (0,) and g.shape == (0, D) and c.shape == (0,)


def test_dedupe_rows_all_duplicates():
    ids = jnp.full((6,), 11, jnp.int32)
    grads = jnp.ones((6, D), jnp.float32)
    u, g, c = map(np.asarray, _dedupe_rows(ids, grads))
    keep = u >= 0
    assert keep.sum() == 1  # one surviving unique row
    np.testing.assert_allclose(g[keep][0], np.full(D, 6.0))
    assert c[keep][0] == 6
    assert np.all(g[~keep] == 0) and np.all(c[~keep] == 0)


def test_empty_push_is_a_noop_every_tier():
    for tier in ("off", "jax"):
        t, _ = _push_through(tier, "adagrad",
                             [(np.zeros((0,), np.int32),
                               np.zeros((0, D), np.float32))])
        np.testing.assert_array_equal(t, _table0())


@pytest.mark.parametrize("tier", ["off", "jax"])
def test_a2a_out_of_range_ids_drop(tier):
    """Ids beyond every shard's range ride the scatter's mode='drop':
    they consume bucket capacity but touch no row (the -1-filler
    convention's hard backstop)."""
    ps.init(backend="tpu", mesh_shape={"data": 8})
    emb = SparseEmbedding(V, D, optimizer="sgd", learning_rate=1.0,
                          exchange="a2a", capacity_factor=8.0,
                          fused_apply=tier)
    emb.init(_table0())
    # the padded table has ceil(96/8)*8 = 96 rows; id 200 routes to the
    # clipped last shard, whose ok-mask (and the route's clip) drops it
    ids = np.array([3, 200, 7, 300, 3, 200, 7, 300], np.int32)
    emb.push(ids, np.ones((8, D), np.float32))
    got = np.asarray(emb.table)[:V]
    exp = _table0()
    exp[3] -= 2.0
    exp[7] -= 2.0
    np.testing.assert_allclose(got, exp, rtol=1e-6)
    ps.shutdown()


@pytest.mark.parametrize("tier", ["off", "jax"])
def test_single_row_table(tier):
    """num_rows=1 pads to the mesh size; every push lands on row 0 of
    shard 0 and the pad rows stay untouched."""
    ps.init(backend="tpu", mesh_shape={"data": 8})
    emb = SparseEmbedding(1, D, optimizer="sgd", learning_rate=1.0,
                          fused_apply=tier)
    emb.init(np.zeros((1, D), np.float32))
    emb.push(np.zeros((8,), np.int32), np.ones((8, D), np.float32))
    got = np.asarray(emb.table)
    assert got.shape == (8, D)  # padded to the axis size
    np.testing.assert_allclose(got[0], np.full(D, -8.0), rtol=1e-6)
    np.testing.assert_array_equal(got[1:], np.zeros((7, D), np.float32))
    ps.shutdown()


# -- knob + tier resolution ---------------------------------------------------


def test_fused_apply_knob_roundtrip(monkeypatch):
    monkeypatch.setenv("PS_FUSED_APPLY", "jax")
    assert Config.from_env().fused_apply == "jax"
    monkeypatch.setenv("PS_FUSED_APPLY", "")
    assert Config.from_env().fused_apply == "auto"
    monkeypatch.setenv("PS_FUSED_APPLY", "cuda")
    with pytest.raises(ValueError, match="fused_apply"):
        Config.from_env()
    with pytest.raises(ValueError, match="fused_apply"):
        Config(fused_apply="no-such-tier")


def test_resolve_tier_auto_is_jax():
    assert resolve_tier(None) == "jax"
    assert resolve_tier("auto") == "jax"
    assert resolve_tier("off") == "off"
    with pytest.raises(ValueError, match="unknown fused-apply tier"):
        resolve_tier("pallas")  # the removed kernel tier is a typo now


def test_backend_resolution_reaches_embedding(monkeypatch):
    """PS_FUSED_APPLY flows Config -> SparseEmbedding.fused_tier (auto
    resolves to jax)."""
    monkeypatch.setenv("PS_FUSED_APPLY", "off")
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd")
    assert emb.fused_tier == "off"
    ps.shutdown()
    monkeypatch.delenv("PS_FUSED_APPLY")
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd")
    assert emb.fused_tier == "jax"  # auto
    ps.shutdown()


def test_off_tier_preserves_buffer_lifetimes():
    """PS_FUSED_APPLY=off promises today's EXACT behavior — including
    that a table reference held across a push stays readable (the fused
    tiers donate; 'off' must not)."""
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd", learning_rate=1.0,
                          fused_apply="off")
    emb.init(_table0())
    held = emb.table
    emb.push(np.array([3], np.int32), np.ones((1, D), np.float32))
    np.testing.assert_array_equal(np.asarray(held), _table0())  # readable
    ps.shutdown()


def test_read_all_versioned_stamps_served_bytes():
    """The aggregator's coalesced snapshot stamps the AS-SERVED version
    (read_all_versioned), never the worker's known version — a
    re-publisher stamping bytes newer than they are would park stale
    rows in version-keyed caches."""
    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import AsyncPSService, connect_async

    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    params = {"p/w": jnp.zeros((4, 4), jnp.float32)}
    st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    st.init(params)
    svc = AsyncPSService(st, bind="127.0.0.1")
    try:
        w = connect_async(f"127.0.0.1:{svc.port}", 0, params)
        w.push_all({"p/w": jnp.ones((4, 4), jnp.float32)})
        tree, version = w.read_all_versioned()
        assert version == w.version == 1
        np.testing.assert_array_equal(
            np.asarray(tree["p/w"]), np.full((4, 4), -0.1, np.float32))
        w.close()
    finally:
        svc.stop()
    ps.shutdown()


def test_hbm_bytes_model_shapes():
    opt = make_rowwise("adagrad")
    m = hbm_bytes_model(1 << 16, 32, 512, opt)
    assert m["fused_bytes_per_apply"] < m["full_table_bytes_per_apply"]
    assert m["ratio"] > 100  # 128x table/batch, state included
    # sgd carries no state; the model must still be finite and ordered
    m2 = hbm_bytes_model(1 << 16, 32, 512, make_rowwise("sgd"))
    assert 0 < m2["fused_bytes_per_apply"] < m2["full_table_bytes_per_apply"]


# -- satellite: the server-side observability surface ------------------------


def test_sparse_service_fused_surface():
    """STATS carries the fused view (per-table tiers + rows_applied),
    the sparse-apply histogram records, and the registry counter
    advances — the 'a shard fell off the fused tier' signal ps_top
    renders."""
    from ps_tpu.backends.remote_sparse import connect_sparse, serve_sparse

    ps.init(backend="tpu")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    emb = SparseEmbedding(V, D, optimizer="adagrad", mesh=mesh,
                          fused_apply="jax")
    emb.init(_table0())
    svc = serve_sparse({"deep": emb}, bind="127.0.0.1")
    try:
        w = connect_sparse(f"127.0.0.1:{svc.port}", 0, {"deep": (V, D)})
        ids = np.array([1, 2, 1, 5], np.int32)
        w.push({"deep": (ids, np.ones((4, D), np.float32))}, dedupe=False)
        st = w.stats()
        assert st["fused"] == {"tiers": {"deep": "jax"},
                               "rows_applied": 4}
        lat = (st.get("metrics") or {}).get("lat") or {}
        assert lat.get("sparse_apply_s", {}).get("count", 0) >= 1
        assert svc.transport.sparse_rows_applied == 4
        w.close()
    finally:
        svc.stop()
    ps.shutdown()
