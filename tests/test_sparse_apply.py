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
    batch_segment_sum,
    hbm_bytes_model,
    resolve_tier,
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


def test_batch_segment_sum_orders_and_counts():
    ids = jnp.asarray([5, -1, 2, 5, 5, 2], jnp.int32)
    grads = jnp.asarray(np.arange(6 * D, dtype=np.float32).reshape(6, D))
    uids, gsum, cnt = batch_segment_sum(ids, grads)
    uids, gsum, cnt = map(np.asarray, (uids, gsum, cnt))
    # one surviving slot per unique id, with duplicate counts
    assert sorted(uids[uids >= 0].tolist()) == [2, 5]
    got = {int(u): (gsum[i], int(cnt[i]))
           for i, u in enumerate(uids) if u >= 0}
    np.testing.assert_allclose(got[2][0],
                               np.asarray(grads)[[2, 5]].sum(0))
    np.testing.assert_allclose(got[5][0],
                               np.asarray(grads)[[0, 3, 4]].sum(0))
    assert got[2][1] == 2 and got[5][1] == 3
    # filler slots are inert: no id, no grads, no count
    dead = uids < 0
    assert dead.sum() == 4
    assert np.all(gsum[dead] == 0) and np.all(cnt[dead] == 0)


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
