"""Sparse KV path tests (reference workload config 4).

Numerics contract per SURVEY.md §5: "sparse apply ≡ dense apply restricted to
touched rows" — checked directly for sgd/adagrad, and the lazy-adam deviation
(untouched rows frozen) is asserted as intended behavior. Shard parity:
the 8-shard scatter-apply (both exchange modes) must equal the 1-device
result exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.data.synthetic import criteo_batches
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.models.wide_deep import (
    WideDeep, WideDeepConfig, make_ids_fn, make_wide_deep_loss_fn,
)
from ps_tpu.train import make_composite_step

V, D = 96, 4


def _table0():
    return np.random.default_rng(0).normal(size=(V, D)).astype(np.float32)


def _make(optimizer="sgd", **kw):
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer=optimizer, **kw)
    emb.init(_table0())
    return emb


def test_push_sums_duplicates():
    emb = _make("sgd", learning_rate=1.0)
    ids = np.array([3, 7, 3, 95, 42, 3, 7, 0], np.int32)
    emb.push(ids, np.ones((8, D), np.float32))
    got = np.asarray(emb.table)[:V]
    exp = _table0()
    for i in ids:
        exp[i] -= 1.0
    np.testing.assert_allclose(got, exp, rtol=1e-6)


def test_pull_returns_current_rows():
    emb = _make("sgd", learning_rate=1.0)
    ids = np.array([5, 5, 90], np.int32)
    emb.push(np.array([5], np.int32), np.ones((1, D), np.float32))
    rows = np.asarray(emb.pull(ids))
    exp = _table0()
    exp[5] -= 1.0
    np.testing.assert_allclose(rows, exp[[5, 5, 90]], rtol=1e-6)


def test_a2a_lossless_matches_gather():
    ids = np.array([3, 7, 3, 95, 42, 3, 7, 0], np.int32)
    grads = np.random.default_rng(1).normal(size=(8, D)).astype(np.float32)
    emb_g = _make("adagrad", learning_rate=0.1)
    emb_g.push(ids, grads)
    got_g = np.asarray(emb_g.table)[:V]
    ps.shutdown()
    emb_a = _make("adagrad", learning_rate=0.1, exchange="a2a", capacity_factor=8.0)
    emb_a.push(ids, grads)
    got_a = np.asarray(emb_a.table)[:V]
    np.testing.assert_allclose(got_g, got_a, rtol=1e-6)


def test_a2a_duplicates_merge_before_routing():
    """Pre-exchange dedupe (the zipf-skew fix, BASELINE.md): duplicate ids
    collapse into one routed row per worker shard, so a hot row no longer
    overflows its bucket — this push is LOSSLESS even at capacity 1."""
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd", learning_rate=1.0,
                          exchange="a2a", capacity_factor=1.0)
    emb.init(_table0())
    ids = np.zeros(16, np.int32)  # all duplicate row 0, 2 per device
    assert emb.dropped_rows == 0
    emb.push(ids, np.ones((16, D), np.float32))
    got = np.asarray(emb.table)[:V]
    np.testing.assert_allclose(_table0()[0] - got[0], np.full(D, 16.0),
                               rtol=1e-6)
    assert emb.dropped_rows == 0  # merged, not dropped
    ps.shutdown()


def test_a2a_capacity_overflow_drops_distinct_rows():
    # DISTINCT ids can still overflow: each device pushes rows {0, 1} (both
    # owned by shard 0) with bucket capacity 1 -> one row per device drops,
    # and the drop is OBSERVABLE (VERDICT r2 item 5)
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd", learning_rate=1.0,
                          exchange="a2a", capacity_factor=1.0)
    emb.init(_table0())
    ids = np.asarray([0, 1] * 8, np.int32)  # 2 distinct ids per device
    emb.push(ids, np.ones((16, D), np.float32))
    got = np.asarray(emb.table)[:V]
    # sorted-order bucketing keeps id 0, drops id 1, on every device
    np.testing.assert_allclose(_table0()[0] - got[0], np.full(D, 8.0),
                               rtol=1e-6)
    np.testing.assert_allclose(_table0()[1], got[1], rtol=1e-6)
    assert emb.dropped_rows == 8
    assert emb.rows_pushed == 16
    assert abs(emb.dropped_fraction - 0.5) < 1e-9


def test_sparse_adagrad_equals_dense_restricted():
    """Adagrad: dense apply with zero grads on untouched rows == sparse."""
    emb = _make("adagrad", learning_rate=0.5)
    ids = np.array([1, 1, 8, 63, 63, 63, 2, 9], np.int32)
    grads = np.random.default_rng(2).normal(size=(8, D)).astype(np.float32)
    emb.push(ids, grads)
    got = np.asarray(emb.table)[:V]

    # dense reference over the whole table
    dense_g = np.zeros((V, D), np.float32)
    for i, g in zip(ids, grads):
        dense_g[i] += g
    acc = (dense_g * dense_g).mean(axis=-1)
    exp = _table0() - 0.5 * dense_g / np.sqrt(acc + 1e-8)[:, None]
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-6)


def test_lazy_adam_freezes_untouched_rows():
    emb = _make("adam", learning_rate=0.1)
    ids = np.array([4, 4, 11, 60, 4, 4, 11, 60], np.int32)
    grads = np.ones((8, D), np.float32)
    emb.push(ids, grads)
    emb.push(ids, grads)
    got = np.asarray(emb.table)[:V]
    untouched = np.setdiff1d(np.arange(V), ids)
    np.testing.assert_allclose(got[untouched], _table0()[untouched])
    # touched rows: g per step = duplicate count; manual lazy adam, 2 steps
    for row, mult in [(4, 4.0), (11, 2.0), (60, 2.0)]:
        m = v = 0.0
        x = _table0()[row].astype(np.float64)
        for t in (1, 2):
            m = 0.9 * m + 0.1 * mult
            v = 0.999 * v + 0.001 * mult * mult
            mhat = m / (1 - 0.9 ** t)
            vhat = v / (1 - 0.999 ** t)
            x = x - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
        np.testing.assert_allclose(got[row], x, rtol=1e-5, atol=1e-5)


def test_padded_rows_reachable_boundary():
    ps.init(backend="tpu")
    emb = SparseEmbedding(97, D, optimizer="sgd", learning_rate=1.0)  # pads to 104
    table = np.zeros((97, D), np.float32)
    emb.init(table)
    emb.push(np.full(8, 96, np.int32), np.ones((8, D), np.float32))
    got = np.asarray(emb.table)
    np.testing.assert_allclose(got[96], -8.0 * np.ones(D))
    assert emb.padded_rows == 104 and got.shape[0] == 104


def _widedeep_setup(mesh_shape):
    ps.init(backend="tpu", mesh_shape=mesh_shape)
    cfg = WideDeepConfig(per_feature_vocab=50, embed_dim=8, mlp=(32, 16))
    model = WideDeep(cfg)
    batch0 = next(criteo_batches(16, vocab_size=cfg.per_feature_vocab, seed=7))
    batch0 = {k: jnp.asarray(v) for k, v in batch0.items()}
    rows_shape = (16, cfg.num_sparse, cfg.embed_dim)
    params = model.init(
        jax.random.key(0), batch0["dense"],
        jnp.zeros(rows_shape), jnp.zeros(rows_shape[:2] + (1,)),
    )["params"]
    dense = ps.KVStore(optimizer="adam", learning_rate=1e-2, placement="sharded")
    dense.init(params)
    deep = SparseEmbedding(cfg.total_rows, cfg.embed_dim, optimizer="adagrad",
                           learning_rate=0.05)
    deep.init(jax.random.key(1), scale=0.01)
    wide = SparseEmbedding(cfg.total_rows, 1, optimizer="sgd", learning_rate=0.05)
    wide.init(jax.random.key(2), scale=0.01)
    run = make_composite_step(
        dense, {"deep": deep, "wide": wide},
        make_wide_deep_loss_fn(model), make_ids_fn(cfg),
    )
    return cfg, dense, deep, wide, run


def test_widedeep_composite_training_decreases_loss():
    cfg, dense, deep, wide, run = _widedeep_setup(None)
    losses = []
    for batch in criteo_batches(16, vocab_size=cfg.per_feature_vocab, seed=0, steps=25):
        loss, _ = run(dense.shard_batch({k: jnp.asarray(v) for k, v in batch.items()}))
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.02, losses
    assert deep.push_count == 25 and deep.bytes_pushed > 0
    assert dense.collective_bytes > 0


def test_widedeep_composite_shard_parity():
    """Full composite step on an 8-way mesh == on a 1-device mesh.

    Since the dense tower takes the fused step's ZeRO-1 constraints
    (ps_tpu/kv/fused.py) its gradients are sums of eight per-device
    partial sums, where GSPMD left alone had gathered the activations and
    contracted over the whole batch in one device's order. Loss and dense
    parameters hold their old tolerances. Of the deep table's 10,400
    elements one near zero is 1.36e-6 off (the sandbox's CPU, PR 30), so
    the table's atol alone goes from 1e-6 to 2e-6."""
    results = {}
    for k in (1, 8):
        cfg, dense, deep, wide, run = _widedeep_setup({"data": k})
        for batch in criteo_batches(16, vocab_size=cfg.per_feature_vocab,
                                    seed=3, steps=3):
            loss, params = run(
                dense.shard_batch({kk: jnp.asarray(v) for kk, v in batch.items()})
            )
        results[k] = (
            float(loss),
            np.asarray(deep.table)[:cfg.total_rows],  # padding differs per k
            jax.tree_util.tree_map(np.asarray, params),
        )
        ps.shutdown()
    np.testing.assert_allclose(results[1][0], results[8][0], rtol=1e-5)
    np.testing.assert_allclose(results[1][1], results[8][1], rtol=1e-4, atol=2e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6),
        results[1][2], results[8][2],
    )


def test_a2a_dropped_counts_raw_updates():
    """Overflow accounting keeps rows_pushed units AFTER the dedupe: a
    merged row that overflows reports every raw update it carried
    (code-review r3 finding)."""
    ps.init(backend="tpu")
    emb = SparseEmbedding(V, D, optimizer="sgd", learning_rate=1.0,
                          exchange="a2a", capacity_factor=1.0)
    emb.init(_table0())
    # per device: id 0 once, id 1 three times -> uniques {0 x1, 1 x3};
    # capacity 1 keeps id 0 and drops the merged id-1 row = 3 raw updates
    ids = np.asarray([0, 1, 1, 1] * 8, np.int32)
    emb.push(ids, np.ones((32, D), np.float32))
    assert emb.dropped_rows == 3 * 8
    assert emb.rows_pushed == 32
    got = np.asarray(emb.table)[:V]
    np.testing.assert_allclose(_table0()[0] - got[0], np.full(D, 8.0),
                               rtol=1e-6)
    np.testing.assert_allclose(_table0()[1], got[1], rtol=1e-6)
    ps.shutdown()
