"""Compression on the wire — the codec-PR tentpole's transport contract.

The codecs ride the existing bucketed (and serial) van transport: packed
keys are negotiated per bucket header, the server decodes before
aggregation, pulls can compress the return path, and the MNIST-MLP gates
hold — cast16/int8 train within tolerance of the dense run and topk (with
error feedback) converges within epsilon of dense on the same seed. Plus
the stale-epoch observability satellite: abandoned staged epochs surface
as counters in STATS/TransportStats instead of only a server log line.
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.backends.common import BucketPlan
from ps_tpu.backends.remote_async import AsyncPSService, RemoteAsyncWorker
from ps_tpu.control import tensor_van as tv
from ps_tpu.kv import keys as keymod


def _params(seed=0, n=5, shape=(64, 33)):
    rng = np.random.default_rng(seed)
    return {f"layer{i}/w": jnp.asarray(
        rng.normal(0, 1, shape).astype(np.float32)) for i in range(n)}


def _flat(tree):
    return {k: np.asarray(v)
            for k, v in keymod.flatten_with_keys(tree)[0].items()}


def _fresh_job(params, num_workers=1):
    ps.init(backend="tpu", mode="async", num_workers=num_workers,
            dc_lambda=0.04)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
    store.init(params)
    return store, AsyncPSService(store, bind="127.0.0.1")


def _run_pushes(params, grads_seq, compress, bucket_bytes=1 << 12):
    store, svc = _fresh_job(params)
    w = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params,
                          bucket_bytes=bucket_bytes, pool_size=2,
                          compress=compress)
    w.pull_all()
    for g in grads_seq:
        w.push_pull(g)
    final = _flat(w._params)
    wire = w.bytes_pushed
    stats = w.transport.summary()
    version = store._engine.version
    w.close()
    svc.stop()
    ps.shutdown()
    return final, wire, stats, version


def _grads_seq(params, steps=3, seed=1, scale=0.01):
    rng = np.random.default_rng(seed)
    return [
        {k: jnp.asarray(rng.normal(0, scale, np.asarray(v).shape)
                        .astype(np.float32)) for k, v in params.items()}
        for _ in range(steps)
    ]


def test_cast16_on_grid_grads_match_serial_bit_for_bit():
    """Grads already on the bf16 grid survive cast16 losslessly, so the
    compressed run lands bit-identical parameters — compression changed
    the bytes, not the math."""
    params = _params()
    rng = np.random.default_rng(2)
    grads = [
        {k: jnp.asarray(rng.normal(0, 0.01, np.asarray(v).shape)
                        .astype(ml_dtypes.bfloat16).astype(np.float32))
         for k, v in params.items()}
        for _ in range(3)
    ]
    dense, wire_raw, _, v0 = _run_pushes(params, grads, None)
    comp, wire_c, stats, v1 = _run_pushes(
        params, grads, {"codec": "cast16", "min_bytes": 1024})
    assert v0 == v1 == 3
    for k in dense:
        np.testing.assert_array_equal(dense[k], comp[k], err_msg=k)
    assert wire_c < wire_raw * 0.7          # ~2x on the compressed keys
    assert stats["compress_ratio"] > 1.5


def test_int8_wire_reduction_and_bounded_divergence():
    params = _params(seed=3)
    grads = _grads_seq(params)
    dense, wire_raw, _, _ = _run_pushes(params, grads, None)
    comp, wire_c, stats, _ = _run_pushes(
        params, grads, {"codec": "int8", "min_bytes": 1024})
    # the acceptance bar: >= 2x fewer push bytes on the wire
    assert wire_c * 2 <= wire_raw, (wire_c, wire_raw)
    assert stats["compress_ratio"] >= 2.0
    # int8 is lossy but bounded: params stay within a few quantization
    # steps of the dense run (lr * sum of per-step bounds)
    for k in dense:
        np.testing.assert_allclose(comp[k], dense[k], atol=5e-5, err_msg=k)


def test_serial_transport_compresses_too():
    """The serial (non-bucketed) path negotiates the same way — the codec
    subsystem is transport-wide, not bucket-only."""
    params = _params(seed=4)
    grads = _grads_seq(params)
    dense, wire_raw, _, _ = _run_pushes(params, grads, None,
                                        bucket_bytes=None)
    comp, wire_c, _, v = _run_pushes(
        params, grads, {"codec": "int8", "min_bytes": 1024},
        bucket_bytes=None)
    assert v == 3
    assert wire_c * 2 <= wire_raw
    for k in dense:
        np.testing.assert_allclose(comp[k], dense[k], atol=5e-5, err_msg=k)


def test_pull_return_path_compression():
    """With pull:true the server packs the params it returns (per the same
    policy) and the worker decodes them — pulled trees match the engine's
    within the codec tolerance, and reply bytes shrink."""
    params = _params(seed=5, shape=(128, 65))
    store, svc = _fresh_job(params)
    spec = {"codec": "int8", "min_bytes": 1024, "pull": True}
    w_raw = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params,
                              bucket_bytes=1 << 12, pool_size=2)
    w_c = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params,
                            bucket_bytes=1 << 12, pool_size=2,
                            compress=spec)
    raw = _flat(w_raw.pull_all())
    raw_bytes = w_raw.bytes_pulled
    got = _flat(w_c.pull_all())
    c_bytes = w_c.bytes_pulled
    want = {k: np.asarray(v)
            for k, v in store._engine.pull_tree(worker=0).items()}
    for k in want:
        scale = np.abs(want[k]).max() / 127.0
        np.testing.assert_allclose(got[k], want[k], atol=scale * 1.01,
                                   err_msg=k)
        np.testing.assert_array_equal(raw[k], want[k], err_msg=k)
    assert c_bytes * 2 <= raw_bytes
    w_raw.close()
    w_c.close()
    svc.stop()
    ps.shutdown()


def test_topk_pull_compression_refused():
    params = _params(seed=6, n=2)
    store, svc = _fresh_job(params)
    with pytest.raises(ValueError, match="pull"):
        RemoteAsyncWorker("127.0.0.1", svc.port, 0, params,
                          bucket_bytes=1 << 12,
                          compress={"codec": "topk", "pull": True})
    svc.stop()
    ps.shutdown()


def test_compression_survives_multi_bucket_and_overlap():
    """Packed payloads slice across fusion buckets and ride background
    cycles like any tensor: tiny buckets force multi-bucket packing, the
    overlapped API still lands every push."""
    params = _params(seed=7, n=4, shape=(96, 41))
    store, svc = _fresh_job(params)
    w = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params,
                          bucket_bytes=1 << 10, pool_size=3,
                          compress={"codec": "int8", "min_bytes": 512})
    w.pull_all()
    grads = _grads_seq(params, steps=4, seed=8)
    for g in grads:
        w.push_pull_async(g).wait()
    assert store._engine.version == 4
    assert w.transport.summary()["compress_ratio"] >= 2.0
    w.close()
    svc.stop()
    ps.shutdown()


def test_sparse_row_push_compression():
    from ps_tpu.backends.remote_sparse import (
        RemoteSparseWorker,
        SparsePSService,
    )
    from ps_tpu.kv.sparse import SparseEmbedding

    ids = np.arange(0, 48, dtype=np.int32)
    grads = np.random.default_rng(9).normal(0, 1, (48, 16)) \
        .astype(ml_dtypes.bfloat16).astype(np.float32)  # cast16-lossless
    finals, wires = [], []
    for compress, bb in ((None, None),
                         ({"codec": "cast16", "min_bytes": 256}, None),
                         ({"codec": "cast16", "min_bytes": 256}, 1 << 9)):
        ps.init(backend="tpu", mode="async", num_workers=1)
        emb = SparseEmbedding(64, 16, optimizer="sgd", learning_rate=0.1)
        emb.init(jax.random.key(1), scale=0.01)
        svc = SparsePSService({"deep": emb}, bind="127.0.0.1")
        w = RemoteSparseWorker([("127.0.0.1", svc.port)], 0,
                               {"deep": (64, 16)}, bucket_bytes=bb,
                               compress=compress)
        w.push({"deep": (ids, grads)})
        assert w.versions() == {"deep": 1}
        finals.append(w.pull({"deep": np.arange(64, dtype=np.int32)})["deep"])
        wires.append(w.bytes_pushed)
        w.close()
        svc.stop()
        ps.shutdown()
    np.testing.assert_array_equal(finals[0], finals[1])  # lossless grads
    np.testing.assert_array_equal(finals[0], finals[2])
    assert wires[1] < wires[0]


def test_sparse_topk_refused():
    from ps_tpu.backends.remote_sparse import RemoteSparseWorker

    with pytest.raises(ValueError, match="topk"):
        RemoteSparseWorker([("127.0.0.1", 1)], 0, {"t": (8, 4)},
                           compress="topk")


# -- satellite: stale-epoch staging drops are observable ----------------------


def test_stale_epoch_drop_is_counted_and_in_stats():
    """A worker that abandons a push epoch mid-flight used to leave only a
    server-side warning; now the drop increments TransportStats counters
    that STATS exposes fleet-wide (and TrainMetrics/StepLogger print)."""
    params = _params(seed=10, n=3, shape=(64, 8))
    store, svc = _fresh_job(params)
    host = {k: np.full(np.asarray(v).shape, 1.0, np.float32)
            for k, v in params.items()}
    plan = BucketPlan.from_arrays(host, 1 << 9)
    assert plan.nbuckets >= 3
    ch = tv.Channel.connect("127.0.0.1", svc.port)
    # two buckets of epoch 1 staged, then the worker "moves on" to epoch 2
    for b in (0, 1):
        kind, _, _, _ = tv.decode(ch.request(plan.encode_bucket(
            tv.BUCKET_PUSH, 0, host, b, extra={"epoch": 1})))
        assert kind == tv.OK
    for b in range(plan.nbuckets):
        kind, _, _, extra = tv.decode(ch.request(plan.encode_bucket(
            tv.BUCKET_PUSH, 0, host, b, extra={"epoch": 2})))
        assert kind == tv.OK
    assert extra.get("committed")
    assert svc.transport.stale_epochs == 1
    assert svc.transport.stale_epoch_buckets == 2
    # observable over the wire, and in the stats summary shape StepLogger
    # prints via TrainMetrics
    kind, _, _, stats = tv.decode(ch.request(
        tv.encode(tv.STATS, 0, None)))
    assert kind == tv.OK
    assert stats["stale_epochs"] == 1
    assert stats["stale_epoch_buckets"] == 2
    s = svc.transport.summary()
    assert s["stale_epochs"] == 1 and s["stale_epoch_buckets"] == 2
    ch.close()
    svc.stop()
    ps.shutdown()


# -- the MNIST-MLP gates ------------------------------------------------------


def _mnist_losses(compress, steps=10, seed=0, lr=0.1):
    from ps_tpu.data.synthetic import mnist_batches
    from ps_tpu.models.mlp import MLP, cross_entropy_loss

    model = MLP(hidden=32)
    params0 = model.init(jax.random.key(seed),
                         jnp.zeros((1, 28, 28, 1)))["params"]

    def loss_fn(p, batch):
        images, labels = batch
        return cross_entropy_loss(model.apply({"params": p}, images), labels)

    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.04)
    store = ps.KVStore(optimizer="sgd", learning_rate=lr, mode="async")
    store.init(params0)
    svc = AsyncPSService(store, bind="127.0.0.1")
    w = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params0,
                          bucket_bytes=1 << 14, pool_size=2,
                          compress=compress)
    run = w.make_async_step(loss_fn)
    losses = []
    for batch in mnist_batches(32, seed=seed, steps=steps):
        images, labels = batch
        losses.append(float(run((jnp.asarray(images), jnp.asarray(labels)))))
    ratio = w.transport.summary().get("compress_ratio")
    w.close()
    svc.stop()
    ps.shutdown()
    return np.asarray(losses), ratio


def test_mnist_parity_cast16_and_int8_tolerance_bounded():
    """The tentpole gate: compressed MNIST-MLP training stays loss-for-loss
    within tolerance of the dense run on the same seed."""
    dense, _ = _mnist_losses(None)
    assert dense[-1] < dense[0], "dense baseline did not learn"
    for spec, tol in (({"codec": "cast16", "min_bytes": 1024}, 0.02),
                      ({"codec": "int8", "min_bytes": 1024}, 0.05)):
        got, ratio = _mnist_losses(spec)
        assert ratio is not None and ratio > 1.5
        np.testing.assert_allclose(got, dense, atol=tol,
                                   err_msg=spec["codec"])
        assert got[-1] < got[0], spec["codec"]


def test_mnist_topk_converges_within_epsilon_of_dense():
    """topk with error feedback: trajectories may wiggle, but the model
    converges — the final loss lands within epsilon of dense on the same
    seed, and the run's residual norm is reported.

    Compared on the plateau (40 steps, loss ~0.2), not mid-descent: at step
    14 the loss still falls ~0.1 a step, so one step of phase shift between
    the two runs is worth the whole band — measured on jax 0.9.0 / flax
    0.12.3 over seeds 0-3 the step-14 gap spans -0.25..+0.13 (seed 0, this
    test's: -0.25, topk AHEAD of dense) while the step-40 gap stays within
    0.06 for every seed."""
    steps = 40
    dense, _ = _mnist_losses(None, steps=steps)
    got, ratio = _mnist_losses(
        {"codec": "topk", "topk": 0.25, "min_bytes": 1024}, steps=steps)
    assert ratio is not None and ratio > 1.5
    assert got[-1] < got[0], "topk run did not learn"
    # epsilon-convergence: mean loss over the last 3 steps within 0.15 of
    # the dense run's (same seed, same batches)
    assert abs(np.mean(got[-3:]) - np.mean(dense[-3:])) < 0.15, (
        got.tolist(), dense.tolist())
