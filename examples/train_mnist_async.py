"""Async-SGD MNIST — workload config 5 in its REAL deployment shape.

The reference's async mode runs the server and each worker as separate,
deliberately unsynchronized nodes (SURVEY.md §4d): the server applies every
arriving gradient immediately with the DC-ASGD correction; workers compute
against whatever (stale) parameters they last pulled. This trainer exposes
both the single-process form (threads as workers — quick start) and the
cross-process form over the native van's TCP layer.

Single process (threads drive the workers round-robin):
    python examples/train_mnist_async.py --steps 60 --num-workers 3

Cross-process (one terminal per node; server first):
    python examples/train_mnist_async.py --role server --port 7077 \
        --num-workers 2 --steps 60
    python examples/train_mnist_async.py --role worker --server localhost:7077 \
        --worker-id 0 --steps 30
    python examples/train_mnist_async.py --role worker --server localhost:7077 \
        --worker-id 1 --steps 30

Multi-server key partition (the reference's N-server topology, SURVEY.md §3
row 4 — each server owns the key range shard_for_key assigns it; workers
route per-subtree pushes/pulls to the owners):
    python examples/train_mnist_async.py --role server --port 7077 \
        --shard 0 --num-shards 2 --num-workers 2 --steps 60
    python examples/train_mnist_async.py --role server --port 7078 \
        --shard 1 --num-shards 2 --num-workers 2 --steps 60
    python examples/train_mnist_async.py --role worker \
        --server localhost:7077,localhost:7078 --worker-id 0 --steps 30

Replicated shard with live failover (README "Replication & failover" —
kill the primary mid-run; the backup promotes on the heartbeat timeout and
workers ride straight through):
    python examples/train_mnist_async.py --role server --port 7078 \
        --backup --watch-port 7979 --num-workers 1
    python examples/train_mnist_async.py --role server --port 7077 \
        --replicate-to localhost:7078 --beat localhost:7979 --num-workers 1
    python examples/train_mnist_async.py --role worker \
        --server "localhost:7077|localhost:7078" --worker-id 0 --steps 60
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp

import ps_tpu as ps
from ps_tpu.data.synthetic import mnist_batches
from ps_tpu.models.mlp import MLP, cross_entropy_loss
from ps_tpu.utils import StepLogger


def build(seed: int):
    model = MLP(hidden=32)
    params = model.init(jax.random.key(seed), jnp.zeros((1, 28, 28, 1)))["params"]

    def loss_fn(p, batch):
        images, labels = batch
        return cross_entropy_loss(model.apply({"params": p}, images), labels)

    return params, loss_fn


def main():
    # env-var topology (PS_ROLE / DMLC_ROLE launcher style, config.py) is
    # the flag default; explicit flags override
    cfg = ps.Config.from_env()
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default=cfg.role or "single",
                    choices=["single", "server", "worker"])
    ap.add_argument("--steps", type=int, default=60,
                    help="single/worker: this node's cycles (the server "
                         "drains after every worker disconnects)")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-workers", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--dc-lambda", type=float, default=0.04)
    ap.add_argument("--seed", type=int, default=0)
    # cross-process wiring
    ap.add_argument("--port", type=int, default=0, help="server listen port")
    ap.add_argument("--bind", default="127.0.0.1",
                    help="server listen address (pass 0.0.0.0 explicitly "
                         "for a multi-host job; the endpoint is "
                         "unauthenticated)")
    ap.add_argument("--server", default=cfg.server_uris,
                    help="worker: host:port, comma-separated for an "
                         "N-server partition (or env PS_SERVER_URIS / "
                         "PS_ASYNC_SERVER_URI)")
    ap.add_argument("--worker-id", type=int, default=cfg.worker_id)
    ap.add_argument("--bucket-bytes", type=int,
                    default=cfg.bucket_bytes or 0,
                    help="worker: fusion-bucket size for the bucketed/"
                         "pipelined transport (0 = serial transport; or "
                         "env PS_BUCKET_BYTES)")
    ap.add_argument("--pool", type=int, default=cfg.transport_pool,
                    help="worker: striped connections per server for the "
                         "bucketed transport (env PS_TRANSPORT_POOL)")
    ap.add_argument("--overlap", action="store_true",
                    help="worker: run each push/pull cycle in the "
                         "background (requires --bucket-bytes); gradients "
                         "are still computed against exactly the serial "
                         "step's params")
    ap.add_argument("--compress", default=cfg.compress or "none",
                    choices=["none", "cast16", "int8", "topk"],
                    help="worker: gradient codec for the wire "
                         "(ps_tpu/compress; env PS_COMPRESS). topk keeps "
                         "--compress-topk of each tensor with error-"
                         "feedback residuals")
    ap.add_argument("--compress-topk", type=float, default=cfg.compress_topk,
                    help="worker: kept fraction for --compress topk "
                         "(env PS_COMPRESS_TOPK)")
    ap.add_argument("--compress-min-bytes", type=int,
                    default=cfg.compress_min_bytes,
                    help="worker: tensors under this size always travel "
                         "raw (env PS_COMPRESS_MIN_BYTES)")
    ap.add_argument("--shard", type=int, default=cfg.shard,
                    help="server: this server's index in an N-server key "
                         "partition (or env PS_SHARD)")
    ap.add_argument("--num-shards", type=int, default=cfg.num_shards,
                    help="server: total servers in the key partition "
                         "(or env PS_NUM_SHARDS / DMLC_NUM_SERVER)")
    # shard replication & live failover (README "Replication & failover"):
    # run a second server with --backup --watch-port W; start the primary
    # with --replicate-to backup:port --beat backup:W; point workers at
    # the replica set "primary:port|backup:port" — killing the primary
    # mid-run promotes the backup and the workers ride straight through
    ap.add_argument("--backup", action="store_true",
                    help="server: start in backup role — follow a "
                         "primary's replication stream, refuse worker "
                         "traffic until promoted")
    ap.add_argument("--watch-port", type=int, default=0,
                    help="backup: heartbeat port the PRIMARY must beat "
                         "(--beat); the backup promotes itself when the "
                         "beats stop (0 = no promotion watch)")
    ap.add_argument("--replicate-to", default=None,
                    help="primary: host:port of this shard's backup "
                         "server (attached before workers are admitted)")
    ap.add_argument("--replica-ack", default=cfg.replica_ack,
                    choices=["sync", "async"],
                    help="primary: sync = replies wait for the backup's "
                         "ack (bitwise promotion); async = bounded lag "
                         "(env PS_REPLICA_ACK)")
    ap.add_argument("--replica-window", type=int, default=cfg.replica_window,
                    help="primary: max commits the backup may trail "
                         "(env PS_REPLICA_WINDOW)")
    ap.add_argument("--beat", default=None,
                    help="primary: host:port of the backup's promotion "
                         "watch to heartbeat")
    args = ap.parse_args()
    params, loss_fn = build(args.seed)

    if args.role == "worker":
        uri = args.server or os.environ.get("PS_ASYNC_SERVER_URI")
        if not uri:
            raise SystemExit("worker needs --server host:port "
                             "(or PS_ASYNC_SERVER_URI)")
        from ps_tpu.utils import TrainMetrics

        compress = None
        if args.compress != "none":
            compress = {"codec": args.compress,
                        "topk": args.compress_topk,
                        "min_bytes": args.compress_min_bytes,
                        "pull": cfg.compress_pull}
        w = ps.connect_async(
            uri, args.worker_id, params,
            bucket_bytes=args.bucket_bytes or None,
            pool_size=args.pool if args.bucket_bytes else None,
            compress=compress,
        )
        run = w.make_async_step(loss_fn, overlap=args.overlap)
        log = StepLogger(every=10)
        # the remote worker carries the same byte-counter surface as
        # KVStore, so TrainMetrics reports push/pull GB/s — here those are
        # REAL wire bytes on the van's TCP sockets, the reference's metric
        # in its physical form
        metrics = TrainMetrics(w, batch_size=args.batch_size, num_chips=1)
        # shard the stream by the JOB's worker count (the server's truth)
        stream = mnist_batches(args.batch_size, seed=args.seed,
                               worker=args.worker_id,
                               num_workers=w.num_workers)
        for step in range(args.steps):
            loss = run(next(stream))
            if step == 0:
                metrics.mark_compiled()
            else:
                metrics.step(loss)
            if log.wants(step):
                log.log(step, loss=float(loss), version=w.version)
        if args.overlap:
            w.flush()  # land the final background cycle before reporting
        s = metrics.summary()
        print(f"worker {args.worker_id}: done at server version {w.version}; "
              f"wire push {s['push_gb']:.4f} GB / pull {s['pull_gb']:.4f} GB "
              f"({s['push_pull_gbps']:.3f} GB/s)")
        if "overlap_efficiency" in s:
            print(f"worker {args.worker_id}: overlap efficiency "
                  f"{s['overlap_efficiency']:.2f} "
                  f"({s['transport_hidden_s']:.2f}s of transport hidden "
                  f"under compute)")
        if "compress_ratio" in s:
            extra = (f", residual norm {s['residual_norm']:.4f}"
                     if "residual_norm" in s else "")
            print(f"worker {args.worker_id}: compression "
                  f"{s['compress_ratio']:.2f}x raw/wire "
                  f"({s['codec_s']:.2f}s in codecs{extra})")
        w.close()
        return

    ps.init(backend="tpu", mode="async", num_workers=args.num_workers,
            dc_lambda=args.dc_lambda)
    store = ps.KVStore(optimizer="sgd", learning_rate=args.lr, mode="async")
    if args.role == "server" and args.num_shards is not None:
        # own only this server's key range of the partition
        store.init(ps.shard_tree(params, args.shard, args.num_shards))
    else:
        store.init(params)

    if args.role == "server":
        import time

        svc = ps.serve_async(store, port=args.port, bind=args.bind,
                             shard=args.shard, num_shards=args.num_shards,
                             backup=args.backup)
        shard_note = ("" if args.num_shards is None else
                      f", shard {args.shard}/{args.num_shards}")
        watch = hb = None
        if args.backup:
            if args.watch_port:
                watch = ps.PromotionWatch(svc, primary_id=1,
                                          port=args.watch_port,
                                          bind=args.bind)
            print(f"async PS BACKUP on port {svc.port}{shard_note} — "
                  f"following the primary"
                  + (f", promotion watch on :{watch.port}" if watch else ""),
                  flush=True)
            while svc.role == "backup":  # until promoted (or Ctrl-C)
                time.sleep(0.1)
            print(f"promoted to primary (reason={svc.promote_reason}, "
                  f"epoch {svc.epoch}) — now serving workers", flush=True)
        else:
            if args.replicate_to:
                host, port = args.replicate_to.rsplit(":", 1)
                svc.attach_backup(host, int(port), ack=args.replica_ack,
                                  window=args.replica_window)
            if args.beat:
                from ps_tpu.control.heartbeat import HeartbeatClient

                host, port = args.beat.rsplit(":", 1)
                hb = HeartbeatClient(host, int(port), node_id=1)
            print(f"async PS server on port {svc.port} "
                  f"({args.num_workers} workers expected{shard_note})"
                  + (f", replicating to {args.replicate_to} "
                     f"[{args.replica_ack}]" if args.replicate_to else ""),
                  flush=True)
        # quiesce on worker goodbyes, not push counts: a worker SHUTDOWNs
        # only after its last reply arrived, so stop() cannot race a reply
        # (the r4 flake — see backends/van_service.py)
        svc.wait_for_goodbyes(args.num_workers)
        hist = dict(store._engine.staleness_hist)
        print(f"served {svc.apply_log.total} pushes, "
              f"final version {store._engine.version}, "
              f"staleness histogram {dict(sorted(hist.items()))}")
        if watch is not None:
            watch.close()
        if hb is not None:
            hb.close(goodbye=True)  # planned leave: peers see 'left'
        svc.stop()
        ps.shutdown()
        return

    # single process: drive workers round-robin (staleness accrues because
    # each worker re-pulls only on its own turn)
    run = store.make_async_step(loss_fn)
    log = StepLogger(every=10)
    streams = [
        mnist_batches(args.batch_size, seed=args.seed, worker=w,
                      num_workers=args.num_workers)
        for w in range(args.num_workers)
    ]
    for step in range(args.steps):
        w = step % args.num_workers
        loss = run(next(streams[w]), worker=w)
        if log.wants(step):
            log.log(step, loss=float(loss), worker=w,
                    staleness=store._engine.staleness(w))
    hist = dict(store._engine.staleness_hist)
    print(f"done: version {store._engine.version}, "
          f"staleness histogram {dict(sorted(hist.items()))}")
    ps.shutdown()


if __name__ == "__main__":
    main()
