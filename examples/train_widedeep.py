"""Wide-&-Deep on Criteo-like data — the sparse push/pull workload.

Reference workload config 4 (BASELINE.json): "sparse push/pull: Wide-&-Deep
on Criteo (row-sparse embedding tables)". The GPU reference pushes (row_ids,
row_grads) to range-sharded servers that scatter-apply with per-row state;
here the whole composite step — sharded-table row gather, dense grads +
psum, row-grad exchange (all_gather or capacity-bounded all_to_all) +
scatter-apply — is ONE jitted SPMD program (ps_tpu/train.py).

Run (CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8):
    python examples/train_widedeep.py --steps 50 --batch-size 512
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import ps_tpu as ps
from ps_tpu.data.synthetic import criteo_batches
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.models.wide_deep import (
    WideDeep, WideDeepConfig, make_ids_fn, make_wide_deep_loss_fn,
)
from ps_tpu.train import make_composite_step
from ps_tpu.utils import StepLogger, TrainMetrics, trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=512, help="global batch")
    ap.add_argument("--vocab", type=int, default=100_000, help="rows per feature")
    ap.add_argument("--embed-dim", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--embed-lr", type=float, default=0.05)
    ap.add_argument("--embed-optimizer", default="adagrad",
                    choices=["sgd", "adagrad", "adam"])
    ap.add_argument("--exchange", default="gather", choices=["gather", "a2a"])
    ap.add_argument("--capacity-factor", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default=None, metavar="DIR",
                    help="column-npy dataset directory (fields dense, "
                         "sparse, label — see ps_tpu.data.files."
                         "write_dataset); default: synthetic generator")
    ap.add_argument("--jsonl", default=None)
    ap.add_argument("--profile-dir", default=None)
    args = ap.parse_args()

    if args.steps < 2:
        raise SystemExit("--steps must be >= 2 (step 0 is compile/warmup)")
    ps.init(backend="tpu")
    ndev = len(jax.devices())
    if args.batch_size % ndev:
        raise SystemExit(f"--batch-size must be divisible by the device count ({ndev})")

    cfg = WideDeepConfig(per_feature_vocab=args.vocab, embed_dim=args.embed_dim)
    model = WideDeep(cfg)
    batch0 = next(criteo_batches(2, vocab_size=cfg.per_feature_vocab, seed=args.seed))
    rows_shape = (2, cfg.num_sparse, cfg.embed_dim)
    params = model.init(
        jax.random.key(args.seed), jnp.asarray(batch0["dense"]),
        jnp.zeros(rows_shape), jnp.zeros(rows_shape[:2] + (1,)),
    )["params"]

    dense = ps.KVStore(optimizer="adam", learning_rate=args.lr, placement="sharded")
    dense.init(params)
    deep = SparseEmbedding(cfg.total_rows, cfg.embed_dim,
                           optimizer=args.embed_optimizer,
                           learning_rate=args.embed_lr,
                           exchange=args.exchange,
                           capacity_factor=args.capacity_factor)
    deep.init(jax.random.key(args.seed + 1), scale=0.01)
    wide = SparseEmbedding(cfg.total_rows, 1, optimizer="sgd",
                           learning_rate=args.embed_lr,
                           exchange=args.exchange,
                           capacity_factor=args.capacity_factor)
    wide.init(jax.random.key(args.seed + 2), scale=0.01)

    ndense = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"Wide&Deep: {ndense/1e6:.2f}M dense params + "
          f"{deep.padded_rows * (cfg.embed_dim + 1) / 1e6:.1f}M embedding rows x dims, "
          f"{ndev} devices, global batch {args.batch_size}, "
          f"exchange={args.exchange}")

    run = make_composite_step(
        dense, {"deep": deep, "wide": wide},
        make_wide_deep_loss_fn(model), make_ids_fn(cfg),
    )

    metrics = TrainMetrics(dense, batch_size=args.batch_size, num_chips=ndev)
    log = StepLogger(every=10, jsonl=args.jsonl)
    if args.data:
        from ps_tpu.data.files import file_batches

        stream = file_batches(args.data, args.batch_size, steps=args.steps,
                              shuffle=True, seed=args.seed,
                              fields=("dense", "sparse", "label"))
    else:
        stream = criteo_batches(args.batch_size,
                                vocab_size=cfg.per_feature_vocab,
                                seed=args.seed, steps=args.steps)
    with trace(args.profile_dir):
        for step, batch in enumerate(stream):
            loss, _ = run(dense.shard_batch(batch))
            if step == 0:
                loss.block_until_ready()
                metrics.mark_compiled()
            else:
                metrics.step(loss)
            if log.wants(step):
                log.log(step, loss=float(loss))
        jax.block_until_ready(dense.params())
    s = metrics.summary()
    emb_gb = (deep.bytes_pushed + deep.bytes_pulled
              + wide.bytes_pushed + wide.bytes_pulled) / 1e9
    print(f"done: {s['examples_per_sec']:.1f} ex/s total, "
          f"{s['examples_per_sec_per_chip']:.1f} ex/s/chip, "
          f"dense ICI {s['ici_gb_per_device']:.3f} GB, "
          f"sparse row traffic {emb_gb:.3f} GB "
          f"(+{(deep.collective_bytes + wide.collective_bytes)/1e9:.3f} GB/device collective)")
    for name, emb in (("deep", deep), ("wide", wide)):
        if emb.exchange == "a2a":
            print(f"  {name}: a2a dropped {emb.dropped_rows} of "
                  f"{emb.rows_pushed} rows "
                  f"({100 * emb.dropped_fraction:.3f}%) — raise "
                  f"--capacity-factor if this is not ~0")
    log.close()


if __name__ == "__main__":
    main()
