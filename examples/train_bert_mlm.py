"""BERT-base MLM with server-side LAMB — reference workload config 3.

Reference workload (BASELINE.json): "BERT-base MLM (dense grads + server-side
LAMB optimizer)". The GPU reference pushes dense grads to PS servers that
apply LAMB; here LAMB runs as a sharded optax update inside the fused SPMD
step — the layerwise trust-ratio norms are per parameter tensor, so with
ZeRO-1 'sharded' placement XLA inserts the per-tensor norm reduces
(SURVEY.md §8 hard part (b); the parity test in tests/test_bert.py asserts
shard-exact numerics).

The MLM head runs on the labelled positions only: ``make_mlm_loss_fn`` picks
them inside groups of whole sequences, a quarter of a group's positions a
trip (at 15% masking one trip does), as the published trainer gathers its
``masked_lm_positions``; ``BertMLM.apply`` without ``positions`` still
returns every position's logits ``[B, S, V]``. The trips a batch takes
beyond its first are counted here, on the host, into
``ps_mlm_head_overflow_total``.

Run (CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8):
    python examples/train_bert_mlm.py --steps 20 --batch-size 32 --seq-len 128
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

import ps_tpu as ps
from ps_tpu.data.synthetic import mlm_batches
from ps_tpu.models.bert import (BertConfig, BertMLM, bert_partition_rules,
                                count_head_overflow, make_mlm_loss_fn)
from ps_tpu.utils import StepLogger, TrainMetrics, trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32, help="global batch")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--weight-decay", type=float, default=0.01)
    ap.add_argument("--size", default="base", choices=["base", "tiny"])
    ap.add_argument("--placement", default="sharded", choices=["replicated", "sharded"])
    ap.add_argument("--model-axis", type=int, default=1,
                    help="tensor-parallel width: Megatron placement via "
                         "bert_partition_rules over a 'model' mesh axis")
    ap.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--jsonl", default=None)
    ap.add_argument("--profile-dir", default=None)
    args = ap.parse_args()

    if args.steps < 2:
        raise SystemExit("--steps must be >= 2 (step 0 is compile/warmup)")
    ndev_all = len(jax.devices())
    tp = args.model_axis
    if tp > 1:
        if ndev_all % tp:
            raise SystemExit(f"--model-axis {tp} must divide the device "
                             f"count ({ndev_all})")
        ps.init(backend="tpu",
                mesh_shape={"data": ndev_all // tp, "model": tp})
    else:
        ps.init(backend="tpu")
    dp = ndev_all // tp if tp > 1 else ndev_all  # data-axis size
    if args.batch_size % dp:
        raise SystemExit(f"--batch-size must be divisible by the data-axis size ({dp})")

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    cfg = BertConfig(dtype=dtype) if args.size == "base" else BertConfig.tiny(dtype=dtype)
    model = BertMLM(cfg)
    shape = (2, args.seq_len)
    params = model.init(
        jax.random.key(args.seed),
        jnp.zeros(shape, jnp.int32), jnp.ones(shape, jnp.int32),
    )["params"]

    store = ps.KVStore(optimizer="lamb", learning_rate=args.lr,
                       weight_decay=args.weight_decay, placement=args.placement,
                       partition_rules=bert_partition_rules() if tp > 1 else None)
    store.init(params)
    nparams = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"BERT-{args.size} MLM: {nparams/1e6:.1f}M params, {ndev_all} "
          f"devices (data={dp}, model={tp}), "
          f"global batch {args.batch_size} x seq {args.seq_len}, "
          f"LAMB placement={args.placement}")

    run = store.make_step(make_mlm_loss_fn(model))
    stream = mlm_batches(args.batch_size, args.seq_len,
                         vocab_size=cfg.vocab_size, seed=args.seed,
                         steps=args.steps)

    # all chips participate in every step (dp AND tp): per-chip
    # rates divide by the full device count, not the data-axis size
    metrics = TrainMetrics(store, batch_size=args.batch_size,
                           num_chips=ndev_all)
    log = StepLogger(every=10, jsonl=args.jsonl)
    with trace(args.profile_dir):
        for step, batch in enumerate(stream):
            count_head_overflow(batch["labels"])
            loss, _ = run(store.shard_batch(batch))
            if step == 0:
                loss.block_until_ready()
                metrics.mark_compiled()
            else:
                metrics.step(loss)
            if log.wants(step):
                log.log(step, loss=float(loss))
        jax.block_until_ready(store.params())
    s = metrics.summary()
    print(f"done: {s['examples_per_sec']:.1f} seq/s total, "
          f"{s['examples_per_sec_per_chip']:.1f} seq/s/chip, "
          f"analytic ICI traffic {s['ici_gb_per_device']:.2f} GB "
          f"({s['ici_gbps_per_device']:.2f} GB/s/device)")
    log.close()


if __name__ == "__main__":
    main()
