"""Measure exact per-step HLO FLOPs of the fused steps on the CPU
backend (where pre-compile cost analysis exists — on the TPU jax 0.9.0's
Lowered.cost_analysis returns none), at two batch sizes to separate the per-example slope from
the per-step constant. The provenance of the ``flops`` constants in
``benchmark/configs/*.json``; the derivations are recorded in BASELINE.md.

Run:  python tools/measure_flops.py bert|widedeep|resnet
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(model: str, batch_sizes=(8, 16)) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import ps_tpu as ps

    out = {}
    for bs in batch_sizes:
        if ps.is_initialized():
            ps.shutdown()
        ps.init(backend="tpu")
        if model == "bert":
            from ps_tpu.data.synthetic import mlm_batches
            from ps_tpu.models.bert import BertConfig, BertMLM, make_mlm_loss_fn

            cfg = BertConfig(dtype=jnp.bfloat16)  # the TPU bench dtype
            m = BertMLM(cfg)
            params = m.init(jax.random.key(0), jnp.zeros((2, 128), jnp.int32),
                            jnp.ones((2, 128), jnp.int32))["params"]
            store = ps.KVStore(optimizer="lamb", learning_rate=1e-3,
                               weight_decay=0.01, placement="replicated")
            store.init(params)
            run = store.make_step(make_mlm_loss_fn(m))
            batch = next(mlm_batches(bs, 128, vocab_size=cfg.vocab_size))
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            ca = run.cost_analysis(batch)
        elif model == "widedeep":
            from ps_tpu.data.synthetic import criteo_batches
            from ps_tpu.kv.sparse import SparseEmbedding
            from ps_tpu.models.wide_deep import (
                WideDeep, WideDeepConfig, make_ids_fn, make_wide_deep_loss_fn,
            )
            from ps_tpu.train import make_composite_step

            cfg = WideDeepConfig(per_feature_vocab=100_000, embed_dim=16)
            m = WideDeep(cfg)
            b0 = next(criteo_batches(2, vocab_size=cfg.per_feature_vocab))
            rows = (2, cfg.num_sparse, cfg.embed_dim)
            params = m.init(jax.random.key(0), jnp.asarray(b0["dense"]),
                            jnp.zeros(rows), jnp.zeros(rows[:2] + (1,)))["params"]
            dense = ps.KVStore(optimizer="adam", learning_rate=1e-3,
                               placement="replicated")
            dense.init(params)
            deep = SparseEmbedding(cfg.total_rows, cfg.embed_dim,
                                   optimizer="adagrad", learning_rate=0.05)
            deep.init(jax.random.key(1), scale=0.01)
            wide = SparseEmbedding(cfg.total_rows, 1, optimizer="sgd",
                                   learning_rate=0.05)
            wide.init(jax.random.key(2), scale=0.01)
            run = make_composite_step(dense, {"deep": deep, "wide": wide},
                                      make_wide_deep_loss_fn(m),
                                      make_ids_fn(cfg))
            batch = next(criteo_batches(bs, vocab_size=cfg.per_feature_vocab))
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            ca = run.cost_analysis(batch)
        elif model == "resnet":
            # reproduces the r3 derivation behind
            # benchmark/configs/resnet50.json's constants (BASELINE.md)
            from ps_tpu.data.synthetic import imagenet_batches
            from ps_tpu.models.resnet import ResNet50, make_loss_fn
            from ps_tpu.parallel.sharding import replicated

            ctx = ps.current_context()
            m = ResNet50(dtype=jnp.bfloat16)
            v = m.init(jax.random.key(0), jnp.zeros((2, 224, 224, 3)),
                       train=False)
            mstate = jax.device_put(v["batch_stats"], replicated(ctx.mesh))
            store = ps.KVStore(optimizer="momentum", learning_rate=0.1,
                               momentum=0.9, placement="replicated")
            store.init(v["params"])
            run = store.make_step(make_loss_fn(m, label_smoothing=0.1),
                                  has_aux=True)
            images, labels = next(imagenet_batches(bs))
            ca = run.cost_analysis(
                (jnp.asarray(images), jnp.asarray(labels)), mstate
            )
        else:
            raise SystemExit(f"unknown model {model}")
        out[bs] = float(ca["flops"])
        ps.shutdown()
    b1, b2 = batch_sizes
    slope = (out[b2] - out[b1]) / (b2 - b1)
    const = out[b1] - slope * b1
    return {"model": model, "flops_by_batch": out,
            "slope_per_example": slope, "const_per_step": const}


if __name__ == "__main__":
    print(json.dumps(measure(sys.argv[1] if len(sys.argv) > 1 else "bert")))
