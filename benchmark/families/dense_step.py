"""Family of the fused dense step: ``ps.init`` -> ``KVStore`` ->
``make_step`` -> ``shard_batch``. ResNet-50 and BERT differ by their
configuration file (``"model"``), not by code path: the library calls are
those of ``chip_smoke.py``'s first two legs.

The yardstick's own pieces live here: the input generators (copied from
``ps_tpu/data/synthetic.py``), the plain reference forward pass, and the
functions that compute operations and bytes from shapes.
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmark.families import flash
from benchmark.harness.loop import Cell, seed_key

#: bf16 unit roundoff
_BF16_U = 2.0 ** -8
# Tolerance of the step-0 check, relative to the reference loss. The fused
# step computes in bf16 as the configuration states, the reference in f32 at
# "highest" matmul precision, so they differ by accumulated bf16 rounding:
# measured 3e-6 to 4e-5 of a loss of 7 (ResNet-50) and 11 nats (BERT-base)
# on the chip over five seeds (my chip runs, PR 24). 1/16 of a bf16 roundoff
# leaves 6x room over the largest seen, and is well inside what a wrong
# mask, a missing label smoothing (0.1 nats of 7), a dropped layer norm or
# an 8-bit float would move.
TOLERANCE = (_BF16_U / 16,
             "bf16 compute against an f32 reference: 1/16 bf16 roundoff of "
             "the loss, 6x the largest difference seen")


def imagenet_pool(batch, image_size, seed, count):
    """What ``imagenet_batches`` of ps_tpu/data/synthetic.py yields: f32
    noise images [B,H,W,3] and labels in [0,1000), as host arrays. The
    noise is drawn on the device in one jitted call and copied to the host:
    1.2 GB of normals take numpy 10 s of every run's set-up, on a host whose
    cores are shared."""
    import jax
    import jax.numpy as jnp

    key = seed_key(seed)
    images = np.asarray(jax.jit(lambda k: jax.random.normal(
        k, (count, batch, image_size, image_size, 3), jnp.float32))(key))
    labels = np.random.default_rng(seed).integers(
        0, 1000, size=(count, batch)).astype(np.int32)
    return [(images[i], labels[i]) for i in range(count)]


def mlm_pool(batch, seq_len, vocab_size, seed, count, mask_rate=0.15,
             mask_id=103):
    """``mlm_batches`` of ps_tpu/data/synthetic.py: input_ids, labels
    (-100 = unmasked), attention_mask."""
    rng = np.random.default_rng(seed)
    low = max(min(1000, vocab_size // 4), mask_id + 1)
    pool = []
    for _ in range(count):
        ids = rng.integers(low, vocab_size,
                           size=(batch, seq_len)).astype(np.int32)
        mask = rng.random((batch, seq_len)) < mask_rate
        pool.append({
            "input_ids": np.where(mask, mask_id, ids).astype(np.int32),
            "labels": np.where(mask, ids, -100).astype(np.int32),
            "attention_mask": np.ones_like(ids),
        })
    return pool


def param_bytes(tree) -> int:
    """``tree_bytes`` of ps_tpu/parallel/collectives.py."""
    import jax

    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def zero1_bytes_per_device(nbytes: int, k: int) -> float:
    """Ring costs of ps_tpu/parallel/collectives.py for placement
    'sharded': reduce-scatter of the gradients plus all-gather of the
    parameters, N*(k-1)/k bytes sent per device each."""
    return 0.0 if k <= 1 else 2.0 * nbytes * (k - 1) / k


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import jax
    import jax.numpy as jnp

    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch, threaded_source
    from ps_tpu.parallel.sharding import replicated

    ctx = ps.init(backend="tpu")
    dtype = jnp.dtype(config["dtype"])
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    key = seed_key(seed)
    opt = dict(config["optimizer"])
    store = ps.KVStore(optimizer=opt.pop("name"),
                       placement="sharded" if chips > 1 else "replicated",
                       **opt)
    facts = {"flops_per_step": None}
    flops = config.get("flops")

    if config["model"] == "resnet50":
        from ps_tpu.models.resnet import (BottleneckBlock, ResNet,
                                          cross_entropy_loss, make_loss_fn)

        size = int(config["image_size"])
        shape = dict(stage_sizes=tuple(config["stage_sizes"]),
                     block_cls=BottleneckBlock,
                     num_classes=config["num_classes"],
                     num_filters=config["num_filters"],
                     small_inputs=config.get("small_inputs", False))
        model = ResNet(dtype=dtype, **shape)
        plain = ResNet(dtype=jnp.float32, **shape)
        variables = jax.jit(lambda k: model.init(
            k, jnp.zeros((2, size, size, 3)), train=False))(key)
        state = {"model_state": jax.device_put(variables["batch_stats"],
                                               replicated(ctx.mesh))}
        store.init(variables["params"])
        smoothing = config["label_smoothing"]
        fused = store.make_step(make_loss_fn(model, smoothing), has_aux=True)
        pool = imagenet_pool(batch, size, seed, int(traffic["pool"]))

        def step(b):
            loss, _, state["model_state"] = fused(b, state["model_state"])
            return loss

        @jax.jit
        def forward(params, model_state, b):
            images, labels = b
            logits, _ = plain.apply(
                {"params": params, "batch_stats": model_state}, images,
                train=True, mutable=["batch_stats"])
            return cross_entropy_loss(logits, labels, smoothing)

        def reference_loss(b):
            with jax.default_matmul_precision("highest"):
                return float(forward(store.params(), state["model_state"], b))

        if flops and size == flops["image_size"]:
            facts["flops_per_step"] = (flops["per_sample"] * batch
                                       + flops["per_step_const"])
    elif config["model"] == "bert":
        from ps_tpu.models.bert import (BertConfig, BertMLM,
                                        make_mlm_loss_fn, mlm_loss)
        from ps_tpu.ops.flash_attention import backward_tiles

        seq = int(traffic["seq_len"])
        shape = dict(vocab_size=config["vocab_size"],
                     hidden_size=config["hidden_size"],
                     num_layers=config["num_hidden_layers"],
                     num_heads=config["num_attention_heads"],
                     intermediate_size=config["intermediate_size"],
                     max_len=config["max_position_embeddings"],
                     type_vocab_size=config["type_vocab_size"])
        model = BertMLM(BertConfig(dtype=dtype, attn=traffic["attn"],
                                   **shape))
        plain = BertMLM(BertConfig(dtype=jnp.float32, attn="full", **shape))
        params = jax.jit(lambda k: model.init(
            k, jnp.zeros((2, seq), jnp.int32),
            jnp.ones((2, seq), jnp.int32)))(key)["params"]
        store.init(params)
        fused = store.make_step(make_mlm_loss_fn(model))
        pool = mlm_pool(batch, seq, config["vocab_size"], seed,
                        int(traffic["pool"]))

        def step(b):
            return fused(b)[0]

        @jax.jit
        def forward(params, b):
            logits = plain.apply({"params": params}, b["input_ids"],
                                 b["attention_mask"])
            return mlm_loss(logits, b["labels"])

        def reference_loss(b):
            with jax.default_matmul_precision("highest"):
                return float(forward(store.params(), b))

        per_seq = (flops or {}).get("by_seq_len", {}).get(str(seq))
        if per_seq:
            facts["flops_per_step"] = (per_seq["per_sample"] * batch
                                       + per_seq["per_step_const"])
        if traffic["attn"] == "flash":
            # a padding mask, no mask over positions: every pair. Each head
            # has K and V of its own, so the backward is the one call where
            # the op's own rule gives it a tile that spans the sequence (512
            # does), and the two calls past that
            heads = config["num_attention_heads"]
            dim = config["hidden_size"] // heads
            spans = backward_tiles(seq, dim, dtype.itemsize, False) \
                == (seq, seq)
            facts["flash_flops"], facts["flash_bytes"] = flash.cost(
                per_chip, heads, heads, seq, dim, dim,
                config["num_hidden_layers"],
                flash.seen_pairs(seq, causal=False),
                "one call" if spans else "two calls", dtype.itemsize)
            facts["kernel_targets"] = config["kernel_targets"]
    else:
        raise ValueError(f"dense_step knows no model {config['model']!r}")

    facts["collective_bytes_per_step"] = zero1_bytes_per_device(
        param_bytes(store.params()), chips)
    source = itertools.cycle(pool)
    if traffic["input"] == "hostfeed":
        source = threaded_source(source)
    elif traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    stream = device_prefetch(source, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=dict, facts=facts, close=ps.shutdown)
