"""Long-context causal LM over a dp×sp (×tp) mesh.

The long-context workload: a causal transformer whose ACTIVATIONS are
sharded along a 'seq' mesh axis, with ring (or Ulysses) attention doing the
cross-shard mixing — per-device attention memory is O((T/s)²) per block pair
instead of O(T²) — while the PS protocol around it is unchanged: fused
grad + psum + sharded server apply per step. Optional 'model' axis adds
Megatron tensor parallelism via partition rules.

Run on any devices (CPU: JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8):
    python examples/train_longctx_lm.py --steps 20 --seq-len 256 \
        --mesh data=2,seq=4 --attn ring
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import ps_tpu as ps
from ps_tpu.models import lm
from ps_tpu.models.blocks import make_attn_fn
from ps_tpu.parallel.mesh import parse_mesh
from ps_tpu.utils import StepLogger, TrainMetrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=8, help="global batch")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="data=2,seq=4",
                    help="e.g. data=2,seq=4, data=2,model=2,seq=2, or "
                         "data=2,pipe=4 with --microbatches")
    ap.add_argument("--attn", default="ring",
                    choices=["full", "ring", "ulysses"])
    ap.add_argument("--microbatches", type=int, default=0,
                    help="> 0 with a 'pipe' mesh axis: GPipe the "
                         "transformer trunk over it (heterogeneous "
                         "stages: embed/readout stay data-parallel); "
                         "n-layers must divide by the pipe size")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    mesh_shape = parse_mesh(args.mesh)
    if "data" not in mesh_shape:
        raise SystemExit("--mesh needs a 'data' axis (the PS worker/server "
                         "axis), e.g. data=1,seq=8 for pure sequence "
                         "parallelism")
    ctx = ps.init(backend="tpu", mesh_shape=mesh_shape)
    sp = mesh_shape.get("seq", 1)
    pp = mesh_shape.get("pipe", 1)
    if args.attn != "full" and sp <= 1:
        raise SystemExit("--attn ring/ulysses needs a seq axis > 1")
    if args.seq_len % max(sp, 1):
        raise SystemExit("--seq-len must be divisible by the seq axis")
    if (pp > 1) != (args.microbatches > 0):
        raise SystemExit("pipelining needs BOTH a pipe mesh axis and "
                         "--microbatches > 0")
    if pp > 1 and args.attn != "full":
        raise SystemExit("--microbatches composes with full attention "
                         "(ring/ulysses shard the sequence axis the "
                         "pipeline microbatches would re-shard)")
    if pp > 1 and mesh_shape.get("model", 1) > 1:
        raise SystemExit("pipe + model axes do not compose yet: the GPipe "
                         "shard_map replicates stage params over 'model', "
                         "so TP would be silently dropped — use one or "
                         "the other")
    if args.microbatches > 0 and args.batch_size % args.microbatches:
        raise SystemExit("--batch-size must be divisible by --microbatches")
    if pp > 1 and args.n_layers % pp:
        raise SystemExit(f"--n-layers {args.n_layers} must divide into "
                         f"{pp} pipeline stages")

    params = lm.init_params(
        np.random.default_rng(args.seed), vocab=args.vocab,
        d_model=args.d_model, n_heads=args.n_heads, n_layers=args.n_layers,
        max_len=args.seq_len + 1,
    )
    nparams = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    print(f"causal LM: {nparams/1e6:.2f}M params, mesh {mesh_shape}, "
          f"attn={args.attn}, T={args.seq_len}")

    rules = lm.lm_partition_rules() if mesh_shape.get("model", 1) > 1 else None
    attn_fn = make_attn_fn(args.attn, mesh=ctx.mesh)
    if pp > 1:
        # heterogeneous dp x pp: blocks stack on 'pipe', embed/readout
        # stay dense (ps_tpu/models/lm.py) — parity vs non-pipelined is
        # asserted in tests/test_pipeline.py. (No extra Megatron rules:
        # model+pipe is rejected above — the stacked trunk leaves could
        # not match the rank-2 TP rules anyway.)
        params = lm.split_pipeline_params(params, num_stages=pp)
        rules = lm.pipeline_lm_partition_rules()
        loss_fn = lm.make_pipelined_loss_fn(
            n_heads=args.n_heads, num_stages=pp,
            microbatches=args.microbatches, attn_fn=attn_fn,
        )
    else:
        loss_fn = lm.make_loss_fn(n_heads=args.n_heads, attn_fn=attn_fn)
    store = ps.KVStore(optimizer="adam", learning_rate=args.lr,
                       placement="sharded", partition_rules=rules)
    store.init(params)
    run = store.make_step(loss_fn)

    # activations shard batch over 'data' AND sequence over 'seq'
    tok_sharding = NamedSharding(
        ctx.mesh, P("data", "seq" if sp > 1 else None)
    )
    # same input pipeline as the other trainers: generation in a producer
    # thread, 2-deep double-buffered placement overlapping the step
    from ps_tpu.data.prefetch import device_prefetch, threaded_source

    def place(batch):
        return {k: jax.device_put(jnp.asarray(v), tok_sharding)
                for k, v in batch.items()}

    stream = device_prefetch(
        threaded_source(lm.lm_batches(args.batch_size, args.seq_len,
                                      vocab=args.vocab, seed=args.seed,
                                      steps=args.steps)),
        place=place,
    )
    metrics = TrainMetrics(store, batch_size=args.batch_size,
                           num_chips=len(jax.devices()))
    log = StepLogger(every=5)
    for step, placed in enumerate(stream):
        loss, _ = run(placed)
        if step == 0:
            loss.block_until_ready()
            metrics.mark_compiled()
        else:
            metrics.step(loss)
        if log.wants(step):
            log.log(step, loss=float(loss))
    jax.block_until_ready(store.params())
    s = metrics.summary()
    print(f"done: {s['steps_per_sec']:.2f} steps/s, final loss {s['loss']:.4f}")


if __name__ == "__main__":
    main()
