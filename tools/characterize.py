"""Single-chip characterization harness (VERDICT r2 item 1; r5: +BERT).

Runs the same fused PS step as bench.py on the real chip, and reports the
numbers the bench's one-line JSON cannot: XLA cost-analysis FLOPs/step, MFU
against the detected chip peak, a jax.profiler trace, and the top op-level
time sinks parsed from the trace (via xprof's xspace converter). Use this to
decide tuning, then fold the distilled metrics into bench.py.

Usage: python tools/characterize.py [--model resnet|bert] [--batch 256]
       [--steps 12] [--trace-dir /tmp/ps_trace]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import ps_tpu as ps
from ps_tpu.data.synthetic import imagenet_batches
from ps_tpu.models.resnet import ResNet50, make_loss_fn
from ps_tpu.parallel.sharding import replicated


def detect_peak_tflops(device):
    from ps_tpu.utils.chips import peak_bf16_tflops

    return peak_bf16_tflops(device)


def top_op_sinks(trace_dir: str, k: int = 10):
    """Parse the .xplane.pb under trace_dir; return top-k ops by self time."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    from xprof.convert import raw_to_tool_data as rtd

    data, _ = rtd.xspace_to_tool_data([paths[-1]], "framework_op_stats", {})
    if isinstance(data, bytes):
        data = data.decode("utf-8", "replace")
    rows = json.loads(data)
    # framework_op_stats JSON: list of tables; first is by-op records
    return rows, paths[-1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet", choices=["resnet", "bert"])
    ap.add_argument("--batch", type=int, default=None,
                    help="default: 256 (resnet) / 128 (bert)")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--trace-dir", default="/tmp/ps_trace")
    ap.add_argument("--placement", default="replicated")
    ap.add_argument("--no-trace", action="store_true")
    args = ap.parse_args()
    if args.batch is None:
        args.batch = 256 if args.model == "resnet" else 128
    if args.model == "bert":
        return char_bert(args)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print(f"device: {dev.device_kind} ({dev.platform}) x{len(jax.devices())}")

    ctx = ps.init(backend="tpu")
    model = ResNet50(dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    variables = model.init(
        jax.random.key(0), jnp.zeros((2, args.image_size, args.image_size, 3)),
        train=False,
    )
    params, model_state = variables["params"], variables["batch_stats"]
    model_state = jax.device_put(model_state, replicated(ctx.mesh))

    store = ps.KVStore(optimizer="momentum", learning_rate=0.1, momentum=0.9,
                       placement=args.placement)
    store.init(params)
    run = store.make_step(make_loss_fn(model, label_smoothing=0.1), has_aux=True)

    batches = [
        store.shard_batch(b) for b in imagenet_batches(
            args.batch, image_size=args.image_size, steps=3
        )
    ]
    jax.block_until_ready(batches)

    # Warmup (compile + relayout); timing below is steady state.
    for step in range(2):
        loss, _, model_state = run(batches[step % len(batches)], model_state)
    loss.block_until_ready()

    t0 = time.time()
    for step in range(args.steps):
        loss, _, model_state = run(batches[step % len(batches)], model_state)
    loss.block_until_ready()
    jax.block_until_ready(store.params())
    dt = time.time() - t0
    ips = args.steps * args.batch / dt
    print(f"throughput: {ips:.1f} imgs/sec  ({dt/args.steps*1e3:.2f} ms/step)"
          f"  loss={float(loss):.4f}")

    # HLO cost analysis of the exact fused step (jax 0.9.0's lowering
    # returns None on the TPU — the CPU backend measures the same program;
    # bench.py carries the resulting per-image constant)
    try:
        ca = run.cost_analysis(batches[0], model_state)
    except Exception:
        ca = None
    if ca and ca.get("flops"):
        flops = float(ca["flops"])
        print(f"flops/step (HLO): {flops:.3e}  "
              f"sustained: {flops * args.steps / dt / 1e12:.1f} TFLOPS")
    else:
        print("flops: live cost analysis unavailable on this platform "
              "(run on JAX_PLATFORMS=cpu for the HLO numbers)")

    if on_tpu:  # an unknown TPU raises; off the TPU there is no peak
        print(f"chip peak (bf16): {detect_peak_tflops(dev)} TFLOPS")

    if not args.no_trace and on_tpu:
        os.makedirs(args.trace_dir, exist_ok=True)
        with jax.profiler.trace(args.trace_dir):
            for step in range(4):
                loss, _, model_state = run(batches[step % len(batches)], model_state)
            loss.block_until_ready()
        print(f"trace written to {args.trace_dir}")
        try:
            rows, path = top_op_sinks(args.trace_dir)
            out = os.path.join(args.trace_dir, "op_stats.json")
            with open(out, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"op stats -> {out}")
        except Exception as e:
            print("trace parse failed:", e)


def char_bert(args):
    """BERT-base MLM + LAMB: the bench_bert step, traced."""
    from ps_tpu.data.synthetic import mlm_batches
    from ps_tpu.models.bert import BertConfig, BertMLM, make_mlm_loss_fn

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print(f"device: {dev.device_kind} ({dev.platform}) x{len(jax.devices())}")

    ps.init(backend="tpu")
    cfg = BertConfig(dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    model = BertMLM(cfg)
    shape = (2, args.seq_len)
    params = model.init(jax.random.key(0), jnp.zeros(shape, jnp.int32),
                        jnp.ones(shape, jnp.int32))["params"]
    store = ps.KVStore(optimizer="lamb", learning_rate=1e-3,
                       weight_decay=0.01, placement=args.placement)
    store.init(params)
    run = store.make_step(make_mlm_loss_fn(model))
    batches = [
        store.shard_batch(b)
        for b in mlm_batches(args.batch, args.seq_len,
                             vocab_size=cfg.vocab_size, steps=3)
    ]
    jax.block_until_ready(batches)
    for step in range(2):
        loss, _ = run(batches[step % 3])
    loss.block_until_ready()

    t0 = time.time()
    for step in range(args.steps):
        loss, _ = run(batches[step % 3])
    loss.block_until_ready()
    jax.block_until_ready(store.params())
    dt = time.time() - t0
    print(f"throughput: {args.steps * args.batch / dt:.1f} seqs/sec  "
          f"({dt/args.steps*1e3:.2f} ms/step)  loss={float(loss):.4f}")

    if on_tpu:  # an unknown TPU raises; off the TPU there is no peak
        print(f"chip peak (bf16): {detect_peak_tflops(dev)} TFLOPS")

    if not args.no_trace and on_tpu:
        os.makedirs(args.trace_dir, exist_ok=True)
        with jax.profiler.trace(args.trace_dir):
            for step in range(4):
                loss, _ = run(batches[step % 3])
            loss.block_until_ready()
        print(f"trace written to {args.trace_dir}")
        try:
            rows, path = top_op_sinks(args.trace_dir)
            out = os.path.join(args.trace_dir, "op_stats.json")
            with open(out, "w") as f:
                json.dump(rows, f, indent=1)
            print(f"op stats -> {out}")
        except Exception as e:
            print("trace parse failed:", e)


if __name__ == "__main__":
    main()
