#!/usr/bin/env python3
"""Kimi-Linear-48B-A3B's share at its published widths, outside any timed
window: the system's gradients of step 0 against the plain reference's, per
tensor, and how far the reference on 8-bit weights moves, which the limits of
the benchmark's step-0 checks have to lie under.

    chiprun --chips 1 -- python3 tools/kimi_grad_check.py --seeds 34,35

The weights and batch 0 are those of the benchmark cell
``kimi-linear-48b-a3b.s8192.b1.zipf`` at the same seed, the selection bias
zero as at step 0. System: ``jax.grad`` of
``models/kimi_linear.py::make_loss_fn`` (bf16, the chunked rule, the Pallas
flash kernel at 192 / 128, grouped matmuls over the held experts), the function
``KVStore.make_step`` differentiates. Reference: ``jax.grad`` of
``benchmark/families/kimi_reference.py::loss_fn`` in f32 at "highest". Per
tensor: cosine, norm of the system's over the reference's, and the relative
distance (the first seed only). Then the reference on weights rounded to an
8-bit float (e4m3, a lower bound of computing in one: the nearest precision
below the configuration's bfloat16) against the whole reference: how far the
loss, the expert counts and the gradients of the benchmark's witness leaves
move. ``--rehearse`` runs the same at the configuration's tiny sizes on the
CPU. Results go to stdout and to ``chiprun_out/kimi_grad_check.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="34",
                    help="comma-separated; the first also gets the table")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import kimi_reference as reference
    from benchmark.families import kimi_step
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import kimi_linear

    with open(os.path.join(
            ROOT, "benchmark/configs/kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/s8192.b1.zipf.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("kimi_grad_check: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = kimi_linear.KimiLinearConfig.from_dict(config)
    witnesses = tuple(kimi_step.GRAD_COSINE)
    bias = kimi_linear.init_expert_bias(cfg)

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"kimi_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    system = jax.jit(jax.value_and_grad(
        kimi_linear.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))
    plain = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, bias, config), has_aux=True))
    on_witnesses = jax.jit(lambda p, b: reference.witness_grads(
        p, b, bias, config, witnesses))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16

    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    def moved(a, b):
        a, b = (np.asarray(x["expert_tokens"], np.int64) for x in (a, b))
        return (np.abs(a - b).sum(axis=-1) // 2).tolist()

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    for seed in [int(x) for x in args.seeds.split(",")]:
        batch = next(kimi_step.fresh_batches(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: kimi_linear.init_params(k, cfg))(
            seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            (ref_loss, ref_aux), whole = timed(
                "reference, the witnesses",
                lambda: on_witnesses(params, batch))
        if not out["seeds"]:
            # every tensor, the system's against the reference's
            (loss, aux), grads = timed("system gradients",
                                       lambda: system(params, batch, bias))
            with jax.default_matmul_precision("highest"):
                _, ref_grads = timed("reference gradients",
                                     lambda: plain(params, batch))
            one["loss"] = {"system": float(loss),
                           "reference": float(ref_loss),
                           "rel_diff": rel(loss, ref_loss)}
            one["pairs_on_another_expert"] = moved(aux, ref_aux)
            rows = []
            flat, _ = jax.tree_util.tree_flatten_with_path(grads)
            for (path, g), r in zip(flat,
                                    jax.tree_util.tree_leaves(ref_grads)):
                g, r = (np.asarray(x, np.float64).ravel() for x in (g, r))
                rows.append({"tensor": jax.tree_util.keystr(path),
                             "cosine": kimi_step.cosine(g, r),
                             "norm_ratio": norm(g) / norm(r),
                             "rel_diff": norm(g - r) / norm(r)})
            one["gradients"] = rows
            one["worst"] = min(rows, key=lambda row: row["cosine"])
            print(f"{'tensor':48s} {'cosine':>12s} {'norm ratio':>12s} "
                  f"{'rel diff':>12s}")
            for row in rows:
                print(f"{row['tensor']:48s} {row['cosine']:12.8f} "
                      f"{row['norm_ratio']:12.6f} {row['rel_diff']:12.3e}")
            del grads, ref_grads, flat
        rounded = jax.tree_util.tree_map(
            lambda w: w.astype(fp8).astype(w.dtype), params)
        with jax.default_matmul_precision("highest"):
            (value, v_aux), v_grads = timed(
                "reference on e4m3 weights",
                lambda: on_witnesses(rounded, batch))
        one["reference_on_e4m3_weights"] = {
            "loss_rel_diff": rel(value, ref_loss),
            "pairs_on_another_expert": moved(v_aux, ref_aux),
            **{f"grad_cosine.{k}": kimi_step.cosine(v_grads[k], whole[k])
               for k in witnesses},
            **{f"grad_norm_ratio.{k}": norm(v_grads[k]) / norm(whole[k])
               for k in witnesses}}
        out["seeds"].append(one)
        # one line a seed; the last line of stdout is the last seed's
        print(json.dumps({k: v for k, v in one.items() if k != "gradients"}),
              flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kimi_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
