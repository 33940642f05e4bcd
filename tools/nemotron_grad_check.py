#!/usr/bin/env python3
"""Nemotron-3-Super-120B-A12B's share at its published widths, outside any
timed window: the system's gradients of step 0 against the plain reference's,
per tensor, and how far the reference on 8-bit weights moves, which the limits
of the benchmark's step-0 checks have to lie under.

    chiprun --chips 1 -- python3 tools/nemotron_grad_check.py --seeds 39,40

The weights and batch 0 are those of the benchmark cell
``nemotron-3-super-120b-a12b.s8192.b1.zipf`` at the same seed, the selection
bias zero as at step 0. System: ``jax.grad`` of
``models/nemotron_h.py::make_loss_fn`` (bf16, the chunked scan, the Pallas
flash kernel at 4 query heads on 1 K/V head, grouped matmuls over the held
experts in the latent), the function ``KVStore.make_step`` differentiates.
Reference: ``jax.grad`` of
``benchmark/families/nemotron_h_reference.py::loss_fn`` in f32 at "highest".
Per tensor: cosine, norm of the system's over the reference's, and the
relative distance (the first seed only, and only with ``--table``). Then the
reference on weights rounded to an 8-bit float (e4m3, a lower bound of
computing in one: the nearest precision below the configuration's bfloat16)
against the whole reference: how far the loss, the expert counts and the
gradients of the benchmark's witness leaves move. Last, the fault that the
limit on the witnesses' lengths is there for (``GRAD_NORM_TOLERANCE``: no
precision moves them past it), planted in the reference: the picks' weights
not scaled by ``routed_scaling_factor``, or not renormalised, against the
whole reference. ``--rehearse`` runs the same
at the configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/nemotron_grad_check.json``.
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
    ap.add_argument("--seeds", default="39",
                    help="comma-separated; the first also gets the table")
    ap.add_argument("--table", action="store_true",
                    help="every tensor's gradient at the first seed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import nemotron_h_reference as reference
    from benchmark.families import nemotron_h_step
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import nemotron_h

    with open(os.path.join(
            ROOT, "benchmark/configs/nemotron-3-super-120b-a12b.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/s8192.b1.zipf.n96.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("nemotron_grad_check: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = nemotron_h.NemotronHConfig.from_dict(config)
    witnesses = tuple(nemotron_h_step.GRAD_COSINE)
    bias = nemotron_h.init_expert_bias(cfg)

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"nemotron_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    system = jax.jit(jax.value_and_grad(
        nemotron_h.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))
    plain = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, bias, config), has_aux=True))
    on_witnesses = jax.jit(lambda p, b: reference.witness_grads(
        p, b, bias, config, witnesses))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16
    faulty = {name: jax.jit(lambda p, b, c={**config, **change}:
                            reference.witness_grads(p, b, bias, c, witnesses))
              for name, change in (
                  ("picks_not_scaled", {"routed_scaling_factor": 1.0}),
                  ("picks_not_renormalised", {"norm_topk_prob": False}))}

    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    def moved(a, b):
        a, b = (np.asarray(x["expert_tokens"], np.int64) for x in (a, b))
        return (np.abs(a - b).sum(axis=-1) // 2).tolist()

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def lengths(grads, whole):
        ratios = {k: norm(grads[k]) / norm(whole[k]) for k in witnesses}
        return {**{f"grad_norm_ratio.{k}": v for k, v in ratios.items()},
                "lengths_apart": nemotron_h_step.lengths_apart(
                    list(ratios.values()))}

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    for seed in [int(x) for x in args.seeds.split(",")]:
        batch = next(nemotron_h_step.fresh_batches(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: nemotron_h.init_params(k, cfg))(
            seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            (ref_loss, ref_aux), whole = timed(
                "reference, the witnesses",
                lambda: on_witnesses(params, batch))
        if args.table and not out["seeds"]:
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
                             "cosine": nemotron_h_step.cosine(g, r),
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
            **{f"grad_cosine.{k}": nemotron_h_step.cosine(v_grads[k], whole[k])
               for k in witnesses},
            **lengths(v_grads, whole)}
        for name, run in faulty.items():
            with jax.default_matmul_precision("highest"):
                _, f_grads = timed(f"reference with {name}",
                                   lambda: run(params, batch))
            one[f"reference_with_{name}"] = lengths(f_grads, whole)
        out["seeds"].append(one)
        # one line a seed; the last line of stdout is the last seed's
        print(json.dumps({k: v for k, v in one.items() if k != "gradients"}),
              flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
