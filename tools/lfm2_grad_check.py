#!/usr/bin/env python3
"""LFM2-24B-A2B's share at its published widths, outside any timed window:
the system's gradients of step 0 against the plain reference's, per tensor,
and what the benchmark's step-0 checks can and cannot tell apart.

    chiprun --chips 1 -- python3 tools/lfm2_grad_check.py --seeds 32,33

The weights and batch 0 are those of the benchmark cell
``lfm2-24b-a2b.s8192.zipf`` at the same seed, the selection bias zero as at
step 0. System: ``jax.grad`` of ``models/lfm2.py::make_loss_fn`` (bf16, the
Pallas flash kernel on grouped K/V, the gated convolution, grouped matmuls
over the held experts), the function ``KVStore.make_step`` differentiates.
Reference: ``jax.grad`` of ``benchmark/families/lfm2_reference.py::loss_fn``
in f32 at "highest". Per tensor: cosine, norm of the system's over the
reference's, and the relative distance. Then the reference with one piece
changed at a time (weights rounded to an 8-bit float, a lower bound of
computing in one: the nearest precision below the configuration's bfloat16;
picks' weights not renormalised; no QK-norm; the convolution without its
output gate) against the whole reference: how far the loss, the expert counts
and the gradients of the benchmark's witness leaves move, which the limits
in ``lfm2_step.py`` have to lie under. ``--rehearse`` runs the same at the
configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/lfm2_grad_check.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="32",
                    help="comma-separated; the first also gets the table")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import lfm2_reference as reference
    from benchmark.families import lfm2_step
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import lfm2

    with open(os.path.join(ROOT, "benchmark/configs/lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark/traffic/s8192.zipf.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("lfm2_grad_check: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = lfm2.Lfm2Config.from_dict(config)
    witnesses = tuple(lfm2_step.GRAD_COSINE)
    bias = lfm2.init_expert_bias(cfg)

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"lfm2_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    system = jax.jit(jax.value_and_grad(
        lfm2.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))
    plain = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, bias, config), has_aux=True))

    def skipping_qk_norm():
        # the QK-norm is the rms_norm whose scale is one head wide
        norm = reference.rms_norm
        return mock.patch.object(
            reference, "rms_norm",
            lambda x, scale, eps: x if scale.shape[0] == cfg.head_dim
            else norm(x, scale, eps))

    def conv_without_output_gate():
        split = jnp.split

        def ungated(x, parts, axis):
            b, c, gate_in = split(x, parts, axis=axis)
            return b, jnp.ones_like(c), gate_in

        return mock.patch.object(reference.jnp, "split", ungated)

    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16
    # name -> (changes to the configuration, patch while tracing, weights)
    variants = {
        "reference_on_e4m3_weights": ({}, None, lambda w: w.astype(
            fp8).astype(w.dtype)),
        "picks_not_renormalised": ({"norm_topk_prob": False}, None, None),
        "no_qk_norm": ({}, skipping_qk_norm, None),
        "conv_without_output_gate": ({}, conv_without_output_gate, None),
    }
    knocked = {
        name: jax.jit(lambda p, b, changed={**config, **changes}:
                      reference.witness_grads(p, b, bias, changed, witnesses))
        for name, (changes, _, _) in variants.items()}

    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    def moved(a, b):
        a, b = (np.asarray(x["expert_tokens"], np.int64) for x in (a, b))
        return (np.abs(a - b).sum(axis=-1) // 2).tolist()

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    for seed in [int(x) for x in args.seeds.split(",")]:
        batch = next(lfm2_step.fresh_batches(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: lfm2.init_params(k, cfg))(seed_key(seed))
        one = {"seed": seed}

        # -- gradients: the system's, then the reference's, on the host
        (loss, aux), grads = timed("system gradients",
                                   lambda: system(params, batch, bias))
        with jax.default_matmul_precision("highest"):
            (ref_loss, ref_aux), ref_grads = timed(
                "reference gradients", lambda: plain(params, batch))
        one["loss"] = {"system": float(loss), "reference": float(ref_loss),
                       "rel_diff": rel(loss, ref_loss)}
        one["pairs_on_another_expert"] = moved(aux, ref_aux)
        one["pairs_held"] = np.asarray(aux["held_tokens"]).sum(-1).tolist()
        rows, square = [], [0.0, 0.0]
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
            g, r = (np.asarray(x, np.float64).ravel() for x in (g, r))
            square[0] += g @ g
            square[1] += r @ r
            rows.append({"tensor": jax.tree_util.keystr(path),
                         "cosine": lfm2_step.cosine(g, r),
                         "norm_ratio": float(np.linalg.norm(g)
                                             / np.linalg.norm(r)),
                         "rel_diff": float(np.linalg.norm(g - r)
                                           / np.linalg.norm(r))})
        one["gradients"] = rows
        one["worst"] = min(rows, key=lambda row: row["cosine"])
        one["global_norm"] = {"system": float(np.sqrt(square[0])),
                              "reference": float(np.sqrt(square[1]))}
        whole = {name: functools.reduce(
            lambda tree, part: tree[part], name.split("/"), ref_grads)
            for name in witnesses}
        one["witness_cosines"] = {
            name: next(row["cosine"] for row in rows if row["tensor"] == "".join(
                f"['{part}']" for part in name.split("/")))
            for name in witnesses}
        del grads, ref_grads, flat

        # -- the reference with one piece out against the whole reference
        one["knocked_out"] = {}
        for name, (_, patch, change_weights) in variants.items():
            weights = params if change_weights is None else \
                jax.tree_util.tree_map(change_weights, params)
            with jax.default_matmul_precision("highest"), \
                    (patch() if patch else contextlib.nullcontext()):
                (value, v_aux), v_grads = timed(
                    name, lambda: knocked[name](weights, batch))
            one["knocked_out"][name] = {
                "loss_rel_diff": rel(value, ref_loss),
                "pairs_on_another_expert": moved(v_aux, ref_aux),
                **{f"grad_cosine.{k}": lfm2_step.cosine(v_grads[k], whole[k])
                   for k in witnesses},
                **{f"grad_norm_ratio.{k}": float(
                    np.linalg.norm(np.asarray(v_grads[k], np.float64))
                    / np.linalg.norm(np.asarray(whole[k], np.float64)))
                   for k in witnesses}}
        if not out["seeds"]:
            print(f"{'tensor':48s} {'cosine':>12s} {'norm ratio':>12s} "
                  f"{'rel diff':>12s}")
            for row in rows:
                print(f"{row['tensor']:48s} {row['cosine']:12.8f} "
                      f"{row['norm_ratio']:12.6f} {row['rel_diff']:12.3e}")
        out["seeds"].append(one)
        # one line a seed; the last line of stdout is the last seed's
        print(json.dumps({k: v for k, v in one.items() if k != "gradients"}),
              flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "lfm2_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
