#!/usr/bin/env python3
"""Qwen3-Next-80B-A3B's share at its published widths, outside any timed
window: the system's gradients of step 0 against the plain reference's, per
tensor; how far the reference on 8-bit weights moves, which the limits of the
benchmark's step-0 checks have to lie under; and the reference with one fault
planted, which they have to catch.

    chiprun --chips 1 -- python3 tools/qwen3_next_grad_check.py \
        --seeds 60,61 [--table 0]

The weights and batch 0 are those of the benchmark cell
``qwen3-next-80b-a3b.s8192.b1.zipf`` at the same seed. System: ``jax.grad`` of
``models/qwen3_next.py::make_loss_fn`` (bf16, the chunked rule on broadcast
operands, the Pallas flash kernel at 256 / 256, grouped matmuls over the held
experts), the function ``KVStore.make_step`` differentiates. Reference:
``jax.grad`` of ``benchmark/families/qwen3_next_reference.py::loss_fn`` in f32
at "highest". Per tensor (the first seed, unless ``--table 0``): cosine, norm
of the system's over the reference's, and the relative distance. Then, every
seed, against the whole reference on the benchmark's witness leaves: the
reference on weights rounded to an 8-bit float (e4m3, a lower bound of
computing in one: the nearest precision below the configuration's bfloat16),
and the reference with each of ``FAULTS`` planted. Each case goes through
``qwen3_next_step.step0_checks`` and the loss's tolerance as if it were the
system, and says whether it would have been ``correct`` and by which checks
not. ``--rehearse`` runs the same at the configuration's tiny sizes on the
CPU. Results go to stdout and to ``chiprun_out/qwen3_next_grad_check.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL_CONFIG = "benchmark/configs/qwen3-next-80b-a3b.json"
CELL_TRAFFIC = "benchmark/traffic/s8192.b1.zipf.json"


def faults():
    """name -> (the reference's functions replaced, the configuration's keys
    changed): what the cell's limits are there for."""
    import jax
    import jax.numpy as jnp

    def gate_before_norm(o, z, scale, eps):
        # Granite's and Nemotron's order: the gate first, the norm after
        o = o * jax.nn.silu(z)
        return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                 + eps) * scale

    def norm_not_zero_centred(x, w, eps):
        # the scale read as w + 1 on the way in but the gradient's path cut:
        # at w = 0 a plain ``w`` would zero the stream, so the fault that can
        # hide is a scale that ignores ``w``
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * (1.0 + jax.lax.stop_gradient(w))

    return {
        "the_gate_before_the_head_norm":
            ({"gated_head_norm": gate_before_norm}, {}),
        "a_norm_whose_scale_ignores_w":
            ({"norm": norm_not_zero_centred}, {}),
        "key_heads_tiled_not_repeated":
            ({"to_value_heads": lambda y, r: jnp.tile(y, (1, r, 1))}, {}),
        "every_channel_rotated": ({}, {"partial_rotary_factor": 1.0}),
        "the_picks_not_renormalised": ({}, {"norm_topk_prob": False}),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="60",
                    help="comma-separated; the first also gets the table")
    ap.add_argument("--table", type=int, choices=(0, 1), default=1)
    ap.add_argument("--faults", type=int, choices=(0, 1), default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import qwen3_next_reference as reference
    from benchmark.families import qwen3_next_step as family
    from benchmark.families.lfm2_step import learning_rate
    from benchmark.families.moe_step import cosine, fresh_batches
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import qwen3_next as model

    tool = "qwen3_next_grad_check"
    with open(os.path.join(ROOT, CELL_CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, CELL_TRAFFIC)) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print(f"{tool}: no TPU found; --rehearse runs the tiny sizes on the "
              "CPU", file=sys.stderr)
        return 1
    cfg = model.Qwen3NextConfig.from_dict(config)
    witnesses = tuple(family.GRAD_COSINE)
    pairs = int(traffic["per_chip_batch"]) * int(traffic["seq_len"]) \
        * cfg.num_experts_per_tok
    opt = dict(config["optimizer"])
    _, rule = learning_rate(opt, opt.pop("warmup_steps", 0))

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"{tool}: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    system = jax.jit(jax.value_and_grad(
        model.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))
    plain = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, config), has_aux=True))
    on_witnesses = jax.jit(lambda p, b: reference.witness_grads(
        p, b, config, witnesses))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16
    faulty = {}
    for name, (swapped, change) in (faults().items()
                                    if args.faults else ()):
        faulty[name] = (swapped, jax.jit(
            lambda p, b, c={**config, **change}:
            reference.witness_grads(p, b, c, witnesses)))

    @contextlib.contextmanager
    def swap(functions):
        """The reference with these functions of its own replaced, while a
        faulty run is traced."""
        kept = {name: getattr(reference, name) for name in functions}
        for name, fn in functions.items():
            setattr(reference, name, fn)
        try:
            yield
        finally:
            for name, fn in kept.items():
                setattr(reference, name, fn)

    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def verdict(loss, aux, grads, ref_loss, ref_aux, whole):
        """A case as if it were the system, against the whole reference: the
        family's own ``step0_checks`` on its witnesses' gradients (as what
        AdamW's first moment holds of an unclipped gradient; no apply to
        read) and the loss under the family's tolerance, which is what the
        loop's ``correct`` holds at step 0."""
        result = family.step0_checks(
            aux, ref_aux,
            {k: {"mu": (1 - rule["b1"]) * np.asarray(grads[k], np.float64),
                 "reference_grad": np.asarray(whole[k])} for k in witnesses},
            rule["clip_by_global_norm"], rule, pairs, config)
        checks = {"step0_matches_reference":
                  rel(loss, ref_loss) <= family.TOLERANCE[0],
                  **result["checks"]}
        detail = result["detail"]
        cosines = {k.partition(".")[2]: v for k, v in detail.items()
                   if k.startswith("grad_cosine.")}
        return {"loss_rel_diff": rel(loss, ref_loss),
                "pairs_on_another_expert":
                detail["pairs_on_another_expert_than_reference"],
                "grad_cosine": cosines,
                "least_grad_cosine": min(cosines.items(),
                                         key=lambda kv: kv[1]),
                "grad_norm_over_reference":
                detail["grad_norm_over_reference"],
                "lengths_apart": detail["lengths_apart"],
                "correct": all(checks.values()),
                "failed": sorted(k for k, ok in checks.items() if not ok)}

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    for seed in [int(x) for x in args.seeds.split(",")]:
        batch = next(fresh_batches(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: model.init_params(k, cfg))(
            seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            (ref_loss, ref_aux), whole = timed(
                "reference, the witnesses",
                lambda: on_witnesses(params, batch))
        if args.table and not out["seeds"]:
            # every tensor, the system's against the reference's
            (loss, aux), grads = timed("system gradients",
                                       lambda: system(params, batch))
            with jax.default_matmul_precision("highest"):
                _, ref_grads = timed("reference gradients",
                                     lambda: plain(params, batch))
            one["system"] = verdict(loss, aux, {
                k: functools.reduce(lambda t, part: t[part], k.split("/"),
                                    grads) for k in witnesses},
                ref_loss, ref_aux, whole)
            rows = []
            flat, _ = jax.tree_util.tree_flatten_with_path(grads)
            for (path, g), r in zip(flat,
                                    jax.tree_util.tree_leaves(ref_grads)):
                g, r = (np.asarray(x, np.float64).ravel() for x in (g, r))
                rows.append({"tensor": jax.tree_util.keystr(path),
                             "cosine": cosine(g, r),
                             "norm": norm(r),
                             "norm_ratio": norm(g) / norm(r),
                             "rel_diff": norm(g - r) / norm(r)})
            one["gradients"] = rows
            one["tensors_read"] = len(rows)
            one["worst"] = min(rows, key=lambda row: row["cosine"])
            print(f"{'tensor':44s} {'cosine':>11s} {'norm':>10s} "
                  f"{'norm ratio':>11s} {'rel diff':>10s}")
            for row in rows:
                print(f"{row['tensor']:44s} {row['cosine']:11.7f} "
                      f"{row['norm']:10.3e} {row['norm_ratio']:11.6f} "
                      f"{row['rel_diff']:10.3e}")
            del grads, ref_grads, flat
        rounded = jax.tree_util.tree_map(
            lambda w: w.astype(fp8).astype(w.dtype), params)
        with jax.default_matmul_precision("highest"):
            (value, v_aux), v_grads = timed(
                "reference on e4m3 weights",
                lambda: on_witnesses(rounded, batch))
        one["reference_on_e4m3_weights"] = verdict(
            value, v_aux, v_grads, ref_loss, ref_aux, whole)
        for name, (swapped, run) in faulty.items():
            with jax.default_matmul_precision("highest"), swap(swapped):
                (f_loss, f_aux), f_grads = timed(
                    f"reference with {name}", lambda: run(params, batch))
            one[f"reference_with_{name}"] = verdict(
                f_loss, f_aux, f_grads, ref_loss, ref_aux, whole)
        out["seeds"].append(one)
        # one line a seed; the last line of stdout is the last seed's
        print(json.dumps({k: v for k, v in one.items() if k != "gradients"}),
              flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"{tool}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
