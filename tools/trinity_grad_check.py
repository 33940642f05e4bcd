#!/usr/bin/env python3
"""Trinity-Mini's share at its published widths, outside any timed window: the
system's gradients of step 0 against the plain reference's, how far the
reference on 8-bit weights moves, and what each fault of ISSUE 41's 6 (a)
does to the benchmark's witnesses, which the limits of the step-0 checks have
to lie between.

    chiprun --chips 1 -- python3 tools/trinity_grad_check.py --seeds 41,42

The weights and batch 0 are those of the benchmark cell
``trinity-mini.s16384.b1.zipf`` at the same seed, the selection bias zero as
at step 0. System: ``jax.grad`` of ``models/trinity.py::make_loss_fn`` (bf16,
the Pallas flash kernels with and without a window at 32 query heads on 4 K/V
heads, grouped matmuls over the held experts), the function
``KVStore.make_step`` differentiates, on the witness leaves. Reference:
``benchmark/families/trinity_reference.py::witness_grads`` in f32 at
"highest". Then the reference on weights rounded to an 8-bit float (e4m3, a
lower bound of computing in one: the nearest precision below the
configuration's bfloat16) against the whole reference. Last, the system with
one fault planted from outside (the module's own functions wrapped, nothing in
it edited): the window ignored on the windowed layers, a rotation on the full
layer, the gate left out (its sigmoid at one half everywhere, which the norm
behind the attention makes a gate of one), the norm behind layer 1's attention
left out: each against the whole reference, by the loss, the counts and the
witnesses' cosines and lengths. ``--rehearse`` runs the same at the
configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/trinity_grad_check.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="41")
    ap.add_argument("--faults", type=int, default=1,
                    help="seeds (the first ones) that also get the faults")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import trinity_reference as reference
    from benchmark.families import trinity_step
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import trinity

    with open(os.path.join(ROOT, "benchmark/configs/trinity-mini.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/s16384.b1.zipf.n96.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("trinity_grad_check: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = trinity.TrinityConfig.from_dict(config)
    seq = int(traffic["seq_len"])
    witnesses = tuple(trinity_step.GRAD_COSINE)
    bias = trinity.init_expert_bias(cfg)

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"trinity_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    def leaf(tree, name):
        for part in name.split("/"):
            tree = tree[part]
        return tree

    def system_of():
        """The system's loss, aux and witness gradients as a jitted
        function, traced now: with whatever wraps the module now."""
        grad = jax.jit(jax.value_and_grad(
            trinity.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))

        def run(params, batch):
            (loss, aux), grads = grad(params, batch, bias)
            return (loss, aux), {k: leaf(grads, k) for k in witnesses}

        return run

    # -- the faults, planted from outside by wrapping the module's functions
    block, norm = trinity.attention_block, trinity.rms_norm
    spanning = dataclasses.replace(cfg, sliding_window=seq)

    def window_ignored(lp, x, config, kind, *rest):
        return block(lp, x, spanning if kind == trinity.WINDOWED else config,
                     kind, *rest)

    def full_layer_rotated(lp, x, config, kind, *rest):
        if kind == trinity.FULL:   # rotated, and still every earlier key
            return block(lp, x, spanning, trinity.WINDOWED, *rest)
        return block(lp, x, config, kind, *rest)

    def gate_left_out(lp, x, *rest):
        shut = {**lp, "gate": {"kernel": jnp.zeros_like(lp["gate"]["kernel"])}}
        return block(shut, x, *rest)

    seen = {"stream_norms": 0}

    def norm_left_out(x, scale, eps):
        if x.ndim == 3:             # the four of a layer, not the heads'
            seen["stream_norms"] += 1
            if seen["stream_norms"] == 6:   # layer 1's post_attn_norm
                return x
        return norm(x, scale, eps)

    faults = {"window_ignored": ("attention_block", window_ignored),
              "full_layer_rotated": ("attention_block", full_layer_rotated),
              "gate_left_out": ("attention_block", gate_left_out),
              "norm_left_out": ("rms_norm", norm_left_out)}

    plain = jax.jit(lambda p, b: reference.witness_grads(
        p, b, bias, config, witnesses))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16

    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    def moved(a, b):
        a, b = (np.asarray(x["expert_tokens"], np.int64) for x in (a, b))
        return (np.abs(a - b).sum(axis=-1) // 2).tolist()

    def norm_of(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    def against(value, aux, grads, ref_value, ref_aux, whole):
        ratios = [norm_of(grads[k]) / norm_of(whole[k]) for k in witnesses]
        cosines = {k: trinity_step.cosine(grads[k], whole[k])
                   for k in witnesses}
        return {"loss_rel_diff": rel(value, ref_value),
                "pairs_on_another_expert": moved(aux, ref_aux),
                **{f"grad_cosine.{k}": v for k, v in cosines.items()},
                "lengths_apart": trinity_step.lengths_apart(ratios),
                "fails": sorted(
                    (["loss"] * (rel(value, ref_value)
                                 > trinity_step.TOLERANCE[0]))
                    + (["counts"] * (max(moved(aux, ref_aux))
                                     > trinity_step.FLIP_SHARE * seq
                                     * cfg.num_experts_per_tok))
                    + [f"cosine.{k}" for k, v in cosines.items()
                       if not v >= trinity_step.GRAD_COSINE[k]]  # or nan
                    + (["lengths"] * (trinity_step.lengths_apart(ratios)
                                      > trinity_step.GRAD_NORM_TOLERANCE)))}

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    whole_system = system_of()
    for n, seed in enumerate(int(x) for x in args.seeds.split(",")):
        batch = next(trinity_step.fresh_batches(
            int(traffic["per_chip_batch"]), seq, cfg.vocab_size,
            traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: trinity.init_params(k, cfg))(
            seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            ref = timed("reference, the witnesses",
                        lambda: plain(params, batch))
        (ref_value, ref_aux), whole = ref
        (value, aux), grads = timed("system", lambda: whole_system(params,
                                                                   batch))
        one["system"] = against(value, aux, grads, ref_value, ref_aux, whole)
        rounded = jax.tree_util.tree_map(
            lambda w: w.astype(fp8).astype(w.dtype), params)
        with jax.default_matmul_precision("highest"):
            (value, aux), grads = timed("reference on e4m3 weights",
                                        lambda: plain(rounded, batch))
        one["reference_on_e4m3_weights"] = against(
            value, aux, grads, ref_value, ref_aux, whole)
        del rounded
        for name, (attribute, wrapped) in faults.items():
            if n >= args.faults:
                break
            kept = getattr(trinity, attribute)
            setattr(trinity, attribute, wrapped)
            seen["stream_norms"] = 0
            try:
                (value, aux), grads = timed(
                    f"system with {name}",
                    lambda: system_of()(params, batch))
            finally:
                setattr(trinity, attribute, kept)
            one[f"system_with_{name}"] = against(
                value, aux, grads, ref_value, ref_aux, whole)
        out["seeds"].append(one)
        print(json.dumps(one), flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "trinity_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
