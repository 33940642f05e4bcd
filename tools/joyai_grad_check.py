#!/usr/bin/env python3
"""JoyAI-LLM-Flash's share at its published widths, outside any timed window:
the system's loss, its two terms and the gradients of step 0 against the plain
reference's, how far the reference on 8-bit weights moves, and what each of
six planted faults does to the benchmark's witnesses, which the limits of the
step-0 checks (``benchmark/families/joyai_step.py``) have to lie between.

    chiprun --chips 1 -- python3 tools/joyai_grad_check.py --seeds 51,52

The weights and batch 0 are those of the benchmark cell
``joyai-llm-flash.s8192.b1.zipf`` at the same seed. System: ``jax.grad`` of
``models/joyai.py::make_loss_fn`` (bf16, the Pallas flash kernel at keys of
192 and values of 128, the rotation by rolls on the lanes, grouped matmuls over
the held experts), the function ``KVStore.make_step`` differentiates, on the
witness leaves. Reference: ``benchmark/families/joyai_reference.py::
witness_grads`` in f32 at "highest". Then the reference on weights rounded to
an 8-bit float (e4m3, a lower bound of computing in one: the nearest precision
below the configuration's bfloat16) against the whole reference. Then the
system with one fault planted from outside (the configuration replaced or the
module's own functions wrapped, nothing in it edited): the rotation left out;
the rotation on halves where pairs are meant; the prediction module fed token
``i`` for token ``i + 1``; the second term's weight dropped (``loss = ce +
mtp_ce``); ``shared_head.norm`` left out. Each goes through
``joyai_step.readings`` and ``joyai_step.fails`` as if it were the system: each
has to miss a limit. Last, on the host alone, **parameters kept in bfloat16**:
``joyai_step.step0_checks`` on a step-0 apply whose result was rounded to
bf16, which has to miss the apply's limit. ``--rehearse`` runs the same at the
configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/joyai_grad_check.json``.
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
    ap.add_argument("--seeds", default="51")
    ap.add_argument("--faults", type=int, default=1,
                    help="seeds (the first ones) that also get the faults")
    ap.add_argument("--probe", default="",
                    help="further leaves whose cosine to the reference's "
                         "gradient is reported, comma-separated: candidates "
                         "for a witness")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families import joyai_reference as reference
    from benchmark.families import joyai_step
    from benchmark.families.lfm2_step import learning_rate
    from benchmark.families.moe_step import adamw_first_step, fresh_batches
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import joyai

    with open(os.path.join(ROOT,
                           "benchmark/configs/joyai-llm-flash.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT,
                           "benchmark/traffic/s8192.b1.zipf.n96.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("joyai_grad_check: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = joyai.JoyaiConfig.from_dict(config)
    seq = int(traffic["seq_len"])
    pairs = seq * cfg.num_experts_per_tok
    probes = [p for p in args.probe.split(",") if p]
    leaves = sorted({k.partition("#")[0] for k in joyai_step.GRAD_COSINE}
                    | set(probes))

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"joyai_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    def leaf(tree, name):
        for part in name.split("/"):
            tree = tree[part]
        return tree

    def system_of(cfg):
        """The system's loss, aux and witness leaves' gradients as a jitted
        function of ``cfg``, traced now: with whatever wraps the module
        now."""
        grad = jax.jit(jax.value_and_grad(
            joyai.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))

        def run(params, batch, bias):
            (loss, aux), grads = grad(params, batch, bias)
            return (loss, aux), {k: leaf(grads, k) for k in leaves}

        return run

    # -- the faults: another configuration, or the module's functions wrapped
    mtp_block = joyai.mtp_block

    def fed_this_token(params, hidden, next_tokens, *a, **kw):
        """Position ``i`` reads token ``i`` where token ``i + 1`` is meant
        (the first position reads the sequence's last)."""
        return mtp_block(params, hidden, jnp.roll(next_tokens, 1, axis=1),
                         *a, **kw)

    rms_norm = joyai.rms_norm

    def no_shared_head_norm(params, hidden, next_tokens, *a, **kw):
        """``shared_head.norm`` the identity: the one norm of the module whose
        scale is ``params['mtp']['norm']``'s own array."""
        scale = params["mtp"]["norm"]["scale"]
        joyai.rms_norm = lambda x, s, eps: (
            x if s is scale else rms_norm(x, s, eps))
        try:
            return mtp_block(params, hidden, next_tokens, *a, **kw)
        finally:
            joyai.rms_norm = rms_norm

    faults = {
        "rotation_left_out":
            (dataclasses.replace(cfg, rope_theta=None), None),
        "rotation_on_halves":
            (dataclasses.replace(cfg, rope_interleave=False), None),
        "module_fed_this_token": (cfg, fed_this_token),
        "second_weight_dropped":
            (dataclasses.replace(cfg, mtp_loss_weight=1.0), None),
        "no_shared_head_norm": (cfg, no_shared_head_norm)}

    plain = jax.jit(lambda p, b, bias: reference.witness_grads(
        p, b, bias, config, leaves))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16

    def witness(grads):
        return {k: joyai_step.of_witness(k, grads.get, config)
                for k in joyai_step.GRAD_COSINE}

    def against(value, aux, grads, ref):
        (ref_value, ref_aux), whole = ref
        read = joyai_step.readings(value, aux, witness(grads), ref_value,
                                   ref_aux, witness(whole))
        return {**read, "fails": joyai_step.fails(read, pairs),
                "step0": {k: float(aux[k]) for k in ("loss", "ce", "mtp_ce")},
                **{f"probe_cosine.{k}": joyai_step.cosine(grads[k], whole[k])
                   for k in probes}}

    def bf16_parameters(params, grads):
        """Host only: step 0's apply on one witness, its result rounded to
        bfloat16 as a store that kept bf16 parameters would hold it, through
        ``step0_checks``' apply limit."""
        opt = dict(config["optimizer"])
        _, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
        name = "layer0/attn/q_a/kernel"
        before = np.asarray(leaf(params, name))
        g = np.asarray(grads[name], np.float64)
        mu, nu = (1 - rule["b1"]) * g, (1 - rule["b2"]) * g * g
        after = adamw_first_step(before, mu, nu, **rule)
        out = {}
        for kept, result in (("float32", after.astype(np.float32)), (
                "bfloat16", np.asarray(jnp.asarray(
                    after, jnp.float32).astype(jnp.bfloat16).astype(
                        jnp.float32)))):
            off = np.abs(result.astype(np.float64) - after)
            out[kept] = float(np.max(np.maximum(
                off - 0.5 * np.spacing(np.abs(result)).astype(np.float64),
                0.0)) / rule["learning_rate"])
        out["limit"] = joyai_step.APPLY_TOLERANCE
        out["fails"] = [k for k in ("float32", "bfloat16")
                        if out[k] > joyai_step.APPLY_TOLERANCE]
        return out

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    whole_system = system_of(cfg)
    bias = joyai.init_expert_bias(cfg)
    for n, seed in enumerate(int(x) for x in args.seeds.split(",")):
        batch = next(fresh_batches(int(traffic["per_chip_batch"]), seq,
                                   cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: joyai.init_params(k, cfg))(seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            ref = timed("reference, the witnesses",
                        lambda: plain(params, batch, bias))
        (value, aux), grads = timed(
            "system", lambda: whole_system(params, batch, bias))
        one["system"] = against(value, aux, grads, ref)
        if n < args.faults:
            one["parameters_kept_in_bf16"] = bf16_parameters(params, grads)
        rounded = jax.tree_util.tree_map(
            lambda w: w.astype(fp8).astype(w.dtype), params)
        with jax.default_matmul_precision("highest"):
            (value, aux), grads = timed(
                "reference on e4m3 weights",
                lambda: plain(rounded, batch, bias))
        one["reference_on_e4m3_weights"] = against(value, aux, grads, ref)
        del rounded
        for name, (faulty, wrapped) in faults.items():
            if n >= args.faults:
                break
            if wrapped is not None:
                joyai.mtp_block = wrapped
            try:
                (value, aux), grads = timed(
                    f"system with {name}",
                    lambda: system_of(faulty)(params, batch, bias))
            finally:
                joyai.mtp_block = mtp_block
            one[f"system_with_{name}"] = against(value, aux, grads, ref)
        out["seeds"].append(one)
        print(json.dumps(one), flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "joyai_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
