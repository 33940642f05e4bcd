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

The model is data (``MODELS``): ``--model granite_h`` runs the same readings
for ``granite-4.0-h-micro.s8192.b1.zipf`` (a loss without a selection bias or
counts; the Pallas flash kernel at 32 query heads on 8 K/V heads, the scan
at 64 heads a group in chunks of 256) with the five faults its limits are
there for: a multiplier read as another model's (the residual's as 1, the
embedding's as 1, the logits not divided, attention scaled by ``head_dim **
-0.5``) and the gated norm before its gate; to
``chiprun_out/granite_h_grad_check.json``.
There the control on 8-bit weights and every fault also go through the
harness's own comparison as if each were the system
(``granite_h_step.step0_checks`` and the loss's ``TOLERANCE``, as
``tools/mellum_grad_check.py`` does it): each has to come out ``correct:
false``, with the checks it ``failed``; with ``--table`` the system itself
goes through the same and has to come out ``correct: true``. Nemotron's checks
read the step's own aux (the held counts, the bias after the sign rule), which
a reference does not return: its cases are printed beside the limits only.

``--model ouro`` runs them for ``ouro-2.6b.s8192.b1.zipf`` (a loss with an
``aux`` and no routing: the step's loss as the loop reads it is ``aux["ce"]``,
and ``ouro_step.step0_checks`` also compares the objective, the passes' cross
entropies and the exit distribution; the Pallas flash kernel at 16 heads on
16, 32 calls each way) with the six faults its limits are there for: the
gradient of the last pass alone (the earlier passes' uses of the stack held
constant), the final norm applied once after the last pass, the two
post-norms left out, the last pass given its own gate's share and not what is
left (mass lost), the entropy term's sign, and the rotation over half the
channels; to ``chiprun_out/ouro_grad_check.json``. ``--table`` is for its
rehearsal: at the published widths the system's gradient tree and the
reference's for every tensor do not fit the chip together, and the cell's own
step-0 checks are where the system comes out ``correct: true``.

``--model phi4flash`` runs them for
``phi-4-mini-flash-reasoning.s16384.b1.zipf`` (a scalar loss; the Pallas flash
kernel at 40 maps on 20 K/V heads, keys of 64 against values of 128, once
under a window of 512; the selective scan in chunks) with the ten faults its
limits are there for: ``lambda_init`` at the cut's own depth (1, 3, 5 for 15,
17, 19), the head norm left out, ``1 - lambda_init`` left out, the memory
taken after the gate, ``D x`` left out of the memory, the window read as
full, the cross layer reading the window layer's K and V, an RMSNorm
where a LayerNorm stands, and the lambda vectors' gradient dropped or of the
wrong sign (the forward pass whole: ``phi4flash_step.LAMBDA_WITNESSES`` are
there for these two); to ``chiprun_out/phi4flash_grad_check.json``.
``--table`` is for its rehearsal, as Ouro's; there the last two may pass,
the tiny sizes' scalars lying under ``LAMBDA_FLOOR``, which is what bf16
reads off them at the published sizes.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _norm_before_gate(reference):
    """The gated norm with the norm first: the fault, for
    ``granite_h_reference.gated_norm``."""
    import jax

    def gated_norm(y, z, scale, groups, eps):
        seq, inner = y.shape
        y = reference.rms_norm(y.reshape(seq, groups, -1),
                               scale.reshape(groups, -1), eps)
        return y.reshape(seq, inner) * jax.nn.silu(z)

    return {"gated_norm": gated_norm}


def _last_pass_alone(reference):
    """``passes`` with every pass but the last reading the stack and the
    final norm as constants: the weights' gradient is the last use's alone,
    not the sum over the passes. For ``ouro_reference.passes``."""
    import jax

    def passes(params, ids, config):
        last = config["total_ut_steps"] - 1
        held = jax.lax.stop_gradient(params)
        u = params["embed"]["tokens"][ids]
        out = []
        for t in range(last + 1):
            p = params if t == last else held
            u = reference.rms_norm(
                reference.run_layers(p, u, config),
                p["final_norm"]["scale"], config["rms_norm_eps"])
            out.append(u)
        return out

    return {"passes": passes}


def _final_norm_once(reference):
    """``passes`` as a plain decoder would close them: the stream runs on
    un-normed from pass to pass, the earlier readouts and gates read it as it
    is, and the final norm is applied once, after the last pass."""
    def passes(params, ids, config):
        u = params["embed"]["tokens"][ids]
        out = []
        for _ in range(config["total_ut_steps"]):
            u = reference.run_layers(params, u, config)
            out.append(u)
        out[-1] = reference.rms_norm(u, params["final_norm"]["scale"],
                                     config["rms_norm_eps"])
        return out

    return {"passes": passes}


def _no_post_norms(reference):
    """``layer`` with a pre-norm alone in front of each part."""
    def layer(lp, x, config):
        eps = config["rms_norm_eps"]
        a = x + reference.attention(lp["attn"], reference.rms_norm(
            x, lp["attn_norm"]["scale"], eps), config)
        return a + reference.swiglu(lp["ffn"], reference.rms_norm(
            a, lp["ffn_norm"]["scale"], eps))

    return {"layer": layer}


def _last_gate_read(reference):
    """``p_T = lambda_T S_{T-1}``: the last pass takes its own gate's share
    and the rest of the mass is lost."""
    import jax.numpy as jnp

    def exit_distribution(lam):
        p, left = [], jnp.ones_like(lam[0])
        for t in range(lam.shape[0]):
            p.append(lam[t] * left)
            left = left * (1.0 - lam[t])
        return jnp.stack(p)

    return {"exit_distribution": exit_distribution}


def _half_rotation(reference):
    """``rope`` over the first half of every head's channels (a
    ``partial_rotary_factor`` of 0.5), the rest passed through."""
    import jax.numpy as jnp

    whole = reference.rope

    def rope(x, theta):
        half = x.shape[-1] // 2
        return jnp.concatenate([whole(x[..., :half], theta), x[..., half:]],
                               -1)

    return {"rope": rope}


def _cuts_own_depth(reference):
    """``lambda_init`` at a layer's index in the cut (1, 3, 5), not in the
    whole model (15, 17, 19). For ``phi4flash_reference.lambda_init``."""
    whole = reference.lambda_init
    return {"lambda_init": lambda depth: whole(depth - 14)}


def _no_head_norm(reference):
    """The two maps' difference goes on un-normed."""
    return {"rms_norm": lambda x, scale, eps: x}


def _no_one_minus_lambda(reference):
    """``combine`` without its last factor."""
    return {"combine": lambda a1, a2, lam, init, scale, eps:
            reference.rms_norm(a1 - lam * a2, scale, eps)}


def _lambda_gradient(times: float):
    """``combine`` reading the same lambda with ``times`` its gradient: 0, the
    four vectors' gradient dropped; -1, of the wrong sign. The forward pass
    is the whole reference's."""
    def fault(reference):
        import jax

        whole = reference.combine

        def combine(a1, a2, lam, init, scale, eps):
            kept = jax.lax.stop_gradient(lam)
            return whole(a1, a2, kept + times * (lam - kept), init, scale,
                         eps)

        return {"combine": combine}

    return fault


def _memory(after_gate: bool):
    """``mamba_mixer`` handing on the scan's output after the ``silu(z)``
    gate, or before it and without the ``D x`` skip."""
    def fault(reference):
        import jax

        whole = reference.mamba_mixer

        def mamba_mixer(lp, u, config):
            out, y = whole(lp, u, config)
            inner = 2 * config["hidden_size"]
            projected = u @ lp["in_proj"]["kernel"]
            if after_gate:
                return out, y * jax.nn.silu(projected[:, inner:])
            x = reference.conv_silu(projected[:, :inner],
                                    lp["conv"]["kernel"], lp["conv"]["bias"])
            return out, y - lp["D"] * x

        return {"mamba_mixer": mamba_mixer}

    return fault


def _rms_for_layer_norm(reference):
    """Every LayerNorm without its mean: an RMSNorm with the same scale and
    bias."""
    import jax
    import jax.numpy as jnp

    def layer_norm(x, p, eps):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"] + p["bias"]

    return {"layer_norm": layer_norm}


#: a model's name in ``ps_tpu.models`` and ``benchmark.families`` -> its
#: configuration class and files, whether its loss takes a selection bias and
#: returns counts (``routed``) or returns an ``aux`` whose ``ce`` is the
#: step's loss as the loop reads it (``aux``), and the faults planted in its
#: reference: a change to the configuration's keys, or functions of the
#: reference to swap
MODELS = {
    "nemotron_h": {
        "config_class": "NemotronHConfig",
        "config": "nemotron-3-super-120b-a12b", "traffic": "s8192.b1.zipf.n96",
        "routed": True,
        "faults": {"picks_not_scaled": {"routed_scaling_factor": 1.0},
                   "picks_not_renormalised": {"norm_topk_prob": False}}},
    "granite_h": {
        "config_class": "GraniteHConfig",
        "config": "granite-4.0-h-micro", "traffic": "s8192.b1.zipf",
        "routed": False,
        "faults": {"residual_multiplier_read_as_1":
                   {"residual_multiplier": 1.0},
                   "embedding_not_times_12": {"embedding_multiplier": 1.0},
                   "logits_not_divided_by_8": {"logits_scaling": 1.0},
                   "attention_scaled_by_an_eighth":
                   {"attention_multiplier": 0.125},
                   "norm_before_the_gate": _norm_before_gate}},
    "ouro": {
        "config_class": "OuroConfig",
        "config": "ouro-2.6b", "traffic": "s8192.b1.zipf.n96",
        "routed": False, "aux": True,
        "faults": {"gradient_of_the_last_pass_alone": _last_pass_alone,
                   "final_norm_once_after_the_last_pass": _final_norm_once,
                   "post_norms_left_out": _no_post_norms,
                   "last_pass_takes_its_own_gates_share": _last_gate_read,
                   "entropy_term_added": {"exit_entropy_beta": -0.05},
                   "rotation_over_half_the_channels": _half_rotation}},
    "phi4flash": {
        "config_class": "Phi4FlashConfig",
        "config": "phi-4-mini-flash-reasoning",
        "traffic": "s16384.b1.zipf",
        "routed": False,
        "faults": {"lambda_init_at_the_cuts_own_depth": _cuts_own_depth,
                   "head_norm_left_out": _no_head_norm,
                   "one_minus_lambda_init_left_out": _no_one_minus_lambda,
                   "memory_taken_after_the_gate": _memory(after_gate=True),
                   "skip_left_out_of_the_memory": _memory(after_gate=False),
                   "window_read_as_full": {"sliding_window": 1 << 30},
                   "cross_reads_the_window_layers_kv": lambda reference: {
                       "PRODUCERS": {**reference.PRODUCERS, "kv": "window"}},
                   "rms_norm_for_layer_norm": _rms_for_layer_norm,
                   "lambda_gradient_dropped": _lambda_gradient(0.0),
                   "lambda_gradient_of_the_wrong_sign":
                   _lambda_gradient(-1.0)}}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="39",
                    help="comma-separated; the first also gets the table")
    ap.add_argument("--table", action="store_true",
                    help="every tensor's gradient at the first seed")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--model", default="nemotron_h", choices=sorted(MODELS))
    args = ap.parse_args(argv)
    spec = MODELS[args.model]
    tool = "nemotron_grad_check" if args.model == "nemotron_h" \
        else f"{args.model}_grad_check"
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.lfm2_step import learning_rate
    from benchmark.families.moe_step import cosine, fresh_batches
    from benchmark.families.nemotron_h_step import lengths_apart
    from benchmark.harness.loop import seed_key

    reference = importlib.import_module(
        f"benchmark.families.{args.model}_reference")
    family = importlib.import_module(f"benchmark.families.{args.model}_step")
    model = importlib.import_module(f"ps_tpu.models.{args.model}")
    with open(os.path.join(
            ROOT, f"benchmark/configs/{spec['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            ROOT, f"benchmark/traffic/{spec['traffic']}.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print(f"{tool}: no TPU found; --rehearse runs the tiny "
              "sizes on the CPU", file=sys.stderr)
        return 1
    cfg = getattr(model, spec["config_class"]).from_dict(config)
    # the leaves whose cosines and lengths are read, and with them those a
    # family hands to its checks for another reading (Phi-4-mini-flash's
    # lambda vectors)
    compared = tuple(family.GRAD_COSINE)
    witnesses = tuple(getattr(family, "WITNESSES", compared))
    routed = spec["routed"]
    has_aux = routed or spec.get("aux", False)
    # what the loss takes beside the parameters and the batch
    extra = (model.init_expert_bias(cfg),) if routed else ()

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"{tool}: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    def with_aux(out):
        """``((loss, aux), grads)`` of a routed model's or of one whose loss
        is a scalar alone."""
        return out if has_aux else ((out[0], None), out[1])

    system = jax.jit(jax.value_and_grad(
        model.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=has_aux))
    plain = jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, *extra, config),
        has_aux=has_aux))
    on_witnesses = jax.jit(lambda p, b: reference.witness_grads(
        p, b, *extra, config, witnesses))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16
    faulty = {}
    for name, fault in spec["faults"].items():
        swapped = fault(reference) if callable(fault) else {}
        change = {} if callable(fault) else fault
        faulty[name] = (swapped, jax.jit(
            lambda p, b, c={**config, **change}:
            reference.witness_grads(p, b, *extra, c, witnesses)))

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

    def moved(a, b):
        if not routed:
            return None
        a, b = (np.asarray(x["expert_tokens"], np.int64) for x in (a, b))
        return (np.abs(a - b).sum(axis=-1) // 2).tolist()

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64)))

    opt = dict(config["optimizer"])
    _, rule = learning_rate(opt, opt.pop("warmup_steps", 0))

    def verdict(loss, grads, ref_loss, whole, aux=None, ref_aux=None):
        """A case as if it were the system, against the whole reference: the
        family's own ``step0_checks`` on its witnesses' gradients (as what
        AdamW's first moment holds of an unclipped gradient; no apply to
        read; with an ``aux`` the objective and the aux too) and the loss as
        the loop reads it (``aux["ce"]`` where the loss has one) under the
        family's tolerance, which is what the loop's ``correct`` holds at
        step 0."""
        if routed:
            return {}
        read = (aux["ce"], ref_aux["ce"]) if aux else (loss, ref_loss)
        result = family.step0_checks(
            {k: {"mu": (1 - rule["b1"]) * np.asarray(grads[k], np.float64),
                 "reference_grad": np.asarray(whole[k])} for k in witnesses},
            rule["clip_by_global_norm"], rule,
            *([{"loss": loss, **aux}, {"loss": ref_loss, **ref_aux}]
              if aux else []))
        checks = {"step0_matches_reference":
                  rel(*read) <= family.TOLERANCE[0],
                  **result["checks"]}
        return {"correct": all(checks.values()),
                "failed": sorted(k for k, ok in checks.items() if not ok),
                # what the aux's checks read, beside their limits
                **{k: v for k, v in result["detail"].items()
                   if k.endswith(("_rel_diff", "_apart")) and aux},
                **{k: v for k, v in result["detail"].items()
                   if k.startswith("lambda_scalar.")}}

    def lengths(grads, whole):
        ratios = {k: norm(grads[k]) / norm(whole[k]) for k in compared}
        return {**{f"grad_norm_ratio.{k}": v for k, v in ratios.items()},
                "lengths_apart": lengths_apart(list(ratios.values()))}

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    for seed in [int(x) for x in args.seeds.split(",")]:
        batch = next(fresh_batches(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            cfg.vocab_size, traffic["ids"]["s"], seed))
        params = jax.jit(lambda k: model.init_params(k, cfg))(
            seed_key(seed))
        one = {"seed": seed}
        with jax.default_matmul_precision("highest"):
            (ref_loss, ref_aux), whole = with_aux(timed(
                "reference, the witnesses",
                lambda: on_witnesses(params, batch)))
        if args.table and not out["seeds"]:
            # every tensor, the system's against the reference's
            (loss, aux), grads = with_aux(timed(
                "system gradients", lambda: system(params, batch, *extra)))
            with jax.default_matmul_precision("highest"):
                _, ref_grads = timed("reference gradients",
                                     lambda: plain(params, batch))
            one["loss"] = {"system": float(loss),
                           "reference": float(ref_loss),
                           "rel_diff": rel(loss, ref_loss)}
            one["pairs_on_another_expert"] = moved(aux, ref_aux)
            one["system"] = verdict(loss, {
                k: functools.reduce(lambda t, part: t[part], k.split("/"),
                                    grads) for k in witnesses},
                ref_loss, whole, aux, ref_aux)
            rows = []
            flat, _ = jax.tree_util.tree_flatten_with_path(grads)
            for (path, g), r in zip(flat,
                                    jax.tree_util.tree_leaves(ref_grads)):
                g, r = (np.asarray(x, np.float64).ravel() for x in (g, r))
                rows.append({"tensor": jax.tree_util.keystr(path),
                             "cosine": cosine(g, r),
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
            (value, v_aux), v_grads = with_aux(timed(
                "reference on e4m3 weights",
                lambda: on_witnesses(rounded, batch)))
        one["reference_on_e4m3_weights"] = {
            "loss_rel_diff": rel(value, ref_loss),
            "pairs_on_another_expert": moved(v_aux, ref_aux),
            **{f"grad_cosine.{k}": cosine(v_grads[k], whole[k])
               for k in compared},
            **lengths(v_grads, whole),
            **verdict(value, v_grads, ref_loss, whole, v_aux, ref_aux)}
        for name, (swapped, run) in faulty.items():
            with jax.default_matmul_precision("highest"), swap(swapped):
                (f_loss, f_aux), f_grads = with_aux(timed(
                    f"reference with {name}", lambda: run(params, batch)))
            one[f"reference_with_{name}"] = {
                "loss_rel_diff": rel(f_loss, ref_loss),
                "least_grad_cosine": min(cosine(f_grads[k], whole[k])
                                         for k in compared),
                **lengths(f_grads, whole),
                **verdict(f_loss, f_grads, ref_loss, whole, f_aux, ref_aux)}
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
