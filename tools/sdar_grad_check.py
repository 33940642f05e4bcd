#!/usr/bin/env python3
"""SDAR's share at its published widths, outside any timed window: the
system's loss and gradients of step 0 against the plain reference's, how far
the reference on 8-bit weights moves, and what a fault in the attention's
edge does to the benchmark's witnesses, which the limits of the step-0 checks
(``benchmark/families/sdar_step.py``) have to lie between.

    chiprun --chips 1 -- python3 tools/sdar_grad_check.py --seeds 51,52

The weights and batch 0 are those of the benchmark cell
``sdar-30b-a3b.s8192.b1.zipf.bd4`` at the same seed. System: ``jax.grad`` of
``models/sdar.py::make_loss_fn`` (bf16, the Pallas flash kernels under the
edge a block wide, the own-block merge, grouped matmuls over the held
experts), the function ``KVStore.make_step`` differentiates, on the witness
leaves. Reference: ``benchmark/families/sdar_reference.py::witness_grads`` in
f32 at "highest". Then the reference on weights rounded to an 8-bit float
(e4m3, a lower bound of computing in one: the nearest precision below the
configuration's bfloat16) against the whole reference. Last, the system with
one fault planted from outside (the module's own functions wrapped, nothing in
it edited): the noised queries' edge not strict (they see the clean keys of
their own block, one block too far), the own-block term left out of the merge,
and the kernels' edge one position off (``ops/flash_attention.py::_visible``
wrapped: every row sees the first key past its edge). Each goes through
``sdar_step.readings`` and ``sdar_step.fails`` as if it were the system: each
has to miss a limit. ``--rehearse`` runs the same at
the configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/sdar_grad_check.json``.
"""

from __future__ import annotations

import argparse
import importlib
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

    from benchmark.families import sdar_reference as reference
    from benchmark.families import sdar_step
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import sdar

    with open(os.path.join(ROOT, "benchmark/configs/sdar-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/s8192.b1.zipf.bd4.n160.json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    elif jax.devices()[0].platform != "tpu":
        print("sdar_grad_check: no TPU found; --rehearse runs the tiny sizes "
              "on the CPU", file=sys.stderr)
        return 1
    cfg = sdar.SdarConfig.from_dict(config)
    seq = int(traffic["seq_len"])
    pairs = 2 * seq * cfg.num_experts_per_tok
    probes = [p for p in args.probe.split(",") if p]
    leaves = sorted({k.partition("#")[0] for k in sdar_step.GRAD_COSINE}
                    | set(probes))

    def timed(name, fn):
        t0 = time.perf_counter()
        value = jax.device_get(fn())
        print(f"sdar_grad_check: {name} in {time.perf_counter() - t0:.1f} s",
              file=sys.stderr)
        return value

    def leaf(tree, name):
        for part in name.split("/"):
            tree = tree[part]
        return tree

    def system_of():
        """The system's loss, aux and witness leaves' gradients as a jitted
        function, traced now: with whatever wraps the module now."""
        grad = jax.jit(jax.value_and_grad(
            sdar.make_loss_fn(cfg, attn=traffic["attn"]), has_aux=True))

        def run(params, batch):
            (loss, aux), grads = grad(params, batch)
            return (loss, aux), {k: leaf(grads, k) for k in leaves}

        return run

    # -- the faults, planted from outside by wrapping the module's functions
    make_attn = sdar.make_edge_attn

    def edge_not_strict(*a, **kw):
        fn = make_attn(*a, **kw)
        return lambda q, k, v, block, strict, lse: fn(q, k, v, block, False,
                                                      lse)

    def own_block_left_out(q, k, v, earlier, lse, block):
        return earlier

    fa = importlib.import_module("ps_tpu.ops.flash_attention")
    visible = fa._visible

    def edge_one_position_off(qi, j, shape, q_axis, edge=None):
        """Every row sees one key more: the first position past its edge
        (where that key's tile is one the kernels run at all)."""
        if edge is None:
            return visible(qi, j, shape, q_axis, edge)
        block, strict = edge
        iota = jax.lax.broadcasted_iota
        qpos = qi * shape[q_axis] + iota(jnp.int32, shape, q_axis)
        kpos = j * shape[1 - q_axis] + iota(jnp.int32, shape, 1 - q_axis)
        first = jnp.bitwise_and(qpos, -block)
        return kpos <= (first if strict else first + block)

    faults = {"noised_edge_not_strict": (sdar, "make_edge_attn",
                                         edge_not_strict),
              "own_block_left_out": (sdar, "own_block", own_block_left_out),
              "edge_one_position_off": (fa, "_visible",
                                        edge_one_position_off)}

    plain = jax.jit(lambda p, b: reference.witness_grads(p, b, config,
                                                         leaves))
    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16

    def against(value, aux, grads, ref, rows):
        (ref_value, ref_aux), whole = ref
        read = sdar_step.readings(
            value, aux,
            {k: sdar_step.of_witness(k, grads.get, rows)
             for k in sdar_step.GRAD_COSINE},
            ref_value, ref_aux,
            {k: sdar_step.of_witness(k, whole.get, rows)
             for k in sdar_step.GRAD_COSINE})
        return {**read, "fails": sdar_step.fails(read, pairs),
                **{f"probe_cosine.{k}": sdar_step.cosine(grads[k], whole[k])
                   for k in probes}}

    out = {"device": jax.devices()[0].device_kind, "seeds": []}
    whole_system = system_of()
    for n, seed in enumerate(int(x) for x in args.seeds.split(",")):
        batch = next(sdar_step.noised_batches(
            int(traffic["per_chip_batch"]), seq, config, traffic["ids"],
            seed))
        rows = sdar_step.witness_rows(batch, config)
        params = jax.jit(lambda k: sdar.init_params(k, cfg))(seed_key(seed))
        one = {"seed": seed, "rows": rows}
        with jax.default_matmul_precision("highest"):
            ref = timed("reference, the witnesses",
                        lambda: plain(params, batch))
        (value, aux), grads = timed("system",
                                    lambda: whole_system(params, batch))
        one["system"] = against(value, aux, grads, ref, rows)
        rounded = jax.tree_util.tree_map(
            lambda w: w.astype(fp8).astype(w.dtype), params)
        with jax.default_matmul_precision("highest"):
            (value, aux), grads = timed("reference on e4m3 weights",
                                        lambda: plain(rounded, batch))
        one["reference_on_e4m3_weights"] = against(value, aux, grads, ref,
                                                   rows)
        del rounded
        for name, (module, attribute, wrapped) in faults.items():
            if n >= args.faults:
                break
            kept = getattr(module, attribute)
            setattr(module, attribute, wrapped)
            try:
                (value, aux), grads = timed(
                    f"system with {name}",
                    lambda: system_of()(params, batch))
            finally:
                setattr(module, attribute, kept)
            one[f"system_with_{name}"] = against(value, aux, grads, ref, rows)
        out["seeds"].append(one)
        print(json.dumps(one), flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sdar_grad_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
