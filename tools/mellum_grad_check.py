#!/usr/bin/env python3
"""Mellum2 at its published widths on the four chips that share each layer,
outside any timed window: how the seed spreads a layer's pairs over the chips
(what ``ops/moe.py::EXCHANGE_ROWS_OVER_EVEN`` is fixed from), and the two
readings the limits of the cell's step-0 checks have to lie between.

    chiprun --chips 4 -- python3 tools/mellum_grad_check.py \
        --load-seeds 1,2,3,4,5,6,7,8,9,10,11,12 --seeds 46

``--load-seeds``: for each seed the weights and batch 0 of the benchmark cell
``mellum2-12b-a2.5b.s8192.b1.zipf.x4`` and one forward pass of
``models/mellum.py`` across the chips; printed: the rows each chip's experts
computed over an even quarter of the layer's pairs, by layer, the fullest
pair of source and owner over an even sixteenth (what one buffer of the
exchange has to hold) and the fullest expert over the mean.

``--seeds``: the plain reference (``benchmark/families/mellum_reference.py``,
f32 at "highest") on the cell's weights and batch 0, whole; then on weights
rounded to an 8-bit float (e4m3: the nearest precision below the
configuration's bfloat16, a lower bound of computing in one); then whole again
but with the full layers' ``attention_factor`` left out (1.0). Each control
is put through the harness's own comparison as if it were the system: its
loss against the whole reference's by ``mellum_step.TOLERANCE``, and its
terms, counts and witness gradients (the stack's as the slices chip 0 and
chip 2 hold) through ``mellum_step.step0_checks``, whose verdicts are printed
beside the readings: each control has to fail one of them. A control has no
exchange and no optimizer, so the rows' counters are given whole and the
gradient unclipped; the update is not checked. ``--rehearse`` runs the same
at the configuration's tiny sizes on the CPU. Results go to stdout and to
``chiprun_out/mellum_grad_check.json``.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "mellum2-12b-a2.5b.s8192.b1.zipf.x4"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--load-seeds", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    chips = 4
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import ps_tpu as ps
    from benchmark.families import mellum_reference as reference
    from benchmark.families import mellum_step
    from benchmark.families.lfm2_step import learning_rate
    from benchmark.families.moe_step import fresh_batches
    from benchmark.harness.loop import seed_key
    from ps_tpu.models import mellum
    from ps_tpu.parallel.sharding import batch_sharding

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config["rehearse"])
        traffic.update(traffic["rehearse"])
    ctx = ps.init(backend="tpu")
    mesh = ctx.mesh
    cfg = mellum.MellumConfig.from_dict(config)
    seq, per_chip = int(traffic["seq_len"]), int(traffic["per_chip_batch"])
    batch = per_chip * chips
    pairs = seq * per_chip * cfg.num_experts_per_tok
    init = mellum_step.placed_init(cfg, mesh)

    def batch0(seed):
        b = next(fresh_batches(batch, seq, cfg.vocab_size,
                               traffic["ids"]["s"], seed))
        return jax.device_put(b, batch_sharding(mesh))

    out = {"device": jax.devices()[0].device_kind, "loads": {}, "checks": {}}

    # -- how the seed spreads a layer's pairs over the chips
    attn = traffic["attn"]

    @jax.jit
    def loads(params, b):
        _, counts, _, _, _, computed, more = mellum.apply(
            params, b["inputs"], cfg, mellum.make_attn_fn(attn), mesh)
        return counts, computed, more

    for seed in [int(s) for s in args.load_seeds.split(",") if s]:
        t0 = time.perf_counter()
        counts, computed, more = jax.device_get(
            loads(init(seed_key(seed)), batch0(seed)))
        counts = np.asarray(counts, np.float64)      # [L, chips, E]
        by_owner = counts.reshape(*counts.shape[:2], chips, -1).sum(-1)
        whole = counts.sum(axis=1)
        one = {"fullest_chip_over_even": (np.asarray(computed).max(-1)
                                          / pairs).round(4).tolist(),
               "fullest_buffer_over_even": (
                   by_owner.max(axis=(1, 2)) / (pairs / chips)
               ).round(4).tolist(),
               "fullest_expert_over_mean": (
                   whole.max(-1) / whole.mean(-1)).round(3).tolist(),
               "trips_beyond_first": np.asarray(more).max(-1).tolist(),
               "seconds": round(time.perf_counter() - t0, 1)}
        out["loads"][str(seed)] = one
        print(f"load seed {seed}: {json.dumps(one)}", flush=True)

    # -- the reference against itself: 8-bit weights, a factor left out
    names = mellum_step.WITNESSES

    opt = dict(config["optimizer"])
    _, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
    held = cfg.num_experts // chips
    layers = cfg.num_hidden_layers
    whole_rows = np.full((layers, chips), pairs)   # nothing to exchange

    def against(got, want):
        """The control ``got`` as the system, the whole reference ``want`` as
        the reference: the harness's verdicts and what they were read from."""
        (loss, aux, grads), (loss0, aux0, grads0) = got, want
        witnesses = {}
        for n in names:
            mine = mellum_step.sliced(n, np.asarray(grads[n]), held)
            for part, g in mellum_step.sliced(n, np.asarray(grads0[n]),
                                              held).items():
                # what AdamW's first moment holds of an unclipped gradient
                witnesses[part] = {"mu": (1 - rule["b1"]) * mine[part],
                                   "reference_grad": g}
        system = {**aux, "sent_rows": whole_rows, "received_rows": whole_rows,
                  "exchange_rows": 0 * whole_rows,
                  "exchange_trips": np.zeros(layers, np.int64)}
        result = mellum_step.step0_checks(
            system, aux0, witnesses, rule["clip_by_global_norm"], rule,
            pairs * chips)
        rel = abs(float(loss) - float(loss0)) / abs(float(loss0))
        checks = {"step0_matches_reference":
                  rel <= mellum_step.TOLERANCE[0], **result["checks"]}
        return {"correct": all(checks.values()),
                "failed": sorted(k for k, ok in checks.items() if not ok),
                "loss_rel_diff": rel, **result["detail"]}

    def run(config, params, b):
        plain = jax.jit(lambda p, b: reference.witness_grads(
            p, b, config, names))
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = plain(params, b)
        return jax.device_get(loss), jax.device_get(aux), grads

    fp8 = jnp.float8_e4m3fn   # the nearest precision below bfloat16
    no_factor = copy.deepcopy(config)
    for rp in no_factor["rope_parameters"].values():
        if "attention_factor" in rp:
            rp["attention_factor"] = 1.0
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        params, b = init(seed_key(seed)), batch0(seed)
        t0 = time.perf_counter()
        whole = run(config, params, b)
        rounded = jax.tree.map(
            lambda x: x.astype(fp8).astype(x.dtype) if x.ndim > 1 else x,
            params)
        one = {"reference_on_e4m3_weights": against(
                   run(config, rounded, b), whole),
               "reference_without_attention_factor": against(
                   run(no_factor, params, b), whole),
               "seconds": round(time.perf_counter() - t0, 1)}
        out["checks"][str(seed)] = one
        print(f"check seed {seed}: {json.dumps(one)}", flush=True)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "mellum_grad_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    ps.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
