"""Family of the fused step over Granite-4.0-H, a dense decoder whole on one
chip of a pipeline stage: ``ps.init`` -> ``KVStore`` (AdamW behind a
global-norm clip, warmed up) -> ``make_step(loss_fn)`` -> ``shard_batch``, the
calls of ``families/nemotron_h_step.py`` with the loss of
``ps_tpu/models/granite_h.py`` and without the extra argument: the model has
no state beside its parameters, so the step returns its loss and nothing else,
and no host read happens in the window.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``); the plain reference
(``families/granite_h_reference.py``); the limits of the step-0 checks with
their measured reasons; and the functions that give operations and bytes from
shapes, whatever implements them (``dense_flops`` here,
``nemotron_h_step.ssd_cost`` for the scan, ``families/flash.py::cost`` for
the kernel's three calls). The warm-up is LFM2's rule
(``lfm2_step.learning_rate``) at this configuration's length.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.families import flash
from benchmark.families import granite_h_reference as reference
from benchmark.families.lfm2_step import learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.families.nemotron_h_step import lengths_apart, ssd_cost
from benchmark.harness.loop import Cell, seed_key

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the
# chunked scan over all 64 heads at once (its decays, cumulated sums and state
# in f32) and the Pallas flash kernel at 32 query heads on 8 K/V heads; the
# reference in f32 at "highest" with the scan token by token and no kernel.
# All readings: my chip runs, PR 56, TPU v5 lite, published widths, 8,192
# tokens. "seen": the system against the reference over 28 runs of the cell at
# 22 seeds (5600000101-707). "e4m3": the reference on weights rounded to an
# 8-bit float (the nearest precision below bfloat16, a lower bound of
# computing in one) against the whole reference, at six seeds (5600000301 /
# 302, 801-804; tools/nemotron_grad_check.py --model granite_h), the mildest
# of the six. The five rows below it: the reference with one fault planted
# against the whole reference (the same tool, the same seeds, the mildest
# reading of the six): what the lengths' limit and the loss's are there for.
# The tool hands the control and every fault to step0_checks and the loss's
# tolerance as if each were the system: at the four later seeds all 24 come
# out not correct, the system itself correct.
#
#                 loss      least cosine (witness)           lengths apart
#  seen, worst    7.49e-6   .999117 (dt_bias; others .9992+) 0.0153
#  LIMIT          2e-5      .99 (dt_bias .97)                0.05
#  e4m3, mildest  2.04e-5   .9785 (A_log; dt_bias .930,      0.056
#                           the matrices .950-.958)
#  residual_multiplier read as 1       1.43e-3   .101   0.335
#  embedding not times 12              1.65e-3   .043   0.353
#  logits not divided by 8             8.9e-2    .460   0.458
#  attention scaled by 1/8             0         .889   3.78  (q's 9.1x longer)
#  the norm before the gate            3.4e-4    .280   0.864
#
# What holds the weights' precision is the cosines: e4m3 misses every one of
# the nine at every seed, with .9991 seen and .9785 at its best around the
# limit of .99. Its loss (2.04e-5 to 6.82e-5) and its lengths (0.056 to 0.196)
# miss their limits too, but at the mildest seed by 2% and 12% only: those two
# limits are there for the faults, which fail the lengths' by 6.7 times and
# more, the cosines' by far, and all but the attention's scale the loss's:
# with 0.02-normal weights the scores are near nothing at either scale and the
# layer's output hardly moves, but q's gradient is eight times as long, which
# is what the lengths read. The clip is not engaged at step 0 (the gradient's
# norm reads 0.459-0.465 against the limit 1.0), so the lengths over the
# reference's read 1 +- 0.002 and the clip's check holds the scale to 1.
TOLERANCE = (2e-5,
             "bf16 compute against an f32 reference whose scan runs token by "
             "token: 2.7x the largest of 28 runs (7.49e-6; the next "
             "6.58e-6, the median 1.7e-6); the reference on e4m3 weights "
             "moves 2.04e-5 to 6.82e-5 over six seeds. Blunt for the weights "
             "(0.02-normal weights give every token nearly the entropy of "
             "the vocabulary; the cosines hold those) but not for the "
             "multipliers: logits not divided by 8 move step 0's loss by 9%, "
             "the residual's or the embedding's read as 1 by 0.15%. "
             "after_step0 holds the gradient, the clip and the apply")
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: the mixer's in
#: projection, its filter, the three per-head vectors of the scan (A_log,
#: dt_bias, D), the gated norm's scale, the attention's q (under
#: attention_multiplier), a SwiGLU's W_in, and the tied embedding, whose
#: gradient is the sum of the lookup's and the head's. Read from AdamW's first
#: moment: no hook in the step.
GRAD_COSINE = {"layer0/mamba/in_proj/kernel": 0.99,
               "layer2/mamba/conv/kernel": 0.99,
               "layer0/mamba/A_log": 0.99,
               "layer4/mamba/dt_bias": 0.97,
               "layer7/mamba/D": 0.99,
               "layer8/mamba/out_norm/scale": 0.99,
               "layer5/attn/q/kernel": 0.99,
               "layer9/ffn/w_in/kernel": 0.99,
               "embed/tokens": 0.99}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them). Seen: 0.0153 at
#: most; e4m3: 0.056 to 0.196. It is what catches a multiplier read as
#: another model's: a part's gradient then grows or shrinks against the
#: others' (0.335 at the least, the table above)
GRAD_NORM_TOLERANCE = 0.05
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (lfm2_step.py says why the rounding is allowed for)
APPLY_TOLERANCE = 1e-5


# -- operations and bytes from shapes -----------------------------------------

def dense_flops(config, tokens, seq_len):
    """Operations of one training step that the model requires: forward and
    backward (3 x 2 a parameter a token) over the matmuls every token passes
    (a Mamba-2 mixer's two projections; the attention layer's four; every
    layer's SwiGLU; the tied head, once: the lookup is no product),
    attention's quadratic term (QK^T and PV, forward and backward, halved for
    the causal mask) and the scan's own (``ssd_cost``). The taps, gates and
    norms are not counted, nor is recomputation."""
    d = config["hidden_size"]
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    inner = heads * p
    q_heads, kv_heads = (config["num_attention_heads"],
                         config["num_key_value_heads"])
    dim = d // q_heads
    kinds = config["layer_types"]
    mamba, attention = kinds.count("mamba"), kinds.count("attention")
    per_token = 6.0 * d * config["vocab_size"]
    per_token += len(kinds) * 6.0 * 3 * d * config["shared_intermediate_size"]
    per_token += mamba * 6.0 * (
        d * (2 * inner + 2 * groups * n + heads) + inner * d)
    per_token += attention * (
        6.0 * d * dim * (2 * q_heads + 2 * kv_heads)
        + 3 * q_heads * seq_len * 2 * dim)
    scan, _ = ssd_cost(tokens // seq_len, seq_len, heads, p, groups, n,
                       min(config["mamba_chunk_size"], seq_len), mamba)
    return float(tokens * per_token) + scan


def step0_checks(witnesses, clipped_norm, rule):
    """What ``correct`` holds beyond step 0's loss, as
    ``nemotron_h_step.step0_checks`` without a router's counts.
    ``witnesses``: per name ``before`` and ``after`` (the parameter around
    step 0), ``mu`` and ``nu`` (the store's moments after it) and
    ``reference_grad``; a witness without ``after`` is a gradient alone and
    no apply is read from it. ``clipped_norm``: the global norm of the clipped
    gradient. Returns the loop's ``{"checks": .., "detail": ..}``."""
    detail = {"clipped_gradient_norm": clipped_norm}
    clip = rule["clip_by_global_norm"]
    scales = []
    for name, w in witnesses.items():
        grad = np.asarray(w["mu"], np.float64) / (1 - rule["b1"])
        detail[f"grad_cosine.{name}"] = cosine(grad, w["reference_grad"])
        scales.append(np.linalg.norm(grad)
                      / np.linalg.norm(np.asarray(w["reference_grad"],
                                                  np.float64)))
        if "after" not in w:   # a gradient alone: the checker's cases
            continue
        after = np.asarray(w["after"], np.float32)
        off = np.abs(after.astype(np.float64) - adamw_first_step(
            w["before"], w["mu"], w["nu"], **rule))
        detail[f"apply_error_lr.{name}"] = float(np.max(np.maximum(
            off - 0.5 * np.spacing(np.abs(after)).astype(np.float64), 0.0))
            / rule["learning_rate"])
    detail["grad_norm_over_reference"] = [float(s) for s in scales]
    detail["clip_scale"] = scale = float(np.mean(scales))
    detail["lengths_apart"] = lengths_apart(scales)
    clipped_to_limit = abs(clipped_norm - clip) <= 1e-3 * clip
    return {"checks": {
        "gradient_matches_reference": all(
            detail[f"grad_cosine.{name}"] >= GRAD_COSINE[name]
            for name in witnesses)
        and detail["lengths_apart"] <= GRAD_NORM_TOLERANCE,
        "gradient_clipped_to_global_norm":
            clipped_norm <= clip * (1 + 1e-3) and (
                clipped_to_limit or abs(scale - 1) <= GRAD_NORM_TOLERANCE),
        "adamw_apply_matches_rule": all(
            value <= APPLY_TOLERANCE for key, value in detail.items()
            if key.startswith("apply_error_lr."))},
        "detail": detail}


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.models.granite_h import (GraniteHConfig, init_params,
                                         make_loss_fn)

    if config["model"] != "granite_h":
        raise ValueError(f"granite_h_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"granite_h_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ps.init(backend="tpu")
    cfg = GraniteHConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step

    opt = dict(config["optimizer"])
    rate, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
    store = ps.KVStore(optimizer=opt.pop("name"), placement="replicated",
                       **{**opt, "learning_rate": rate})
    # the weights are made on the device from the seed; the store keeps its
    # own buffers (it donates them every step), so the tree made here goes
    params = jax.block_until_ready(
        jax.jit(lambda k: init_params(k, cfg))(seed_key(seed)))
    t_weights = time.perf_counter()
    jax.block_until_ready(store.init(params))
    del params
    t_store = time.perf_counter()
    fused = store.make_step(make_loss_fn(cfg, attn=traffic["attn"]))
    batches = fresh_batches(per_chip * chips, seq, cfg.vocab_size,
                            traffic["ids"]["s"], seed)

    def step(b):
        loss, _ = fused(b)
        return loss

    plain = jax.jit(lambda params, b: reference.witness_grads(
        params, b, config, GRAD_COSINE))
    first = {}

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            loss, grads = plain(params, b)
        first["witnesses"] = {
            # the store donates its buffers to step 0: copies, on the host
            name: {"before": np.asarray(store.pull(name)),
                   "reference_grad": np.asarray(grad)}
            for name, grad in grads.items()}
        return float(loss)

    def after_step0():
        """More than step 0's loss: ``step0_checks`` on what the store
        holds once step 0 is done."""
        def moment(key, which):
            return optax.tree_utils.tree_get(store.optimizer_state(key),
                                             which)

        for name, w in first["witnesses"].items():
            w.update(after=np.asarray(store.pull(name)),
                     mu=np.asarray(moment(name, "mu")),
                     nu=np.asarray(moment(name, "nu")))
        clipped_norm = float(jnp.sqrt(sum(
            jnp.vdot(m, m) for m in (moment(k, "mu") for k in store.keys())))
        ) / (1 - rule["b1"])
        return step0_checks(first["witnesses"], clipped_norm, rule)

    itemsize = np.dtype(cfg.dtype).itemsize
    mamba = cfg.layer_types.count("mamba")
    facts = {
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    facts["ssd_flops"], facts["ssd_bytes"] = ssd_cost(
        per_chip, seq, cfg.mamba_n_heads, cfg.mamba_d_head,
        cfg.mamba_n_groups, cfg.mamba_d_state,
        min(cfg.mamba_chunk_size, seq), mamba, itemsize)
    if traffic["attn"] == "flash":
        # K and V counted a query head each, as Nemotron-H's
        facts["flash_flops"], facts["flash_bytes"] = flash.cost(
            per_chip, cfg.num_attention_heads, cfg.num_attention_heads, seq,
            cfg.head_dim, cfg.head_dim, cfg.layer_types.count("attention"),
            flash.seen_pairs(seq), itemsize=itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=dict, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
