"""Family of the fused step over Nemotron-H, one chip's share of a group that
divides each layer by heads and by experts: ``ps.init`` -> ``KVStore`` (AdamW
behind a global-norm clip, warmed up) -> ``make_step(loss_fn, has_aux=True)``
-> ``shard_batch``, the calls of ``families/kimi_step.py`` with the loss of
``ps_tpu/models/nemotron_h.py``. The router's selection bias goes in as the
step's extra argument and comes back in ``aux`` as a device value, every step,
with the step's expert counts; no host read in the window.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``); the plain reference, the benchmark's own copy
(``families/nemotron_h_reference.py``, letter for letter the tests'
``tests/nemotron_h_reference.py``); the limits of the step-0 checks with their
measured reasons; and the functions that give operations and bytes from shapes
of the share that is computed, whatever implements it (``ssd_cost``,
``dense_flops``, ``pair_flops``, ``step_flops``; the flash kernel's are
``families/flash.py``'s). The warm-up and the sign rule are LFM2's
(``lfm2_step.learning_rate``, ``lfm2_step.bias_by_sign_rule``).
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.families import flash
from benchmark.families import nemotron_h_reference as reference
from benchmark.families.lfm2_step import bias_by_sign_rule, learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.harness.loop import Cell, seed_key

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the
# chunked scan (its decays, cumulated sums and state in f32), the Pallas flash
# kernel at 4 query heads on 1 K/V head and the grouped matmuls over the held
# experts in the latent; the reference in f32 at "highest" with the scan token
# by token and none of the kernels. All readings: my chip runs, PR 39, TPU v5
# lite, published widths, 8,192 tokens. "seen": the system against the
# reference over the cell's 121 runs, 120 seeds. "e4m3": the reference on
# weights rounded to an 8-bit float (the nearest precision below bfloat16, a
# lower bound of computing in one) against the whole reference, at fifteen
# seeds
# (tools/nemotron_grad_check.py). "scaled": the reference with the picks'
# weights not multiplied by routed_scaling_factor against the whole reference,
# at three seeds (the same tool): the fault the last column is there for.
# Each limit lies between its two readings.
#
#              loss     flips a layer  in_proj  A_log    dt_bias  attn q   latent_down  router   expert w1  shared w1  lengths apart
#  seen, worst 8.12e-5  643 (0.36%)    .999825  .998587  .994656  .999804  .990125      .965489  .987368    .999974    0.0466
#  LIMIT       1.5e-4   1,622 (0.9%)   .99      .99      .97      .99      .97          .90      .95        .99        0.15
#  e4m3, best  3.75e-4  4,648          .7451    .9502    .9308    .7221    .5964        .6198    .6090      .8902      (0.083)
#  scaled, least                                                                                                       0.716
#
# e4m3 is "not correct" at every one of its fifteen seeds by the loss, by the
# counts of every layer and by every cosine (A_log's reads .28 to .95,
# dt_bias's -.24 to .93). The
# last column tells no precision apart, as in the older families, and is not
# asked to: e4m3's lengths lie 0.083 to 1.26 from their mean over fifteen
# seeds, on both sides of the limit; the system's up to 0.0466 (the router's,
# whose cosine is the lowest; 0.020 +- 0.012). Its upper
# reading is the fault it catches, a witness that is scaled: with the picks
# not scaled by 5 the latent projection, the router and the expert stack keep
# 0.191-0.203 of their length and the farthest lies 0.716-0.727 from the mean
# (three seeds); not renormalised they grow 14.4-16.8 times: 1.52-1.60. The
# limit has 3.2 times of room above what was seen and 4.8 under the fault. The
# router's, the latent projection's and the expert stack's gradients sit lower
# than the other witnesses for LFM2's and Kimi-Linear's reason: 392-643 of a
# layer's 180,224 pairs flip between bf16 and f32 activations, a 64th of them
# on held experts, and under Zipf ids the flipped tokens are copies of a few
# hot ids whose contributions add up coherently. A_log and dt_bias (16 numbers
# each) witness the scan's own backward pass, the cumulated decays, the masked
# exponentials and the carried state: a wrong sign or a missing term of the
# chunked form turns them, where the token-by-token reference has no chunk at
# all.
TOLERANCE = (1.5e-4,
             "bf16 compute with top-22 flips against an f32 reference whose "
             "scan runs token by token: 1.85x the largest of 121 runs "
             "(8.12e-5; the next 6.81e-5; mean 2.1e-5); the reference on "
             "e4m3 weights moves 3.75e-4 to 5.7e-3 at fifteen seeds. Blunt "
             "(0.02-normal weights give every token nearly the entropy of "
             "the vocabulary), so after_step0 "
             "holds the counts, the gradient, the clip, the apply and the "
             "bias")
#: token-expert pairs, of T * top_k a layer, that may sit on another expert
#: than the reference's (top-22 flips between bf16 and f32 activations):
#: half the sum over the 512 experts of |count - reference count|, per layer.
#: Seen: 392 to 643 of 180,224; e4m3: 4,648 to 27,390
FLIP_SHARE = 0.009
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes.
#: Read from AdamW's first moment: no hook in the step.
GRAD_COSINE = {"layer0/mamba/in_proj/kernel": 0.99,
               "layer0/mamba/A_log": 0.99,
               "layer4/mamba/dt_bias": 0.97,
               "layer9/attn/q/kernel": 0.99,
               "layer1/moe/latent_down/kernel": 0.97,
               "layer5/moe/router/kernel": 0.90,
               "layer3/moe/w1": 0.95,
               "layer7/moe/shared/w1/kernel": 0.99}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them; it scaled by 0.0199
#: to 0.0221). Seen: 0.0466 at most; the picks' weights not scaled by 5:
#: 0.716 at the least (the table above). It tells no precision apart
GRAD_NORM_TOLERANCE = 0.15
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (5e-8 under the warm-up: lfm2_step.py says why the rounding
#: is allowed for)
APPLY_TOLERANCE = 1e-5


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    two matrices in the latent, 2 x 3 x 2 x L x F."""
    return 2 * 6.0 * config["moe_latent_size"] \
        * config["moe_intermediate_size"]


def ssd_cost(batch, seq, heads, head_dim, groups, state, chunk, layers,
             itemsize=2):
    """Operations and HBM bytes of the state-space scan (``ops/ssd.py``'s
    part of the mixer) in one step, forward and backward, from its shapes:
    the chunked form's own matmuls, the masked halves not counted and
    recomputation not counted. A chunk of Q tokens, forward: ``C B^T`` is the
    causal half of a Q x Q x N product a group (Q^2 N); a head's ``(L o C
    B^T)(dt x)`` the causal half of 2 Q^2 P; the chunk's contribution to the
    state and its read of the entering state 2 Q P N each. The backward pass
    is twice the forward. Bytes: the forward reads x, B, C (``itemsize``) and
    the f32 steps and writes y; the backward reads those and dy and writes the
    four gradients."""
    q, p, n = chunk, head_dim, state
    forward = groups * q * q * n + heads * (q * q * p + 4 * q * p * n)
    flops = 3.0 * forward * layers * batch * (seq // chunk)
    inputs = (heads * p + 2 * groups * n) * itemsize + 4 * heads
    out = heads * p * itemsize
    per_token = (inputs + out) + (inputs + out + inputs)
    return flops, float(layers * batch * seq * per_token)


def dense_flops(config, tokens, seq_len):
    """Operations of one training step outside the routed experts, that the
    model requires of the share that is computed: forward and backward (3 x 2
    a parameter a token) over the matmuls every token passes (a Mamba mixer's
    two projections; the attention layer's four; an expert layer's router,
    its two latent projections and the shared expert; the untied head),
    attention's quadratic term (QK^T and PV, forward and backward, halved for
    the causal mask) and the scan's own (``ssd_cost``). The taps, gates and
    norms are not counted, nor is recomputation."""
    d = config["hidden_size"]
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    inner = heads * p
    q_heads, kv_heads, dim = (config["num_attention_heads"],
                              config["num_key_value_heads"],
                              config["head_dim"])
    pattern = config["hybrid_override_pattern"]
    per_token = 6.0 * d * config["vocab_size"]
    per_token += pattern.count("M") * 6.0 * (
        d * (2 * inner + 2 * groups * n + heads) + inner * d)
    per_token += pattern.count("*") * (
        6.0 * d * dim * (2 * q_heads + 2 * kv_heads)
        + 3 * q_heads * seq_len * 2 * dim)
    per_token += pattern.count("E") * 6.0 * (
        d * config["router_width"] + 2 * d * config["moe_latent_size"]
        + 2 * d * config["moe_shared_expert_intermediate_size"])
    scan, _ = ssd_cost(tokens // seq_len, seq_len, heads, p, groups, n,
                       config["chunk_size"], pattern.count("M"))
    return float(tokens * per_token) + scan


def step_flops(config, tokens, seq_len, live_pairs):
    """``dense_flops`` plus the pairs the step computed here."""
    return dense_flops(config, tokens, seq_len) \
        + live_pairs * pair_flops(config)


def lengths_apart(scales):
    """How far the farthest of the witnesses' lengths over the reference's
    lies from their mean, as a share of it: what ``GRAD_NORM_TOLERANCE``
    bounds."""
    scales = np.asarray(scales, np.float64)
    return float(np.max(np.abs(scales / scales.mean() - 1)))


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs, rate):
    """What ``correct`` holds beyond step 0's loss, as
    ``kimi_step.step0_checks`` with this family's limits. ``got`` / ``want``:
    the step's and the reference's aux. ``witnesses``: per name ``before``
    and ``after`` (the parameter around step 0), ``mu`` and ``nu`` (the
    store's moments after it) and ``reference_grad``. ``clipped_norm``: the
    global norm of the clipped gradient. ``pairs``: T * top_k, a layer.
    Returns the loop's ``{"checks": .., "detail": ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)        # [L, 512]
    ref_counts = np.asarray(want["expert_tokens"], np.int64)
    held = np.asarray(got["held_tokens"], np.int64)            # [L, held]
    moved = np.abs(counts - ref_counts).sum(axis=-1) // 2      # a layer
    detail = {"pairs_routed_per_layer": counts.sum(axis=-1).tolist(),
              "pairs_held_per_layer": held.sum(axis=-1).tolist(),
              "reference_pairs_held_per_layer": np.asarray(
                  want["held_tokens"], np.int64).sum(axis=-1).tolist(),
              "pairs_on_another_expert_than_reference": moved.tolist(),
              "clipped_gradient_norm": clipped_norm}
    clip = rule["clip_by_global_norm"]
    scales = []
    for name, w in witnesses.items():
        grad = np.asarray(w["mu"], np.float64) / (1 - rule["b1"])
        detail[f"grad_cosine.{name}"] = cosine(grad, w["reference_grad"])
        scales.append(np.linalg.norm(grad)
                      / np.linalg.norm(np.asarray(w["reference_grad"],
                                                  np.float64)))
        after = np.asarray(w["after"], np.float32)
        off = np.abs(after.astype(np.float64) - adamw_first_step(
            w["before"], w["mu"], w["nu"], **rule))
        detail[f"apply_error_lr.{name}"] = float(np.max(np.maximum(
            off - 0.5 * np.spacing(np.abs(after)).astype(np.float64), 0.0))
            / rule["learning_rate"])
    detail["grad_norm_over_reference"] = [float(s) for s in scales]
    detail["clip_scale"] = scale = float(np.mean(scales))
    clipped_to_limit = abs(clipped_norm - clip) <= 1e-3 * clip
    bias = np.asarray(got["expert_bias"], np.float32)
    return {"checks": {
        "no_dropped_tokens": bool((counts.sum(axis=-1) == pairs).all()),
        "expert_counts_match_reference":
            bool((moved <= FLIP_SHARE * pairs).all()),
        "gradient_matches_reference": all(
            detail[f"grad_cosine.{name}"] >= GRAD_COSINE[name]
            for name in witnesses)
        and lengths_apart(scales) <= GRAD_NORM_TOLERANCE,
        "gradient_clipped_to_global_norm":
            clipped_norm <= clip * (1 + 1e-3) and (
                clipped_to_limit or abs(scale - 1) <= GRAD_NORM_TOLERANCE),
        "adamw_apply_matches_rule": all(
            detail[f"apply_error_lr.{name}"] <= APPLY_TOLERANCE
            for name in witnesses),
        # exactly the rule, on the step's own counts: the bias is not the
        # optimizer's and nothing rounds on the way
        "expert_bias_follows_sign_rule":
            bool(np.array_equal(bias, bias_by_sign_rule(counts, rate)))},
        "detail": detail}


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.models.nemotron_h import (NemotronHConfig, init_expert_bias,
                                          init_params, make_loss_fn)
    from ps_tpu.parallel.sharding import replicated

    if config["model"] != "nemotron_h":
        raise ValueError(
            f"nemotron_h_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"nemotron_h_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ctx = ps.init(backend="tpu")
    cfg = NemotronHConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step
    pairs = tokens * cfg.num_experts_per_tok     # a chip, a step, a layer

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
    fused = store.make_step(make_loss_fn(cfg, attn=traffic["attn"]),
                            has_aux=True)
    batches = fresh_batches(batch, seq, cfg.vocab_size, traffic["ids"]["s"],
                            seed)
    # the state that is not the optimizer's: one device value, handed from
    # each step to the next
    state = {"expert_bias": jax.device_put(init_expert_bias(cfg),
                                           replicated(ctx.mesh))}

    # device values, read at the end only: [L, 512] and [L, held]
    expert_tokens, held_tokens = [], []
    first = {}

    def step(b):
        loss, _, aux = fused(b, state["expert_bias"])
        if not expert_tokens:
            first["system"] = aux
        state["expert_bias"] = aux["expert_bias"]
        expert_tokens.append(aux["expert_tokens"])
        held_tokens.append(aux["held_tokens"])
        return loss

    plain = jax.jit(lambda params, b, bias: reference.witness_grads(
        params, b, bias, config, GRAD_COSINE))

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = plain(params, b, state["expert_bias"])
        first["reference"] = jax.device_get(aux)
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
        return step0_checks(jax.device_get(first["system"]),
                            first["reference"], first["witnesses"],
                            clipped_norm, rule, pairs * chips,
                            cfg.bias_update_rate)

    def counters():
        counts = np.asarray(jax.device_get(expert_tokens), np.float64)
        held = np.asarray(jax.device_get(held_tokens), np.float64)
        routed = pairs * chips * cfg.num_expert_layers * len(counts)
        fullest = float(np.mean(counts.max(axis=-1) / counts.mean(axis=-1)))
        print("nemotron_h_step: held share of the pairs, by layer "
              + json.dumps((held.sum(axis=(0, 2))
                            / counts.sum(axis=(0, 2))).round(5).tolist())
              + f", over the run {held.sum() / counts.sum():.5f}; fullest "
              f"expert over the mean {fullest:.3f}; final expert_bias range "
              f"{float(jnp.min(state['expert_bias'])):+.4f} .. "
              f"{float(jnp.max(state['expert_bias'])):+.4f}",
              file=sys.stderr)
        return {"dropped_tokens": float(routed - counts.sum()),
                "load_max_over_mean": fullest,
                "held_pair_share": float(held.sum() / counts.sum()),
                # all expert layers of one chip, a step
                "live_pairs_per_step":
                float(held.sum() / len(held) / chips)}

    itemsize = np.dtype(cfg.dtype).itemsize
    pattern = cfg.hybrid_override_pattern
    facts = {
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "flops_per_pair": pair_flops(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    facts["ssd_flops"], facts["ssd_bytes"] = ssd_cost(
        per_chip, seq, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
        cfg.ssm_state_size, min(cfg.chunk_size, seq), pattern.count("M"),
        itemsize)
    if traffic["attn"] == "flash":
        # K and V counted a query head each, as since PR 39
        facts["flash_flops"], facts["flash_bytes"] = flash.cost(
            per_chip, cfg.num_attention_heads, cfg.num_attention_heads, seq,
            cfg.head_dim, cfg.head_dim, pattern.count("*"),
            flash.seen_pairs(seq), itemsize=itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
