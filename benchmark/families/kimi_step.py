"""Family of the fused step over Kimi-Linear, one chip's share of an
expert-parallel group: ``ps.init`` -> ``KVStore`` (AdamW behind a global-norm
clip, warmed up) -> ``make_step(loss_fn, has_aux=True)`` -> ``shard_batch``,
the calls of ``families/lfm2_step.py`` with the loss of
``ps_tpu/models/kimi_linear.py``. The router's selection bias goes in as the
step's extra argument and comes back in ``aux`` as a device value, every step,
with the step's expert counts; no host read in the window.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``); the plain reference, the benchmark's own copy
(``families/kimi_reference.py``, letter for letter the tests'
``tests/kimi_reference.py``); the limits of the step-0 checks with their
measured reasons; and the functions that give operations and bytes from shapes
(``kda_core_cost``, ``flash_cost``, ``dense_flops``, ``pair_flops``,
``step_flops``). The warm-up and the sign rule are LFM2's
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
from benchmark.families import kimi_reference as reference
from benchmark.families.lfm2_step import bias_by_sign_rule, learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the
# chunked delta rule (its state, decays and inverse in f32), the Pallas flash
# kernel at keys of 192 and values of 128 and the grouped matmuls over the held
# experts; the reference in f32 at "highest" with the rule token by token and
# none of the kernels. All readings: my chip runs, PR 34, TPU v5 lite,
# published widths, 8,192 tokens. "seen": the system against the reference
# over 30 seeds of the cell. "e4m3": the reference on weights rounded to an
# 8-bit float (the nearest precision below bfloat16, a lower bound of computing
# in one) against the whole reference, at three seeds
# (tools/kimi_grad_check.py). Each limit lies between the two.
#
#              loss     flips a layer  cos KDA k  cos f_b   cos kv_b  cos shared w1  cos router  cos expert gate
#  seen, worst 7.86e-5  347 (0.53%)    .999485    .999467   .999945   .999492        .960104     .980146
#  LIMIT       1.5e-4   590 (0.9%)     .99        .99       .997      .99            .90         .93
#  e4m3        8.8e-5,  980 .. 1231    .9537 ..   .9512 ..  .9882 ..  .9372 ..       .614 ..     .742 ..
#              1.2e-4,  (1.5-1.9%)     .9556      .9530     .9887     .9384          .640        .764
#              2.5e-4
#
# e4m3 is "not correct" by the counts and by every cosine at every seed, by
# the loss at one of three (the loss is the blunt one: 0.02-normal weights give
# every token nearly the entropy of the vocabulary). The router's and the
# expert stack's gradients sit lower than the other witnesses for LFM2's
# reason, more so: 255-347 of a layer's 65,536 pairs flip between bf16 and f32
# activations, a 32nd of them on held experts, and under Zipf ids the flipped
# tokens are copies of a few hot ids whose contributions add up coherently
# (the routers read 0.971-0.978 over the four layers, every tensor outside the
# expert layers 0.9995 or better: the table of tools/kimi_grad_check.py). The
# decay gate's f_b witnesses the rule's own backward pass (the cumulated
# decays, the sub-blocks' exponentials, the inverse and the scan): a wrong
# sign or a missing term of the chunked form turns it, where the token-by-token
# reference has no chunk at all.
TOLERANCE = (1.5e-4,
             "bf16 compute with top-8 flips against an f32 reference whose "
             "delta rule runs token by token: 1.9x the largest of 30 seeds "
             "(7.86e-5; the next 4.9e-5); the reference on e4m3 weights moves "
             "8.8e-5, 1.2e-4 and 2.5e-4. Blunt (0.02-normal weights give "
             "every token nearly the entropy of the vocabulary), so "
             "after_step0 holds the counts, the gradient, the clip, the apply "
             "and the bias")
#: token-expert pairs, of T * top_k a layer, that may sit on another expert
#: than the reference's (top-8 flips between bf16 and f32 activations):
#: half the sum over the 256 experts of |count - reference count|, per layer.
#: Seen: 255 to 347 of 65,536; e4m3: 980 to 1,231
FLIP_SHARE = 0.009
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: the leading KDA
#: layer's k projection (upstream of everything: its gradient comes back
#: through the head, four expert layers with their recomputation, the flash
#: kernel's backward at 192 / 128, four chunked rules and the dense SwiGLU)
#: and its decay gate's outer matrix (the decay's path through the rule), the
#: latent attention's expansion of K and V (the kernel's dk at 192 and dv at
#: 128 side by side), a shared expert's gate matrix (the dense branch beside
#: the routed one), the router of the attention layer (the sigmoid, the
#: renormalisation over all eight picks, the scaling and the absent experts'
#: zero weights) and an expert stack of a KDA layer (the grouped matmul's
#: gradient over the held groups).
#: Read from AdamW's first moment: no hook in the step.
GRAD_COSINE = {"layer0/kda/k/kernel": 0.99,
               "layer0/kda/f_b/kernel": 0.99,
               "layer3/attn/kv_b/kernel": 0.997,
               "layer1/moe/shared/w1/kernel": 0.99,
               "layer3/moe/router/kernel": 0.90,
               "layer2/moe/gate": 0.93}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them; it scaled by 0.297
#: to 0.305). Seen: 0.0123 at most; e4m3's lengths lie as near (3.5% the
#: router's at one seed, 1.5% the rest), so this limit tells no precision
#: apart: it catches a witness that is scaled (picks not renormalised or not
#: scaled by 2.446: the router's and the stack's lengths move by that factor)
GRAD_NORM_TOLERANCE = 0.025
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (5e-8 under the warm-up: lfm2_step.py says why the rounding
#: is allowed for). Seen beyond the rounding: 3.1e-7 to 4.3e-7 of the rate
APPLY_TOLERANCE = 1e-5

#: tokens of a chunk of ``ops/kda.py``, its default
KDA_CHUNK = 64
#: the steps n at which a run says its mean loss over n-7..n on stderr: the
#: values ISSUE 34 lets the traffic's ``loss_step`` take
LOSS_STEPS = (32, 48, 64)


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    three matrices, 3 x 2 x D x F."""
    return 3 * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def kda_core_cost(batch, seq, heads, k_dim, v_dim, chunk, layers,
                  itemsize=2):
    """Operations and HBM bytes of ``ops/kda.py`` in one step, forward and
    backward, from its shapes: the chunked form's own matmuls, the masked
    halves not counted and recomputation not counted. A chunk of C tokens of
    one head, forward: A and B are the causal halves of two C x C x K
    products (C^2 K each); the unit-lower inverse applied to V + K columns is
    a forward substitution (C^2 (V + K)); against the state, ``W_k S``,
    ``Q S`` and ``K^T U`` are 2 C K V each and ``B U`` the causal half of
    2 C^2 V. The backward pass is twice the forward. Bytes: the forward
    reads q, k, v (``itemsize``), the f32 decays and beta and writes o; the
    backward reads those and do and writes the five gradients."""
    c, k, v = chunk, k_dim, v_dim
    forward = 2 * c * c * k + c * c * (v + k) + 6 * c * k * v + c * c * v
    flops = 3.0 * forward * layers * batch * heads * (seq // chunk)
    inputs = (2 * k + v) * itemsize + 4 * k + 4
    per_token_head = (inputs + v * itemsize) + (inputs + v * itemsize
                                                + inputs)
    return flops, float(layers * batch * seq * heads * per_token_head)


def flash_cost(batch, heads, seq, qk_dim, v_dim, layers, itemsize=2):
    """``flash.cost`` of the causal kernel's three calls, **forward and
    backward**, where every query head has K and V of its own (the latent
    attention; Nemotron-H's one K/V head is read as four here, as since PR
    39), keys ``qk_dim`` wide and values ``v_dim``, the causal mask counted
    as half the square, without the diagonal's half. No cell's facts are made
    here since PR 67 (they count ``flash.seen_pairs``, the diagonal in);
    ``tests/test_joyai.py`` holds JoyAI's count against this one, so it goes
    with that line (PERF.md section 7)."""
    return flash.cost(batch, heads, heads, seq, qk_dim, v_dim, layers,
                      seq * seq / 2, itemsize=itemsize)


def dense_flops(config, tokens, seq_len):
    """Operations of one training step outside the routed experts, that the
    model requires: forward and backward (3 x 2 a parameter a token) over the
    matmuls every token passes (a KDA layer's four projections, its two
    low-rank gates and the write strength; the MLA layer's q, the latent's
    two and the out projection; the dense SwiGLU; the routers and the shared
    experts; the untied head), attention's quadratic term (QK^T at 192 and PV
    at 128, forward and backward, halved for the causal mask) and the
    chunked rule's own (``kda_core_cost``). The taps, gates and norms are not
    counted, nor is recomputation."""
    d = config["hidden_size"]
    linear = config["linear_attn_config"]
    hk = linear["num_heads"] * linear["head_dim"]
    rank = config.get("gate_low_rank", linear["head_dim"])
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v_dim = config["v_head_dim"]
    per_token = 6.0 * d * config["vocab_size"]
    for i in range(config["num_hidden_layers"]):
        if i + 1 in linear["kda_layers"]:
            per_token += 6.0 * (4 * d * hk + 2 * (d * rank + rank * hk)
                                + d * linear["num_heads"])
        else:
            per_token += 6.0 * (
                d * heads * qk
                + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
                + config["kv_lora_rank"] * heads
                * (config["qk_nope_head_dim"] + v_dim)
                + heads * v_dim * d) + 3 * heads * seq_len * (qk + v_dim)
        if i < config["first_k_dense_replace"]:
            per_token += 6.0 * 3 * d * config["intermediate_size"]
        else:
            per_token += 6.0 * (d * config["router_width"] + 3 * d
                                * config["moe_intermediate_size"]
                                * config["num_shared_experts"])
    rule, _ = kda_core_cost(tokens // seq_len, seq_len, linear["num_heads"],
                            linear["head_dim"], linear["head_dim"],
                            KDA_CHUNK, len(linear["kda_layers"]))
    return float(tokens * per_token) + rule


def step_flops(config, tokens, seq_len, live_pairs):
    """``dense_flops`` plus the pairs the step computed here."""
    return dense_flops(config, tokens, seq_len) \
        + live_pairs * pair_flops(config)


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs, rate):
    """What ``correct`` holds beyond step 0's loss, as
    ``lfm2_step.step0_checks`` with this family's limits. ``got`` / ``want``:
    the step's and the reference's aux. ``witnesses``: per name ``before``
    and ``after`` (the parameter around step 0), ``mu`` and ``nu`` (the
    store's moments after it) and ``reference_grad``. ``clipped_norm``: the
    global norm of the clipped gradient. ``pairs``: T * top_k, a layer.
    Returns the loop's ``{"checks": .., "detail": ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)        # [L, 256]
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
            for name in witnesses) and bool(max(
                abs(s / scale - 1) for s in scales) <= GRAD_NORM_TOLERANCE),
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
    from ps_tpu.models.kimi_linear import (KimiLinearConfig,
                                           init_expert_bias, init_params,
                                           make_loss_fn)
    from ps_tpu.parallel.sharding import replicated

    if config["model"] != "kimi_linear":
        raise ValueError(f"kimi_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"kimi_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ctx = ps.init(backend="tpu")
    cfg = KimiLinearConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step
    pairs = tokens * cfg.num_experts_per_token   # a chip, a step, a layer

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

    # device values, read at the end only: a scalar, [L, 256] and [L, held]
    losses, expert_tokens, held_tokens = [], [], []
    first = {}

    def step(b):
        loss, _, aux = fused(b, state["expert_bias"])
        if not expert_tokens:
            first["system"] = aux
        state["expert_bias"] = aux["expert_bias"]
        losses.append(loss)
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
        values = [float(x) for x in jax.device_get(losses)]
        print("kimi_step: mean loss of steps n-7..n " + json.dumps(
            {n: stats.loss_at_n(values, n) for n in LOSS_STEPS
             if n < len(values)}), file=sys.stderr)
        counts = np.asarray(jax.device_get(expert_tokens), np.float64)
        held = np.asarray(jax.device_get(held_tokens), np.float64)
        routed = pairs * chips * cfg.num_expert_layers * len(counts)
        share = held.sum(axis=-1) / counts.sum(axis=-1)        # [steps, L]
        print("kimi_step: held share of the pairs, by layer "
              + json.dumps((held.sum(axis=(0, 2))
                            / counts.sum(axis=(0, 2))).round(5).tolist())
              + f", by step (the first {LOSS_STEPS[-1] + 1}) "
              f"{share.mean(axis=-1).round(4).tolist()[:LOSS_STEPS[-1] + 1]}"
              + f"; final expert_bias range "
              f"{float(jnp.min(state['expert_bias'])):+.4f} .. "
              f"{float(jnp.max(state['expert_bias'])):+.4f}",
              file=sys.stderr)
        return {"dropped_tokens": float(routed - counts.sum()),
                "load_max_over_mean":
                float(np.mean(counts.max(axis=-1) / counts.mean(axis=-1))),
                "held_pair_share": float(held.sum() / counts.sum()),
                # all expert layers of one chip, a step
                "live_pairs_per_step":
                float(held.sum() / len(held) / chips)}

    linear = config["linear_attn_config"]
    itemsize = np.dtype(cfg.dtype).itemsize
    facts = {
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "flops_per_pair": pair_flops(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    facts["kda_core_flops"], facts["kda_core_bytes"] = \
        kda_core_cost(per_chip, seq, linear["num_heads"], linear["head_dim"],
                      linear["head_dim"], KDA_CHUNK,
                      len(linear["kda_layers"]), itemsize)
    if traffic["attn"] == "flash":
        facts["flash_flops"], facts["flash_bytes"] = flash.cost(
            per_chip, cfg.num_attention_heads, cfg.num_attention_heads, seq,
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim,
            len(linear["full_attn_layers"]), flash.seen_pairs(seq),
            itemsize=itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
