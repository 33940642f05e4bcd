"""Family of the fused step over Qwen3-Next, one chip's share of an
expert-parallel group: ``ps.init`` -> ``KVStore`` (AdamW behind a global-norm
clip, warmed up) -> ``make_step(loss_fn, has_aux=True)`` -> ``shard_batch``,
the calls of ``families/kimi_step.py`` with the loss of
``ps_tpu/models/qwen3_next.py`` and without the extra argument: the model has
no state beside its parameters (no selection bias), so the step takes its
batch alone and returns its expert counts in ``aux`` as device values; no host
read in the window.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``); the plain reference
(``families/qwen3_next_reference.py``); the limits of the step-0 checks with
their measured reasons; and the functions that give operations and bytes from
shapes, whatever implements them (``gdn_core_cost`` for the scalar-decay
rule's least work, ``flash.cost`` for the kernel's three calls at 256 / 256,
``dense_flops``, ``pair_flops``). The warm-up is LFM2's rule
(``lfm2_step.learning_rate``).
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
from benchmark.families import qwen3_next_reference as reference
from benchmark.families.lfm2_step import learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.families.nemotron_h_step import lengths_apart
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the
# chunked delta rule (its state, decays and inverse in f32; since PR 61 the
# scalar-decay Mosaic kernels read one decay a head and q and k at their 16
# key heads: at the readings below the decay was broadcast to the head's
# channels and the key heads repeated to their 32 readers in front of the
# general kernels), the Pallas flash kernel at keys and
# values of 256 and the grouped matmuls over the held experts; the reference
# in f32 at "highest" with the rule token by token and none of the kernels.
# All readings: my chip runs, PR 60, TPU v5 lite, published widths, 8,192
# tokens. "seen": the system against the reference over 22 runs of the cell
# at 22 seeds (2400000011 / 12, 21, 31-36, 101-113). "e4m3": the reference on
# weights rounded to an 8-bit float (the nearest precision below bfloat16, a
# lower bound of computing in one) against the whole reference, at four seeds
# (2400000021-24; tools/qwen3_next_grad_check.py). Each limit lies between
# the two. The four rows below them: the reference with one fault planted
# against the whole reference (the same tool, the same seeds): what the
# limits are there for. The tool hands the control and every fault to
# step0_checks and the loss's tolerance as if each were the system: all come
# out not correct at every seed, but the last row.
#
#                 loss       flips, worst   nine cosines   A_log     dt_bias   router    expert    lengths
#                            layer of 81,920 at .97                                      stack     apart
#  seen, worst    9.74e-5    841 (1.03%)    .9901          .9717     .9731     .9618     .9741     .0187
#  LIMIT          2e-4       1,065 (1.3%)   .97            .93       .93       .90       .93       .04
#  e4m3           5.0e-5 ..  1,323 .. 1,376 .7623 ..       .5426 ..  .4865 ..  .5808 ..  .6684 ..  .032 ..
#                 9.8e-4     (layer 0;      .8338          .9675     .8513     .5947     .6801     .039
#                            2,485+ layer 3)
#  a norm whose scale ignores w        0          0      the two w's gradient is nothing (nan)        1.0
#  key heads tiled, not repeated       1.8e-4 ..  3,472+ .0012 .. .06 (every matrix)   -.40 .. .73    .02-.05
#  every channel rotated               4.0e-5 ..  1,130+ .527 .. .554 (q_norm/w), .584 .. .590 (q)   .077-.097
#  the picks not renormalised          1.3e-4 ..  1,289+ .80 .. .87, router .62 .. .67                .85
#  the gate before the head norm       1.0e-6 ..  642+   .9960 .. .9977, router .972 .. .980          .010-.011
#                                      8.6e-5     (as the system)                  (CORRECT: see below)
#
# Every tensor of this model reads lower than Kimi-Linear's (.9995 there,
# .990-.996 here, all 70 alike: the table of tools/qwen3_next_grad_check.py)
# and so does its 8-bit control (.76-.83 where Kimi's read .95): at 0.02-normal
# weights the q and k norms give the attention scores a deviation of 1 and the
# head norm gives every mixer's output unit scale, so a rounding anywhere
# turns the whole backward signal, the system's eight bits by a tenth of what
# e4m3's three do. The per-head vectors A_log and dt_bias are the weakest
# witnesses (32 numbers, each a sum over 8,192 tokens of terms that cancel):
# their limit is low and their length is not read (LENGTH_NOT_READ). The
# routers and the expert stacks sit lower for LFM2's reason: 370-823 of a
# layer's 81,920 pairs flip between bf16 and f32 activations, a 16th of them
# on held experts.
# **The gate in front of the head norm cannot be told from the system at step
# 0**: against the whole reference it reads what bf16 reads (.996-.998, loss
# 1e-6 to 9e-5, lengths .01), because at this initialisation it is nearly a
# rescaling of the mixer's output (1 / rms(silu(z)) a head and a token, about
# 2.5 with a tenth of spread) that the next zero-centred norm divides out. No
# limit between "seen" and e4m3 catches it; the CPU tests do, in f32 at
# weights five times as large (tests/test_qwen3_next.py holds the model to
# the reference to 5e-5).
TOLERANCE = (2e-4,
             "bf16 compute with top-10 flips against an f32 reference whose "
             "delta rule runs token by token: 2.1x the largest of 22 seeds "
             "(9.74e-5; the next 9.23e-5 and 9.14e-5, the median 4.2e-5); the "
             "reference on e4m3 weights moves 5.0e-5, 8.9e-5, 3.3e-4 and "
             "9.8e-4. Blunt (0.02-normal weights give every token nearly the "
             "entropy of the vocabulary), so after_step0 holds the counts, "
             "the gradient, the clip and the apply")
#: token-expert pairs, of T * top_k a layer, that may sit on another expert
#: than the reference's (top-10 flips between bf16 and f32 activations):
#: half the sum over the 512 experts of |count - reference count|, per layer.
#: Seen: 357 to 841 of 81,920; e4m3: 1,323 to 2,559
FLIP_SHARE = 0.013
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: one tensor of
#: each new kind. The leading mixer's fused in-projection (upstream of
#: everything: its gradient comes back through the head, four expert layers,
#: the flash kernel's backward at 256 / 256, three chunked rules with the sums
#: over a key head's two readers and over the broadcast decay), b | a's
#: projection, the one filter, ``A_log`` and ``dt_bias`` (the scalar decay's
#: path through the rule: a wrong sum over the channels turns them), the
#: gated head norm's scale, a zero-centred norm's ``w`` on the stream and on a
#: head, the q projection that carries its gate (the whole matrix and, apart,
#: the columns of the gate's half: ``#gate``), the router of the attention
#: layer (the softmax over 512, the renormalisation over all ten picks and the
#: absent experts' zero weights), a shared expert's gate and an expert stack
#: of a delta-rule layer (the grouped matmul's gradient over the held groups).
#: Read from AdamW's first moment: no hook in the step.
GRAD_COSINE = {"layer0/gdn/in_qkvz/kernel": 0.97,
               "layer1/gdn/in_ba/kernel": 0.97,
               "layer0/gdn/conv": 0.97,
               "layer1/gdn/A_log": 0.93,
               "layer2/gdn/dt_bias": 0.93,
               "layer2/gdn/out_norm/scale": 0.97,
               "layer0/mixer_norm/w": 0.97,
               "layer3/attn/q_norm/w": 0.97,
               "layer3/attn/q/kernel": 0.97,
               "layer3/moe/router/kernel": 0.90,
               "layer1/moe/shared_gate/kernel": 0.97,
               "layer2/moe/gate": 0.93}
#: the gate's half of the q projection's columns, read apart from the whole
#: matrix: the lowest cosine that passes (seen .9922 to .9958; e4m3 .8075 to
#: .8217; every channel rotated .79 to .81)
GATE_HALF = ("layer3/attn/q/kernel", 0.97)
#: the witnesses whose LENGTH is not read: a head's ``A_log`` and ``dt_bias``
#: take the sum over every token of ``dg_t g_t``, terms of both signs that
#: cancel to a hundredth of their size, and the sum's length follows the
#: rounding (0.84 to 1.11 of the other witnesses' at nine seeds, where those
#: lie within 0.019 of one another); their direction is held by their cosines
LENGTH_NOT_READ = ("A_log", "dt_bias")
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them; it scaled by 0.156
#: to 0.165). Seen: 0.0187 at most (a head norm's ``w``); e4m3's lengths lie
#: as near (0.032, 0.039), so this limit tells no precision apart: it catches
#: a witness that is scaled (picks not renormalised: the router's and the
#: stack's lengths read 0.13 of the others'; every channel rotated: q's 0.93)
GRAD_NORM_TOLERANCE = 0.04
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (5e-8 under the warm-up: lfm2_step.py says why the rounding
#: is allowed for). Seen beyond the rounding: 0 to 3.7e-7 of the rate
APPLY_TOLERANCE = 1e-5

#: tokens of a chunk of ``ops/kda.py``, its default
RULE_CHUNK = 64
#: the steps n at which a run says its mean loss over n-7..n on stderr
LOSS_STEPS = (32, 48, 64, 96)


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    three matrices, 3 x 2 x D x F."""
    return 3 * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def gdn_core_cost(batch, seq, key_heads, value_heads, k_dim, v_dim, chunk,
                  layers, itemsize=2):
    """Operations and HBM bytes of the **scalar-decay** gated delta rule in
    one step, forward and backward, from its shapes: the least work of the
    chunked form at one decay a head and a token and ``key_heads`` q / k
    heads read by ``value_heads`` value heads, whatever computes it (since PR
    61 ``ops/kda_mosaic.py``'s scalar-decay kernels on the operands' own
    shapes; before it the general rule on broadcast operands, which moved
    more).
    A chunk of C tokens, forward: ``K K^T`` and ``Q K^T`` are the causal
    halves of two C x C x K products **a key head** (C^2 K each: a scalar
    decay scales their entries and is no part of the product); a value head
    then has the unit-lower inverse applied to V + K columns (a forward
    substitution, C^2 (V + K)), ``W_k S``, ``Q S`` and ``K^T U`` against the
    state (2 C K V each) and ``B U`` (the causal half of 2 C^2 V). The
    backward pass is twice the forward; the masked halves and recomputation
    are not counted. Bytes: the forward reads q and k at ``key_heads``, v,
    and **one f32 decay and one f32 beta a value head and a token** (no
    [S, H, K] gate), and writes o; the backward reads those and do and
    writes the five gradients at the same shapes."""
    c, k, v = chunk, k_dim, v_dim
    a_key_head = 2 * c * c * k
    a_value_head = c * c * (v + k) + 6 * c * k * v + c * c * v
    flops = 3.0 * (key_heads * a_key_head + value_heads * a_value_head) \
        * layers * batch * (seq // chunk)
    inputs = (2 * key_heads * k + value_heads * v) * itemsize \
        + value_heads * 8
    out = value_heads * v * itemsize
    return flops, float(layers * batch * seq * (3 * inputs + 2 * out))


def flash_cost(config, batch, seq, layers, itemsize=2):
    """``flash.cost`` of the causal kernel's three calls, forward and
    backward, at this attention's shapes: ``num_attention_heads`` query
    heads on ``num_key_value_heads`` K/V heads (read at their own count),
    keys and values ``head_dim`` wide, the triangle with its diagonal."""
    return flash.cost(batch, config["num_attention_heads"],
                      config["num_key_value_heads"], seq, config["head_dim"],
                      config["head_dim"], layers, flash.seen_pairs(seq),
                      itemsize=itemsize)


def layer_kinds(config):
    """(delta-rule layers, attention layers) of the configuration."""
    every = config["full_attention_interval"]
    full = sum((i + 1) % every == 0
               for i in range(config["num_hidden_layers"]))
    return config["num_hidden_layers"] - full, full


def dense_flops(config, tokens, seq_len):
    """Operations of one training step outside the routed experts, that the
    model requires: forward and backward (3 x 2 a parameter a token) over the
    matmuls every token passes (a delta-rule layer's two in-projections and
    its out projection; the attention layer's q with its gate, k, v and out;
    every layer's router, shared expert and the shared expert's gate; the
    untied head), attention's quadratic term (QK^T and PV at ``head_dim``,
    forward and backward, halved for the causal mask) and the rule's own
    (``gdn_core_cost``). The taps, gates and norms are not counted, nor is
    recomputation."""
    d = config["hidden_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    heads, kv, dim = (config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    linear, full = layer_kinds(config)
    per_token = 6.0 * d * config["vocab_size"]
    per_token += linear * 6.0 * (
        d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d)
    per_token += full * (
        6.0 * (d * heads * 2 * dim + 2 * d * kv * dim + heads * dim * d)
        + 3 * heads * seq_len * 2 * dim)
    per_token += (linear + full) * 6.0 * (
        d * config["router_width"]
        + 3 * d * config["shared_expert_intermediate_size"] + d)
    core, _ = gdn_core_cost(tokens // seq_len, seq_len, hk, hv, dk, dv,
                            RULE_CHUNK, linear)
    return float(tokens * per_token) + core


def step_flops(config, tokens, seq_len, live_pairs):
    """``dense_flops`` plus the pairs the step computed here."""
    return dense_flops(config, tokens, seq_len) \
        + live_pairs * pair_flops(config)


def gate_half(grad, config):
    """The columns of the q projection's gradient [D, H * 2 * head_dim] that
    are the gate's: the second ``head_dim`` of each head's pair."""
    dim = config["head_dim"]
    grad = np.asarray(grad)
    return grad.reshape(grad.shape[0], -1, 2 * dim)[..., dim:]


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs, config):
    """What ``correct`` holds beyond step 0's loss, as
    ``kimi_step.step0_checks`` without a selection bias. ``got`` / ``want``:
    the step's and the reference's aux. ``witnesses``: per name ``mu`` (the
    store's first moment after step 0) and ``reference_grad``, and, of the
    step itself, ``before`` and ``after`` (the parameter around step 0) and
    ``nu``; a witness without ``after`` is a gradient alone and no apply is
    read from it (``tools/qwen3_next_grad_check.py``'s cases).
    ``clipped_norm``: the global norm of the clipped gradient. ``pairs``:
    T * top_k, a layer. Returns the loop's ``{"checks": .., "detail": ..}``."""
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
    if "expert_windows" in got:
        detail["expert_windows"] = np.asarray(
            got["expert_windows"]).tolist()
    clip = rule["clip_by_global_norm"]
    scales, lengths = [], {}
    for name, w in witnesses.items():
        grad = np.asarray(w["mu"], np.float64) / (1 - rule["b1"])
        detail[f"grad_cosine.{name}"] = cosine(grad, w["reference_grad"])
        lengths[name] = float(np.linalg.norm(grad) / np.linalg.norm(
            np.asarray(w["reference_grad"], np.float64)))
        if not name.endswith(LENGTH_NOT_READ):
            scales.append(lengths[name])
        if name == GATE_HALF[0]:
            detail[f"grad_cosine.{name}#gate"] = cosine(
                gate_half(grad, config),
                gate_half(w["reference_grad"], config))
        if "after" not in w:   # a gradient alone: the checker's cases
            continue
        after = np.asarray(w["after"], np.float32)
        off = np.abs(after.astype(np.float64) - adamw_first_step(
            w["before"], w["mu"], w["nu"], **rule))
        detail[f"apply_error_lr.{name}"] = float(np.max(np.maximum(
            off - 0.5 * np.spacing(np.abs(after)).astype(np.float64), 0.0))
            / rule["learning_rate"])
    detail["grad_norm_over_reference"] = lengths
    detail["clip_scale"] = scale = float(np.mean(scales))
    detail["lengths_apart"] = lengths_apart(scales)
    clipped_to_limit = abs(clipped_norm - clip) <= 1e-3 * clip
    limits = {**GRAD_COSINE, f"{GATE_HALF[0]}#gate": GATE_HALF[1]}
    return {"checks": {
        "no_dropped_tokens": bool((counts.sum(axis=-1) == pairs).all()),
        "expert_counts_match_reference":
            bool((moved <= FLIP_SHARE * pairs).all()),
        "gradient_matches_reference": all(
            value >= limits[key.partition(".")[2]]
            for key, value in detail.items()
            if key.startswith("grad_cosine."))
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
    from ps_tpu.models.qwen3_next import (Qwen3NextConfig, init_params,
                                          make_loss_fn)

    if config["model"] != "qwen3_next":
        raise ValueError(f"qwen3_next_step knows no model "
                         f"{config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"qwen3_next_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ps.init(backend="tpu")
    cfg = Qwen3NextConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
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
    batches = fresh_batches(per_chip * chips, seq, cfg.vocab_size,
                            traffic["ids"]["s"], seed)

    # device values, read at the end only: a scalar, [L, 512], [L, held], [L]
    losses, expert_tokens, held_tokens, windows = [], [], [], []
    first = {}

    def step(b):
        loss, _, aux = fused(b)
        if not expert_tokens:
            first["system"] = aux
        losses.append(loss)
        expert_tokens.append(aux["expert_tokens"])
        held_tokens.append(aux["held_tokens"])
        windows.append(aux["expert_windows"])
        return loss

    plain = jax.jit(lambda params, b: reference.witness_grads(
        params, b, config, GRAD_COSINE))

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = plain(params, b)
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
                            clipped_norm, rule, pairs * chips, config)

    def counters():
        values = [float(x) for x in jax.device_get(losses)]
        print("qwen3_next_step: mean loss of steps n-7..n " + json.dumps(
            {n: stats.loss_at_n(values, n) for n in LOSS_STEPS
             if n < len(values)}), file=sys.stderr)
        counts = np.asarray(jax.device_get(expert_tokens), np.float64)
        held = np.asarray(jax.device_get(held_tokens), np.float64)
        ran = np.asarray(jax.device_get(windows), np.int64)    # [steps, L]
        layers = counts.shape[1]
        routed = pairs * chips * layers * len(counts)
        fullest = counts.max(axis=-1) / counts.mean(axis=-1)   # [steps, L]
        print("qwen3_next_step: held share of the pairs, by layer "
              + json.dumps((held.sum(axis=(0, 2))
                            / counts.sum(axis=(0, 2))).round(5).tolist())
              + f"; fullest expert over the mean, by layer "
              f"{fullest.mean(axis=0).round(2).tolist()}"
              f"; windows of rows a layer ran, by layer (mean, most) "
              f"{ran.mean(axis=0).round(3).tolist()} "
              f"{ran.max(axis=0).tolist()}", file=sys.stderr)
        return {"dropped_tokens": float(routed - counts.sum()),
                "load_max_over_mean": float(np.mean(fullest)),
                "held_pair_share": float(held.sum() / counts.sum()),
                # all expert layers of one chip, a step
                "live_pairs_per_step":
                float(held.sum() / len(held) / chips)}

    itemsize = np.dtype(cfg.dtype).itemsize
    linear, full = layer_kinds(config)
    facts = {
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "flops_per_pair": pair_flops(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    facts["kda_core_flops"], facts["kda_core_bytes"] = gdn_core_cost(
        per_chip, seq, cfg.linear_num_key_heads, cfg.linear_num_value_heads,
        cfg.linear_key_head_dim, cfg.linear_value_head_dim, RULE_CHUNK,
        linear, itemsize)
    if traffic["attn"] == "flash":
        facts["flash_flops"], facts["flash_bytes"] = flash_cost(
            config, per_chip, seq, full, itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
