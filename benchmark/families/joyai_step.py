"""Family of the fused step over JoyAI-LLM-Flash, one chip's share of an
expert-parallel group: ``ps.init`` -> ``KVStore`` (AdamW behind a global-norm
clip, warmed up) -> ``make_step(loss_fn, has_aux=True)`` -> ``shard_batch``,
the calls of ``families/kimi_step.py`` with the loss of
``ps_tpu/models/joyai.py``: latent attention with rotated keys of their own and
a compressed q on every layer, and a prediction module for the token after
next whose loss rides the step's one scalar. The router's selection bias goes
in as the step's extra argument and comes back in ``aux`` as a device value,
every step, with the step's counts and both cross entropies; no host read in
the window. The loss a run is followed by (``loss_at_n``) is ``aux["ce"]``,
the main head's cross entropy: comparable with the other decoders' and free of
the assumed weight of the second term; the loss the step differentiates and
the module's term are held to the reference at step 0, and ``correct`` also
needs the module's term at n below its value at step 0.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``, the traffic Kimi-Linear's cell runs); the
plain reference (``families/joyai_reference.py``); the limits of the step-0
checks with their measured reasons; and the functions that give operations
and bytes from shapes (``flash_cost``, ``dense_flops``, ``pair_flops``,
``step_flops``, ``param_count``). The warm-up and the sign rule are LFM2's
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
from benchmark.families import joyai_reference as reference
from benchmark.families.lfm2_step import bias_by_sign_rule, learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.families.nemotron_h_step import lengths_apart
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key
# at the top, not in build: a tree without the model fails here, at once
from ps_tpu.models import joyai

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the Pallas
# flash kernel at keys of 192 and values of 128 (six calls a step), the
# rotation by rolls on the lanes and the grouped matmuls over the held
# experts; the reference in f32 at "highest" with whole rows of the scores
# under an explicit causal mask, the rotation as a complex product of the
# pairs, and none of the kernels. The readings are in PERF.md section 6 (PR 54)
# and beside each limit below: "seen" the system against the reference over
# the seeds of my chip runs, "e4m3" the reference on weights rounded to an
# 8-bit float (the nearest precision below bfloat16) against the whole
# reference, "fault" the system with one fault planted
# (tools/joyai_grad_check.py).
#
#              ce        loss      mtp_ce    flips a layer  lengths apart
#  seen, worst 2.05e-4   1.63e-4   1.49e-4   649            .062
#  LIMIT       1e-3      1e-3      1e-3      1,966 (3%)     .2
#  e4m3        1.6e-5    4.6e-5    2.5e-4    5,940          .047
#         to   3.2e-3    2.2e-3    1.2e-3    9,218          .229
#  least fault 6.9e-3 (the loss, ``shared_head.norm`` left out; its mtp_ce 3.0e-2)
#
# (seen: thirteen seeds, 5400000111-114, 201-203 and 801-806; e4m3: the four
# seeds 111-114.) The loss terms move more than the older families' (7e-5 to
# 9e-5 there): under 0.02-normal weights and Zipf ids most tokens of a layer
# pick the same eight experts (the fullest holds 20 to 24 times the mean), so
# where an eighth and a ninth score lie within a bf16 rounding of each other
# hundreds of tokens flip together, each by a weight scaled 2.5. The 8-bit
# weights move a loss term no further than a seed's flips do at one seed in
# four, so the loss terms tell no precision apart: their limit lies midway, by
# ratio, between the worst seen and the least a planted fault moves, and e4m3
# is "not correct" by the counts and the cosines.
TOLERANCE = (1e-3,
             "the main head's cross entropy, bf16 compute with top-8 flips "
             "against an f32 reference whose attention is whole rows under an "
             "explicit causal mask: 3.6e-6 to 2.05e-4 seen over thirteen "
             "seeds; the reference on e4m3 weights moves 1.6e-5 to 3.2e-3 "
             "(tools/joyai_grad_check.py). Blunt (0.02-normal weights give "
             "every token nearly the entropy of the vocabulary), so "
             "after_step0 holds the loss and both its terms, the counts, the "
             "gradient, the clip, the apply and the bias")
#: the loss the step differentiates, the main head's term and the module's
#: against the reference's, relative. A missing ``shared_head.norm`` moves the
#: module's term 3.0e-2 and the loss 6.9e-3, the second term's weight dropped
#: the loss 0.53; e4m3's best seed passes all three (1.6e-5 to 2.5e-4) and
#: misses the counts and five cosines
LOSS_TOLERANCE = 1e-3
#: token-expert pairs, of T * top_k a layer, that may sit on another expert
#: than the reference's (top-8 flips between bf16 and f32 activations): half
#: the sum over the 256 experts of |count - reference count|, per layer, the
#: worst layer (the module's among them). A rotation left out moves 3,217 to
#: 4,950 a layer, one on halves 2,570 to 4,730, a module fed token i for
#: token i + 1 5,199 in the module's layer and none elsewhere
FLIP_SHARE = 0.03
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: **the embedding
#: and the head, the leaves a step reads twice** (each gradient is the sum of
#: the main stack's use and the module's: a second term with the wrong weight
#: or a missing ``shared_head.norm`` turns them), the module's ``eh_proj``
#: (both of its halves, and everything between the module's loss and its
#: join: a module fed the wrong token turns it), the leading layer's ``q_a``
#: (upstream of everything: through both heads, the module, five expert
#: layers with their recomputation, six kernel backward passes at 192 / 128,
#: the rotation's and the q latent's norm), **the 64 shared columns of a
#: ``kv_a``** (``#pe``: the rotated key channels every head reads, their
#: gradient the sum over the 32 heads, through the rotation), a router (the
#: sigmoid, the renormalisation over all eight picks, the scaling and the
#: absent experts' zero weights) and a held expert stack (the grouped
#: matmul's gradient over the window of rows).
#: Read from AdamW's first moment: no hook in the step.
#:
#:                  embed    head     eh_proj  q_a      kv_a#pe  router   stack
#:  seen, worst     .99994   .99995   .99995   .99981   .99985   .99543   .99183
#:  LIMIT           .997     .9985    .9985    .998     .998     .97      .95
#:  e4m3, best      .98841   .99209   .99117   .98196   .97826   .98822   .97603
#:  e4m3, worst     .98693   .99018   .98890   .97795   .96743   .87292   .75327
#:  no rotation     .99082   .99699   .99702   .77271   .71692   .90337   .91029
#:  on halves       .99321   .99770   .99768   .75201   .60106   .94088   .95361
#:  fed token i     .99967   .99952   .99029   .99921   .99985   .99738   .99830
#:  weight dropped  .89727   .91974   .99996   .90438   .91909   .97919   .91424
#:  no head norm    .99575   .99741   .98968   .99666   .99712   .99641   .99496
#:
#: (seen: the thirteen seeds above; the five faults: my chip run, PR 54,
#: tools/joyai_grad_check.py, seed 5400000111, the router and the stack there a
#: layer further up; seeds 112-114 read alike.) The router's and the stack's
#: gradients follow the seed's flips (a held expert that is many tokens'
#: eighth or ninth pick gains or loses hundreds of rows at once), so their
#: cosines range over 3e-4 to 8e-3 from 1 and their limits stand six times the
#: worst seen away: they are there for a grouped matmul or a routing weight
#: that is wrong, not to tell a precision apart. e4m3 is "not correct" at each
#: of its seeds by the counts and the embedding's, the head's, ``eh_proj``'s,
#: ``q_a``'s and the shared columns' cosines; a rotation left out or on halves
#: by ``q_a`` and the shared columns (and the counts); the module fed token
#: ``i`` by ``eh_proj`` and the module's counts; the weight dropped by the
#: loss and five cosines; the missing norm by the module's term, the loss and
#: ``eh_proj``.
GRAD_COSINE = {"embed/tokens": 0.997,
               "head/kernel": 0.9985,
               "mtp/eh_proj/kernel": 0.9985,
               "layer0/attn/q_a/kernel": 0.998,
               "layer1/attn/kv_a/kernel#pe": 0.998,
               "layer1/moe/router/kernel": 0.97,
               "layer2/moe/gate": 0.95}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them). Seen 0.004 to 0.062
#: (the router's length is the one that strays, with the seed's flips); e4m3
#: 0.047 to 0.229, so it tells no precision apart at every seed. It is there
#: for a leaf whose gradient is scaled: the second term's weight dropped reads
#: 1.17, picks not scaled by 2.5 move the router's and the stack's lengths by
#: that factor
GRAD_NORM_TOLERANCE = 0.2
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (lfm2_step.py says why the rounding is allowed for). Seen
#: 2.2e-7 to 3.7e-7 of the rate. Parameters kept in bf16 lose the whole of
#: step 0's update (5e-8 of a 0.02-normal weight is a thousandth of its bf16
#: ulp): 4,882 rates off (tools/joyai_grad_check.py, on the host)
APPLY_TOLERANCE = 1e-5

#: the steps n at which a run says its mean loss over n-7..n on stderr: the
#: values ISSUE 54 lets the traffic's ``loss_step`` take
LOSS_STEPS = (32, 48, 96)


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    three matrices, 3 x 2 x D x F."""
    return 3 * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def blocks_of(config):
    """``(latent-attention layers, expert layers)`` of a step: the main
    stack's and the module's."""
    layers = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    return layers, layers - config["first_k_dense_replace"]


def flash_cost(batch, heads, seq, qk_dim, v_dim, layers, itemsize=2):
    """``flash.cost`` of the causal kernel's three calls, **forward and
    backward**, a layer (the module's among ``layers``), every query head with
    K and V of its own, keys ``qk_dim`` wide and values ``v_dim``, the causal
    mask counted as half the square, without the diagonal's half: the count of
    ``kimi_step.flash_cost``, whose cell runs the same call."""
    return flash.cost(batch, heads, heads, seq, qk_dim, v_dim, layers,
                      seq * seq / 2, itemsize=itemsize)


def dense_flops(config, tokens, seq_len):
    """Operations of one training step outside the routed experts: forward
    and backward (3 x 2 a parameter a token) over the matmuls every token
    passes (each latent-attention layer's ``q_a``, ``q_b``, ``kv_a``,
    ``kv_b`` and out projection, the module's among them; the dense SwiGLU;
    the routers and the shared experts; the module's ``eh_proj``; **the
    untied head twice**, the module's pass counted at every position as the
    program runs it) and attention's quadratic term (QK^T at 192 and PV at
    128, forward and backward, halved for the causal mask). The rotation, the
    norms and the second embedding lookup are not counted, nor is
    recomputation."""
    d = config["hidden_size"]
    heads = config["num_attention_heads"]
    nope, pe, v_dim = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    latent_layers, expert_layers = blocks_of(config)
    mixer = 6.0 * (d * q_rank + q_rank * heads * (nope + pe)
                   + d * (kv_rank + pe) + kv_rank * heads * (nope + v_dim)
                   + heads * v_dim * d) \
        + 3 * heads * seq_len * (nope + pe + v_dim)
    per_token = latent_layers * mixer
    per_token += config["first_k_dense_replace"] * 6.0 * 3 * d \
        * config["intermediate_size"]
    per_token += expert_layers * 6.0 * (
        d * config["router_width"] + 3 * d * config["moe_intermediate_size"]
        * config["n_shared_experts"])
    modules = config["num_nextn_predict_layers"]
    per_token += modules * 6.0 * 2 * d * d
    per_token += (1 + modules) * 6.0 * d * config["vocab_size"]
    return float(tokens * per_token)


def step_flops(config, tokens, seq_len, live_pairs):
    """``dense_flops`` plus the pairs the step computed here."""
    return dense_flops(config, tokens, seq_len) \
        + live_pairs * pair_flops(config)


def param_count(config):
    """Parameters in the store, by the parts the configuration's file
    states: a latent-attention mixer, the leading dense layer, an expert
    layer, the prediction module, and the embedding with the untied head and
    the final norm."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, pe, v_dim = (config["qk_nope_head_dim"],
                       config["qk_rope_head_dim"], config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    mixer = (d * q_rank + q_rank + q_rank * heads * (nope + pe)
             + d * (kv_rank + pe) + kv_rank
             + kv_rank * heads * (nope + v_dim) + heads * v_dim * d)
    dense = mixer + 2 * d + 3 * d * config["intermediate_size"]
    one_expert = 3 * d * config["moe_intermediate_size"]
    expert = (mixer + 2 * d + d * config["router_width"]
              + config["n_shared_experts"] * one_expert
              + config["n_routed_experts"] * one_expert)
    modules = config["num_nextn_predict_layers"]
    module = 2 * d * d + 3 * d + expert
    ends = 2 * d * config["vocab_size"] + d
    dense_layers = config["first_k_dense_replace"]
    return {"a_latent_attention_mixer": mixer, "the_dense_layer": dense,
            "an_expert_layer": expert, "the_prediction_module": module,
            "embedding_head_final_norm": ends,
            "total": dense_layers * dense
            + (config["num_hidden_layers"] - dense_layers) * expert
            + modules * module + ends}


# -- what correct holds -------------------------------------------------------

def of_witness(name, read_leaf, config):
    """A witness's array: the leaf ``read_leaf(key)`` gives or, of
    ``<key>#pe``, the columns of a ``kv_a`` behind the latent's: the shared
    rotated key channels."""
    key, _, part = name.partition("#")
    leaf = np.asarray(read_leaf(key))
    return leaf[:, config["kv_lora_rank"]:] if part == "pe" else leaf


def readings(value, aux, grads, ref_value, ref_aux, ref_grads):
    """The numbers the limits are held against: a loss with its aux and its
    witness gradients (by ``GRAD_COSINE``'s names) beside the reference's."""
    def rel(a, b):
        return abs(float(a) - float(b)) / abs(float(b))

    counts = np.asarray(aux["expert_tokens"], np.int64)
    ref_counts = np.asarray(ref_aux["expert_tokens"], np.int64)
    scales = [np.linalg.norm(np.asarray(grads[k], np.float64))
              / np.linalg.norm(np.asarray(ref_grads[k], np.float64))
              for k in GRAD_COSINE]
    return {"loss_rel_diff": rel(value, ref_value),
            "ce_rel_diff": rel(aux["ce"], ref_aux["ce"]),
            "mtp_ce_rel_diff": rel(aux["mtp_ce"], ref_aux["mtp_ce"]),
            "pairs_on_another_expert_than_reference":
                (np.abs(counts - ref_counts).sum(axis=-1) // 2).tolist(),
            **{f"grad_cosine.{k}": cosine(grads[k], ref_grads[k])
               for k in GRAD_COSINE},
            "grad_norm_over_reference": [float(s) for s in scales],
            "lengths_apart": lengths_apart(scales)}


def fails(read, pairs):
    """The limits a set of ``readings`` misses, by name; ``pairs``: T *
    top_k, a layer."""
    out = [name for name in ("loss", "ce", "mtp_ce")
           if not read[f"{name}_rel_diff"] <= LOSS_TOLERANCE]
    if max(read["pairs_on_another_expert_than_reference"]) \
            > FLIP_SHARE * pairs:
        out.append("counts")
    out += [f"cosine.{k}" for k, least in GRAD_COSINE.items()
            if not read[f"grad_cosine.{k}"] >= least]      # or nan
    if not read["lengths_apart"] <= GRAD_NORM_TOLERANCE:
        out.append("lengths")
    return out


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs, rate):
    """What ``correct`` holds beyond step 0's loss. ``got`` / ``want``: the
    step's and the reference's aux. ``witnesses``: per name ``before`` and
    ``after`` (the parameter, or the columns of it, around step 0), ``mu``
    and ``nu`` (the store's moments after it) and ``reference_grad``.
    ``clipped_norm``: the global norm of the clipped gradient. ``pairs``:
    T * top_k, a layer. ``rate``: the bias rule's. Returns the loop's
    ``{"checks": .., "detail": ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)        # [L, 256]
    grads = {k: np.asarray(w["mu"], np.float64) / (1 - rule["b1"])
             for k, w in witnesses.items()}
    read = readings(got["loss"], got, grads, want["loss"], want,
                    {k: w["reference_grad"] for k, w in witnesses.items()})
    missed = fails(read, pairs)
    detail = {"pairs_routed_per_layer": counts.sum(axis=-1).tolist(),
              "pairs_held_per_layer": np.asarray(
                  got["held_tokens"], np.int64).sum(axis=-1).tolist(),
              "reference_pairs_held_per_layer": np.asarray(
                  want["held_tokens"], np.int64).sum(axis=-1).tolist(),
              "windows_per_layer": np.asarray(
                  got["expert_windows"]).tolist(),
              "mtp_positions": int(got["mtp_positions"]),
              "step0": {k: float(got[k]) for k in ("loss", "ce", "mtp_ce")},
              "reference_step0": {k: float(want[k])
                                  for k in ("loss", "ce", "mtp_ce")},
              "clipped_gradient_norm": clipped_norm, **read}
    for name, w in witnesses.items():
        after = np.asarray(w["after"], np.float32)
        off = np.abs(after.astype(np.float64) - adamw_first_step(
            w["before"], w["mu"], w["nu"], **rule))
        detail[f"apply_error_lr.{name}"] = float(np.max(np.maximum(
            off - 0.5 * np.spacing(np.abs(after)).astype(np.float64), 0.0))
            / rule["learning_rate"])
    detail["clip_scale"] = scale = float(np.mean(
        read["grad_norm_over_reference"]))
    clip = rule["clip_by_global_norm"]
    clipped_to_limit = abs(clipped_norm - clip) <= 1e-3 * clip
    bias = np.asarray(got["expert_bias"], np.float32)
    return {"checks": {
        "no_dropped_tokens": bool((counts.sum(axis=-1) == pairs).all())
        and int(got["dropped_tokens"]) == 0,
        "loss_and_its_terms_match_reference":
            not {"loss", "ce", "mtp_ce"} & set(missed),
        "expert_counts_match_reference": "counts" not in missed,
        "gradient_matches_reference": not [
            m for m in missed if m.startswith("cosine.") or m == "lengths"],
        "gradient_clipped_to_global_norm":
            clipped_norm <= clip * (1 + 1e-3) and (
                clipped_to_limit or abs(scale - 1) <= GRAD_NORM_TOLERANCE),
        "adamw_apply_matches_rule": all(
            detail[f"apply_error_lr.{name}"] <= APPLY_TOLERANCE
            for name in witnesses),
        # exactly the rule, on the step's own counts, the module's row too:
        # the bias is not the optimizer's and nothing rounds on the way
        "expert_bias_follows_sign_rule":
            bool(np.array_equal(bias, bias_by_sign_rule(counts, rate)))},
        "detail": detail}


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.parallel.sharding import replicated

    if config["model"] != "joyai":
        raise ValueError(f"joyai_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"joyai_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ctx = ps.init(backend="tpu")
    cfg = joyai.JoyaiConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step
    pairs = tokens * cfg.num_experts_per_tok     # a chip, a step, a layer
    n = int(traffic["loss_step"])

    opt = dict(config["optimizer"])
    rate, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
    store = ps.KVStore(optimizer=opt.pop("name"), placement="replicated",
                       **{**opt, "learning_rate": rate})
    # the weights are made on the device from the seed; the store keeps its
    # own buffers (it donates them every step), so the tree made here goes
    params = jax.block_until_ready(
        jax.jit(lambda k: joyai.init_params(k, cfg))(seed_key(seed)))
    t_weights = time.perf_counter()
    jax.block_until_ready(store.init(params))
    del params
    t_store = time.perf_counter()
    fused = store.make_step(joyai.make_loss_fn(cfg, attn=traffic["attn"]),
                            has_aux=True)
    batches = fresh_batches(per_chip * chips, seq, cfg.vocab_size,
                            traffic["ids"]["s"], seed)
    # the state that is not the optimizer's: one device value, handed from
    # each step to the next
    state = {"expert_bias": jax.device_put(joyai.init_expert_bias(cfg),
                                           replicated(ctx.mesh))}

    # device values, read at the end only
    losses, auxes = [], []
    first = {}

    def step(b):
        _, _, aux = fused(b, state["expert_bias"])
        if not losses:
            first["system"] = aux
        state["expert_bias"] = aux["expert_bias"]
        # what a run is followed by: the main head's cross entropy
        loss = aux["ce"]
        losses.append(loss)
        auxes.append({k: aux[k] for k in (
            "mtp_ce", "expert_tokens", "held_tokens", "expert_windows",
            "load_max_over_mean", "held_pair_share", "dropped_tokens",
            "live_pairs_per_step", "mtp_positions")})
        return loss

    leaves = sorted({k.partition("#")[0] for k in GRAD_COSINE})
    plain = jax.jit(lambda params, b, bias: reference.witness_grads(
        params, b, bias, config, leaves))

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            (_, aux), grads = plain(params, b, state["expert_bias"])
        first["reference"] = jax.device_get(aux)
        first["witnesses"] = {
            # the store donates its buffers to step 0: copies, on the host
            name: {"before": of_witness(name, store.pull, config),
                   "reference_grad": of_witness(name, grads.get, config)}
            for name in GRAD_COSINE}
        return float(aux["ce"])

    def after_step0():
        """More than step 0's loss: ``step0_checks`` on what the store
        holds once step 0 is done."""
        def moment(which):
            return lambda key: optax.tree_utils.tree_get(
                store.optimizer_state(key), which)

        for name, w in first["witnesses"].items():
            w.update(after=of_witness(name, store.pull, config),
                     mu=of_witness(name, moment("mu"), config),
                     nu=of_witness(name, moment("nu"), config))
        clipped_norm = float(jnp.sqrt(sum(
            jnp.vdot(m, m) for m in map(moment("mu"), store.keys())))
        ) / (1 - rule["b1"])
        # kept: ``counters`` adds the one check that needs the run's end
        first["more"] = step0_checks(
            jax.device_get(first["system"]), first["reference"],
            first["witnesses"], clipped_norm, rule, pairs * chips,
            cfg.bias_update_rate)
        del first["witnesses"]
        return first["more"]

    def counters():
        values = [float(x) for x in jax.device_get(losses)]
        seen = {k: np.asarray(jax.device_get([a[k] for a in auxes]),
                              np.float64) for k in auxes[0]}
        module = seen["mtp_ce"].tolist()
        print("joyai_step: mean ce of steps n-7..n " + json.dumps(
            {m: stats.loss_at_n(values, m) for m in LOSS_STEPS
             if m < len(values)}) + ", mean mtp_ce " + json.dumps(
            {m: stats.loss_at_n(module, m) for m in LOSS_STEPS
             if m < len(module)}) + f"; step 0: ce {values[0]:.5f}, mtp_ce "
            f"{module[0]:.5f}", file=sys.stderr)
        if "more" in first and len(module) > n:
            # the loop holds ``loss_at_n`` (the main head's) below step 0's;
            # the module's term is held here, into the same checks: the
            # loop reads them after this call
            at_n = stats.loss_at_n(module, n)
            first["more"]["checks"]["mtp_loss_fell"] = at_n < module[0]
            first["more"]["detail"]["mtp_ce_at_n"] = at_n
        joyai.observe_losses(values[-1], module[-1])
        counts, held = seen["expert_tokens"], seen["held_tokens"]
        fullest = float(seen["load_max_over_mean"].mean())
        print("joyai_step: held share of the pairs, by layer (the module's "
              "last) " + json.dumps((held.sum(axis=(0, 2))
                                     / counts.sum(axis=(0, 2))).round(
                                         5).tolist())
              + f", over the run {held.sum() / counts.sum():.5f}; fullest "
              f"expert over the mean {fullest:.3f}; most windows a layer ran "
              f"{int(seen['expert_windows'].max())}; final expert_bias range "
              f"{float(jnp.min(state['expert_bias'])):+.4f} .. "
              f"{float(jnp.max(state['expert_bias'])):+.4f}",
              file=sys.stderr)
        return {"dropped_tokens": float(seen["dropped_tokens"].sum()),
                "load_max_over_mean": fullest,
                "held_pair_share": float(held.sum() / counts.sum()),
                # all expert layers of one chip, a step
                "live_pairs_per_step":
                float(seen["live_pairs_per_step"].mean() / chips),
                "ce": values[-1], "mtp_ce": module[-1],
                "mtp_positions": float(seen["mtp_positions"].mean())}

    itemsize = np.dtype(cfg.dtype).itemsize
    latent_layers, _ = blocks_of(config)
    facts = {
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "flops_per_pair": pair_flops(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        "parameters": param_count(config),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    if traffic["attn"] == "flash":
        facts["flash_flops"], facts["flash_bytes"] = flash_cost(
            per_chip, cfg.num_attention_heads, seq,
            cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim,
            latent_layers, itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
