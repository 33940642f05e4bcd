"""Family of the fused step over SDAR, one chip's share of an
expert-parallel group, trained by block diffusion: ``ps.init`` -> ``KVStore``
(AdamW behind a global-norm clip, warmed up) -> ``make_step(loss_fn,
has_aux=True)`` -> ``shard_batch``, the calls of ``families/trinity_step.py``
with the loss of ``ps_tpu/models/sdar.py``. A sample is a sequence; the step
runs two copies of it, the clean one and the noised one. ``aux`` comes back
as device values, every step; no host read in the window. The loss a run is
followed by (``loss_at_n``) is ``aux["masked_ce"]``, the masked positions'
plain cross entropy; the loss the step differentiates is held to the
reference at step 0 (``TOLERANCE`` below says why).

The yardstick's own pieces live here and beside this file: **the noising as
data made from ``--seed`` on the host** (``noised_batches``: Zipf ids, one
noise level a block, the mask id where a token is masked, ``1 / t_b`` as its
weight), so that program and reference get the same draw; the plain reference
(``families/sdar_reference.py``); the limits of the step-0 checks with their
measured reasons; and the functions that give operations and bytes from
shapes (``flash_cost``, ``dense_flops``, ``pair_flops``, ``step_flops``,
``param_count``). The warm-up is LFM2's (``lfm2_step.learning_rate``).
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
from benchmark.families import sdar_reference as reference
from benchmark.families.lfm2_step import learning_rate
from benchmark.families.moe_step import (DRAWN_AT_A_TIME, adamw_first_step,
                                         cosine, zipf_entropy,
                                         zipf_probabilities)
from benchmark.families.nemotron_h_step import lengths_apart
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key
# at the top, not in build: a tree without the model fails here, at once
from ps_tpu.models import sdar

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the Pallas
# flash kernels under the edge a block wide (32 query heads on 4 K/V heads,
# the noised queries' call strict and merged with their own block by the
# logsumexps) and the grouped matmuls over the held experts; the reference in
# f32 at "highest" on the doubled sequence under one explicit [2 L, 2 L] mask
# and none of the kernels. The readings are in PERF.md section 6 (PR 50) and
# beside each limit below: "seen" the system against the reference over the
# seeds of my chip runs, "e4m3" the reference on weights rounded to an 8-bit
# float (the nearest precision below bfloat16) against the whole reference,
# "fault" the system with the edge moved (tools/sdar_grad_check.py).
#: What a run of the cell is followed by (``Cell.step``'s loss, so the
#: harness's ``loss_at_n`` and its step-0 comparison): **the cross entropy at
#: the masked positions as a plain mean** (``aux["masked_ce"]``), not the loss
#: the step differentiates. The loss weighs a masked position ``1 / t_b`` and
#: divides by ``L``: the draw of the weights alone (a position is masked with
#: probability ``t`` and then weighs ``1 / t``: variance ``ln(1 / t_min) - 1``
#: = 5.9 a position) moves it by 2.7% of itself from step to step at 8,192
#: positions, 0.95% in an eight-step mean, whatever the step: over six seeds
#: its eight-step mean spread 1.3 / 2.4 / 0.73 / 1.8 / 1.3% at n = 48 / 64 /
#: 96 / 128 / 160 (quartile distance over the median) where half the bound is
#: 0.75%, and 0.86% and 1.4% at 96 in two sets before them; the same runs'
#: plain mean spread 1.1 / 0.83 / 0.63 / 0.62 / 0.29% (my chip runs, PR 50,
#: seeds 5000000401-406). The loss itself and its two terms are held to the
#: reference at step 0 (``LOSS_TOLERANCE``, ``BALANCE_TOLERANCE``).
#: This first limit was 1.8e-4 until PR 67: 2.1x the largest of nineteen
#: seeds (8.7e-5). The twentieth (825088846, which the driver's check of PR 63
#: drew) read 2.249e-4 on the accepted program, three times and to the digit,
#: every other check true (2.183e-4 on PR 67's tree, behind PR 64's
#: rotation): the same long tail as the weighted loss's below (2.03e-4 at
#: that seed). That reading lies over e4m3's best seed (2.2e-4),
#: so **this number has no upper reading and tells no precision apart**; it is
#: ``LOSS_TOLERANCE``, 1.33x the largest of twenty, and holds what a gross
#: fault moves. What refuses the reference on e4m3 weights, at each of its six
#: seeds (tools/sdar_grad_check.py, seeds 5000000111-116), are the pairs on
#: another expert than the reference's (12,541 to 20,549 in the worst layer
#: against the system's worst 1,589 and ``FLIP_SHARE``'s 5,242) and the q, k,
#: stack and row cosines below. Again in PR 67, three more seeds (825088846,
#: 6700000131 / 132): the control's plain mean read 1.51e-3, **7.8e-6** and
#: 3.63e-4 where the system's read 2.183e-4, 4.4e-5 and 3.9e-5, and it was
#: refused at each by the counts (9,352 / 12,527 / 25,103) and four to six of
#: the six cosines.
TOLERANCE = (3e-4,
             "the masked positions' plain cross entropy, bf16 compute with "
             "top-8 flips against an f32 reference whose attention is an "
             "explicit [2L, 2L] mask under a softmax: 6e-7 to 8.7e-5 at "
             "nineteen seeds and 2.249e-4 at the twentieth (825088846), the "
             "limit 1.33x the largest; the reference on e4m3 weights moves "
             "2.2e-4 to 1.78e-3 at six, so this number refuses it at some "
             "seeds only and the counts and cosines at all six; the edge "
             "one position off 3.7e-4 (tools/sdar_grad_check.py, seeds "
             "5000000111-116). Blunt (0.02-normal weights give every token "
             "nearly the entropy of the vocabulary), so after_step0 holds "
             "the loss and its two terms, the counts, the gradient, the "
             "clip and the apply")
#: the loss the step differentiates and its cross-entropy term against the
#: reference's, relative. A sum weighted 1 / t_b, so a few positions at a
#: small t carry much of it and a seed's reading has a long tail: 7e-6 to
#: 1.14e-4 over 27 seeds, four of them between 9.2e-5 and 1.14e-4; the limit
#: is 2.6x the largest. The reference on e4m3 weights moves 2.33e-4, 4.18e-4
#: and 1.0e-3 to 1.7e-3 at six seeds: its best seed passes this check and
#: misses the masked positions' plain mean, the counts and four cosines
LOSS_TOLERANCE = 3e-4
#: the load-balancing term against the reference's, relative: a sum over
#: 128 experts of f32 means, moved only by the pairs that flip, and by all
#: copies of a token at once where they flip together. Seen: 1.3e-4 to
#: 1.21e-3 over 27 seeds; e4m3: 6.3e-4 to 1.05e-2; the edge one position off
#: 2.7e-3. As the lengths below it tells no precision apart (e4m3's best seed
#: reads what the system's worst does): it holds the term's mathematics, a
#: coefficient or a share left out
BALANCE_TOLERANCE = 4e-3
#: token-expert pairs, of T * top_k a layer, that may sit on another expert
#: than the reference's (top-8 flips between bf16 and f32 activations): half
#: the sum over the 128 experts of |count - reference count|, per layer, the
#: worst layer. A quarter of a step's tokens are one token, the mask id:
#: where its eighth and ninth experts lie within a rounding of each other
#: its copies flip together, so a layer's count jumps (1,006 and 1,337 of
#: 131,072 in two layers of one seed where the others read 163 to 884) and
#: 4,096 copies moving one pair each would be 3.1%. Seen, the worst layer of
#: a seed: 514 to 1,589 (1.21%) over 27 seeds; e4m3: 12,541 to 20,549; the
#: edge one position off 6,490
FLIP_SHARE = 0.04
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: the first
#: layer's q projection (both calls' dq and the own-block term's), **a k
#: projection (its gradient is the sum over both streams' queries: the clean
#: call's dk, the strict call's dk and the own-block term's)**, a router (the
#: softmax over 128, the renormalisation over the picks, the balancing term),
#: a held expert stack (the grouped matmul's gradient over the window of
#: rows), and two rows of the embedding, upstream of everything: **the mask
#: id's** (every masked position of the noised copy and nothing of the clean
#: one) and the batch's most frequent id's (both copies).
#: Read from AdamW's first moment: no hook in the step.
#:
#:                q        k        router   stack    mask row  hot row
#:  seen, worst   .99980   .99985   .99862   .99850   .99893    .99998
#:  LIMIT         .996     .995     .99      .99      .996      .998
#:  e4m3, best    .99187   .98744   .99574   .98858   .99396    .99572
#:  one off       .97133   .95208   .99582   .99870   .99893    .99966
#:  no own block  .99977   .99964   .99119   .99994   .98683    .99998
#:  not strict    .99983   .99980   .99989   .99995   .99884    .99998
#:
#: ("one off": every row sees the first key past its edge; "no own block": the
#: merge left out; "not strict": the noised queries see their own block's clean
#: keys. My chip runs, PR 50, tools/sdar_grad_check.py, seeds 5000000111-116,
#: and the cell's own runs.) e4m3 is "not correct" at each of its six seeds by
#: the masked positions' plain mean, the counts, q, k, the stack and both rows;
#: the edge one position off by that mean, the counts, q and k; the missing own
#: block by the mask row. **The edge not strict is told apart by nothing
#: here**: the clean keys it leaks are, at the unmasked positions, the noised
#: keys the query sees anyway, and the mask row moves to .99884 where a seed of
#: the system itself read .99893: tests/test_sdar.py holds the strict edge
#: exactly, against the explicit mask in f32 (PERF.md section 7).
GRAD_COSINE = {"layer0/attn/q/kernel": 0.996,
               "layer1/attn/k/kernel": 0.995,
               "layer2/moe/router/kernel": 0.99,
               "layer1/moe/gate": 0.99,
               "embed/tokens#mask": 0.996,
               "embed/tokens#hot": 0.998}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them). Seen: 0.0007 to
#: 0.024; the faults 0.009 to 0.024; e4m3 0.042 to 0.159: as in the older
#: families it tells no precision apart, and is there for a leaf whose
#: gradient is scaled
GRAD_NORM_TOLERANCE = 0.15
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself (half an ulp of the result), in units of step 0's
#: learning rate (lfm2_step.py says why the rounding is allowed for)
APPLY_TOLERANCE = 1e-5

#: the steps n at which a run says its mean loss over n-7..n on stderr: the
#: values ISSUE 50 lets the traffic's ``loss_step`` take
LOSS_STEPS = (96, 128, 160)


# -- the noising: data, from the seed -----------------------------------------

def noised_batches(batch, seq_len, config, ids, seed):
    """Batch after batch of one block-diffusion draw each, for ever, none of
    them twice (the traffic's ``"pool": "fresh"``): ``ids`` [B, L] i.i.d.
    Zipf(``ids["s"]``) over one seeded permutation of the drawable ids (all
    but ``mask_token_id``, which is never drawn as a token); one noise level
    ``t_b`` a block of ``block_length``, uniform on ``noise["t_min"]`` ..
    ``noise["t_max"]``; a token masked with probability ``t_b``;
    ``noised_ids`` the ids with the mask id at the masked positions;
    ``weights`` f32, ``1 / t_b`` at a masked position and 0 elsewhere.
    Everything comes from the seed's one generator: the same seed gives the
    same stream. Runs in the producer thread of ``device_prefetch``, under
    its ``input.produce`` span; each batch's masked share goes to the
    program's gauge (``sdar.observe_masked_share``)."""
    vocab, mask_id = config["vocab_size"], config["mask_token_id"]
    block, noise = config["block_length"], config["noise"]
    if noise["kind"] != "uniform_per_block":
        raise ValueError(f"unknown noise schedule {noise['kind']!r}")
    rng = np.random.default_rng(seed)
    p = zipf_probabilities(vocab - 1, ids["s"])
    id_of_rank = rng.permutation(
        np.delete(np.arange(vocab, dtype=np.int32), mask_id))
    while True:
        shape = (DRAWN_AT_A_TIME, batch, seq_len)
        drawn = id_of_rank[rng.choice(vocab - 1, size=shape, p=p)]
        level = np.repeat(rng.uniform(
            noise["t_min"], noise["t_max"],
            size=shape[:2] + (seq_len // block,)), block, axis=-1)
        masked = rng.uniform(size=shape) < level
        for x, t, m in zip(drawn, level, masked):
            weights = np.where(m, 1.0 / t, 0.0).astype(np.float32)
            sdar.observe_masked_share(weights)
            yield {"ids": x, "noised_ids": np.where(m, mask_id, x).astype(
                np.int32), "weights": weights}


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    three matrices, 3 x 2 x D x F."""
    return 3 * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def seen_pairs(seq, block):
    """Query-key pairs one head of one sequence attends over in the
    kernels, both copies: a clean row sees the keys to the end of its block,
    ``L (L + B) / 2`` in all, a noised row the clean keys before its block,
    ``L (L - B) / 2``: ``L ** 2`` together. The noised rows' own blocks,
    ``L B`` pairs more, are no kernel's."""
    return seq * (seq + block) // 2 + seq * (seq - block) // 2


def flash_cost(sequences, heads, kv_heads, seq, dim, layers, block,
               itemsize=2):
    """``flash.cost`` of the kernel's calls, **forward and backward**: two
    calls a layer and sequence, the clean queries' and the noised queries',
    both over the clean K/V, counted as a batch of two at half the pairs
    each (nine matmuls over the pairs in all)."""
    return flash.cost(2 * sequences, heads, kv_heads, seq, dim, dim, layers,
                      seen_pairs(seq, block) // 2, itemsize=itemsize)


def dense_flops(config, sequences, seq_len):
    """Operations of one training step outside the routed experts, **as the
    program runs it**: forward and backward (3 x 2 a parameter a token) over
    the matmuls that both copies' tokens pass in every layer (attention's
    four projections, the router), the head over the noised copy's positions
    alone, and attention's quadratic term over what the mask lets each row
    see (QK^T and PV, forward and backward: 3 x 2 matmuls x 2 x pairs x
    head_dim a head), the own blocks' ``L B`` pairs with it. The clean
    copy's last layer is counted whole though only its keys and values feed
    the loss: the program runs it (PERF.md section 7). The rotation and the
    norms are not counted, nor is recomputation."""
    d = config["hidden_size"]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"],
                            config["head_dim"])
    layers, block = config["num_hidden_layers"], config["block_length"]
    per_token = layers * 6.0 * d * (dim * (2 * heads + 2 * kv_heads)
                                    + config["router_width"])
    cores = layers * 3 * 4.0 * heads * dim * (
        seen_pairs(seq_len, block) + seq_len * block)
    head = 6.0 * d * config["vocab_size"] * seq_len
    return float(sequences * (2 * seq_len * per_token + cores + head))


def step_flops(config, sequences, seq_len, live_pairs):
    """``dense_flops`` plus the pairs the step computed here."""
    return dense_flops(config, sequences, seq_len) \
        + live_pairs * pair_flops(config)


def param_count(config):
    """Parameters in the store, by the parts the configuration's file
    states: a layer beside its experts, its held experts, and the embedding
    with the untied head and the final norm."""
    d, dim = config["hidden_size"], config["head_dim"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    beside = (d * dim * (2 * heads + 2 * kv_heads) + 2 * dim + 2 * d
              + d * config["router_width"])
    held = config["num_experts"] * 3 * d * config["moe_intermediate_size"]
    ends = 2 * d * config["vocab_size"] + d
    return {"a_layer_beside_its_experts": beside,
            "a_layers_held_experts": held, "embedding_head_final_norm": ends,
            "total": config["num_hidden_layers"] * (beside + held) + ends}


# -- what correct holds -------------------------------------------------------

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
    return {"masked_ce_rel_diff": rel(aux["masked_ce"],
                                      ref_aux["masked_ce"]),
            "loss_rel_diff": rel(value, ref_value),
            "ce_rel_diff": rel(aux["ce"], ref_aux["ce"]),
            "load_balance_rel_diff": rel(aux["load_balance"],
                                         ref_aux["load_balance"]),
            "pairs_on_another_expert_than_reference":
                (np.abs(counts - ref_counts).sum(axis=-1) // 2).tolist(),
            **{f"grad_cosine.{k}": cosine(grads[k], ref_grads[k])
               for k in GRAD_COSINE},
            "grad_norm_over_reference": [float(s) for s in scales],
            "lengths_apart": lengths_apart(scales)}


def fails(read, pairs):
    """The limits a set of ``readings`` misses, by name; ``pairs``: T *
    top_k, a layer."""
    out = []
    if read["masked_ce_rel_diff"] > TOLERANCE[0]:
        out.append("masked_ce")
    if read["loss_rel_diff"] > LOSS_TOLERANCE:
        out.append("loss")
    if read["ce_rel_diff"] > LOSS_TOLERANCE:
        out.append("ce")
    if not read["load_balance_rel_diff"] <= BALANCE_TOLERANCE:
        out.append("load_balance")
    if max(read["pairs_on_another_expert_than_reference"]) \
            > FLIP_SHARE * pairs:
        out.append("counts")
    out += [f"cosine.{k}" for k, least in GRAD_COSINE.items()
            if not read[f"grad_cosine.{k}"] >= least]      # or nan
    if not read["lengths_apart"] <= GRAD_NORM_TOLERANCE:
        out.append("lengths")
    return out


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs):
    """What ``correct`` holds beyond step 0's loss. ``got`` / ``want``: the
    step's and the reference's aux. ``witnesses``: per name ``before`` and
    ``after`` (the parameter, or the row of it, around step 0), ``mu`` and
    ``nu`` (the store's moments after it) and ``reference_grad``.
    ``clipped_norm``: the global norm of the clipped gradient. ``pairs``:
    T * top_k, a layer. Returns the loop's ``{"checks": .., "detail":
    ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)        # [L, 128]
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
              "masked_positions": int(got["masked_positions"]),
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
    return {"checks": {
        "no_dropped_tokens": bool((counts.sum(axis=-1) == pairs).all())
        and int(got["dropped_tokens"]) == 0,
        "loss_and_its_terms_match_reference":
            not {"loss", "ce", "load_balance"} & set(missed),
        "expert_counts_match_reference": "counts" not in missed,
        "gradient_matches_reference": not [
            m for m in missed if m.startswith("cosine.") or m == "lengths"],
        "gradient_clipped_to_global_norm":
            clipped_norm <= clip * (1 + 1e-3) and (
                clipped_to_limit or abs(scale - 1) <= GRAD_NORM_TOLERANCE),
        "adamw_apply_matches_rule": all(
            detail[f"apply_error_lr.{name}"] <= APPLY_TOLERANCE
            for name in witnesses)},
        "detail": detail}


def witness_rows(batch, config):
    """The rows of the embedding that witness its gradient: the mask id's
    and the batch's most frequent id's."""
    return {"mask": int(config["mask_token_id"]),
            "hot": int(np.bincount(np.asarray(batch["ids"]).ravel()).argmax())}


def of_witness(name, read_leaf, rows):
    """A witness's array: the leaf ``read_leaf(key)`` gives, or the row of
    it that ``name``'s ``#`` part names."""
    key, _, row = name.partition("#")
    leaf = np.asarray(read_leaf(key))
    return leaf[rows[row]] if row else leaf


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch

    if config["model"] != "sdar":
        raise ValueError(f"sdar_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"sdar_step re-uses no batch: pool {traffic['pool']!r}")
    if traffic["block_length"] != config["block_length"]:
        raise ValueError(
            f"the traffic is cut in blocks of {traffic['block_length']}, the "
            f"configuration in blocks of {config['block_length']}")
    t_start = time.perf_counter()
    ps.init(backend="tpu")
    cfg = sdar.SdarConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    seq = int(traffic["seq_len"])
    tokens = 2 * per_chip * seq                  # a chip, a step: both copies
    pairs = tokens * cfg.num_experts_per_tok     # a chip, a step, a layer

    opt = dict(config["optimizer"])
    rate, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
    store = ps.KVStore(optimizer=opt.pop("name"), placement="replicated",
                       **{**opt, "learning_rate": rate})
    # the weights are made on the device from the seed; the store keeps its
    # own buffers (it donates them every step), so the tree made here goes
    params = jax.block_until_ready(
        jax.jit(lambda k: sdar.init_params(k, cfg))(seed_key(seed)))
    t_weights = time.perf_counter()
    jax.block_until_ready(store.init(params))
    del params
    t_store = time.perf_counter()
    fused = store.make_step(sdar.make_loss_fn(cfg, attn=traffic["attn"]),
                            has_aux=True)
    batches = noised_batches(per_chip * chips, seq, config, traffic["ids"],
                             seed)

    # device values, read at the end only
    losses, auxes = [], []
    first = {}

    def step(b):
        _, _, aux = fused(b)
        # what a run is followed by: the masked positions' plain cross
        # entropy (LOSS_AT_N above); the loss itself is held at step 0
        loss = aux["masked_ce"]
        auxes.append({k: aux[k] for k in (
            "expert_tokens", "held_tokens", "expert_windows",
            "load_max_over_mean", "held_pair_share", "dropped_tokens",
            "masked_positions")})
        if not losses:
            first["system"] = aux
        losses.append(loss)
        return loss

    plain = jax.jit(lambda params, b: reference.witness_grads(
        params, b, config, sorted({k.partition("#")[0]
                                   for k in GRAD_COSINE})))

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = plain(params, b)
        first["reference"] = jax.device_get(aux)
        first["rows"] = rows = witness_rows(jax.device_get(b), config)
        first["witnesses"] = {
            # the store donates its buffers to step 0: copies, on the host
            name: {"before": of_witness(name, store.pull, rows),
                   "reference_grad": of_witness(name, grads.get, rows)}
            for name in GRAD_COSINE}
        return float(aux["masked_ce"])

    def after_step0():
        """More than step 0's loss: ``step0_checks`` on what the store
        holds once step 0 is done."""
        def moment(which):
            return lambda key: optax.tree_utils.tree_get(
                store.optimizer_state(key), which)

        for name, w in first["witnesses"].items():
            w.update(after=of_witness(name, store.pull, first["rows"]),
                     mu=of_witness(name, moment("mu"), first["rows"]),
                     nu=of_witness(name, moment("nu"), first["rows"]))
        clipped_norm = float(jnp.sqrt(sum(
            jnp.vdot(m, m) for m in map(moment("mu"), store.keys())))
        ) / (1 - rule["b1"])
        return step0_checks(jax.device_get(first["system"]),
                            first["reference"], first["witnesses"],
                            clipped_norm, rule, pairs * chips)

    def counters():
        values = [float(x) for x in jax.device_get(losses)]
        print("sdar_step: mean loss of steps n-7..n " + json.dumps(
            {n: stats.loss_at_n(values, n) for n in LOSS_STEPS
             if n < len(values)}), file=sys.stderr)
        seen = {k: np.asarray(jax.device_get([a[k] for a in auxes]),
                              np.float64) for k in auxes[0]}
        counts, held = seen["expert_tokens"], seen["held_tokens"]
        fullest = float(seen["load_max_over_mean"].mean())
        masked = float(seen["masked_positions"].mean()
                       / (per_chip * chips * seq))
        print("sdar_step: held share of the pairs, by layer "
              + json.dumps((held.sum(axis=(0, 2))
                            / counts.sum(axis=(0, 2))).round(5).tolist())
              + f", over the run {held.sum() / counts.sum():.5f}; fullest "
              f"expert over the mean {fullest:.3f}; most windows a layer ran "
              f"{int(seen['expert_windows'].max())}; masked share of the "
              f"positions {masked:.5f}", file=sys.stderr)
        return {"dropped_tokens": float(seen["dropped_tokens"].sum()),
                "load_max_over_mean": fullest,
                "held_pair_share": float(held.sum() / counts.sum()),
                # all layers of one chip, a step
                "live_pairs_per_step":
                float(held.sum() / len(held) / chips),
                "masked_share": masked}

    itemsize = np.dtype(cfg.dtype).itemsize
    facts = {
        "dense_flops_per_step": dense_flops(config, per_chip, seq),
        "flops_per_pair": pair_flops(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size - 1,
                                             traffic["ids"]["s"]),
        "parameters": param_count(config),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    if traffic["attn"] == "flash":
        facts["flash_flops"], facts["flash_bytes"] = flash_cost(
            per_chip, cfg.num_attention_heads, cfg.num_key_value_heads, seq,
            cfg.head_dim, cfg.num_hidden_layers, cfg.block_length, itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
