"""Family of the fused step over an expert model: ``ps.init`` -> ``KVStore``
(AdamW behind a global-norm clip) -> ``make_step(loss_fn, has_aux=True)`` ->
``shard_batch``, the calls of ``families/dense_step.py`` with OLMoE's loss
(``ps_tpu/models/olmoe.py``), whose auxiliary outputs (the three loss terms
and the router's per-expert token counts) leave the step as device values.

The yardstick's own pieces live here and beside this file: the stream of
Zipf ids, which under the traffic's ``"pool": "fresh"`` never hands out a
batch twice (``fresh_batches``); the plain reference, the benchmark's own
copy (``families/olmoe_reference.py``, letter for letter the tests'
``tests/olmoe_reference.py``); the limits of the step-0 checks with their
measured reasons; and the functions that give operations and bytes from
shapes.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark.families import olmoe_reference as reference
from benchmark.families import flash
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the Pallas
# flash kernel and the grouped matmuls; the reference in f32 at "highest" with
# neither. All readings: chip runs of PR 27 (refused for its traffic; the
# program is unchanged since), TPU v5 lite, published widths; "seen" is the
# system against the reference over 28 seeds (the gradients' cosines over
# 13); PR 28's fourteen seeds lie inside every range but the flips (84 at
# one). Each limit is about twice the largest seen. What each
# tells apart, below it: the reference with one piece out against the whole
# reference on 7 seeds (tools/olmoe_grad_check.py), "e4m3" being the weights
# rounded to an 8-bit float, a lower bound of computing in one.
#
#                 loss     ce       load_bal. z_loss   cos q       cos router
#  seen, at most  7.1e-5   7.3e-5   1.33e-3   4.7e-4   >= .999377  >= .999384
#  LIMIT          1.5e-4   1.5e-4   3e-3      1e-3     .9985       .9985
#  renormalised   1.2e-3.. 1.2e-3.. 0         0        .865-.939   .919-.995
#  no QK-norm     1.7e-4.. 1.9e-5.. 7.2e-2..  2e-6..   .975-.982   .983-.998
#  no load_bal.   1.9e-3.. 0        0         0        .999-1      .911-.986
#  no z_loss      1.7e-3.. 0        0         0        1           .990-.999
#  e4m3           4.0e-5.. 3.1e-5.. 1.1e-3..  2.6e-4.. .988-.990   .973-.996
#
# Every knock-out is "not correct" at every seed by at least one limit: the
# q projection's cosine for e4m3 (7 to 8 limits away), a missing QK-norm (12
# to 17; also load_balance, 24 limits) and a renormalised top-8; the loss for
# a dropped term (11 to 17 limits). The loss alone is a blunt witness: at one
# seed of nine e4m3 moved it 4.0e-5 and a missing QK-norm 1.1e-4, inside its
# limit (0.02-normal weights give every token nearly the entropy of the
# vocabulary whatever the block computes).
#
# The loss (11.2 to 11.4 nats at step 0) differs by 6.8e-7 to 7.1e-5, of
# either sign: more than the dense cells' 4e-5 at worst because under Zipf ids
# the causal attention's output is nearly the same vector for every token (the
# frequent ids dominate every prefix), so roundings that would average out
# over 8,192 tokens are correlated, and because 84 to 216 of the 65,536
# token-expert pairs flip: where a token's 8th and 9th router probabilities
# are nearer than the bf16 rounding of the activations the router reads, the
# two sides send it to different experts, and the loss moves by the difference
# of two near-equal weights times an expert's output.
TOLERANCE = (1.5e-4,
             "bf16 compute with top-8 flips against an f32 reference: 2.1x "
             "the largest of 28 seeds (7.1e-5); blunt (e4m3 weights move the "
             "reference 4.0e-5 to 7.2e-4), so after_step0 holds the terms, "
             "the gradient, the clip and the apply")
#: each loss term against the reference's, relative (the two router terms are
#: sums over 64 experts of shares that a flipped pair moves whole)
TERM_TOLERANCE = {"ce": 1.5e-4, "load_balance": 3e-3, "z_loss": 1e-3}
#: token-expert pairs, of T * top_k, that may sit on another expert than the
#: reference's (top-8 flips between bf16 and f32 activations): counted as
#: half the sum over experts of |count - reference count|. Seen: 84 to 216
#: of 65,536 (0.13 to 0.33%); a router fed by an 8-bit float, or one
#: renormalising before top-k, is not what this catches (the other limits
#: do): it catches a step that routes by something else than the router.
FLIP_SHARE = 0.01
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes. The q projection
#: sits upstream of everything hand-written: its gradient comes back through
#: the head, the experts' combine, grouped matmuls and dispatch (ops/moe.py's
#: custom_vjp rules), the residual, the out projection and the flash kernel's
#: backward; of all 15 tensors it is the one the system is furthest off on
#: (at six seeds of seven; at the seventh the router is).
#: The router's gradient is where the two auxiliary losses and the top-8
#: weights act directly. The system's gradient is read from the store: after
#: AdamW's first step its first moment is (1 - b1) * clip_scale * gradient,
#: so no hook in the step is needed.
GRAD_COSINE = {"layer0/attn/q/kernel": 0.9985,
               "layer0/moe/router/kernel": 0.9985}
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them); seen: q 0.9994 to
#: 1.0005, router 0.9929 to 1.0036 before the scale, 0.38% apart at most
GRAD_NORM_TOLERANCE = 0.01
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments, largest distance in units of the learning rate. Seen
#: 9.4e-6 to 9.7e-6 (f32 rounding of the parameter); leaving out the weight
#: decay moves a 0.02-normal tensor's largest entries by 1e-2.
APPLY_TOLERANCE = 1e-4


#: the steps n at which a run says its mean loss over n-7..n on stderr: the
#: values ISSUE 28 lets the traffic's ``loss_step`` take
LOSS_STEPS = (48, 96, 128)
#: how many batches ``fresh_batches`` draws from its generator at a time: the
#: family's own business (14 ms on the host every 16 steps at the cell's
#: size, PR 27's chip runs), no part of the traffic
DRAWN_AT_A_TIME = 16


def zipf_probabilities(vocab_size, s):
    """``p(rank r) ~ r^-s`` over ``vocab_size`` ranks, f64."""
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(s)
    return p / p.sum()


def fresh_batches(batch, seq_len, vocab_size, s, seed):
    """Batch after batch of next-token pairs, for ever, none of them twice
    (the traffic's ``"pool": "fresh"``): ids drawn i.i.d. Zipf(``s``) over one
    seeded permutation of the vocabulary, so that a few ids dominate as in
    text, pre-shifted into ``inputs`` and ``targets`` [B, S]. Everything
    comes from the seed's one generator: the same seed gives the same
    stream."""
    rng = np.random.default_rng(seed)
    p = zipf_probabilities(vocab_size, s)
    id_of_rank = rng.permutation(vocab_size).astype(np.int32)
    while True:
        ids = id_of_rank[rng.choice(
            vocab_size, size=(DRAWN_AT_A_TIME, batch, seq_len + 1), p=p)]
        for drawn in ids:
            yield {"inputs": drawn[:, :-1], "targets": drawn[:, 1:]}


def zipf_entropy(vocab_size, s):
    """Nats of the unigram distribution: what ``loss_at_n`` can fall to."""
    p = zipf_probabilities(vocab_size, s)
    return float(-(p * np.log(p)).sum())


# -- operations and bytes from shapes -----------------------------------------

def step_flops(config, tokens, seq_len):
    """Operations of one training step that the model requires: forward and
    backward (3 x 2 a parameter a token) over the matmuls of the parameters
    a token touches (attention's four projections, the router, top_k experts
    of three matrices, the head), plus attention's quadratic term (QK^T and
    PV, forward and backward) halved for the causal mask. Recomputation is
    not counted."""
    d, f = config["hidden_size"], config["intermediate_size"]
    per_layer = (4 * d * d + d * config["num_experts"]
                 + config["num_experts_per_tok"] * 3 * d * f)
    quadratic = 3 * 4 * seq_len * d / 2
    per_token = (config["num_hidden_layers"] * (6 * per_layer + quadratic)
                 + 6 * d * config["vocab_size"])
    return float(tokens * per_token)


def expert_flops(config, tokens):
    """The grouped matmuls of one step: three matrices, forward and two
    gradients each, 2 * D * F a token-expert pair."""
    pairs = tokens * config["num_experts_per_tok"]
    return float(config["num_hidden_layers"] * 3 * 3 * 2 * pairs
                 * config["hidden_size"] * config["intermediate_size"])


def cosine(a, b):
    a, b = (np.asarray(x, np.float64).ravel() for x in (a, b))
    return float(a @ b / np.linalg.norm(a) / np.linalg.norm(b))


def adamw_first_step(before, mu, nu, *, learning_rate, b1, b2, eps,
                     weight_decay, **_):
    """A parameter after AdamW's first step, written from the rule in f64,
    given the two moments as they are after that step (bias-corrected here:
    ``mu / (1 - b1)`` is the clipped gradient, ``nu / (1 - b2)`` its
    square)."""
    before, mu, nu = (np.asarray(x, np.float64) for x in (before, mu, nu))
    return before - learning_rate * (
        mu / (1 - b1) / (np.sqrt(nu / (1 - b2)) + eps)
        + weight_decay * before)


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs):
    """What ``correct`` holds beyond step 0's loss. ``got`` / ``want``: the
    step's and the reference's aux. ``witnesses``: per name ``before`` and
    ``after`` (the parameter around step 0), ``mu`` and ``nu`` (the store's
    moments after it) and ``reference_grad``. ``clipped_norm``: the global
    norm of the clipped gradient, the store's whole first moment over
    ``1 - b1``. Returns the loop's ``{"checks": .., "detail": ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)
    ref_counts = np.asarray(want["expert_tokens"], np.int64)
    moved = int(np.abs(counts - ref_counts).sum()) // 2
    detail = {"expert_tokens_sum": int(counts.sum()),
              "expert_tokens_max": int(counts.max()),
              "expert_tokens_min": int(counts.min()),
              "pairs_on_another_expert_than_reference": moved,
              "clipped_gradient_norm": clipped_norm}
    for name in TERM_TOLERANCE:
        detail[f"rel_diff.{name}"] = abs(
            float(got[name]) - float(want[name])) / abs(float(want[name]))
    clip = rule["clip_by_global_norm"]
    scales = []
    for name, w in witnesses.items():
        grad = np.asarray(w["mu"], np.float64) / (1 - rule["b1"])
        detail[f"grad_cosine.{name}"] = cosine(grad, w["reference_grad"])
        scales.append(np.linalg.norm(grad)
                      / np.linalg.norm(np.asarray(w["reference_grad"],
                                                  np.float64)))
        detail[f"apply_error_lr.{name}"] = float(np.max(np.abs(
            np.asarray(w["after"], np.float64)
            - adamw_first_step(w["before"], w["mu"], w["nu"], **rule)))
            / rule["learning_rate"])
    # what the clip did, as the witnesses show it: the scale it applied and,
    # from that, the gradient's norm before it
    detail["clip_scale"] = scale = float(np.mean(scales))
    clipped_to_limit = abs(clipped_norm - clip) <= 1e-3 * clip
    return {"checks": {
        "no_dropped_tokens": int(counts.sum()) == pairs,
        "expert_counts_match_reference": moved <= FLIP_SHARE * pairs,
        "loss_terms_match_reference": all(
            detail[f"rel_diff.{name}"] <= tol
            for name, tol in TERM_TOLERANCE.items()),
        # each witness points as the reference's does, and all are as long
        # against the reference's (the clip's scale is common to them)
        "gradient_matches_reference": all(
            detail[f"grad_cosine.{name}"] >= GRAD_COSINE[name]
            for name in witnesses) and bool(max(
                abs(s / scale - 1) for s in scales) <= GRAD_NORM_TOLERANCE),
        # the whole clipped gradient is no longer than the clip allows, and
        # where the clip scaled it down (the witnesses' gradients are shorter
        # than the reference's) it is exactly that long
        "gradient_clipped_to_global_norm":
            clipped_norm <= clip * (1 + 1e-3) and (
                clipped_to_limit or abs(scale - 1) <= GRAD_NORM_TOLERANCE),
        "adamw_apply_matches_rule": all(
            detail[f"apply_error_lr.{name}"] <= APPLY_TOLERANCE
            for name in witnesses)},
        "detail": detail}


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.models.olmoe import OlmoeConfig, init_params, make_loss_fn

    if config["model"] != "olmoe":
        raise ValueError(f"moe_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(f"moe_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ps.init(backend="tpu")
    cfg = OlmoeConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step
    pairs = tokens * cfg.num_experts_per_tok * cfg.num_hidden_layers

    opt = dict(config["optimizer"])
    store = ps.KVStore(optimizer=opt.pop("name"), placement="replicated",
                       **opt)
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
    # every step trains on tokens no step has seen. A small pool cycled
    # is memorised at a pace that is the seed's: 16 batches over 48 steps
    # read loss_at_n 7.04 to 7.36 over twelve seeds, under the ids' unigram
    # entropy of 7.566 nats, and PR 27 was refused for that spread (ledger)
    batches = fresh_batches(batch, seq, cfg.vocab_size, traffic["ids"]["s"],
                            seed)

    # one device scalar and one [E] device array a step, read at the end only
    losses, expert_tokens = [], []
    first = {}

    def step(b):
        loss, _, aux = fused(b)
        if not expert_tokens:
            first["system"] = aux
        losses.append(loss)
        expert_tokens.append(aux["expert_tokens"])
        return loss

    plain = jax.jit(lambda params, b: reference.witness_grads(
        params, b, config, GRAD_COSINE))
    rule = config["optimizer"]

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
                            clipped_norm, rule, pairs * chips)

    def counters():
        # what n the traffic's loss_step could be instead (PERF.md section 6,
        # PR 28: the twelve-seed table), for as far as this run got
        values = [float(x) for x in jax.device_get(losses)]
        print("moe_step: mean loss of steps n-7..n " + json.dumps(
            {n: stats.loss_at_n(values, n) for n in LOSS_STEPS
             if n < len(values)}), file=sys.stderr)
        counts = np.asarray(jax.device_get(expert_tokens), np.float64)
        return {"dropped_tokens":
                float(pairs * chips * len(counts) - counts.sum()),
                "load_max_over_mean":
                float(np.mean(counts.max(axis=1) / counts.mean(axis=1)))}

    heads = cfg.num_attention_heads
    facts = {
        "dense_flops_per_step": (step_flops(config, tokens, seq)
                                 - expert_flops(config, tokens)),
        # no share is held and none dropped by capacity: every routed pair
        # of every layer is computed here, so the count is a fact
        "flops_per_pair": expert_flops(config, tokens) / pairs,
        "live_pairs_per_step": float(pairs),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    if traffic["attn"] == "flash":
        dim = cfg.hidden_size // heads
        facts["flash_flops"], facts["flash_bytes"] = flash.cost(
            per_chip, heads, heads, seq, dim, dim, cfg.num_hidden_layers,
            flash.seen_pairs(seq), itemsize=np.dtype(cfg.dtype).itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
