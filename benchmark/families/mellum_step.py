"""Family of the fused step over Mellum on the four chips of one host that
share each layer: ``ps.init`` -> ``KVStore`` (AdamW behind a global-norm clip,
warmed up; ``placement="sharded"`` with ``mellum_partition_rules()``: the
expert stacks stored split by expert, everything else ZeRO-1) ->
``make_step(make_loss_fn(config, mesh=...), has_aux=True)`` ->
``shard_batch``, the calls of ``families/trinity_step.py`` with the loss of
``ps_tpu/models/mellum.py``. The step's expert counts, the rows each chip sent
and computed and the exchange's further trips leave it in ``aux`` as device
values, every step; no host read in the window.

The yardstick's own pieces live here and beside this file: the stream of Zipf
ids (``moe_step.fresh_batches``); the plain reference
(``families/mellum_reference.py``: all 64 experts in one place, no exchange);
the limits of the step-0 checks with their measured reasons; and the functions
that give operations and bytes from shapes (``pair_flops``, ``dense_flops``,
``step_flops``, ``exchange_bytes``; the flash kernels' are
``families/flash.py``'s). The warm-up is LFM2's (``lfm2_step.learning_rate``).
"""

from __future__ import annotations

import json
import sys
import time

import jax
import numpy as np
import optax

from benchmark.families import flash
from benchmark.families import mellum_reference as reference
from benchmark.families.flash import seen_pairs
from benchmark.families.lfm2_step import learning_rate
from benchmark.families.moe_step import (adamw_first_step, cosine,
                                         fresh_batches, zipf_entropy)
from benchmark.families.nemotron_h_step import lengths_apart
from benchmark.harness import stats
from benchmark.harness.loop import Cell, seed_key

WINDOWED, FULL = "sliding_attention", "full_attention"

# -- the limits of the step-0 checks, with what was measured ------------------
# The fused step computes in bf16 as the configuration states, with the Pallas
# flash kernels at 32 query heads on 4 K/V heads (a band of 1,024 keys in
# three layers, the triangle under YaRN's table in the fourth), the token
# exchange over four chips and the grouped matmuls over each chip's sixteen
# experts; the reference in f32 at "highest" with an explicit [S, S] mask
# under a softmax, all 64 experts in one place and none of the kernels. All
# readings: my chip runs, PR 46, four TPU v5 lite, published widths, 4 x 8,192
# tokens. "seen": the system against the reference over 13 seeds (the cell's
# thirteen runs, chiprun_out/pr46_a_trace.*, pr46_b/). "e4m3": the reference
# on weights rounded to an 8-bit float (the nearest precision below bfloat16,
# a lower bound of computing in one) against the whole reference, and "no
# factor": the reference with the full layers' attention_factor left out
# (1.0) against the whole one (tools/mellum_grad_check.py, seed 4600000001:
# the first session's form of the tool, which compared the readings itself
# and read the stack witness whole; since the review it hands each control to
# step0_checks below as if it were the system and prints the verdicts, the
# stack sliced as chip 0 and chip 2 hold it. That form ran on the chip at
# the cell's size in PR 67, three seeds: the rows "e4m3, PR 67" below and
# "no factor" again (loss 1.7e-5 to 1.0e-4, full q .983-.990, lengths apart
# 0.337-0.349): both controls "not correct" at every seed).
#
#               loss     ce       balance  flips a layer  window k  full q   router   stack 0  stack 2  embed    lengths apart
#  seen, worst  8.52e-5  8.49e-5  5.92e-4  1,348 (0.51%)  .99988    .99990   .99919   .99988   .99966   .99998   0.0112
#  14th seed    1.539e-4 1.543e-4 2.96e-4  1,799         (seed 1807744670, which the driver's check of PR 60 drew: my chip runs, PR 60;
#                                                        on PR 67's tree, behind PR 64's rotation: 1.565e-4, 1.569e-4, 3.15e-4, 1,819)
#  e4m3, PR 67  2.78e-4  2.79e-4  1.36e-3  7,226-20,437  .98731    .97929   .98790   .98552   .98832   .99462   0.0172
#               (the mildest of three seeds each, through step0_checks on the four chips at the cell's size: 1807744670, 6700000121 / 122)
#  LIMIT        2.5e-4   2.5e-4   3e-3     3,145 (1.2%)   .995      .995     .99      .995     .995     .997     0.05
#  e4m3         5.67e-4  5.50e-4  1.82e-2  8,039-14,298   .98449    .98497   .97226   .98642 (whole)    .99349   0.0791
#  no factor    3.28e-5  3.26e-5  1.99e-4  1,945 (last)   .99909    .98900   .99802   .99958 (whole)    .99972   0.3587
#
# e4m3 is "not correct" by every limit of the row; the factor left out by the
# full layer's q (the witness that sits on the scaled table: .98900 under
# .995, every other witness above .998) and by the lengths (the clip's scale
# is no longer common: .3587 over .05), while the loss, blunt as in every
# family here (0.02-normal weights give every token nearly the entropy of
# the vocabulary), moves 3.3e-5 and would pass. The router's gradient is
# whole here (every chip's tokens, all 64 experts): .99919 at worst where
# the share cells, whose router gets one share's part of a sum, read .955.
# The loss's and the cross entropy's limits were 1.5e-4 until PR 67: 1.8x
# the largest of the first thirteen seeds. The fourteenth (1807744670) read
# 1.539e-4 and 1.543e-4 on the accepted program, twice and to the digit, every
# other check true, so any PR whose check drew it was refused whatever it
# changed. Lower reading 1.569e-4 (the largest of fourteen seeds, on PR 67's
# tree). The control, run through step0_checks on the chip at the cell's size
# for the first time in PR 67, reads 2.78e-4, 3.71e-4 and 7.64e-4 in the loss
# at three seeds (PR 46's one reading was 5.67e-4): its mildest is 1.8x the
# lower reading, not three times, so **the loss has no upper reading of its
# own**, and 2.5e-4 (1.6x the lower reading, 0.9x the control's mildest)
# refuses the control at all three seeds with little room. What tells e4m3
# apart with room are the pairs on another expert (7,226 in its best layer of
# its best seed against the system's worst 1,819: 4.0x, limit 3,145) and the
# six cosines (.974-.994 against limits of .99-.997): "not correct" at every
# seed by the counts, the gradient, the terms and the loss.
TOLERANCE = (2.5e-4,
             "bf16 compute with top-8 flips against an f32 reference whose "
             "attention is an explicit mask under a softmax: 1.6x the "
             "largest of 14 seeds (1.565e-4 at seed 1807744670; the next "
             "8.52e-5); the reference on e4m3 weights moves 2.78e-4 to "
             "7.64e-4 at three seeds. Blunt (a full layer without its "
             "YaRN factor moves it 3.3e-5), so after_step0 holds the two "
             "terms, the counts, the exchange's rows, the gradient, the clip "
             "and the apply")
#: each loss term against the reference's, relative: the balance term is a sum
#: over 64 experts of shares that a flipped pair moves whole. Seen: 1.569e-4
#: (the fourteenth seed; 8.49e-5 over the thirteen before it) and 5.92e-4 at
#: most; e4m3 2.79e-4 to 7.69e-4 and 1.36e-3 to 1.36e-2 (three seeds, PR 67)
TERM_TOLERANCE = {"ce": 2.5e-4, "load_balance": 3e-3}
#: token-expert pairs, of the global batch's T * top_k a layer, that may sit on
#: another expert than the reference's (top-8 flips between bf16 and f32
#: activations): half the sum over the 64 experts of |count - reference
#: count|, per layer. Seen: 658 to 1,348 of 262,144 in the worst layer; e4m3
#: 8,039 to 14,298
FLIP_SHARE = 0.012
#: the stack witness: one layer's first expert matrix, read as the sixteen
#: experts chip 0 holds and the sixteen chip 2 holds
STACK = "layers/2/experts/w1"
STACK_CHIPS = (0, 2)
#: leaves (the store's keys) whose gradient witnesses the backward pass, with
#: the lowest cosine to the reference's jax.grad that passes: a windowed
#: layer's k projection (the band's dk summed over a group of eight, through
#: the plain rotation and the head norm), the full layer's q projection (the
#: triangle's dq through YaRN's table and its factor), a router (whole here:
#: the softmax, the renormalisation over the eight picks, the balance term
#: over the global batch; held to the uncut reference), an expert stack on
#: chip 0 and on another chip (the exchange's backward: the rows' cotangents
#: by the inverse exchange, the grouped matmul's gradient on what arrived)
#: and the embedding (upstream of everything). Read from AdamW's first
#: moment: no hook in the step.
GRAD_COSINE = {"layers/0/attn/k/kernel": 0.995,
               "layers/3/attn/q/kernel": 0.995,
               "layers/1/router/kernel": 0.99,
               f"{STACK}[chip 0]": 0.995,
               f"{STACK}[chip 2]": 0.995,
               "embed/tokens": 0.997}
#: the leaves the reference differentiates
WITNESSES = ("layers/0/attn/k/kernel", "layers/3/attn/q/kernel",
             "layers/1/router/kernel", STACK, "embed/tokens")
#: the two whose update is held to AdamW's rule: one ZeRO-split, one
#: expert-split
APPLIED = ("layers/0/attn/k/kernel", STACK)
#: how far a witness's length over the reference's may lie from the
#: witnesses' mean (the clip's scale is common to them; it scaled by 0.064
#: to 0.078). Seen: 0.0112 at most; e4m3 0.0791, the factor left out 0.3587
GRAD_NORM_TOLERANCE = 0.05
#: the updated witnesses against AdamW's rule applied by numpy in f64 to the
#: store's own moments: the largest distance beyond the f32 rounding of the
#: parameter itself, in units of step 0's learning rate (5e-8 under the
#: warm-up: lfm2_step.py says why the rounding is allowed for). Seen beyond
#: the rounding: 1.7e-7 to 3.5e-7 of the rate
APPLY_TOLERANCE = 1e-5

#: the steps n at which a run says its mean loss over n-7..n on stderr: the
#: values ISSUE 46 lets the traffic's ``loss_step`` take
LOSS_STEPS = (32, 48, 64, 96)


# -- operations and bytes from shapes -----------------------------------------

def pair_flops(config):
    """Forward and backward of one token-expert pair through its expert:
    three matrices, 3 x 2 x D x F."""
    return 3 * 6.0 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_flops(config, tokens, seq_len):
    """Operations of one training step of one chip outside the routed
    experts, that the model requires: forward and backward (3 x 2 a parameter
    a token) over the matmuls every token passes (an attention layer's four
    projections, the routers, the untied head) and attention's quadratic term
    over what each layer sees (QK^T and PV, forward and backward: 3 x 2
    matmuls x 2 x pairs x head_dim a head). The rotation and norms are not
    counted, nor is recomputation."""
    d = config["hidden_size"]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"],
                            config["head_dim"])
    per_token = 6.0 * d * config["vocab_size"]
    cores = 0.0
    for kind in config["layer_types"]:
        per_token += 6.0 * d * (dim * (2 * heads + 2 * kv_heads)
                                + config["num_experts"])
        window = config["sliding_window"] if kind == WINDOWED else None
        cores += 3 * 4.0 * heads * dim * seen_pairs(seq_len, window)
    return float(tokens * per_token) + cores * (tokens // seq_len)


def step_flops(config, tokens, seq_len):
    """``dense_flops`` plus every pair of the chip's tokens through its
    expert: dropless, so all ``tokens x top_k`` a layer, wherever they are
    computed (the chips compute as many between them as they route)."""
    pairs = tokens * config["num_experts_per_tok"] * len(
        config["layer_types"])
    return dense_flops(config, tokens, seq_len) + pairs * pair_flops(config)


def exchange_bytes(config, rows, exchanges, itemsize=2):
    """Bytes a chip sends to other chips in a step whose layers sent them
    ``rows`` rows between them and each ran ``exchanges`` exchanges: each
    row is ``hidden_size`` wide, and every exchange of a layer (there, back,
    again in the recomputation, the cotangents' two) moves the same rows one
    way or the other. How many a layer runs is the program's to say: the
    reader counts them in the trace."""
    return float(rows * config["hidden_size"] * itemsize * exchanges)


def param_count(config):
    """Parameters of the stack, from the configuration's keys alone."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    q, kv = (n * config["head_dim"] for n in (config["num_attention_heads"],
                                              config["num_key_value_heads"]))
    layer = (2 * d * q + 2 * d * kv + 2 * d + d * config["num_experts"]
             + config["num_experts"] * 3 * d * f
             + (2 * config["head_dim"] if config.get("qk_norm", True) else 0))
    return (len(config["layer_types"]) * layer
            + 2 * d * config["vocab_size"] + d)


def placed_init(cfg, mesh):
    """``init_params`` jitted so that each leaf is made where the store will
    keep it (the expert stacks split by expert, the rest as ZeRO-1 splits
    them): no chip holds the 8.5e9 B whole. ``key -> params``."""
    from ps_tpu.models.mellum import init_params, mellum_partition_rules
    from ps_tpu.parallel.sharding import param_sharding

    rules = mellum_partition_rules()
    make = lambda k: init_params(k, cfg)  # noqa: E731

    def placed(path, leaf):
        key = "/".join(str(p.key) for p in path)
        return param_sharding(mesh, leaf, "sharded", key=key, rules=rules)

    shardings = jax.tree_util.tree_map_with_path(
        placed, jax.eval_shape(make, jax.random.key(0)))
    return jax.jit(make, out_shardings=shardings)


def sliced(name, value, held):
    """A witness by the names of ``GRAD_COSINE``: the stack's as the slices
    of ``held`` experts that two chips hold."""
    if name != STACK:
        return {name: value}
    return {f"{STACK}[chip {c}]": value[c * held:(c + 1) * held]
            for c in STACK_CHIPS}


def step0_checks(got, want, witnesses, clipped_norm, rule, pairs):
    """What ``correct`` holds beyond step 0's loss. ``got`` / ``want``: the
    step's and the reference's aux. ``witnesses``: per name ``mu`` (the
    store's first moment after step 0) and ``reference_grad``, and for the
    two of ``APPLIED`` ``before``, ``after`` and ``nu`` too.
    ``clipped_norm``: the global norm of the clipped gradient. ``pairs``:
    the global batch's T * top_k, a layer. Returns the loop's ``{"checks":
    .., "detail": ..}``."""
    counts = np.asarray(got["expert_tokens"], np.int64)        # [L, 64]
    ref_counts = np.asarray(want["expert_tokens"], np.int64)
    sent = np.asarray(got["sent_rows"], np.int64)              # [L, chips]
    received = np.asarray(got["received_rows"], np.int64)      # [L, chips]
    moved = np.abs(counts - ref_counts).sum(axis=-1) // 2      # a layer
    detail = {"pairs_routed_per_layer": counts.sum(axis=-1).tolist(),
              "pairs_sent_per_layer": sent.sum(axis=-1).tolist(),
              "pairs_computed_per_layer": received.sum(axis=-1).tolist(),
              "rows_computed_by_chip": received.tolist(),
              "rows_sent_to_other_chips": np.asarray(
                  got["exchange_rows"], np.int64).tolist(),
              "exchange_trips_beyond_first": np.asarray(
                  got["exchange_trips"], np.int64).tolist(),
              "pairs_on_another_expert_than_reference": moved.tolist(),
              "clipped_gradient_norm": clipped_norm}
    for name, tol in TERM_TOLERANCE.items():
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
        if "after" not in w:
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
        # dropless: every pair routed is sent (the senders' figure, from
        # their routing) and received and computed (the owners' count of the
        # group sizes the exchange handed their grouped matmuls)
        "no_dropped_tokens": bool((counts.sum(axis=-1) == pairs).all()
                                  and (sent.sum(axis=-1) == pairs).all()
                                  and (received.sum(axis=-1) == pairs).all()),
        "expert_counts_match_reference":
            bool((moved <= FLIP_SHARE * pairs).all()),
        "loss_terms_match_reference": all(
            detail[f"rel_diff.{name}"] <= tol
            for name, tol in TERM_TOLERANCE.items()),
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
    from ps_tpu.models.mellum import (MellumConfig, make_loss_fn,
                                      mellum_partition_rules)
    from ps_tpu.ops.moe import exchange_rows

    if config["model"] != "mellum":
        raise ValueError(f"mellum_step knows no model {config['model']!r}")
    if traffic["ids"]["kind"] != "zipf":
        raise ValueError(f"unknown id distribution {traffic['ids']['kind']!r}")
    if traffic["input"] != "direct":
        raise ValueError(f"unknown input mode {traffic['input']!r}")
    if traffic["pool"] != "fresh":
        raise ValueError(
            f"mellum_step re-uses no batch: pool {traffic['pool']!r}")
    t_start = time.perf_counter()
    ctx = ps.init(backend="tpu")
    cfg = MellumConfig.from_dict(config)
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    seq = int(traffic["seq_len"])
    tokens = per_chip * seq                      # a chip, a step
    pairs = tokens * cfg.num_experts_per_tok     # a chip, a step, a layer
    layers = cfg.num_hidden_layers

    opt = dict(config["optimizer"])
    rate, rule = learning_rate(opt, opt.pop("warmup_steps", 0))
    store = ps.KVStore(optimizer=opt.pop("name"), placement="sharded",
                       partition_rules=mellum_partition_rules(),
                       **{**opt, "learning_rate": rate})
    # the weights are made on the devices from the seed; the store keeps its
    # own buffers (it donates them every step), so the tree made here goes
    params = jax.block_until_ready(placed_init(cfg, ctx.mesh)(seed_key(seed)))
    t_weights = time.perf_counter()
    jax.block_until_ready(store.init(params))
    del params
    t_store = time.perf_counter()
    fused = store.make_step(
        make_loss_fn(cfg, attn=traffic["attn"], mesh=ctx.mesh), has_aux=True)
    batches = fresh_batches(batch, seq, cfg.vocab_size, traffic["ids"]["s"],
                            seed)

    # device values, read at the end only: a scalar, [L, 64], [L, chips] x 3,
    # [L]
    kept = {name: [] for name in ("loss", "expert_tokens", "sent_rows",
                                  "exchange_rows", "received_rows",
                                  "exchange_trips")}
    first = {}

    def step(b):
        loss, _, aux = fused(b)
        if not kept["loss"]:
            first["system"] = aux
        kept["loss"].append(loss)
        for name in kept:
            if name != "loss":
                kept[name].append(aux[name])
        return loss

    plain = jax.jit(lambda params, b: reference.witness_grads(
        params, b, config, WITNESSES))

    held = cfg.num_experts // chips

    def reference_loss(b):
        params = store.params()
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = plain(params, b)
        first["reference"] = jax.device_get(aux)
        first["witnesses"] = {}
        for name, grad in grads.items():
            grad = np.asarray(grad)
            # the store donates its buffers to step 0: copies, on the host
            before = np.asarray(store.pull(name)) if name in APPLIED else None
            for part, g in sliced(name, grad, held).items():
                first["witnesses"][part] = {"reference_grad": g}
                if before is not None:
                    first["witnesses"][part]["before"] = sliced(
                        name, before, held)[part]
        return float(loss)

    def after_step0():
        """More than step 0's loss: ``step0_checks`` on what the store
        holds once step 0 is done."""
        def moment(key, which):
            return optax.tree_utils.tree_get(store.optimizer_state(key),
                                             which)

        for name in WITNESSES:
            stored = {"mu": np.asarray(moment(name, "mu"))}
            if name in APPLIED:
                stored.update(after=np.asarray(store.pull(name)),
                              nu=np.asarray(moment(name, "nu")))
            for which, value in stored.items():
                for part, v in sliced(name, value, held).items():
                    first["witnesses"][part][which] = v
        # one program over the whole first moment, not a reduction a key
        clipped_norm = float(jax.jit(optax.global_norm)(
            {k: moment(k, "mu") for k in store.keys()})) / (1 - rule["b1"])
        return step0_checks(jax.device_get(first["system"]),
                            first["reference"], first["witnesses"],
                            clipped_norm, rule, pairs * chips)

    def counters():
        values = [float(x) for x in jax.device_get(kept["loss"])]
        print("mellum_step: mean loss of steps n-7..n " + json.dumps(
            {n: stats.loss_at_n(values, n) for n in LOSS_STEPS
             if n < len(values)}), file=sys.stderr)
        counts, sent, moved, received, trips = (
            np.asarray(jax.device_get(kept[name]), np.float64)
            for name in ("expert_tokens", "sent_rows", "exchange_rows",
                         "received_rows", "exchange_trips"))
        steps = len(counts)
        routed = pairs * chips * layers * steps
        fullest = float(np.mean(counts.max(axis=-1) / counts.mean(axis=-1)))
        share = received.max(axis=-1) / pairs      # [steps, L] over even
        print("mellum_step: the fullest chip's rows over an even quarter, by "
              "layer " + json.dumps(share.mean(axis=0).round(4).tolist())
              + f", most {share.max():.4f}; fullest expert over the mean "
              f"{fullest:.3f}; rows sent to other chips a chip a layer "
              f"{moved.mean():.0f} of {pairs}; steps with a trip beyond the "
              f"first {int((trips.max(axis=-1) > 0).sum())} of {steps}, most "
              f"trips beyond it {int(trips.max())}", file=sys.stderr)
        # pairs routed that the senders did not send, and pairs sent that
        # the owners' grouped matmuls were not handed: both 0, dropless
        return {"dropped_tokens":
                float(abs(routed - counts.sum()) + abs(routed - sent.sum())
                      + abs(routed - received.sum())),
                # all layers of one chip, a step, mean over the chips
                "exchange_rows_per_step":
                float(moved.sum() / steps / chips)}

    itemsize = np.dtype(cfg.dtype).itemsize
    facts = {
        # a chip's: step_flops in its two parts. Dropless, so every pair
        # of the chip's tokens is computed on some chip: the count is a fact
        "dense_flops_per_step": dense_flops(config, tokens, seq),
        "flops_per_pair": pair_flops(config),
        "live_pairs_per_step": float(pairs * layers),
        # one row, one exchange; the reader counts the exchanges a layer
        "exchange_bytes_per_row": exchange_bytes(config, 1, 1,
                                                        itemsize),
        "layers": layers,
        "exchange_buffer_rows": exchange_rows(
            tokens, cfg.num_experts_per_tok, chips),
        "parameters": param_count(config),
        "unigram_entropy_nats": zipf_entropy(cfg.vocab_size,
                                             traffic["ids"]["s"]),
        # where set-up's build phase goes, seconds
        "build_s": {"init_and_weights": t_weights - t_start,
                    "store_init": t_store - t_weights},
    }
    if traffic["attn"] == "flash":
        shape = (per_chip, cfg.num_attention_heads, cfg.num_key_value_heads,
                 seq, cfg.head_dim)
        for name, kind, window in (
                ("window_flash", WINDOWED, cfg.sliding_window),
                ("flash", FULL, None)):
            count = cfg.layer_types.count(kind)
            if count:
                facts[f"{name}_flops"], facts[f"{name}_bytes"] = flash.cost(
                    *shape, cfg.head_dim, count, seen_pairs(seq, window),
                    itemsize=itemsize)
        facts["kernel_targets"] = config["kernel_targets"]
    stream = device_prefetch(batches, place=store.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
