"""SDAR's plain reference: the block-diffusion training step's forward pass,
loss and, through ``jax.grad``, the gradients, in straightforward
``jax.numpy`` and float32, for one chip's share of an expert-parallel group.
The one copy: the CPU tests (``tests/test_sdar.py``) hold
``ps_tpu/models/sdar.py`` and the edged ``ps_tpu/ops/flash_attention.py`` to
it, and on the chip it decides ``correct``.

Written from the published ``config.json`` (``model_type: sdar_moe``, the
Qwen3-MoE layer the family is converted from) and the block-diffusion
objective (Arriola et al. 2025, "Block Diffusion", the vectorised training
step, which the SDAR recipe keeps) as the writer knows them (no network
here), and from nothing in the program under test: no import of the model, of
its expert ops or of any kernel. Attention runs on **the doubled sequence**
``[x~ ; x]``, the noised copy first and the clean one after, ``2 L`` rows,
under **one explicit boolean ``[2 L, 2 L]`` mask** (``mask_rows``, whole or a
block of query rows at a time so that 2 x 8,192 fits) and a softmax; the
experts are a loop over the held ones, each on every token, with a 0/1 mask
that keeps what the router chose. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys and the file's assumed ones (``block_length``, ``mask_token_id``,
``router_aux_loss_coef``); ``params`` is a tree of f32 arrays, matrices
stored ``[in, out]`` (the transpose of ``nn.Linear``'s)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layer<i>": {"input_norm", "post_attn_norm": {"scale"},
       "attn": {"q", "k", "v", "out": {"kernel"},
                "q_norm", "k_norm": {"scale"}},
       "moe": {"router": {"kernel"}, "gate", "up", "down"}}}

The training step, a sequence ``x`` of ``L`` tokens at a time, cut in blocks
of ``B = block_length`` (``blk(i) = i // B``). The batch brings the draw:
``x~`` is ``x`` with ``mask_token_id`` at the masked positions, and
``weights`` is ``1 / t_b`` there and 0 elsewhere (one noise level ``t_b`` a
block). Both copies go through the stack together, each at positions
``0 .. L-1``::

    z0 = embed([x~ ; x])                            # [2 L, D]
    h = z + attn(norm1(z));  z' = h + moe(norm2(h))  # RMSNorm, rms_norm_eps
    after the last:  logits = norm_f(z[:L]) W_head   # the noised half

``attn`` on ``u`` [2 L, D] (h = ``num_attention_heads`` query heads of
``head_dim`` on ``num_key_value_heads`` K/V heads, each serving h / kv
consecutive query heads; no bias)::

    q = rope(rmsnorm_head(u Wq));  k = rope(rmsnorm_head(u Wk));  v = u Wv
        # rope_theta, halves rotated (rotate_half), row r at position r mod L
    row r sees row c  iff  one of the mask's three terms holds:
        r <  L, c <  L:  blk(r) == blk(c)        # block-diagonal, noised
        r <  L, c >= L:  blk(r) >  blk(c - L)    # offset block-causal,
                                                 # noised to clean
        r >= L, c >= L:  blk(r - L) >= blk(c - L)  # block-causal, clean
        (r >= L, c < L: never: no clean query sees a noised key)
    o = softmax(q k^T / sqrt(head_dim) over what r sees) v;  out = o Wo

``moe`` on ``u`` [T, D], the tokens of both copies of every sequence::

    p = softmax(u Wr)                     # [T, router_width], f32
    picks = top num_experts_per_tok of p
    w_e = p_e / sum over the picks of p   # norm_topk_prob
    out = sum over the HELD picks of w_e * swiglu_e(u)

and the loss, position ``i`` predicting token ``i`` (no shift)::

    ce = sum_i weights_i * CE(logits_i, x_i) / L      # mean over sequences
    balance = sum over layers of  router_width * sum_e f_e * P_e
        # f_e: share of the step's T * k pairs on expert e (no gradient),
        # P_e: mean of p_e over the T tokens
    loss = ce + router_aux_loss_coef * balance

Departures from the published model, each at its line below:

- The share: ``num_experts`` of ``router_width`` experts are held, from
  ``expert_start`` on; a token's picks and their renormalisation are over all
  ``router_width``, and what the absent experts would add is left out.
- ``block_length``, the noise (in the batch), ``mask_token_id`` and
  ``router_aux_loss_coef`` are the configuration file's ``assumed``: the
  published config carries none of them.
- No document mask, no dropout, every sequence starts at position 0.
- Where two scores tie exactly, which of them ``top_k`` takes is the
  library's choice.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 256
#: rows of the logits formed at a time
LOGIT_BLOCK = 4096


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, positions, theta):
    """Rotary ``positions`` [R] on ``x`` [R, h, d]: the two halves of each
    head rotated against each other."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mask_rows(start, rows: int, seq: int, block: int):
    """Rows ``start .. start + rows - 1`` of the explicit boolean
    ``[2 L, 2 L]`` mask of the doubled sequence ``[x~ ; x]`` (``L = seq``),
    built from its three terms; ``mask_rows(0, 2 * seq, seq, block)`` is the
    whole mask."""
    r = (start + jnp.arange(rows))[:, None]
    c = jnp.arange(2 * seq)[None, :]
    r_noised, c_noised = r < seq, c < seq
    r_block, c_block = (r % seq) // block, (c % seq) // block
    block_diagonal = r_noised & c_noised & (r_block == c_block)
    offset_block_causal = r_noised & ~c_noised & (r_block > c_block)
    block_causal = ~r_noised & ~c_noised & (r_block >= c_block)
    return block_diagonal | offset_block_causal | block_causal


def attention(lp, x, config):
    """Attention of one doubled sequence ``x`` [2 L, D], the noised copy
    first, under the explicit mask."""
    rows = x.shape[0]
    seq = rows // 2
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"],
                            config["head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    positions = jnp.arange(rows) % seq      # both copies at 0 .. L-1
    q = (x @ lp["q"]["kernel"]).reshape(rows, heads, dim)
    k = (x @ lp["k"]["kernel"]).reshape(rows, kv_heads, dim)
    v = (x @ lp["v"]["kernel"]).reshape(rows, kv_heads, dim)
    q = rope(rms_norm(q, lp["q_norm"]["scale"], eps), positions, theta)
    k = rope(rms_norm(k, lp["k_norm"]["scale"], eps), positions, theta)
    # query head h reads K/V head h // (heads / kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    at_a_time = min(rows, QUERY_BLOCK)

    @jax.checkpoint
    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, at_a_time, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dim)
        seen = mask_rows(start, at_a_time, seq, config["block_length"])
        # every row sees a key: a noised one its own position, a clean one too
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(some_rows, jnp.arange(0, rows, at_a_time))
    return out.reshape(rows, -1) @ lp["out"]["kernel"]


def experts(lp, x, config):
    """The expert layer on tokens ``x`` [T, D]: the held experts' part of
    the output [T, D], the 0/1 mask [T, router_width] of each token's picks
    over all experts, and the router's probabilities [T, router_width]."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    # departure: the share
    start, held = config["expert_start"], config["num_experts"]
    probs = jax.nn.softmax(x @ lp["router"]["kernel"], -1)
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = probs * mask
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    @jax.checkpoint
    def expert(gate, up, down, w):     # w [T]: this expert's weight per token
        return w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    # a loop over the held experts, each on all tokens; a scan keeps the
    # compile short and the memory at one expert's
    total, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None), jnp.zeros_like(x),
        (lp["gate"], lp["up"], lp["down"],
         weights[:, start:start + held].T))
    return total, mask, probs


def hidden_states(params, ids, noised_ids, config):
    """The stack on the doubled sequences of ``ids``, ``noised_ids`` [B, L]:
    the noised halves after the final norm [B, L, D], each layer's picks per
    expert over all ``router_width`` [layers, router_width] and the sum of
    the layers' load-balancing terms."""
    eps = config["rms_norm_eps"]
    b, seq = ids.shape
    doubled = jnp.concatenate([noised_ids, ids], axis=1)     # [x~ ; x]
    x = params["embed"]["tokens"][doubled]
    counts, balance = [], 0.0
    for i in range(config["num_hidden_layers"]):
        lp = params[f"layer{i}"]
        # one doubled sequence at a time, recomputed in the backward pass
        mixer = jax.checkpoint(lambda one: attention(  # noqa: E731
            lp["attn"], one, config))
        x = x + jax.lax.map(mixer, rms_norm(x, lp["input_norm"]["scale"],
                                            eps))
        h = rms_norm(x, lp["post_attn_norm"]["scale"], eps)
        out, mask, probs = experts(lp["moe"], h.reshape(b * 2 * seq, -1),
                                   config)
        x = x + out.reshape(b, 2 * seq, -1)
        picked = jnp.sum(mask, axis=0)
        counts.append(picked)
        # departure: the coefficient's term, over all router_width experts
        share = jax.lax.stop_gradient(picked) / jnp.sum(picked)
        balance = balance + config["router_width"] * jnp.sum(
            share * jnp.mean(probs, axis=0))
    return (rms_norm(x[:, :seq], params["final_norm"]["scale"], eps),
            jnp.stack(counts), balance)


def logits_fn(params, ids, noised_ids, config):
    """The noised copy's logits over the slice at every position:
    [B, L, V]."""
    h, _, _ = hidden_states(params, ids, noised_ids, config)
    return h @ params["head"]["kernel"]


def loss_fn(params, batch, config):
    """``(loss, aux)`` as ``models/sdar.py::make_loss_fn``'s, of ``batch =
    {"ids", "noised_ids", "weights"}``, each [B, L]."""
    start, held = config["expert_start"], config["num_experts"]
    ids, weights = batch["ids"], batch["weights"]
    b, seq = ids.shape
    h, counts, balance = hidden_states(params, ids, batch["noised_ids"],
                                       config)
    at_a_time = next(n for n in range(min(LOGIT_BLOCK, b * seq), 0, -1)
                     if (b * seq) % n == 0)

    @jax.checkpoint
    def some_nll(args):                # [at_a_time, V] logits at a time
        h, targets, w = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        nll = -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]
        return jnp.sum(w * nll), jnp.sum(jnp.where(w > 0, nll, 0.0))

    # position i against token i, weighted, over L a sequence; and the same
    # positions' plain mean, which the loss does not hold
    weighted, plain = jax.lax.map(some_nll, (
        h.reshape(-1, at_a_time, h.shape[-1]),
        ids.reshape(-1, at_a_time),
        weights.reshape(-1, at_a_time)))
    ce = jnp.sum(weighted) / (b * seq)
    loss = ce + config["router_aux_loss_coef"] * balance
    counts = counts.astype(jnp.int32)
    return loss, {"loss": loss, "ce": ce, "load_balance": balance,
                  "masked_ce": jax.lax.stop_gradient(
                      jnp.sum(plain) / jnp.sum(weights > 0)),
                  "expert_tokens": counts,
                  "held_tokens": counts[:, start:start + held]}


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer1/attn/k/kernel"``): the whole backward pass
    runs, but no gradient of the other leaves is kept."""
    def with_leaves(tree, prefix, leaves):
        if not isinstance(tree, dict):
            return leaves.get(prefix, tree)
        return {k: with_leaves(v, f"{prefix}/{k}" if prefix else k, leaves)
                for k, v in tree.items()}

    def leaf(name):
        tree = params
        for part in name.split("/"):
            tree = tree[part]
        return tree

    def loss_of(leaves):
        return loss_fn(with_leaves(params, "", leaves), batch, config)

    return jax.value_and_grad(loss_of, has_aux=True)(
        {name: leaf(name) for name in names})
