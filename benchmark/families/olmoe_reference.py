"""OLMoE's plain reference: forward pass, the three loss terms and, through
``jax.grad``, the gradients, in straightforward ``jax.numpy`` and float32.

Two copies of this file exist, letter for letter: ``tests/olmoe_reference.py``
(what the CPU tests hold ``ps_tpu/models/olmoe.py`` to) and
``benchmark/families/olmoe_reference.py`` (the yardstick's own, which decides
``correct`` on the chip and which a later PR to the program cannot edit).
``tests/test_olmoe.py`` holds the two equal, in text and in value.

Written from the published description (arXiv:2409.02060) and the layer
equations of ``transformers/models/olmoe/modeling_olmoe.py`` (4.57.6), and
from nothing in ``ps_tpu``: no import of ``models/olmoe.py``, ``ops/moe.py``
or any kernel. No sort, no permutation, no ``ragged_dot``: every expert runs
on every token and a 0/1 mask keeps what the router chose; attention forms
the whole ``[S, S]`` matrix; RoPE and the QK-norm are written out. Call it
under ``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys and the recipe's ``load_balance_coef`` and ``z_loss_coef``; ``params``
is a tree of f32 arrays, ``{"embed": {"tokens"}, "layer<i>": {"attn_norm",
"attn": {"q", "k", "v", "out", "q_norm", "k_norm"}, "ffn_norm", "moe":
{"router", "gate", "up", "down"}}, "final_norm", "head"}``, matrices stored
``[in, out]`` (the transpose of ``nn.Linear``'s). Departures from
``modeling_olmoe.py`` and the training code:

- The load-balancing term is ``E * sum_e(f_e * P_e)`` with ``f_e`` the share
  of token-expert *pairs* on expert e: 1.0 under uniform routing.
  ``load_balancing_loss_func`` sums the same product over the ``k`` picks
  with ``f`` a share of tokens, which is ``k`` times this; the coefficient
  0.01 multiplies this form here (the configuration lists it as assumed).
- Both router terms are computed per layer over all tokens of the batch and
  summed over layers; ``load_balancing_loss_func`` concatenates the layers'
  logits first (one layer: the same), and the training code takes them per
  device micro-batch.
- The router z-loss is not in ``modeling_olmoe.py``; it is the paper's
  (section 2, coefficient 0.001): the mean squared logsumexp of the logits.
- The router's matmul runs in f32 like everything here; ``modeling_olmoe.py``
  runs it in the activations' dtype and casts the softmax to f32.
- No dropout (the model has none), no document mask, no ``clip_qkv`` (null
  in the published config), no padding mask.
- Where two router probabilities tie exactly, which of them ``top_k`` takes
  is the library's choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rotate(x, theta):
    """RoPE on [S, h, d]: pairs (i, i + d/2) turned by pos * theta^(-2i/d)."""
    seq, _, dim = x.shape
    half = dim // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None, None] * inv_freq
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def attention(lp, x, config):
    """Causal multi-head attention of one sequence ``x`` [S, D]."""
    seq, d = x.shape
    heads = config["num_attention_heads"]
    eps = config["rms_norm_eps"]
    q = rms_norm(x @ lp["q"]["kernel"], lp["q_norm"]["scale"], eps)
    k = rms_norm(x @ lp["k"]["kernel"], lp["k_norm"]["scale"], eps)
    v = x @ lp["v"]["kernel"]
    q = rotate(q.reshape(seq, heads, -1), config["rope_theta"])
    k = rotate(k.reshape(seq, heads, -1), config["rope_theta"])
    v = v.reshape(seq, heads, -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(d // heads)
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(seq, d)
    return out @ lp["out"]["kernel"]


def experts(lp, x, config):
    """The expert layer on tokens ``x`` [T, D]: output [T, D], router logits
    and probabilities [T, E], and the 0/1 mask [T, E] of each token's picks."""
    num, top_k = config["num_experts"], config["num_experts_per_tok"]
    logits = x @ lp["router"]["kernel"]
    probs = jax.nn.softmax(logits, -1)
    _, picks = jax.lax.top_k(probs, top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, num, dtype=x.dtype), axis=1)
    weights = probs * mask
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    @jax.checkpoint
    def expert(gate, up, down, w):     # w [T]: this expert's weight per token
        return w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    # a loop over the experts, each on all tokens; a scan keeps the compile
    # short and the memory at one expert's
    total, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None), jnp.zeros_like(x),
        (lp["gate"], lp["up"], lp["down"], weights.T))
    return total, logits, probs, mask


def loss_fn(params, batch, config):
    """``(loss, aux)`` as ``models/olmoe.py::make_loss_fn``'s, of pre-shifted
    ``batch = {"inputs": [B, S], "targets": [B, S]}``."""
    eps = config["rms_norm_eps"]
    num, top_k = config["num_experts"], config["num_experts_per_tok"]
    ids = batch["inputs"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    load_balance = z_loss = 0.0
    expert_tokens = jnp.zeros((num,), jnp.float32)
    for i in range(config["num_hidden_layers"]):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["attn_norm"]["scale"], eps)
        # one sequence at a time, its [h, S, S] probabilities recomputed in
        # the backward pass and not kept
        x = x + jax.lax.map(jax.checkpoint(
            lambda seq: attention(lp["attn"], seq, config)), h)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps).reshape(b * s, -1)
        out, logits, probs, mask = experts(lp["moe"], h, config)
        x = x + out.reshape(b, s, -1)
        counts = jnp.sum(mask, axis=0)
        share = jax.lax.stop_gradient(counts) / (b * s * top_k)
        load_balance += num * jnp.sum(share * jnp.mean(probs, axis=0))
        z_loss += jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
        expert_tokens += counts
    h = rms_norm(x, params["final_norm"]["scale"], eps)

    @jax.checkpoint
    def sequence_nll(args):            # one sequence's [S, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    ce = jnp.sum(jax.lax.map(sequence_nll, (h, batch["targets"]))) / (b * s)
    loss = (ce + config["load_balance_coef"] * load_balance
            + config["z_loss_coef"] * z_loss)
    return loss, {"ce": ce, "load_balance": load_balance, "z_loss": z_loss,
                  "expert_tokens": expert_tokens.astype(jnp.int32)}


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer0/attn/q/kernel"``): the whole backward pass
    runs, but no gradient of the other 2.5 GB of leaves is kept."""
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
