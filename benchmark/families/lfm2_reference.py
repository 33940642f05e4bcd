"""LFM2-MoE's plain reference: forward pass, loss and, through ``jax.grad``,
the gradients, in straightforward ``jax.numpy`` and float32, for one chip's
share of an expert-parallel group.

Two copies of this file exist, letter for letter: ``tests/lfm2_reference.py``
(what the CPU tests hold ``ps_tpu/models/lfm2.py`` to) and
``benchmark/families/lfm2_reference.py`` (the yardstick's own, which decides
``correct`` on the chip and which a later PR to the program cannot edit).
``tests/test_lfm2.py`` holds the two equal, in text and in value.

Written from the published ``config.json`` (``model_type: lfm2_moe``) and the
layer equations of ``transformers/models/lfm2_moe/modeling_lfm2_moe.py``,
and from nothing in ``ps_tpu``: no import of ``models/lfm2.py``,
``ops/moe.py``, ``ops/gated_conv.py`` or any kernel. No sort, no permutation,
no ``ragged_dot``: a loop over the held experts, each on every token, with a
0/1 mask that keeps what the router chose; attention forms whole rows of the
score matrix (in blocks of query rows, so that 8,192 positions fit) against
K and V repeated for their group of query heads; the convolution is three
shifted products; RoPE and the per-head QK-norm are written out. Call it
under ``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys; ``params`` is a tree of f32 arrays, ``{"embed": {"tokens"},
"layer<i>": {"operator_norm", "ffn_norm", "conv": {"in_proj", "filter",
"out_proj"} or "attn": {"q", "k", "v", "out", "q_norm", "k_norm"}, "ffn":
{"w1", "w3", "w2"} or "moe": {"router", "gate", "up", "down"}},
"final_norm"}``, matrices stored ``[in, out]`` (the transpose of
``nn.Linear``'s), the filter ``[channels, taps]`` (``nn.Conv1d``'s weight
without its middle axis). Departures from ``modeling_lfm2_moe.py``:

- **The share.** ``num_experts`` is the number of experts held here, of
  ``router_width`` published ones, from ``expert_start`` on. The router
  scores all ``router_width``, every token picks its ``num_experts_per_tok``
  among all of them and its weights are renormalised over all its picks;
  only the held experts are computed, and what the absent ones would have
  added is left out of the layer's output and of everything after it.
  ``vocab_size`` is this chip's slice: ids, logits and loss are over it.
- The head is the embedding transposed (the config names no
  ``tie_word_embeddings``; the family's default ties them).
- The loss is the cross entropy alone: no auxiliary load-balancing loss and
  no z-loss (the buffer ``expert_bias`` is there to balance without one).
- ``expert_bias`` is updated here, by the rule it exists for (loss-free
  balancing, Wang et al. 2024, arXiv:2408.15664): after the step, per layer,
  ``b_e += bias_update_rate * sign(mean(c) - c_e)`` with ``c`` the step's
  picks over all ``router_width`` experts. ``modeling_lfm2_moe.py`` only
  reads the buffer; the update is the training code's, which is not public.
- The router's matmul runs in f32 like everything here.
- No dropout, no document mask, no padding mask, no cache: training on whole
  sequences.
- Where two scores tie exactly, which of them ``top_k`` takes is the
  library's choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 1024


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


def conv_mixer(lp, x, config):
    """The double-gated short convolution of one sequence ``x`` [S, D]."""
    taps = config["conv_L_cache"]
    b, c, gate_in = jnp.split(x @ lp["in_proj"]["kernel"], 3, axis=-1)
    u = b * gate_in
    padded = jnp.concatenate([jnp.zeros_like(u[:taps - 1]), u], axis=0)
    # v_t = sum_j w[:, j] * u_{t - (taps - 1) + j}: nn.Conv1d with
    # padding taps - 1, cut to the first S outputs
    v = sum(lp["filter"][:, j] * padded[j:j + u.shape[0]]
            for j in range(taps))
    return (c * v) @ lp["out_proj"]["kernel"]


def attention(lp, x, config):
    """Causal grouped-query attention of one sequence ``x`` [S, D]."""
    seq, d = x.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    eps, theta = config["norm_eps"], config["rope_parameters"]["rope_theta"]
    q = (x @ lp["q"]["kernel"]).reshape(seq, heads, -1)
    k = (x @ lp["k"]["kernel"]).reshape(seq, kv_heads, -1)
    v = (x @ lp["v"]["kernel"]).reshape(seq, kv_heads, -1)
    q = rotate(rms_norm(q, lp["q_norm"]["scale"], eps), theta)
    k = rotate(rms_norm(k, lp["k_norm"]["scale"], eps), theta)
    # query head i reads K/V head i // (heads / kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(d // heads)
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, d)
    return out @ lp["out"]["kernel"]


def dense_ffn(lp, x):
    return ((jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"]))
            @ lp["w2"]["kernel"])


def experts(lp, x, bias, config):
    """The expert layer on tokens ``x`` [T, D] with the selection ``bias``
    [router_width]: the held experts' part of the output [T, D] and the 0/1
    mask [T, router_width] of each token's picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    start, held = config["expert_start"], config["num_experts"]
    scores = jax.nn.sigmoid(x @ lp["router"]["kernel"])
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = scores * mask
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    weights = weights * config["routed_scaling_factor"]

    @jax.checkpoint
    def expert(gate, up, down, w):     # w [T]: this expert's weight per token
        return w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    # a loop over the held experts, each on all tokens; a scan keeps the
    # compile short and the memory at one expert's
    total, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None), jnp.zeros_like(x),
        (lp["gate"], lp["up"], lp["down"],
         weights[:, start:start + held].T))
    return total, mask


def loss_fn(params, batch, expert_bias, config):
    """``(loss, aux)`` as ``models/lfm2.py::make_loss_fn``'s, of pre-shifted
    ``batch = {"inputs": [B, S], "targets": [B, S]}`` and the selection
    bias ``expert_bias`` [expert layers, router_width] of this step."""
    eps = config["norm_eps"]
    start, held = config["expert_start"], config["num_experts"]
    ids = batch["inputs"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    counts = []
    for i, kind in enumerate(config["layer_types"]):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["operator_norm"]["scale"], eps)
        # one sequence at a time, recomputed in the backward pass
        if kind == "conv":
            mixer = lambda seq: conv_mixer(lp["conv"], seq, config)  # noqa: E731
        else:
            mixer = lambda seq: attention(lp["attn"], seq, config)  # noqa: E731
        x = x + jax.lax.map(jax.checkpoint(mixer), h)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if i < config["num_dense_layers"]:
            x = x + jax.lax.map(
                jax.checkpoint(lambda seq: dense_ffn(lp["ffn"], seq)), h)
        else:
            out, mask = experts(lp["moe"], h.reshape(b * s, -1),
                                expert_bias[len(counts)], config)
            x = x + out.reshape(b, s, -1)
            counts.append(jnp.sum(mask, axis=0))
    h = rms_norm(x, params["final_norm"]["scale"], eps)

    @jax.checkpoint
    def sequence_nll(args):            # one sequence's [S, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["embed"]["tokens"].T, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    ce = jnp.sum(jax.lax.map(sequence_nll, (h, batch["targets"]))) / (b * s)
    counts = jnp.stack(counts)
    new_bias = expert_bias
    if config["use_expert_bias"]:
        new_bias = expert_bias + config["bias_update_rate"] * jnp.sign(
            jnp.mean(counts, axis=-1, keepdims=True) - counts)
    counts = counts.astype(jnp.int32)
    return ce, {"ce": ce, "expert_tokens": counts,
                "held_tokens": counts[:, start:start + held],
                "expert_bias": new_bias}


def witness_grads(params, batch, expert_bias, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer0/conv/in_proj/kernel"``): the whole backward
    pass runs, but no gradient of the other leaves is kept."""
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
        return loss_fn(with_leaves(params, "", leaves), batch, expert_bias,
                       config)

    return jax.value_and_grad(loss_of, has_aux=True)(
        {name: leaf(name) for name in names})
