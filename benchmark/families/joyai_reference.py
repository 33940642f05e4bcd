"""JoyAI-LLM-Flash's plain reference: the training step's forward pass, its
loss of two terms and, through ``jax.grad``, the gradients, in straightforward
``jax.numpy`` and float32, for one chip's share of an expert-parallel group.
The one copy: the CPU tests (``tests/test_joyai.py``) hold
``ps_tpu/models/joyai.py`` and ``ps_tpu/models/blocks.py::mla_block`` to it,
and on the chip it decides ``correct``.

Written from the published ``config.json`` (``model_type: joyai_llm_flash``, a
DeepSeek-V3-shaped config), DeepSeek-V2 (arXiv:2405.04434 section 2.1: latent
attention with decoupled rotary keys) and DeepSeek-V3 (arXiv:2412.19437
sections 2.1-2.2: sigmoid routing with a selection bias, the multi-token
prediction module, with the published checkpoints' tensor names) as the writer
knows them (no network here), and from nothing in the program under test: no
import of the model, of its blocks, of its expert ops or of any kernel.
Attention forms whole rows of the scores under **an explicit boolean causal
mask**, a block of query rows at a time; **the rotation is a complex
multiplication of the pairs** ``(x_2j, x_2j+1)``, not a permutation in front
of a rotation of halves; the experts are a loop over the held ones, each on
every token, with a 0/1 mask that keeps what the router chose. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys and the file's assumed ones (``bias_update_rate``, ``mtp_loss_weight``);
``params`` is a tree of f32 arrays, matrices stored ``[in, out]`` (the
transpose of ``nn.Linear``'s)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layer<i>": {"input_norm", "post_attn_norm": {"scale"},
       "attn": {"q_a", "q_b", "kv_a", "kv_b", "out": {"kernel"},
                "q_norm", "kv_norm": {"scale"}},
       "ffn": {"w1", "w3", "w2": {"kernel"}}
       or "moe": {"router": {"kernel"}, "gate", "up", "down",
                  "shared": {"w1", "w3", "w2": {"kernel"}}}},
     "mtp": {"enorm", "hnorm", "norm": {"scale"}, "eh_proj": {"kernel"},
             "layer": an expert layer as above}}

The layer equations, a sequence ``x`` [S, D] at a time (pre-norm residual
blocks, RMSNorm eps ``rms_norm_eps``, no bias anywhere)::

    h = x + attn(norm1(x));  y = h + ffn(norm2(h))

``attn`` on ``u`` [S, D] (H = ``num_attention_heads`` heads; nope =
``qk_nope_head_dim``, rope = ``qk_rope_head_dim``, v = ``v_head_dim``)::

    c_q = rmsnorm(u Wqa);  q = c_q Wqb -> [S, H, nope + rope]
    [c_kv ; k_pe] = u Wkva            # kv_lora_rank + rope; k_pe ONE head's
    [k_nope ; v] = rmsnorm(c_kv) Wkvb -> [S, H, nope + v]
    q_pe (each head's last rope channels) and k_pe rotated at position i:
        (x_2j + i x_2j+1) * exp(i * pos * theta ** (-2j / rope))   # pairs
    k = [k_nope ; k_pe to every head]
    o = causal softmax(q k^T (nope + rope) ** -0.5) v;  out = o Wo

``ffn`` of layer ``i < first_k_dense_replace``: ``W2(silu(W1 u) * W3 u)`` at
``intermediate_size``; of the others, on ``u`` [T, D]::

    s = sigmoid(u Wr)                     # [T, router_width], f32
    picks = top num_experts_per_tok of s + bias     # the bias selects only
    w_e = s_e / (sum over the picks of s + 1e-20) * routed_scaling_factor
    out = sum over the HELD picks of w_e * swiglu_e(u) + shared(u)

The main loss, with ``h`` the last layer's output after the final norm::

    ce = mean over positions 0..S-1 of CE(h_i W_head, token i+1)

The prediction module (depth 1), at every position ``i``::

    u_i = [rmsnorm_e(Emb(token i+1)) ; rmsnorm_h(h_i)] W_eh     # 2D -> D
    z = one expert layer as above (its own bias row) on u, positions 0..S-1
    mtp_ce = mean over positions 0..S-2 of CE(rmsnorm_s(z_i) W_head,
                                              token i+2)
    loss = ce + mtp_loss_weight * mtp_ce

``Emb`` and ``W_head`` are the main model's. ``batch = {"inputs", "targets"}``
is pre-shifted (``targets[i]`` is token ``i + 1``), so a sequence brings
``S + 1`` tokens: the main loss counts every position, the module every one
but the last, which has no token after next.

Departures from the published model, each at its line below:

- The share: ``n_routed_experts`` of ``router_width`` experts are held, from
  ``expert_start`` on; a token's picks and their renormalisation are over all
  ``router_width``, and what the absent experts would add is left out. The
  shared expert is whole.
- ``h_i`` is taken after the main stack's final norm (what the public
  inference implementations hand the module); ``eh_proj``'s input is the
  embedding's half first; ``mtp_loss_weight`` and the bias rule are the
  configuration file's ``assumed``.
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


def rotate_pairs(x, theta):
    """Rotary positions ``0 .. S-1`` on ``x`` [S, h, d]: the pair
    ``(x_2j, x_2j+1)`` read as one complex number and multiplied by
    ``exp(i * pos * theta ** (-2j / d))``."""
    seq, _, dim = x.shape
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.reshape(seq, -1, dim // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    # (re + i im) (cos + i sin), written out: no complex dtype on the chip
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     axis=-1).reshape(x.shape)


def swiglu(lp, x):
    return (jax.nn.silu(x @ lp["w1"]["kernel"])
            * (x @ lp["w3"]["kernel"])) @ lp["w2"]["kernel"]


def attention(lp, x, config):
    """Latent attention of one sequence ``x`` [S, D] under the explicit
    causal mask."""
    seq = x.shape[0]
    heads, nope, pe, v_dim = (config["num_attention_heads"],
                              config["qk_nope_head_dim"],
                              config["qk_rope_head_dim"],
                              config["v_head_dim"])
    eps, theta, rank = (config["rms_norm_eps"], config["rope_theta"],
                        config["kv_lora_rank"])
    q = (rms_norm(x @ lp["q_a"]["kernel"], lp["q_norm"]["scale"], eps)
         @ lp["q_b"]["kernel"]).reshape(seq, heads, nope + pe)
    latent = x @ lp["kv_a"]["kernel"]
    c_kv, k_pe = latent[:, :rank], latent[:, rank:]
    kv = (rms_norm(c_kv, lp["kv_norm"]["scale"], eps)
          @ lp["kv_b"]["kernel"]).reshape(seq, heads, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], rotate_pairs(q[..., nope:], theta)],
                        axis=-1)
    k_pe = rotate_pairs(k_pe[:, None, :], theta)        # one head's worth
    k = jnp.concatenate([kv[..., :nope], jnp.tile(k_pe, (1, heads, 1))],
                        axis=-1)
    v = kv[..., nope:]
    at_a_time = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, at_a_time, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(nope + pe)
        seen = (jnp.arange(seq)[None, :]
                <= (start + jnp.arange(at_a_time))[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(some_rows, jnp.arange(0, seq, at_a_time))
    return out.reshape(seq, -1) @ lp["out"]["kernel"]


def experts(lp, x, config, bias):
    """The expert layer on tokens ``x`` [T, D] with the layer's selection
    ``bias`` [router_width]: the held experts' part of the output plus the
    shared expert's [T, D], and the 0/1 mask [T, router_width] of each
    token's picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    # departure: the share
    start, held = config["expert_start"], config["n_routed_experts"]
    scores = jax.nn.sigmoid(x @ lp["router"]["kernel"])
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(scores) + bias, top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = scores * mask
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
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
    return total + swiglu(lp["shared"], x), mask


def layer(lp, x, config, bias):
    """One layer on ``x`` [B, S, D]: the stream out and, of an expert layer,
    its picks per expert over all ``router_width`` (None of a dense one)."""
    eps = config["rms_norm_eps"]
    b, seq, d = x.shape
    # one sequence at a time, recomputed in the backward pass
    mixer = jax.checkpoint(lambda one: attention(  # noqa: E731
        lp["attn"], one, config))
    x = x + jax.lax.map(mixer, rms_norm(x, lp["input_norm"]["scale"], eps))
    h = rms_norm(x, lp["post_attn_norm"]["scale"], eps)
    if "ffn" in lp:
        return x + swiglu(lp["ffn"], h), None
    out, mask = experts(lp["moe"], h.reshape(b * seq, d), config, bias)
    return x + out.reshape(b, seq, d), jnp.sum(mask, axis=0)


def hidden_states(params, tokens, config, expert_bias):
    """The main stack on ``tokens`` [B, S]: its output after the final norm
    [B, S, D] and each expert layer's picks per expert [expert layers of the
    main stack, router_width]."""
    x = params["embed"]["tokens"][tokens]
    counts = []
    for i in range(config["num_hidden_layers"]):
        bias = None if i < config["first_k_dense_replace"] \
            else expert_bias[len(counts)]
        x, picked = layer(params[f"layer{i}"], x, config, bias)
        if picked is not None:
            counts.append(picked)
    return rms_norm(x, params["final_norm"]["scale"],
                    config["rms_norm_eps"]), counts


def module_states(params, hidden, next_tokens, config, bias):
    """The prediction module on the main stack's normed output ``hidden``
    [B, S, D] and ``next_tokens`` [B, S], token ``i + 1`` at position ``i``:
    its output after ``shared_head.norm`` and its layer's picks per
    expert."""
    mp, eps = params["mtp"], config["rms_norm_eps"]
    # departure: the embedding's half first, h after the final norm
    joined = jnp.concatenate(
        [rms_norm(params["embed"]["tokens"][next_tokens],
                  mp["enorm"]["scale"], eps),
         rms_norm(hidden, mp["hnorm"]["scale"], eps)], axis=-1)
    z, picked = layer(mp["layer"], joined @ mp["eh_proj"]["kernel"], config,
                      bias)
    return rms_norm(z, mp["norm"]["scale"], eps), picked


def mean_nll(params, h, targets, weights):
    """Mean over the positions of ``weights`` (0 or 1) of the cross entropy
    of ``h`` [B, S, D] through the head against ``targets`` [B, S], a block
    of rows of the logits at a time."""
    rows = targets.size
    at_a_time = next(n for n in range(min(LOGIT_BLOCK, rows), 0, -1)
                     if rows % n == 0)

    @jax.checkpoint
    def some_nll(args):                # [at_a_time, V] logits at a time
        h, targets, w = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return jnp.sum(
            w * -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0])

    total = jax.lax.map(some_nll, (
        h.reshape(-1, at_a_time, h.shape[-1]),
        targets.reshape(-1, at_a_time), weights.reshape(-1, at_a_time)))
    return jnp.sum(total) / jnp.sum(weights)


def loss_fn(params, batch, expert_bias, config):
    """``(loss, aux)`` as ``models/joyai.py::make_loss_fn``'s, of pre-shifted
    ``batch = {"inputs", "targets"}``, each [B, S], and the selection bias
    [expert layers, router_width], the module's row the last."""
    start, held = config["expert_start"], config["n_routed_experts"]
    inputs, targets = batch["inputs"], batch["targets"]
    h, counts = hidden_states(params, inputs, config, expert_bias)
    every = jnp.ones(targets.shape, jnp.float32)
    ce = mean_nll(params, h, targets, every)
    # position i reads token i + 1 and is scored on token i + 2; the last
    # position has none and counts for nothing
    z, picked = module_states(params, h, targets, config, expert_bias[-1])
    counts.append(picked)
    after_next = jnp.concatenate(
        [targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1)
    mtp_ce = mean_nll(params, z, after_next, every.at[:, -1].set(0.0))
    # departure: the weight
    loss = ce + config["mtp_loss_weight"] * mtp_ce
    counts = jnp.stack(counts).astype(jnp.int32)
    return loss, {"loss": loss, "ce": ce, "mtp_ce": mtp_ce,
                  "expert_tokens": counts,
                  "held_tokens": counts[:, start:start + held]}


def witness_grads(params, batch, expert_bias, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer1/attn/kv_a/kernel"``): the whole backward
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
