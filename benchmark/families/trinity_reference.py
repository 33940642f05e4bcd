"""Trinity's plain reference: forward pass, loss and, through ``jax.grad``, the
gradients, in straightforward ``jax.numpy`` and float32, for one chip's share
of an expert-parallel group.

Two copies of this file exist, letter for letter: ``tests/trinity_reference.py``
(what the CPU tests hold ``ps_tpu/models/trinity.py`` and the windowed
``ps_tpu/ops/flash_attention.py`` to) and
``benchmark/families/trinity_reference.py`` (the yardstick's own, which decides
``correct`` on the chip and which a later PR to the program cannot edit).
``tests/test_trinity.py`` holds the two equal, in text and in value.

Written from the published ``config.json`` (``model_type: afmoe``) and the
layer equations of the ``modeling_afmoe.py`` it names as the writer knows them
(no network here), and from nothing in the program under test: no import of
the model, of its expert ops or of any kernel. Attention forms whole rows of
the score matrix under an explicit mask, a band or a triangle (in blocks of
query rows, so that 16,384 fits); the experts are a loop over the held ones,
each on every token, with a 0/1 mask that keeps what the router chose; the
shared expert runs on every token. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys; ``params`` is a tree of f32 arrays, matrices stored ``[in, out]`` (the
transpose of ``nn.Linear``'s)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layer<i>": {"input_norm", "post_attn_norm", "pre_mlp_norm",
                  "post_mlp_norm": {"scale"},
       "attn": {"q", "k", "v", "gate", "out": {"kernel"},
                "q_norm", "k_norm": {"scale"}},
       "ffn": {"w1", "w3", "w2": {"kernel"}}
       or "moe": {"router": {"kernel"}, "gate", "up", "down",
                  "shared": {"w1", "w3", "w2": {"kernel"}}}}}

The layer equations, a sequence ``x`` [S, D] at a time (RMSNorm with a learned
scale, eps ``rms_norm_eps``)::

    x0 = embed(ids) * sqrt(hidden_size)            # mup_enabled
    h = x + post_attn_norm(attn(input_norm(x)))
    y = h + post_mlp_norm(ffn(pre_mlp_norm(h)))
    after the last:  logits = norm_f(x) W_head

``attn`` on ``u`` [S, D] (h = ``num_attention_heads`` query heads of
``head_dim`` on ``num_key_value_heads`` K/V heads, each serving h / kv
consecutive query heads; no bias)::

    q = rmsnorm_head(u Wq);  k = rmsnorm_head(u Wk);  v = u Wv;  g = u Wg
    layer_types[l] == "sliding_attention":
        q, k = rope(q), rope(k)     # rope_theta, halves rotated (rotate_half)
        i sees j  iff  0 <= i - j < sliding_window
    layer_types[l] == "full_attention":
        no rotation at all;  i sees j  iff  j <= i
    o = softmax(q k^T / sqrt(head_dim) over what i sees) v
    out = (o * sigmoid(g)) Wo

``ffn`` is ``W2(silu(W1 u) * W3 u)`` of width ``intermediate_size`` in the
first ``num_dense_layers`` layers, the expert layer after::

    s = sigmoid(u Wr)                              # [S, router_width], f32
    picks = top num_experts_per_tok of (s + b)     # b selects only
    w_e = s_e / (sum over the picks of s + 1e-20)  # route_norm
    out = swiglu_shared(u) + route_scale * sum over the HELD picks of
          w_e * swiglu_e(u)

and after the step ``b_e += load_balance_coeff * sign(mean(c) - c_e)`` over the
step's picks ``c`` of all ``router_width`` experts. The loss is the mean
next-token cross entropy and nothing else.

Departures from the published code, each at its line below:

- The share: ``num_experts`` of ``router_width`` experts are held, from
  ``expert_start`` on; a token's picks and their renormalisation are over all
  ``router_width``, and what the absent experts would add is left out.
- ``load_balance_coeff`` is read as the update rate of the selection bias
  (the published code keeps ``expert_bias`` as a buffer and only reads it):
  the sign rule of loss-free balancing (Wang et al. 2024, arXiv:2408.15664).
- ``n_group`` 1 and ``topk_group`` 1: grouped top-k over one group is plain
  top-k, which is what is computed.
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


def swiglu(lp, x):
    return ((jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"]))
            @ lp["w2"]["kernel"])


def rope(x, theta):
    """Rotary positions 0 .. S-1 on ``x`` [S, h, d]: the two halves of each
    head rotated against each other."""
    seq, _, dim = x.shape
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(lp, x, kind, config):
    """Gated attention of one sequence ``x`` [S, D], of the layer's
    ``kind``."""
    seq = x.shape[0]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"],
                            config["head_dim"])
    eps = config["rms_norm_eps"]
    q = (x @ lp["q"]["kernel"]).reshape(seq, heads, dim)
    k = (x @ lp["k"]["kernel"]).reshape(seq, kv_heads, dim)
    v = (x @ lp["v"]["kernel"]).reshape(seq, kv_heads, dim)
    q = rms_norm(q, lp["q_norm"]["scale"], eps)
    k = rms_norm(k, lp["k_norm"]["scale"], eps)
    if kind == "sliding_attention":
        q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
        reach = config["sliding_window"]
    elif kind == "full_attention":
        reach = seq         # no rotation, and every earlier key
    else:
        raise ValueError(f"layer type {kind!r}")
    # query head h reads K/V head h // (heads / kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(dim)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(seq)
        seen = (ahead >= 0) & (ahead < reach)      # a band, or the triangle
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, -1)
    return (out * jax.nn.sigmoid(x @ lp["gate"]["kernel"])) \
        @ lp["out"]["kernel"]


def experts(lp, x, bias, config):
    """The expert layer on tokens ``x`` [T, D] with the selection ``bias``
    [router_width]: the held experts' part of the output plus the shared
    expert's [T, D], and the 0/1 mask [T, router_width] of each token's
    picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    # departure: the share
    start, held = config["expert_start"], config["num_experts"]
    scores = jax.nn.sigmoid(x @ lp["router"]["kernel"])
    # departure: one group, plain top-k; the bias selects and has no gradient
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = scores * mask
    if config["route_norm"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * config["route_scale"]

    @jax.checkpoint
    def expert(gate, up, down, w):     # w [T]: this expert's weight per token
        return w[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down)

    # a loop over the held experts, each on all tokens; a scan keeps the
    # compile short and the memory at one expert's
    total, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None), jnp.zeros_like(x),
        (lp["gate"], lp["up"], lp["down"],
         weights[:, start:start + held].T))
    return total + jax.checkpoint(swiglu)(lp["shared"], x), mask


def hidden_states(params, ids, expert_bias, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]`` and each expert layer's picks per expert over all
    ``router_width`` [expert layers, router_width]."""
    eps = config["rms_norm_eps"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    if config["mup_enabled"]:
        x = x * math.sqrt(config["hidden_size"])
    counts = []
    for i, kind in enumerate(config["layer_types"]):
        lp = params[f"layer{i}"]

        def norm(name, t):
            return rms_norm(t, lp[name]["scale"], eps)

        # one sequence at a time, recomputed in the backward pass
        mixer = jax.checkpoint(lambda seq: attention(  # noqa: E731
            lp["attn"], seq, kind, config))
        x = x + norm("post_attn_norm", jax.lax.map(mixer,
                                                   norm("input_norm", x)))
        h = norm("pre_mlp_norm", x)
        if i < config["num_dense_layers"]:
            out = jax.lax.map(
                jax.checkpoint(lambda seq: swiglu(lp["ffn"], seq)), h)
        else:
            out, mask = experts(lp["moe"], h.reshape(b * s, -1),
                                expert_bias[len(counts)], config)
            out = out.reshape(b, s, -1)
            counts.append(jnp.sum(mask, axis=0))
        x = x + norm("post_mlp_norm", out)
    return (rms_norm(x, params["final_norm"]["scale"], eps),
            jnp.stack(counts))


def logits_fn(params, ids, expert_bias, config):
    """Every position's logits over the slice: [B, S, V]."""
    h, _ = hidden_states(params, ids, expert_bias, config)
    return h @ params["head"]["kernel"]


def loss_fn(params, batch, expert_bias, config):
    """``(loss, aux)`` as ``models/trinity.py::make_loss_fn``'s, of
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}`` and the
    selection bias ``expert_bias`` [expert layers, router_width] of this
    step."""
    start, held = config["expert_start"], config["num_experts"]
    b, s = batch["inputs"].shape
    h, counts = hidden_states(params, batch["inputs"], expert_bias, config)
    block = next(n for n in range(min(LOGIT_BLOCK, b * s), 0, -1)
                 if (b * s) % n == 0)

    @jax.checkpoint
    def block_nll(args):               # [block, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    ce = jnp.sum(jax.lax.map(block_nll, (
        h.reshape(-1, block, h.shape[-1]),
        batch["targets"].reshape(-1, block)))) / (b * s)
    # departure: load_balance_coeff as the bias's update rate, the sign rule
    new_bias = expert_bias + config["load_balance_coeff"] * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)
    counts = counts.astype(jnp.int32)
    return ce, {"ce": ce, "expert_tokens": counts,
                "held_tokens": counts[:, start:start + held],
                "expert_bias": new_bias}


def witness_grads(params, batch, expert_bias, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer1/attn/gate/kernel"``): the whole backward
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
