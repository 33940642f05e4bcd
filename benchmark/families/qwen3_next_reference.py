"""Qwen3-Next's plain reference: forward pass, loss and, through ``jax.grad``,
the gradients, in straightforward ``jax.numpy`` and float32, for one chip's
share of an expert-parallel group.

The one copy: ``tests/test_qwen3_next.py`` reads it where it is, and it decides
``correct`` on the chip (``families/qwen3_next_step.py``).

Written from the published ``config.json`` (``model_type: qwen3_next``), the
layer equations of transformers' ``modeling_qwen3_next.py`` and the ``fla``
library's naive gated delta rule as the writer knows them (no network here),
and from nothing in the program under test: no import of the model, of the
chunked rule, of the expert layer or of any kernel. The delta rule runs
**token by token** (a ``lax.scan`` over tokens, in blocks of ``TOKEN_BLOCK``
under ``jax.checkpoint`` so that the gradient of 8,192 tokens keeps 128 states
a head and not 8,192); attention forms whole rows of the score matrix (in
blocks of query rows), K and V repeated for the eight query heads they serve;
the experts are a loop over the held ones, each on every token, with a 0/1
mask that keeps what the router chose; the shared expert is a SwiGLU on every
token under its own gate. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys; ``params`` is a tree of f32 arrays, matrices stored ``[in, out]`` (the
transpose of ``nn.Linear``'s), the convolution's filter ``[channels, taps]``
(``nn.Conv1d``'s weight without its middle axis)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"w"},
     "layer<i>": {"mixer_norm": {"w"}, "ffn_norm": {"w"},
       "gdn": {"in_qkvz", "in_ba", "out": {"kernel"}, "conv", "A_log",
               "dt_bias", "out_norm": {"scale"}}
       or "attn": {"q", "k", "v", "out": {"kernel"}, "q_norm", "k_norm":
                   {"w"}},
       "moe": {"router", "gate", "up", "down", "shared": {"w1", "w3", "w2"},
               "shared_gate": {"kernel"}}}}

The layer equations, a sequence ``x`` [S, D] at a time (pre-norm residual
blocks; every norm of the residual stream and the attention's q and k norms
are **zero-centred**, the parameter ``w`` starts at 0)::

    norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)
    x += mixer(norm(x));  x += moe(norm(x));  logits = norm(x) W_head

Layer ``i`` (from 0) attends where ``(i + 1) % full_attention_interval == 0``
and runs the delta rule otherwise.

Gated DeltaNet (Hk = ``linear_num_key_heads`` key heads of K =
``linear_key_head_dim``, Hv = ``linear_num_value_heads`` value heads of V =
``linear_value_head_dim``, r = Hv / Hk value heads read each key head)::

    [q | k | v | z] = x W_qkvz, a key head at a time: W_qkvz's columns are Hk
        groups of (K | K | r V | r V): the head's q, its k, its r value
        heads' v, their z
    [b | a] = x W_ba, a key head at a time (r | r)
    q, k, v = split(silu(conv(concat(q, k, v over all heads))))
        # ONE depthwise causal filter over 2 Hk K + Hv V channels,
        # linear_conv_kernel_dim taps, no bias, zero pad
    q, k: each head's K channels divided by sqrt(sum of squares + 1e-6);
        q times K ** -0.5; both repeated to their r value heads
        (value head h reads key head h // r)
    beta = sigmoid(b)                                          # [S, Hv]
    g    = -exp(A_log) * softplus(a + dt_bias)                 # [S, Hv], <= 0
    per value head, S_0 = 0 [K, V]:   S *= exp(g_t)        # one scalar
                                      u  = beta_t * (v_t - S^T k_t)
                                      S += outer(k_t, u);  o_t = S^T q_t
    y = (o / sqrt(mean over V (o^2) + rms_norm_eps) * w_norm) * silu(z)
        # the norm FIRST, the gate after it; w_norm [V] starts at 1
    out = y W_o

Gated attention (H = ``num_attention_heads`` query heads on
``num_key_value_heads`` K/V heads, all of ``head_dim``)::

    [q | gate] = x W_q, a head at a time (head_dim | head_dim)
    k = x W_k, v = x W_v
    q = norm_head(q), k = norm_head(k)          # zero-centred, over head_dim
    the first head_dim * partial_rotary_factor channels of q and k rotated by
        position: with R that many channels, channel j < R / 2 against channel
        j + R / 2 by the angle pos * rope_theta ** (-2 j / R); the others pass
    causal softmax(q k^T head_dim ** -0.5) v
    out = (attn * sigmoid(gate)) W_o

Expert block (every layer)::

    p = softmax(x W_r) over all router_width;  picks = top-k of p
    w = the picks' p / their sum                             # norm_topk_prob
    y = sum_k w_k expert_k(x) + sigmoid(x w_sg) * shared(x)
        # SwiGLU: W2(silu(W1 x) * W3 x); w_sg [D, 1]

Departures from ``modeling_qwen3_next.py``:

- **The share.** ``num_experts`` is the number of experts held here, of
  ``router_width`` published ones, from ``expert_start`` on. The router scores
  all ``router_width``, every token picks its ``num_experts_per_tok`` among
  all of them and its weights are renormalised over all its picks; only the
  held experts are computed, and what the absent ones would have added is left
  out of the layer's output and of everything after it. The gated shared
  expert is whole here, as on every chip of the group. ``vocab_size`` is this
  chip's slice: ids, logits and loss are over it.
- The loss is the next-token cross entropy alone: no auxiliary balance loss
  (``router_aux_loss_coef``) and no z-loss.
- No prediction module (the family's description names one; the config this
  was written from has no key for it).
- The gated head norm is computed in float32 throughout (the published code
  rounds the normalised values to the input's dtype before the gate: no
  difference in float32).
- No dropout, no document mask, no padding mask, no cache, no state carried
  from one sequence to the next: training on whole sequences from a zero
  state.
- Where two probabilities tie exactly, which of them ``top_k`` takes is the
  library's choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 1024
#: tokens of the recurrence under one ``jax.checkpoint``
TOKEN_BLOCK = 64


def norm(x, w, eps):
    """The zero-centred RMSNorm over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def swiglu(lp, x):
    return ((jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"]))
            @ lp["w2"]["kernel"])


def conv_silu(x, w):
    """``silu`` of the depthwise causal convolution of ``x`` [S, C] with the
    filter ``w`` [C, taps]: ``nn.Conv1d`` with padding taps - 1, cut to the
    first S outputs."""
    taps = w.shape[-1]
    padded = jnp.concatenate([jnp.zeros_like(x[:taps - 1]), x], axis=0)
    return jax.nn.silu(sum(w[:, j] * padded[j:j + x.shape[0]]
                           for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule of one sequence at one decay a head, token by
    token: ``q``, ``k`` [S, H, K], ``v`` [S, H, V], ``g``, ``beta`` [S, H]
    -> ``o`` [S, H, V]."""
    seq, heads, width = q.shape

    def token(state, args):            # state [H, K, V]
        q_t, k_t, v_t, g_t, beta_t = args
        state = state * jnp.exp(g_t)[:, None, None]
        u = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[..., None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, args):
        return jax.lax.scan(token, state, args)

    size = next(s for s in range(min(TOKEN_BLOCK, seq), 0, -1)
                if seq % s == 0)
    state = jnp.zeros((heads, width, v.shape[-1]), q.dtype)
    _, out = jax.lax.scan(block, state, tuple(
        x.reshape(seq // size, size, *x.shape[1:])
        for x in (q, k, v, g, beta)))
    return out.reshape(seq, heads, -1)


def to_value_heads(y, r):
    """Key heads [S, Hk, K] as their value heads read them [S, Hk r, K]:
    value head ``h`` reads key head ``h // r``."""
    return jnp.repeat(y, r, axis=1)


def gated_head_norm(o, z, scale, eps):
    """``o`` [S, H, V] normalised over V and scaled, THEN gated by
    ``silu(z)``."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    return o * jax.nn.silu(z)


def gdn_mixer(lp, x, config):
    """Gated DeltaNet of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    r = hv // hk
    qkvz = (x @ lp["in_qkvz"]["kernel"]).reshape(seq, hk, -1)
    ba = (x @ lp["in_ba"]["kernel"]).reshape(seq, hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(seq, hv, dv)
    b, a = ba[..., :r].reshape(seq, hv), ba[..., r:].reshape(seq, hv)
    mixed = conv_silu(jnp.concatenate(
        [q.reshape(seq, -1), k.reshape(seq, -1), v.reshape(seq, -1)], -1),
        lp["conv"])
    q = mixed[:, :hk * dk].reshape(seq, hk, dk)
    k = mixed[:, hk * dk:2 * hk * dk].reshape(seq, hk, dk)
    v = mixed[:, 2 * hk * dk:].reshape(seq, hv, dv)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k = (to_value_heads(y, r) for y in (unit(q) * dk ** -0.5, unit(k)))
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    o = gated_head_norm(delta_rule(q, k, v, g, beta), z,
                        lp["out_norm"]["scale"], config["rms_norm_eps"])
    return o.reshape(seq, -1) @ lp["out"]["kernel"]


def rotate(x, theta, channels):
    """The first ``channels`` channels of ``x`` [S, h, d] rotated by
    position, channel ``j`` against channel ``j + channels / 2``; the rest
    pass."""
    half = channels // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi, rest = x[..., :half], x[..., half:channels], x[..., channels:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest],
                           axis=-1)


def attention_mixer(lp, x, config):
    """Causal gated grouped-query attention of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dim, eps = config["head_dim"], config["rms_norm_eps"]
    channels = int(dim * config["partial_rotary_factor"])
    qg = (x @ lp["q"]["kernel"]).reshape(seq, heads, 2 * dim)
    q, gate = qg[..., :dim], qg[..., dim:]
    k = (x @ lp["k"]["kernel"]).reshape(seq, kv, dim)
    v = (x @ lp["v"]["kernel"]).reshape(seq, kv, dim)
    q = rotate(norm(q, lp["q_norm"]["w"], eps), config["rope_theta"],
               channels)
    k = rotate(norm(k, lp["k_norm"]["w"], eps), config["rope_theta"],
               channels)
    k, v = (jnp.repeat(y, heads // kv, axis=1) for y in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(dim)
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, heads,
                                                               dim)
    return (out * jax.nn.sigmoid(gate)).reshape(seq, -1) @ lp["out"]["kernel"]


def experts(lp, x, config):
    """The expert layer on tokens ``x`` [T, D]: the held experts' part of the
    output plus the gated shared expert's [T, D], and the 0/1 mask [T,
    router_width] of each token's picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    start, held = config["expert_start"], config["num_experts"]
    probs = jax.nn.softmax(x @ lp["router"]["kernel"], axis=-1)
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

    @jax.checkpoint
    def shared(x):
        return jax.nn.sigmoid(x @ lp["shared_gate"]["kernel"]) \
            * swiglu(lp["shared"], x)

    return total + shared(x), mask


def hidden_states(params, ids, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]`` and each layer's picks per expert over all
    ``router_width`` [layers, router_width]."""
    eps = config["rms_norm_eps"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    counts = []
    for i in range(config["num_hidden_layers"]):
        lp = params[f"layer{i}"]
        h = norm(x, lp["mixer_norm"]["w"], eps)
        # one sequence at a time, recomputed in the backward pass
        if (i + 1) % config["full_attention_interval"]:
            mixer = lambda seq: gdn_mixer(lp["gdn"], seq, config)  # noqa: E731
        else:
            mixer = lambda seq: attention_mixer(  # noqa: E731
                lp["attn"], seq, config)
        x = x + jax.lax.map(jax.checkpoint(mixer), h)
        h = norm(x, lp["ffn_norm"]["w"], eps)
        out, mask = experts(lp["moe"], h.reshape(b * s, -1), config)
        x = x + out.reshape(b, s, -1)
        counts.append(jnp.sum(mask, axis=0))
    return norm(x, params["final_norm"]["w"], eps), jnp.stack(counts)


def logits_fn(params, ids, config):
    """Every position's logits over the slice: [B, S, V]."""
    h, _ = hidden_states(params, ids, config)
    return h @ params["head"]["kernel"]


def loss_fn(params, batch, config):
    """``(loss, aux)`` as ``models/qwen3_next.py::make_loss_fn``'s, of
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}``."""
    start, held = config["expert_start"], config["num_experts"]
    b, s = batch["inputs"].shape
    h, counts = hidden_states(params, batch["inputs"], config)

    @jax.checkpoint
    def sequence_nll(args):            # one sequence's [S, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    ce = jnp.sum(jax.lax.map(sequence_nll, (h, batch["targets"]))) / (b * s)
    counts = counts.astype(jnp.int32)
    return ce, {"ce": ce, "expert_tokens": counts,
                "held_tokens": counts[:, start:start + held]}


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer0/gdn/A_log"``): the whole backward pass runs,
    but no gradient of the other leaves is kept."""
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
