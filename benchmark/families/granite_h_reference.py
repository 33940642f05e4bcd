"""Granite-4.0-H's plain reference: forward pass, loss and, through
``jax.grad``, the gradients, in straightforward ``jax.numpy`` and float32.

The one copy (``tests/test_blocks.py`` holds that no second one exists):
``tests/test_granite_h.py`` holds ``ps_tpu/models/granite_h.py``,
``ps_tpu/models/blocks.py::mamba_block`` and ``ps_tpu/ops/ssd.py`` to it on the
CPU, and it decides ``correct`` on the chip, where a later PR to the program
cannot edit it.

Written from the published ``config.json`` (``model_type: granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json),
the layer equations of ``transformers``' ``modeling_granitemoehybrid.py``
(its mixer is Bamba's Mamba-2: Dao & Gu 2024, arXiv:2405.21060) and
``mamba_ssm``'s reference selective scan as the writer knows them (no network
here), and from
nothing in the program under test: no import of the model, of its blocks, of
its scan or of any kernel. The state-space scan runs **token by token** (a
``lax.scan`` over tokens, in blocks of ``TOKEN_BLOCK`` under
``jax.checkpoint`` so that the gradient of 8,192 tokens keeps 128 states a
head and not 8,192; no chunk and no cumulated sum anywhere); attention forms
whole rows of the score matrix (in blocks of query rows) and multiplies them
by ``attention_multiplier`` itself; the loss is taken a block of positions at
a time. Call it under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs in bf16 passes. ``config`` is a plain dict with
the published keys; ``params`` is a tree of f32 arrays, matrices stored
``[in, out]`` (the transpose of ``nn.Linear``'s), the filter ``[channels,
taps]`` (``nn.Conv1d``'s weight without its middle axis)::

    {"embed": {"tokens"}, "final_norm": {"scale"},
     "layer<i>": {"norm": {"scale"}, "ffn_norm": {"scale"},
       "ffn": {"w_in", "w_out": {"kernel"}},
       "mamba": {"in_proj", "out_proj": {"kernel"}, "conv": {"kernel",
                 "bias"}, "dt_bias", "A_log", "D", "out_norm": {"scale"}}
       or "attn": {"q", "k", "v", "out": {"kernel"}}}}

The equations, a sequence of ids ``t`` [S] at a time. ``E`` [V, D] is the one
tensor that embeds and, transposed, is the head; every norm is an RMSNorm with
a learned scale and eps ``rms_norm_eps``; m = ``residual_multiplier``::

    x = embedding_multiplier * E[t]
    per layer i of kind layer_types[i]:
        h = x + m * Mixer_i(norm(x))
        x = h + m * (silu(a) * b) W_out,   [a | b] = norm'(h) W_in
    logits = norm_f(x) E^T / logits_scaling
    loss = mean over positions of -log softmax(logits)[target]

``W_in`` is [D, 2 F] and ``W_out`` [F, D] with F = ``shared_intermediate_size``
(``shared_mlp``: ``input_linear`` chunked in two, the first half under the
SiLU); no bias. ``mamba``, a Mamba-2 mixer (H = ``mamba_n_heads`` heads of P =
``mamba_d_head``, G = ``mamba_n_groups`` groups of B and C, state N =
``mamba_d_state``; ``d_inner`` = H P = ``mamba_expand`` D)::

    z, xBC, dt = split(u W_in, [d_inner, d_inner + 2 G N, H])
    xBC = silu(conv(xBC) + b)    # depthwise, causal, mamba_d_conv taps, 0 pad
    x, B, C = split(xBC, [d_inner, G N, G N])    # x [S, H, P]; B, C [S, G, N]
    dt = softplus(dt + dt_bias)                  # [S, H], > 0
    per head h of group g, S_0 = 0 [P, N]:
        S = exp(dt_t * -exp(A_log[h])) * S + dt_t * outer(x_t, B_t[g])
        y_t = S @ C_t[g] + D[h] * x_t
    y = rmsnorm over each group's d_inner / G channels (y * silu(z)) * w_norm
    out = y W_out

(the gate first, then the norm: ``mamba_ssm``'s ``norm_before_gate`` false,
``GraniteMoeHybridRMSNormGated``; at G = 1 one norm over all ``d_inner``
channels). ``attention`` (h = ``num_attention_heads`` query heads of
``hidden_size / h`` on ``num_key_value_heads`` K/V heads, each serving h / kv
consecutive query heads; no bias, no position: ``position_embedding_type``
``nope``)::

    causal softmax(q k^T * attention_multiplier) v, then W_o

Departures from ``modeling_granitemoehybrid.py``:

- ``num_hidden_layers`` and ``layer_types`` are a cut of the published forty,
  and ``vocab_size`` is this chip's slice: ids, logits and loss are over it.
- No expert branch: ``num_local_experts`` 0 leaves ``block_sparse_moe`` out
  of every layer, and ``shared_mlp`` alone is the feed-forward part.
- No rotary embedding (``rope_theta`` is read by nothing under ``nope``).
- The mixer's time-step is not clamped (``time_step_limit`` (0, inf), the
  default) and ``mamba_chunk_size`` is read by nothing: there is no chunk.
- No dropout, no document mask, no padding mask, no cache, no state carried
  from one sequence to the next: training on whole sequences from a zero
  state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 1024
#: tokens of the recurrence under one ``jax.checkpoint``
TOKEN_BLOCK = 64
#: positions whose logits are formed at a time
LOSS_BLOCK = 2048


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def conv_silu(x, w, b):
    """``silu`` of the depthwise causal convolution of ``x`` [S, C] with the
    filter ``w`` [C, taps] plus the bias ``b`` [C]: ``nn.Conv1d`` with padding
    taps - 1, cut to the first S outputs."""
    taps = w.shape[-1]
    padded = jnp.concatenate([jnp.zeros_like(x[:taps - 1]), x], axis=0)
    return jax.nn.silu(sum(w[:, j] * padded[j:j + x.shape[0]]
                           for j in range(taps)) + b)


def selective_scan(x, dt, a, b, c):
    """The state-space recurrence of one sequence, token by token: ``x``
    [S, H, P], ``dt`` [S, H], ``a`` [H] (< 0), ``b`` and ``c`` [S, H, N] (the
    group's, repeated to its heads) -> ``y`` [S, H, P]."""
    seq, heads, width = x.shape

    def token(state, args):            # state [H, P, N]
        x_t, dt_t, b_t, c_t = args
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    @jax.checkpoint
    def block(state, args):
        return jax.lax.scan(token, state, args)

    size = next(s for s in range(min(TOKEN_BLOCK, seq), 0, -1)
                if seq % s == 0)
    state = jnp.zeros((heads, width, b.shape[-1]), x.dtype)
    _, out = jax.lax.scan(block, state, tuple(
        t.reshape(seq // size, size, *t.shape[1:]) for t in (x, dt, b, c)))
    return out.reshape(seq, heads, width)


def gated_norm(y, z, scale, groups, eps):
    """``rmsnorm(y * silu(z)) * scale`` over each group's channels of ``y``
    and ``z`` [S, d_inner]: the gate first."""
    seq, inner = y.shape
    y = rms_norm((y * jax.nn.silu(z)).reshape(seq, groups, -1),
                 scale.reshape(groups, -1), eps)
    return y.reshape(seq, inner)


def mamba_mixer(lp, x, config):
    """The Mamba-2 mixer of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    heads, groups = config["mamba_n_heads"], config["mamba_n_groups"]
    inner = heads * config["mamba_d_head"]
    state = groups * config["mamba_d_state"]
    projected = x @ lp["in_proj"]["kernel"]
    z = projected[:, :inner]
    xbc = conv_silu(projected[:, inner:2 * inner + 2 * state],
                    lp["conv"]["kernel"], lp["conv"]["bias"])
    dt = jax.nn.softplus(projected[:, 2 * inner + 2 * state:]
                         + lp["dt_bias"])
    xs = xbc[:, :inner].reshape(seq, heads, -1)
    b, c = (jnp.repeat(t.reshape(seq, groups, -1), heads // groups, axis=1)
            for t in (xbc[:, inner:inner + state], xbc[:, inner + state:]))
    y = selective_scan(xs, dt, -jnp.exp(lp["A_log"]), b, c) \
        + lp["D"][:, None] * xs
    y = gated_norm(y.reshape(seq, inner), z, lp["out_norm"]["scale"], groups,
                   config["rms_norm_eps"])
    return y @ lp["out_proj"]["kernel"]


def attention_mixer(lp, x, config):
    """Causal grouped-query attention of one sequence ``x`` [S, D], no
    position anywhere, the scores times ``attention_multiplier``."""
    seq, hidden = x.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = hidden // heads
    q = (x @ lp["q"]["kernel"]).reshape(seq, heads, dim)
    k, v = (jnp.repeat((x @ lp[n]["kernel"]).reshape(seq, kv_heads, dim),
                       heads // kv_heads, axis=1) for n in ("k", "v"))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) \
            * config["attention_multiplier"]
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, -1)
    return out @ lp["out"]["kernel"]


def swiglu(lp, x, config):
    """``(silu(a) * b) W_out`` with ``[a | b] = x W_in`` of tokens ``x``
    [S, D]."""
    width = config["shared_intermediate_size"]
    both = x @ lp["w_in"]["kernel"]
    return (jax.nn.silu(both[:, :width]) * both[:, width:]) \
        @ lp["w_out"]["kernel"]


def hidden_states(params, ids, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]``."""
    eps, m = config["rms_norm_eps"], config["residual_multiplier"]
    x = config["embedding_multiplier"] * params["embed"]["tokens"][ids]
    for i, kind in enumerate(config["layer_types"]):
        lp = params[f"layer{i}"]

        # one sequence at a time, recomputed in the backward pass
        @jax.checkpoint
        def layer(seq, lp=lp, kind=kind):
            h = rms_norm(seq, lp["norm"]["scale"], eps)
            if kind == "mamba":
                mixed = mamba_mixer(lp["mamba"], h, config)
            else:
                mixed = attention_mixer(lp["attn"], h, config)
            seq = seq + m * mixed
            h = rms_norm(seq, lp["ffn_norm"]["scale"], eps)
            return seq + m * swiglu(lp["ffn"], h, config)

        x = jax.lax.map(layer, x)
    return rms_norm(x, params["final_norm"]["scale"], eps)


def logits_fn(params, ids, config):
    """Every position's logits over the slice: [B, S, V]."""
    h = hidden_states(params, ids, config)
    return h @ params["embed"]["tokens"].T / config["logits_scaling"]


def loss_fn(params, batch, config):
    """The mean next-token cross entropy of pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, as
    ``models/granite_h.py::make_loss_fn``'s."""
    b, s = batch["inputs"].shape
    h = hidden_states(params, batch["inputs"], config).reshape(b * s, -1)
    targets = batch["targets"].reshape(b * s)
    size = next(n for n in range(min(LOSS_BLOCK, b * s), 0, -1)
                if (b * s) % n == 0)

    @jax.checkpoint
    def block_nll(args):               # one block's [size, V] logits at a time
        h, targets = args
        logits = h @ params["embed"]["tokens"].T / config["logits_scaling"]
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    return jnp.sum(jax.lax.map(block_nll, (
        h.reshape(-1, size, h.shape[-1]),
        targets.reshape(-1, size)))) / (b * s)


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value and its gradients with respect to the named leaves
    only (``"layer0/mamba/in_proj/kernel"``): the whole backward pass runs,
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

    return jax.value_and_grad(loss_of)({name: leaf(name) for name in names})
