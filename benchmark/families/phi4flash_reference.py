"""Phi-4-mini-flash-reasoning's plain reference: forward pass, loss and,
through ``jax.grad``, the gradients, in straightforward ``jax.numpy`` and
float32.

The one copy (``tests/test_blocks.py`` holds that no second one exists):
``tests/test_phi4flash.py`` holds ``ps_tpu/models/phi4flash.py``, the blocks
it calls and ``ps_tpu/ops/selective_scan.py`` to it on the CPU, and it decides
``correct`` on the chip, where a later PR to the program cannot edit it.

Written from the published ``config.json`` (``model_type: phi4flash``,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json),
the SambaY paper (Ren et al., arXiv:2507.06607: self-decoder, cross-decoder,
the Gated Memory Unit), Mamba's selective scan (Gu & Dao, arXiv:2312.00752
section 3, and ``mamba_ssm``'s reference ``selective_scan_ref``), Differential
Transformer (Ye et al., arXiv:2410.05258 section 2) and the model's
``modeling_phi4flash.py`` as the writer knows them (no network here), and
from nothing in the program under test: no import of the model, of its blocks,
of its scan or of any kernel. The state-space scan runs **token by token** (a
``lax.scan`` over tokens, in blocks of ``TOKEN_BLOCK`` under
``jax.checkpoint`` and ``CHANNEL_BLOCK`` channels at a time, so that the
gradient of 16,384 tokens keeps 256 states of a block of channels and never
[S, d_inner, N]); attention forms whole rows of the score matrix (in blocks of
query rows) under an explicit mask; the loss is taken a block of positions at
a time. Call it under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs in bf16 passes. ``config`` is a plain dict with
the published keys and the cut's (``first_layer``, ``published``); ``params``
is a tree of f32 arrays, matrices stored ``[in, out]`` (the transpose of
``nn.Linear``'s), the taps ``[channels, taps]`` (``nn.Conv1d``'s weight
without its middle axis), a layer's under its **published** index::

    {"embed": {"tokens"}, "final_norm": {"scale", "bias"},
     "layer<i>": {"norm", "ffn_norm": {"scale", "bias"},
       "ffn": {"w_in", "w_out": {"kernel"}},
       "mamba": {"in_proj", "x_proj", "out_proj": {"kernel"}, "conv",
                 "dt_proj": {"kernel", "bias"}, "A_log", "D"}
       or "gmu": {"in_proj", "out_proj": {"kernel"}}
       or "attn": {"qkv" or "q", "out": {"kernel", "bias"}, "lambda_q1",
                   "lambda_k1", "lambda_q2", "lambda_k2",
                   "head_norm": {"scale"}}}}

The equations, a sequence of ids ``t`` [S] at a time. ``E`` [V, D] is the one
tensor that embeds and, transposed, is the head; ``LN`` is a LayerNorm with a
learned scale and bias and eps ``layer_norm_eps``. With ``L`` the whole
model's layers, layer ``i`` (counted from 0 in the whole model) is::

    even i <  L/2      mamba          Mixer = Mamba(u)
    odd  i <  L/2      window         Mixer = Diff(q, k, v; 0 <= a - b < sliding_window)
    i == L/2           mamba_memory   Mixer = Mamba(u), and M = its scan output
    i == L/2 + 1       full           Mixer = Diff(q, k, v; b <= a), and K, V = its k, v
    even i >= L/2 + 2  gmu            Mixer = (M * silu(u W_in)) W_out
    odd  i >= L/2 + 2  cross          Mixer = Diff(u W_q + b_q, K, V; b <= a)

    x = E[t]
    per layer:  h = x + Mixer(LN(x));  x = h + (silu(g) * v) W_down,
                [g | v] = LN'(h) W_gate_up
    logits = LN_f(x) E^T;  loss = mean over positions of -log softmax(logits)[target]

``Mamba(u)`` (``d_inner`` = 2 D channels, a state of N = 16 a channel, 4
taps, ``dt_rank`` = ceil(D / 16))::

    [x | z] = u W_in
    x = silu(conv(x) + b)            # depthwise, causal, 4 taps, zero pad
    [delta | B | C] = x W_x          # dt_rank, N, N
    dt = softplus(delta W_dt + b_dt) # [S, d_inner], > 0
    A = -exp(A_log)                  # [d_inner, N]
    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) outer B_t,   h_{-1} = 0
    y_t = h_t C_t + D * x_t          # the memory M of a mamba_memory layer
    out = (y * silu(z)) W_out

``Diff`` (h = ``num_attention_heads`` / 2 pairs of query heads on
``num_key_value_heads`` / 2 pairs of K/V heads, d = D / ``num_attention_heads``
= 64; q, k, v = split(u W_qkv + b); consecutive heads are a pair, and a pair
of value heads is one value head 2 d wide; K/V pair ``j`` serves the query
pairs ``j h / h_kv .. (j + 1) h / h_kv - 1``)::

    a_j = softmax(mask(q_j k_j^T / sqrt(d))) v           j = 1, 2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 i)                  # i: the layer's index
    out = ((1 - lambda_init) * rmsnorm_{2d}(a_1 - lambda a_2) * w) W_o + b_o

Departures from ``modeling_phi4flash.py``:

- ``num_hidden_layers`` counts a cut: layers ``first_layer`` to ``first_layer
  + num_hidden_layers - 1`` of the ``published.num_hidden_layers``, under
  their own indices; ``vocab_size`` is this chip's slice: ids, logits and
  loss are over it.
- The published code spells one map against a 2 d wide value head as two
  flash calls on the halves of v, concatenated (its kernel takes no values
  wider than keys): the same numbers.
- No dropout (both ``pdrop`` 0), no document mask, no padding mask, no cache,
  no state carried from one sequence to the next: training on whole sequences
  from a zero state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 256
#: tokens of the recurrence under one ``jax.checkpoint``
TOKEN_BLOCK = 64
#: channels whose states are scanned at a time
CHANNEL_BLOCK = 1280
#: positions whose logits are formed at a time
LOSS_BLOCK = 1024
#: what later layers read, and the kind of layer that makes it
PRODUCERS = {"memory": "mamba_memory", "kv": "full"}


def kinds(layers, mb_per_layer):
    """The table above, for the whole model."""
    half = layers // 2
    out = []
    for i in range(layers):
        if i % mb_per_layer == 0:
            out.append("mamba" if i < half else
                       "mamba_memory" if i == half else "gmu")
        else:
            out.append("window" if i < half else
                       "full" if i == half + 1 else "cross")
    return out


def held_layers(config):
    """(index in the whole model, kind) of the layers the dict holds."""
    whole = config.get("published", {}).get("num_hidden_layers",
                                            config["num_hidden_layers"])
    first = config.get("first_layer", 0)
    table = kinds(whole, config["mb_per_layer"])
    return [(i, table[i])
            for i in range(first, first + config["num_hidden_layers"])]


def layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def linear(p, x):
    out = x @ p["kernel"]
    return out + p["bias"] if "bias" in p else out


def conv_silu(x, w, b):
    """``silu`` of the depthwise causal convolution of ``x`` [S, C] with the
    taps ``w`` [C, taps] plus the bias ``b`` [C]: ``nn.Conv1d`` with padding
    taps - 1, cut to the first S outputs."""
    taps = w.shape[-1]
    padded = jnp.concatenate([jnp.zeros_like(x[:taps - 1]), x], axis=0)
    return jax.nn.silu(sum(w[:, j] * padded[j:j + x.shape[0]]
                           for j in range(taps)) + b)


def selective_scan(x, dt, a, b, c):
    """Mamba-1's recurrence of one sequence, token by token: ``x``, ``dt``
    [S, C], ``a`` [C, N] (< 0), ``b`` and ``c`` [S, N] -> ``y`` [S, C]
    without the skip."""
    seq, channels = x.shape

    def token(state, args):            # state [C', N]
        x_t, dt_t, a_, b_t, c_t = args
        state = jnp.exp(dt_t[:, None] * a_) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t

    size = next(s for s in range(min(TOKEN_BLOCK, seq), 0, -1)
                if seq % s == 0)
    width = next(w for w in range(min(CHANNEL_BLOCK, channels), 0, -1)
                 if channels % w == 0)

    @jax.checkpoint
    def of_channels(args):             # one block of channels, all tokens
        x_, dt_, a_ = args             # [S, C'], [S, C'], [C', N]

        @jax.checkpoint
        def block(state, rows):
            x_b, dt_b, b_b, c_b = rows
            return jax.lax.scan(
                lambda s, r: token(s, (r[0], r[1], a_, r[2], r[3])), state,
                (x_b, dt_b, b_b, c_b))

        _, out = jax.lax.scan(
            block, jnp.zeros((width, a.shape[1]), x.dtype),
            tuple(t.reshape(seq // size, size, -1) for t in (x_, dt_, b, c)))
        return out.reshape(seq, width)

    def blocks(t):                     # [S, C] -> [C / C', S, C']
        return jnp.moveaxis(t.reshape(seq, channels // width, width), 1, 0)

    out = jax.lax.map(of_channels, (blocks(x), blocks(dt),
                                    a.reshape(channels // width, width, -1)))
    return jnp.moveaxis(out, 0, 1).reshape(seq, channels)


def mamba_mixer(lp, u, config):
    """The Mamba-1 mixer of one sequence ``u`` [S, D]: its output and the
    scan's ``y`` with the skip, before the gate."""
    inner = 2 * config["hidden_size"]
    n = lp["A_log"].shape[1]
    rank = math.ceil(config["hidden_size"] / 16)
    projected = u @ lp["in_proj"]["kernel"]
    x, z = projected[:, :inner], projected[:, inner:]
    x = conv_silu(x, lp["conv"]["kernel"], lp["conv"]["bias"])
    dbc = x @ lp["x_proj"]["kernel"]
    dt = jax.nn.softplus(linear(lp["dt_proj"], dbc[:, :rank]))
    y = selective_scan(x, dt, -jnp.exp(lp["A_log"]), dbc[:, rank:rank + n],
                       dbc[:, rank + n:]) + lp["D"] * x
    return (y * jax.nn.silu(z)) @ lp["out_proj"]["kernel"], y


def softmax_rows(q, k, v, window):
    """``softmax(mask(q k^T / sqrt(d))) v`` of ``q`` [S, h, d] over ``k``
    [S, h, d], ``v`` [S, h, d_v] (the heads already matched), causal, under a
    window of ``window`` keys where one is given, rows in blocks."""
    seq, _, d = q.shape
    block = next(s for s in range(min(QUERY_BLOCK, seq), 0, -1)
                 if seq % s == 0)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        apart = (start + jnp.arange(block))[:, None] - jnp.arange(seq)
        seen = apart >= 0
        if window is not None:
            seen = seen & (apart < window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(
        seq, q.shape[1], -1)


def lambda_init(depth):
    """Differential attention's constant at a layer's index in the whole
    model."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def combine(a1, a2, lam, init, scale, eps):
    """The two maps' outputs [S, h, 2 d] -> one: the difference, normed a
    head, times ``1 - lambda_init``."""
    return rms_norm(a1 - lam * a2, scale, eps) * (1 - init)


def diff_attention(lp, q, k, v, depth, window, config):
    """Differential attention of one sequence: ``q`` [S, D], ``k``, ``v``
    [S, kv d] as projected -> [S, D] before the out projection."""
    seq = q.shape[0]
    heads, kv_heads = (config["num_attention_heads"] // 2,
                       config["num_key_value_heads"] // 2)
    d = config["hidden_size"] // config["num_attention_heads"]
    q = q.reshape(seq, heads, 2, d)
    # K/V pair j serves heads / kv_heads consecutive query pairs
    k = jnp.repeat(k.reshape(seq, kv_heads, 2, d), heads // kv_heads, axis=1)
    v = jnp.repeat(v.reshape(seq, kv_heads, 2 * d), heads // kv_heads, axis=1)
    a1 = softmax_rows(q[:, :, 0], k[:, :, 0], v, window)
    a2 = softmax_rows(q[:, :, 1], k[:, :, 1], v, window)
    init = lambda_init(depth)
    lam = jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"])) \
        - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init
    return combine(a1, a2, lam, init, lp["head_norm"]["scale"],
                   config["layer_norm_eps"]).reshape(seq, -1)


def attention_mixer(lp, u, kv, kind, depth, config):
    """A ``window``, ``full`` or ``cross`` layer's mixer of one sequence
    ``u`` [S, D]; ``kv`` the ``full`` layer's k, v where ``cross``. Returns
    the output and the k, v it read."""
    hidden = config["hidden_size"]
    if kind == "cross":
        q, (k, v) = linear(lp["q"], u), kv
    else:
        width = (hidden // config["num_attention_heads"]
                 * config["num_key_value_heads"])
        qkv = linear(lp["qkv"], u)
        q, k, v = (qkv[:, :hidden], qkv[:, hidden:hidden + width],
                   qkv[:, hidden + width:])
    window = config["sliding_window"] if kind == "window" else None
    a = diff_attention(lp, q, k, v, depth, window, config)
    return linear(lp["out"], a), (k, v)


def swiglu(lp, x, config):
    width = config["intermediate_size"]
    both = x @ lp["w_in"]["kernel"]
    return (jax.nn.silu(both[:, :width]) * both[:, width:]) \
        @ lp["w_out"]["kernel"]


def run_layers(params, x, config):
    """The held layers over one sequence's residual stream ``x`` [S, D]."""
    eps = config["layer_norm_eps"]
    memory = kv = None
    for i, kind in held_layers(config):
        lp = params[f"layer{i}"]

        # recomputed in the backward pass; what later layers read goes
        # through as it came
        @jax.checkpoint
        def layer(x, memory, kv, lp=lp, kind=kind, i=i):
            u = layer_norm(x, lp["norm"], eps)
            if kind in ("mamba", "mamba_memory"):
                mixed, y = mamba_mixer(lp["mamba"], u, config)
                if kind == PRODUCERS["memory"]:
                    memory = y
            elif kind == "gmu":
                gate = jax.nn.silu(u @ lp["gmu"]["in_proj"]["kernel"])
                mixed = (memory * gate) @ lp["gmu"]["out_proj"]["kernel"]
            else:
                mixed, read = attention_mixer(lp["attn"], u, kv, kind, i,
                                              config)
                if kind == PRODUCERS["kv"]:
                    kv = read
            x = x + mixed
            return (x + swiglu(lp["ffn"], layer_norm(x, lp["ffn_norm"], eps),
                               config), memory, kv)

        x, memory, kv = layer(x, memory, kv)
    return x


def hidden_states(params, ids, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]``, one sequence at a time."""
    x = params["embed"]["tokens"][ids]
    x = jax.lax.map(lambda seq: run_layers(params, seq, config), x)
    return layer_norm(x, params["final_norm"], config["layer_norm_eps"])


def logits_fn(params, ids, config):
    """Every position's logits over the slice: [B, S, V]."""
    return hidden_states(params, ids, config) @ params["embed"]["tokens"].T


def loss_fn(params, batch, config):
    """The mean next-token cross entropy of pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, as
    ``models/phi4flash.py::make_loss_fn``'s."""
    b, s = batch["inputs"].shape
    h = hidden_states(params, batch["inputs"], config).reshape(b * s, -1)
    targets = batch["targets"].reshape(b * s)
    size = next(n for n in range(min(LOSS_BLOCK, b * s), 0, -1)
                if (b * s) % n == 0)

    @jax.checkpoint
    def block_nll(args):               # one block's [size, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["embed"]["tokens"].T, -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    return jnp.sum(jax.lax.map(block_nll, (
        h.reshape(-1, size, h.shape[-1]),
        targets.reshape(-1, size)))) / (b * s)


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value and its gradients with respect to the named leaves
    only (``"layer14/mamba/A_log"``): the whole backward pass runs, but no
    gradient of the other leaves is kept."""
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
