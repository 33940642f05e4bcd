"""Nemotron-H's plain reference: forward pass, loss and, through ``jax.grad``,
the gradients, in straightforward ``jax.numpy`` and float32, for one chip's
share of a group that divides each layer by heads and by experts.

Two copies of this file exist, letter for letter:
``tests/nemotron_h_reference.py`` (what the CPU tests hold
``ps_tpu/models/nemotron_h.py`` and ``ps_tpu/ops/ssd.py`` to) and
``benchmark/families/nemotron_h_reference.py`` (the yardstick's own, which
decides ``correct`` on the chip and which a later PR to the program cannot
edit). ``tests/test_nemotron_h.py`` holds the two equal, in text and in value.

Written from the published ``config.json`` (``model_type: nemotron_h``), the
family's published description, the layer equations of its
``modeling_nemotron_h.py`` and ``mamba_ssm``'s reference selective scan as the
writer knows them (no network here), and from nothing in the program under
test: no import of the model, of its scan, of its expert ops or of any kernel.
The state-space scan runs **token by token** (a ``lax.scan`` over tokens, in
blocks of ``TOKEN_BLOCK`` under ``jax.checkpoint`` so that the gradient of
8,192 tokens keeps 128 states a head and not 8,192; no chunk and no cumulated
sum anywhere); attention forms whole rows of the score matrix (in blocks of
query rows); the experts are a loop over the held ones, each on every token's
latent, with a 0/1 mask that keeps what the router chose; the shared expert
runs on every token at the model's width. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys; ``params`` is a tree of f32 arrays, matrices stored ``[in, out]`` (the
transpose of ``nn.Linear``'s), the filter ``[channels, taps]`` (``nn.Conv1d``'s
weight without its middle axis)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layer<i>": {"norm": {"scale"},
       "mamba": {"in_proj", "out_proj": {"kernel"}, "conv": {"kernel",
                 "bias"}, "dt_bias", "A_log", "D", "out_norm": {"scale"}}
       or "attn": {"q", "k", "v", "out": {"kernel"}}
       or "moe": {"router", "latent_down", "latent_up": {"kernel"}, "w1",
                  "w2", "shared": {"w1", "w2": {"kernel"}}}}}

The layer equations, a sequence ``x`` [S, D] at a time. Every layer is one
part, behind a pre-norm (RMSNorm with a learned scale, eps
``layer_norm_epsilon``), and ``hybrid_override_pattern`` names each layer's::

    x += part(norm(x));  after the last:  logits = norm_f(x) W_head

``M``, a Mamba-2 mixer (H = ``mamba_num_heads`` heads of P =
``mamba_head_dim``, G = ``n_groups`` groups of B and C, state N =
``ssm_state_size``; ``d_inner`` = H P)::

    z, xBC, dt = split(u W_in, [d_inner, d_inner + 2 G N, H])
    xBC = silu(conv(xBC) + b)    # depthwise, causal, conv_kernel taps, zero pad
    x, B, C = split(xBC, [d_inner, G N, G N])    # x [S, H, P]; B, C [S, G, N]
    dt = softplus(dt + dt_bias)                  # [S, H], > 0
    per head h of group g, S_0 = 0 [P, N]:
        S = exp(dt_t * -exp(A_log[h])) * S + dt_t * outer(x_t, B_t[g])
        y_t = S @ C_t[g] + D[h] * x_t
    y = rmsnorm over each group's d_inner / G channels (y * silu(z)) * w_norm
    out = y W_out

``*``, attention without positions (h = ``num_attention_heads`` query heads of
``head_dim`` on ``num_key_value_heads`` K/V heads, each serving h / kv
consecutive query heads; no bias)::

    causal softmax(q k^T head_dim ** -0.5) v, then W_o

``E``, the latent expert layer::

    scores = sigmoid(u W_r);  picks = top-k of scores + bias
    w = the picks' scores / (their sum + 1e-20) * routed_scaling_factor
    z = u W_down                                     # the latent, 1,024 wide
    y = (sum_k w_k relu(z W1_k) ** 2 W2_k) W_up  +  relu(u V1) ** 2 V2

Departures from ``modeling_nemotron_h.py``:

- **The share.** ``mamba_num_heads`` / ``n_groups``, ``num_attention_heads`` /
  ``num_key_value_heads`` and ``n_routed_experts`` count what is held here, of
  the published 128 / 8, 32 / 2 and ``router_width`` (512), from
  ``mamba_head_start``, ``attention_head_start`` and ``expert_start`` on. A
  mixer computes its heads' part of the sum after the out projection; the
  router scores all ``router_width``, every token picks its
  ``num_experts_per_tok`` among all of them and its weights are renormalised
  over all its picks; only the held experts are computed, and what the absent
  heads and experts would have added is left out of the layer's output and of
  everything after it. The shared expert, the latent projections, the router
  and the norms are whole here, as on every chip of the group.
  ``vocab_size`` is this chip's slice: ids, logits and loss are over it.
- No rotary embedding: the family's attention layers carry no position
  (``rope_theta`` and ``partial_rotary_factor`` are read by nothing).
- ``n_group`` 1 and ``topk_group`` 1: grouped top-k over one group is plain
  top-k. More groups are not computed here.
- No projection has a bias (``use_bias``, ``mlp_bias``, ``attention_bias``,
  ``mamba_proj_bias`` false); the filter has one (``use_conv_bias``).
- ``num_nextn_predict_layers`` 0: the multi-token-prediction module is not
  computed; the loss is the next-token cross entropy alone, no auxiliary loss.
- The selection bias (``e_score_correction_bias``) is updated here, by the
  rule it exists for (loss-free balancing, Wang et al. 2024,
  arXiv:2408.15664): after the step, per layer, ``b_e += bias_update_rate *
  sign(mean(c) - c_e)`` with ``c`` the step's picks over all ``router_width``
  experts. ``modeling_nemotron_h.py`` only reads the buffer.
- No dropout, no document mask, no padding mask, no cache, no state carried
  from one sequence to the next: training on whole sequences from a zero
  state.
- Where two scores tie exactly, which of them ``top_k`` takes is the
  library's choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 1024
#: tokens of the recurrence under one ``jax.checkpoint``
TOKEN_BLOCK = 64


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def relu2_ffn(lp, x):
    return (jnp.square(jax.nn.relu(x @ lp["w1"]["kernel"]))
            @ lp["w2"]["kernel"])


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


def mamba_mixer(lp, x, config):
    """The Mamba-2 mixer of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    heads, groups = config["mamba_num_heads"], config["n_groups"]
    inner = heads * config["mamba_head_dim"]
    state = groups * config["ssm_state_size"]
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
    y = y.reshape(seq, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(seq, groups, -1),
                 lp["out_norm"]["scale"].reshape(groups, -1),
                 config["layer_norm_epsilon"])
    return y.reshape(seq, inner) @ lp["out_proj"]["kernel"]


def attention_mixer(lp, x, config):
    """Causal grouped-query attention of one sequence ``x`` [S, D], no
    position anywhere."""
    seq = x.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    q = (x @ lp["q"]["kernel"]).reshape(seq, heads, dim)
    k, v = (jnp.repeat((x @ lp[n]["kernel"]).reshape(seq, kv_heads, dim),
                       heads // kv_heads, axis=1) for n in ("k", "v"))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(dim)
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, -1)
    return out @ lp["out"]["kernel"]


def experts(lp, x, bias, config):
    """The latent expert layer on tokens ``x`` [T, D] with the selection
    ``bias`` [router_width]: the held experts' part of the output plus the
    shared expert's [T, D], and the 0/1 mask [T, router_width] of each
    token's picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_tok"]
    start, held = config["expert_start"], config["n_routed_experts"]
    scores = jax.nn.sigmoid(x @ lp["router"]["kernel"])
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = scores * mask
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    weights = weights * config["routed_scaling_factor"]
    latent = x @ lp["latent_down"]["kernel"]

    @jax.checkpoint
    def expert(w1, w2, w):             # w [T]: this expert's weight per token
        return w[:, None] * (jnp.square(jax.nn.relu(latent @ w1)) @ w2)

    # a loop over the held experts, each on all tokens; a scan keeps the
    # compile short and the memory at one expert's
    total, _ = jax.lax.scan(
        lambda total, args: (total + expert(*args), None),
        jnp.zeros_like(latent),
        (lp["w1"], lp["w2"], weights[:, start:start + held].T))
    return (total @ lp["latent_up"]["kernel"]
            + jax.checkpoint(relu2_ffn)(lp["shared"], x)), mask


def hidden_states(params, ids, expert_bias, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]`` and each expert layer's picks per expert over all
    ``router_width`` [expert layers, router_width]."""
    eps = config["layer_norm_epsilon"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    counts = []
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["norm"]["scale"], eps)
        if kind == "E":
            out, mask = experts(lp["moe"], h.reshape(b * s, -1),
                                expert_bias[len(counts)], config)
            x = x + out.reshape(b, s, -1)
            counts.append(jnp.sum(mask, axis=0))
            continue
        # one sequence at a time, recomputed in the backward pass
        if kind == "M":
            mixer = lambda seq: mamba_mixer(lp["mamba"], seq,  # noqa: E731
                                            config)
        else:
            mixer = lambda seq: attention_mixer(lp["attn"], seq,  # noqa: E731
                                                config)
        x = x + jax.lax.map(jax.checkpoint(mixer), h)
    return (rms_norm(x, params["final_norm"]["scale"], eps),
            jnp.stack(counts))


def logits_fn(params, ids, expert_bias, config):
    """Every position's logits over the slice: [B, S, V]."""
    h, _ = hidden_states(params, ids, expert_bias, config)
    return h @ params["head"]["kernel"]


def loss_fn(params, batch, expert_bias, config):
    """``(loss, aux)`` as ``models/nemotron_h.py::make_loss_fn``'s, of
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}`` and the
    selection bias ``expert_bias`` [expert layers, router_width] of this
    step."""
    start, held = config["expert_start"], config["n_routed_experts"]
    b, s = batch["inputs"].shape
    h, counts = hidden_states(params, batch["inputs"], expert_bias, config)

    @jax.checkpoint
    def sequence_nll(args):            # one sequence's [S, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))

    ce = jnp.sum(jax.lax.map(sequence_nll, (h, batch["targets"]))) / (b * s)
    new_bias = expert_bias + config["bias_update_rate"] * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)
    counts = counts.astype(jnp.int32)
    return ce, {"ce": ce, "expert_tokens": counts,
                "held_tokens": counts[:, start:start + held],
                "expert_bias": new_bias}


def witness_grads(params, batch, expert_bias, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layer0/mamba/in_proj/kernel"``): the whole backward
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
