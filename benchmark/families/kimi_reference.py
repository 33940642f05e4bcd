"""Kimi-Linear's plain reference: forward pass, loss and, through ``jax.grad``,
the gradients, in straightforward ``jax.numpy`` and float32, for one chip's
share of an expert-parallel group.

Two copies of this file exist, letter for letter: ``tests/kimi_reference.py``
(what the CPU tests hold ``ps_tpu/models/kimi_linear.py`` and
``ps_tpu/ops/kda.py`` to) and ``benchmark/families/kimi_reference.py`` (the
yardstick's own, which decides ``correct`` on the chip and which a later PR to
the program cannot edit). ``tests/test_kimi_linear.py`` holds the two equal, in
text and in value.

Written from the published ``config.json`` (``model_type: kimi_linear``), the
paper (arXiv:2510.26692), the layer equations of the model's
``modeling_kimi.py`` and the ``fla`` library's naive recurrence as the writer
knows them (no network here), and from nothing in ``ps_tpu``: no import of
``models/kimi_linear.py``, ``ops/kda.py``, ``ops/moe.py`` or any kernel. The
delta rule runs **token by token** (a ``lax.scan`` over tokens, in blocks of
``TOKEN_BLOCK`` under ``jax.checkpoint`` so that the gradient of 8,192 tokens
keeps 128 states a head and not 8,192); attention forms whole rows of the
score matrix over the concatenated 192-wide keys (in blocks of query rows);
the experts are a loop over the held ones, each on every token, with a 0/1
mask that keeps what the router chose; the shared expert is a SwiGLU on
every token. Call it under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul otherwise runs in bf16 passes. ``config`` is a plain dict
with the published keys; ``params`` is a tree of f32 arrays, matrices stored
``[in, out]`` (the transpose of ``nn.Linear``'s), a convolution's filter
``[channels, taps]`` (``nn.Conv1d``'s weight without its middle axis)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layer<i>": {"mixer_norm", "ffn_norm",
       "kda": {"q", "k", "v", "f_a", "f_b", "b", "g_a", "g_b", "out":
               {"kernel"}, "q_conv", "k_conv", "v_conv", "dt_bias", "A_log",
               "out_norm": {"scale"}}
       or "attn": {"q", "kv_a", "kv_b", "out": {"kernel"}, "kv_norm"},
       "ffn": {"w1", "w3", "w2"}
       or "moe": {"router", "gate", "up", "down", "shared": {"w1", "w3",
                  "w2"}}}}

The layer equations, a sequence ``x`` [S, D] at a time (pre-norm residual
blocks, RMSNorm eps ``rms_norm_eps``)::

    x += mixer(norm(x));  x += ffn(norm(x));  logits = norm(x) W_head

KDA mixer (layers ``linear_attn_config.kda_layers``, counted from 1; H heads
of K = V = ``head_dim``)::

    q = silu(conv(x Wq)), k = silu(conv(x Wk)), v = silu(conv(x Wv))
        # depthwise, causal, short_conv_kernel_size taps, no bias, zero pad
    q, k: each head's K channels divided by sqrt(sum of squares + 1e-6);
        q times K ** -0.5
    g    = -exp(A_log)[h] * softplus((x Wfa) Wfb + dt_bias)   # [S, H, K], <= 0
    beta = sigmoid(x Wb)                                      # [S, H]
    per head, S_0 = 0 [K, V]:   S *= exp(g_t)[:, None]
                                u  = beta_t * (v_t - S^T k_t)
                                S += outer(k_t, u);  o_t = S^T q_t
    y = (rmsnorm over each head's V (o) * w_norm * sigmoid((x Wga) Wgb)) Wo

MLA mixer without positions (layers ``full_attn_layers``)::

    q = x Wq -> [S, H, qk_nope + qk_rope]
    c, k_pe = split(x Wkv_a, [kv_lora_rank, qk_rope])
    k_nope, v = split(rmsnorm(c) Wkv_b -> [S, H, qk_nope + v_head_dim])
    k = concat(k_nope, k_pe to every head)
    causal softmax(q k^T (qk_nope + qk_rope) ** -0.5) v, then Wo

Expert block (layers past ``first_k_dense_replace``; the leading ones have a
dense SwiGLU of ``intermediate_size``)::

    scores = sigmoid(x Wr);  picks = top-k of scores + bias
    w = the picks' scores / (their sum + 1e-20) * routed_scaling_factor
    y = sum_k w_k expert_k(x) + shared(x)       # SwiGLU: W2(silu(W1 x) * W3 x)

Departures from ``modeling_kimi.py``:

- **The share.** ``num_experts`` is the number of experts held here, of
  ``router_width`` published ones, from ``expert_start`` on. The router scores
  all ``router_width``, every token picks its ``num_experts_per_token`` among
  all of them and its weights are renormalised over all its picks; only the
  held experts are computed, and what the absent ones would have added is left
  out of the layer's output and of everything after it. The shared expert is
  whole here, as on every chip of the group. ``vocab_size`` is this chip's
  slice: ids, logits and loss are over it.
- ``mla_use_nope``: no rotary embedding is applied to the ``qk_rope`` channels
  (the published model's setting; ``rope_theta`` is unused), and
  ``q_lora_rank`` is null, so q has no latent. Another value of either is not
  computed here.
- ``num_expert_group`` 1 and ``topk_group`` 1: grouped top-k over one group
  is plain top-k. More groups are not computed here.
- The low-rank width of the decay gate (``Wfa``) and of the output gate
  (``Wga``) is ``head_dim`` (128), and no projection has a bias: the config
  gives neither.
- The selection bias (``e_score_correction_bias``) is updated here, by the
  rule it exists for (loss-free balancing, Wang et al. 2024,
  arXiv:2408.15664): after the step, per layer, ``b_e += bias_update_rate *
  sign(mean(c) - c_e)`` with ``c`` the step's picks over all ``router_width``
  experts. ``modeling_kimi.py`` only reads the buffer.
- The loss is the next-token cross entropy alone: no auxiliary loss.
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
    """The gated delta rule of one sequence, token by token: ``q``, ``k``,
    ``g`` [S, H, K], ``v`` [S, H, V], ``beta`` [S, H] -> ``o`` [S, H, V]."""
    seq, heads, width = q.shape

    def token(state, args):            # state [H, K, V]
        q_t, k_t, v_t, g_t, beta_t = args
        state = state * jnp.exp(g_t)[..., None]
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


def kda_mixer(lp, x, config):
    """Kimi Delta Attention of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    heads = config["linear_attn_config"]["num_heads"]

    def head_wise(y):
        return y.reshape(seq, heads, -1)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k, v = (head_wise(conv_silu(x @ lp[n]["kernel"], lp[n + "_conv"]))
               for n in ("q", "k", "v"))
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = -jnp.exp(lp["A_log"])[:, None] * head_wise(jax.nn.softplus(
        x @ lp["f_a"]["kernel"] @ lp["f_b"]["kernel"] + lp["dt_bias"]))
    beta = jax.nn.sigmoid(x @ lp["b"]["kernel"])
    o = delta_rule(q, k, v, g, beta)
    gate = head_wise(x @ lp["g_a"]["kernel"] @ lp["g_b"]["kernel"])
    o = rms_norm(o, lp["out_norm"]["scale"], config["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate)
    return o.reshape(seq, -1) @ lp["out"]["kernel"]


def mla_mixer(lp, x, config):
    """Causal multi-head latent attention of one sequence ``x`` [S, D], no
    position anywhere."""
    seq = x.shape[0]
    heads = config["num_attention_heads"]
    nope, rope, rank = (config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"], config["kv_lora_rank"])
    q = (x @ lp["q"]["kernel"]).reshape(seq, heads, nope + rope)
    latent = x @ lp["kv_a"]["kernel"]
    c, k_pe = latent[:, :rank], latent[:, rank:]
    kv = (rms_norm(c, lp["kv_norm"]["scale"], config["rms_norm_eps"])
          @ lp["kv_b"]["kernel"]).reshape(seq, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (seq, heads, rope))], -1)
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(nope + rope)
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, -1)
    return out @ lp["out"]["kernel"]


def experts(lp, x, bias, config):
    """The expert layer on tokens ``x`` [T, D] with the selection ``bias``
    [router_width]: the held experts' part of the output plus the shared
    expert's [T, D], and the 0/1 mask [T, router_width] of each token's
    picks over all experts."""
    width, top_k = config["router_width"], config["num_experts_per_token"]
    start, held = config["expert_start"], config["num_experts"]
    scores = jax.nn.sigmoid(x @ lp["router"]["kernel"])
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = scores * mask
    if config["moe_renormalize"]:
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
    return total + jax.checkpoint(swiglu)(lp["shared"], x), mask


def hidden_states(params, ids, expert_bias, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]`` and each expert layer's picks per expert over all
    ``router_width`` [expert layers, router_width]."""
    eps = config["rms_norm_eps"]
    linear = config["linear_attn_config"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    counts = []
    for i in range(config["num_hidden_layers"]):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["mixer_norm"]["scale"], eps)
        # one sequence at a time, recomputed in the backward pass
        if i + 1 in linear["kda_layers"]:
            mixer = lambda seq: kda_mixer(lp["kda"], seq, config)  # noqa: E731
        else:
            mixer = lambda seq: mla_mixer(lp["attn"], seq,  # noqa: E731
                                          config)
        x = x + jax.lax.map(jax.checkpoint(mixer), h)
        h = rms_norm(x, lp["ffn_norm"]["scale"], eps)
        if i < config["first_k_dense_replace"]:
            x = x + jax.lax.map(
                jax.checkpoint(lambda seq: swiglu(lp["ffn"], seq)), h)
        else:
            out, mask = experts(lp["moe"], h.reshape(b * s, -1),
                                expert_bias[len(counts)], config)
            x = x + out.reshape(b, s, -1)
            counts.append(jnp.sum(mask, axis=0))
    return (rms_norm(x, params["final_norm"]["scale"], eps),
            jnp.stack(counts))


def logits_fn(params, ids, expert_bias, config):
    """Every position's logits over the slice: [B, S, V]."""
    h, _ = hidden_states(params, ids, expert_bias, config)
    return h @ params["head"]["kernel"]


def loss_fn(params, batch, expert_bias, config):
    """``(loss, aux)`` as ``models/kimi_linear.py::make_loss_fn``'s, of
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}`` and the
    selection bias ``expert_bias`` [expert layers, router_width] of this
    step."""
    start, held = config["expert_start"], config["num_experts"]
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
    named leaves only (``"layer0/kda/k/kernel"``): the whole backward pass
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
        return loss_fn(with_leaves(params, "", leaves), batch, expert_bias,
                       config)

    return jax.value_and_grad(loss_of, has_aux=True)(
        {name: leaf(name) for name in names})
