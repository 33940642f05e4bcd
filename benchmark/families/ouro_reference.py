"""Ouro's plain reference: forward pass, loss and, through ``jax.grad``, the
gradients, in straightforward ``jax.numpy`` and float32.

The one copy: ``tests/test_ouro.py`` holds ``ps_tpu/models/ouro.py`` and
``ps_tpu/models/blocks.py::blocked_head_nll`` to it on the CPU, and it decides
``correct`` on the chip, where a later PR to the program cannot edit it.

Written from the published ``config.json`` (``model_type: ouro``,
https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json), from the
paper's section 3 (ByteDance Seed, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) and from the checkpoint's own
``modeling_ouro.py`` as the writer knows them (no network here), and from
nothing in the program under test: no import of the model, of its blocks or of
any kernel. The passes are a **Python loop over t and l**: ``T x L`` layer
applications stand in the traced program one after another, each reading the
parameters it is handed, so a weight's gradient is the sum that ``jax.grad``
makes of its ``T`` uses and nothing here sums cotangents by hand; no scan over
passes or layers anywhere. Attention forms whole rows of the score matrix (in
blocks of query rows); the loss is taken a block of positions at a time. Call
it under ``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys and ``exit_entropy_beta``; ``params`` is a tree of f32 arrays, matrices
stored ``[in, out]`` (the transpose of ``nn.Linear``'s)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "gate": {"kernel" [D, 1], "bias" [1]},
     "layer<i>": {"attn_norm", "attn_out_norm", "ffn_norm", "ffn_out_norm":
                  {"scale"}, "attn": {"q", "k", "v", "out": {"kernel"}},
                  "ffn": {"w1", "w3", "w2": {"kernel"}}}}

or a sequence of ``T`` such trees, one a pass (the weights **untied**: pass
``t`` reads tree ``t``'s layers, final norm, head and gate, the embedding is
tree 0's), which is how the tests hold that the shared weight's gradient is
the sum of its uses'.

The equations, a sequence of ids ``x`` [S] at a time; every norm is an RMSNorm
``x / rms(x) * w`` with a learned scale and eps ``rms_norm_eps``, T =
``total_ut_steps``, L = ``num_hidden_layers``::

    h(0) = E[x]                                E [V, D], untied from the head
    Layer_l(h):  a = h + N2_l(Attn_l(N1_l(h)))
                 y = a + N4_l(SwiGLU_l(N3_l(a)))
    Attn:   causal softmax(rot(q) rot(k)^T * head_dim ** -0.5) v, then W_o;
            ``num_attention_heads`` heads of ``head_dim`` on
            ``num_key_value_heads`` K/V heads, no bias, no q / k norm; rot
            turns channel pairs (j, j + head_dim / 2) of every head by
            ``pos * rope_theta ** (-2 j / head_dim)``, all channels
    SwiGLU: W_2(silu(W_1 x) * W_3 x), ``intermediate_size`` wide, no bias
    pass t = 1..T:  u = h(t-1);  u = Layer_l(u) for l = 1..L, the same
                    parameters every pass;  h(t) = N_f(u)
    z(t) = h(t) W_head [V]        lambda_t = sigmoid(h(t) . w_g + b_g)
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j) for 1 < t < T;
    p_T = prod_{j<T}(1 - lambda_j)             (sum_t p_t = 1)
    loss = mean over positions of [ sum_t p_t CE(z(t), target) - beta H(p) ],
           H(p) = - sum_t p_t log p_t,  beta = ``exit_entropy_beta``

Departures from ``modeling_ouro.py`` and the paper:

- ``num_hidden_layers`` (and ``layer_types`` / ``max_window_layers`` with it)
  is a cut of the published 48; every width and the vocabulary are whole.
- The objective is the paper's first-stage one (the expected task loss under
  the exit distribution, entropy-regularised towards a uniform prior); the
  checkpoint's code computes logits and gates and trains nothing. The later
  stage that trains the gate alone against the loss's improvement is not
  here.
- ``lambda_T`` is computed and read by nothing: the last pass takes the mass
  that is left. ``early_exit_threshold`` is read by nothing: it ends
  generation early, and nothing here generates. ``max_position_embeddings``
  is read by nothing.
- No dropout, no document mask, no padding mask, no cache.
- Every layer application, block of query rows and block of the loss runs
  under a ``jax.checkpoint``: at 8,192 tokens in f32 the ``T x L`` = 32
  applications would otherwise keep 45e9 B. It changes what is kept between
  the forward and the backward pass and no value (ISSUE 63 asked for none:
  written so, the program does not fit the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 1024
#: positions whose logits are formed at a time
LOSS_BLOCK = 2048


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, theta):
    """Channel pairs (j, j + d / 2) of ``x`` [S, h, d] turned by the
    position's angle ``pos * theta ** (-2 j / d)``: every channel."""
    seq, _, dim = x.shape
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(lp, x, config):
    """Causal attention of one sequence ``x`` [S, D]."""
    seq = x.shape[0]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim, theta = config["head_dim"], config["rope_theta"]
    q = rope((x @ lp["q"]["kernel"]).reshape(seq, heads, dim), theta)
    k = rope((x @ lp["k"]["kernel"]).reshape(seq, kv_heads, dim), theta)
    v = (x @ lp["v"]["kernel"]).reshape(seq, kv_heads, dim)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * dim ** -0.5
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(seq)
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block)).reshape(seq, -1)
    return out @ lp["out"]["kernel"]


def swiglu(lp, x):
    return (jax.nn.silu(x @ lp["w1"]["kernel"]) * (x @ lp["w3"]["kernel"])) \
        @ lp["w2"]["kernel"]


def layer(lp, x, config):
    """One application of one layer to one sequence ``x`` [S, D]: each part
    between its two norms."""
    eps = config["rms_norm_eps"]
    a = x + rms_norm(attention(
        lp["attn"], rms_norm(x, lp["attn_norm"]["scale"], eps), config),
        lp["attn_out_norm"]["scale"], eps)
    return a + rms_norm(swiglu(
        lp["ffn"], rms_norm(a, lp["ffn_norm"]["scale"], eps)),
        lp["ffn_out_norm"]["scale"], eps)


def of_pass(params, t):
    """The tree pass ``t`` (from 0) reads: ``params`` itself, or its
    ``t``-th where the weights are untied."""
    return params if isinstance(params, dict) else params[t]


def run_layers(p, u, config):
    """The layers of tree ``p``, one after another, on ``u`` [B, S, D]: a
    Python loop, one sequence at a time, each application recomputed in the
    backward pass."""
    application = jax.checkpoint(lambda lp, u: jax.lax.map(
        lambda seq: layer(lp, seq, config), u))
    for i in range(config["num_hidden_layers"]):
        u = application(p[f"layer{i}"], u)
    return u


def passes(params, ids, config):
    """``[h(1), .., h(T)]``, each [B, S, D], of ``ids`` [B, S]: the stack
    ``total_ut_steps`` times over, the final norm closing every pass."""
    u = of_pass(params, 0)["embed"]["tokens"][ids]
    out = []
    for t in range(config["total_ut_steps"]):
        p = of_pass(params, t)
        u = rms_norm(run_layers(p, u, config), p["final_norm"]["scale"],
                     config["rms_norm_eps"])
        out.append(u)
    return out


def position_nll(head, h, targets):
    """``-log softmax(h W_head)[target]`` of every position: ``h`` [N, D],
    ``targets`` [N] -> [N], one block's [LOSS_BLOCK, V] logits at a time."""
    n = h.shape[0]
    size = next(m for m in range(min(LOSS_BLOCK, n), 0, -1) if n % m == 0)

    @jax.checkpoint
    def block_nll(args):
        h, targets = args
        logp = jax.nn.log_softmax(h @ head, -1)
        return -jnp.take_along_axis(logp, targets[:, None], -1)[:, 0]

    return jax.lax.map(block_nll, (h.reshape(-1, size, h.shape[-1]),
                                   targets.reshape(-1, size))).reshape(n)


def exit_distribution(lam):
    """``p`` [T, N] from the gates ``lam`` [T, N]; the last gate is read by
    nothing."""
    p, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def entropy(p):
    """``- sum_t p_t log p_t`` over the first axis, ``0 log 0 = 0``."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def loss_fn(params, batch, config):
    """``(loss, aux)`` of pre-shifted ``batch = {"inputs": [B, S], "targets":
    [B, S]}``, as ``models/ouro.py::make_loss_fn``'s: the whole objective, and
    ``ce``, ``ce_pass`` [T], ``exit_mass`` [T], ``exit_entropy``,
    ``expected_passes``."""
    b, s = batch["inputs"].shape
    targets = batch["targets"].reshape(b * s)
    nll, lam = [], []
    for t, h in enumerate(passes(params, batch["inputs"], config)):
        p = of_pass(params, t)
        h = h.reshape(b * s, -1)
        nll.append(position_nll(p["head"]["kernel"], h, targets))
        lam.append(jax.nn.sigmoid(h @ p["gate"]["kernel"][:, 0]
                                  + p["gate"]["bias"][0]))
    nll, p = jnp.stack(nll), exit_distribution(jnp.stack(lam))   # [T, N]
    ce = jnp.mean(jnp.sum(p * nll, axis=0))
    exit_entropy = jnp.mean(entropy(p))
    order = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    aux = {"ce": ce, "ce_pass": jnp.mean(nll, axis=1),
           "exit_mass": jnp.mean(p, axis=1), "exit_entropy": exit_entropy,
           "expected_passes": jnp.mean(order @ p)}
    return ce - config["exit_entropy_beta"] * exit_entropy, aux


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value and aux, and its gradients with respect to the
    named leaves only (``"layer0/attn/q/kernel"``): the whole backward pass
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
