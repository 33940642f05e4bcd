"""Mellum's plain reference: forward pass, loss and, through ``jax.grad``, the
gradients, in straightforward ``jax.numpy`` and float32, of the whole layer:
all 64 experts in one place, no mesh, no exchange, no kernel.

The one copy (``tests/test_mellum.py`` reads it where it is): what the CPU
tests hold ``ps_tpu/models/mellum.py`` and the exchange of ``ps_tpu/ops/moe.py``
to, and what decides ``correct`` on the chip. Written from the published
``config.json`` (``model_type: mellum``) and the layer equations its keys
name, those of the Qwen3-MoE lineage with a rotation a layer type, as the
writer knows them (no network here), and from nothing in the program under
test. Attention forms whole rows of the score matrix under an explicit mask,
a band or a triangle (in blocks of query rows, so that 8,192 fits); the
experts are a loop over all of them, each on every token, with the weight
the router gave it there (zero where it was not picked). Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes. ``config`` is a plain dict with the published
keys; ``params`` is a tree of f32 arrays, matrices stored ``[in, out]`` (the
transpose of ``nn.Linear``'s)::

    {"embed": {"tokens"}, "head": {"kernel"}, "final_norm": {"scale"},
     "layers": {"<i>": {"input_norm", "post_attn_norm": {"scale"},
        "attn": {"q", "k", "v", "out": {"kernel"},
                 "q_norm", "k_norm": {"scale"}},     # with qk_norm
        "router": {"kernel"},
        "experts": {"w1", "w3": [E, D, F], "w2": [E, F, D]}}}}

The layer equations, sequences ``x`` [B, S, D] (RMSNorm with a learned scale,
eps ``rms_norm_eps``)::

    x0 = embed(ids)                                # unscaled
    h = x + attn(input_norm(x))
    y = h + moe(post_attn_norm(h))
    after the last:  logits = norm_f(x) W_head

``attn`` on ``u`` (h = ``num_attention_heads`` query heads of ``head_dim`` on
``num_key_value_heads`` K/V heads, each serving h / kv consecutive query
heads; no bias)::

    q = rmsnorm_head(u Wq);  k = rmsnorm_head(u Wk);  v = u Wv   # qk_norm
    q, k = rope_l(q), rope_l(k)        # halves rotated (rotate_half)
    layer_types[l] == "sliding_attention":  i sees j  iff  0 <= i - j < sliding_window
    layer_types[l] == "full_attention":     i sees j  iff  j <= i
    o = softmax(q k^T / sqrt(head_dim) over what i sees) v
    out = o Wo

``rope_l`` by ``rope_parameters[layer_types[l]]``, d = ``head_dim``, theta =
``rope_theta``, position p, i = 0 .. d/2 - 1::

    rope_type "default":  inv_freq_i = theta^(-2i/d);  cos(p inv_freq), sin(p inv_freq)
    rope_type "yarn" (factor, original_max_position_embeddings, beta_fast,
    beta_slow, attention_factor):
        extra_i = theta^(-2i/d);  inter_i = extra_i / factor
        dim(n) = d ln(original / (2 pi n)) / (2 ln theta)
        low = max(floor(dim(beta_fast)), 0);  high = min(ceil(dim(beta_slow)), d - 1)
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)
        attention_factor cos(p inv_freq), attention_factor sin(p inv_freq)

so that a full layer's scores carry the factor's square; the table does not
depend on the sequence length. ``moe`` on tokens ``u`` [T, D]::

    p = softmax(u Wr)                              # [T, E], f32
    picks = top num_experts_per_tok of p
    w_e = p_e / (sum over the picks of p)          # norm_topk_prob
    out = sum over the picks of w_e W2_e(silu(W1_e u) * W3_e u)

The loss is the mean next-token cross entropy plus ``router_aux_loss_coef``
times the sum over layers of ``E sum_e f_e P_e``, ``f_e`` expert e's share of
the batch's token-expert pairs (no gradient) and ``P_e`` its mean probability
over the batch's tokens.

Departures from the published model, each at its line below:

- ``qk_norm`` (an RMSNorm over each q and k head's own ``head_dim`` before
  the rotation) is assumed from the lineage whose keys the config has; the
  config has no key for it. ``qk_norm: false`` leaves it out.
- The balance term is each layer's own product summed over layers, as
  ``ps_tpu/models/olmoe.py``'s; the lineage's published code pools the
  layers' tokens before the product. ``router_aux_loss_coef`` is assumed.
- ``described_as`` names a multi-token-prediction head; the config has no
  key for one and none is built.
- No document mask, no dropout, every sequence starts at position 0.
- Where two probabilities tie exactly, which of them ``top_k`` takes is the
  library's choice.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: query rows of the score matrix formed at a time
QUERY_BLOCK = 256
#: positions of every sequence whose logits are formed at a time
LOGIT_BLOCK = 1024
#: experts a step of the loop over them computes, ``E / 4`` apart (experts e,
#: e + E/4, ...): the values are those of one at a time, and a compiler given
#: four chips that hold a quarter of the stacks each can leave them there
EXPERTS_AT_A_TIME = 4


def rms_norm(x, scale, eps):
    return scale * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope_table(rp, dim):
    """``(inv_freq [dim / 2], factor on cos and sin)`` of one layer type's
    ``rope_parameters``, by the formulas of the module docstring."""
    theta = float(rp["rope_theta"])
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    kind = rp.get("rope_type", "default")
    if kind == "default":
        return extra, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}")
    inter = extra / rp["factor"]

    def dim_of(turns):
        return dim * math.log(rp["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return inter * ramp + extra * (1 - ramp), float(rp["attention_factor"])


def rope(x, rp):
    """Rotary positions 0 .. S-1 on ``x`` [B, S, h, d] by one layer type's
    ``rope_parameters``: the two halves of each head rotated against each
    other."""
    seq, dim = x.shape[1], x.shape[-1]
    inv_freq, factor = rope_table(rp, dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = factor * jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None]
    sin = factor * jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(lp, x, kind, config):
    """Attention of sequences ``x`` [B, S, D], of the layer's ``kind``."""
    b, seq, _ = x.shape
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"],
                            config["head_dim"])
    q = (x @ lp["q"]["kernel"]).reshape(b, seq, heads, dim)
    k = (x @ lp["k"]["kernel"]).reshape(b, seq, kv_heads, dim)
    v = (x @ lp["v"]["kernel"]).reshape(b, seq, kv_heads, dim)
    if config.get("qk_norm", True):     # departure: assumed from the lineage
        q = rms_norm(q, lp["q_norm"]["scale"], config["rms_norm_eps"])
        k = rms_norm(k, lp["k_norm"]["scale"], config["rms_norm_eps"])
    rp = config["rope_parameters"][kind]
    q, k = rope(q, rp), rope(k, rp)
    if kind == "sliding_attention":
        reach = config["sliding_window"]
    elif kind == "full_attention":
        reach = seq
    else:
        raise ValueError(f"layer type {kind!r}")
    # query head h reads K/V head h // (heads / kv_heads)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(dim)
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(seq)
        seen = (ahead >= 0) & (ahead < reach)      # a band, or the triangle
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))   # [blocks, B, q, h, d]
    out = jnp.moveaxis(out, 0, 1).reshape(b, seq, heads * dim)
    return out @ lp["out"]["kernel"]


def experts(lp, x, config):
    """The expert layer on tokens ``x`` [T, D]: its output [T, D], the 0/1
    mask [T, E] of each token's picks and the probabilities [T, E]."""
    width, top_k = config["num_experts"], config["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ lp["router"]["kernel"], -1)
    _, picks = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    mask = jnp.sum(jax.nn.one_hot(picks, width, dtype=x.dtype), axis=1)
    weights = probs * mask
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

    groups = EXPERTS_AT_A_TIME if width % EXPERTS_AT_A_TIME == 0 else 1

    @jax.checkpoint
    def some(w1, w3, w2, w):       # w [groups, T]: their weights per token
        hidden = (jax.nn.silu(jnp.einsum("td,gdf->gtf", x, w1))
                  * jnp.einsum("td,gdf->gtf", x, w3))
        return jnp.einsum("gtf,gfd,gt->td", hidden, w2, w)

    def at_a_time(stack):          # [E, ..] -> [E / groups, groups, ..]
        return jnp.swapaxes(stack.reshape(groups, -1, *stack.shape[1:]), 0, 1)

    # a loop over the experts, each on all tokens; a scan keeps the compile
    # short and the memory at a few experts'
    stacks = lp["experts"]
    total, _ = jax.lax.scan(
        lambda total, args: (total + some(*args), None), jnp.zeros_like(x),
        tuple(at_a_time(t) for t in (stacks["w1"], stacks["w3"], stacks["w2"],
                                     weights.T)))
    return total, mask, probs


def hidden_states(params, ids, config):
    """The decoder up to and with its final norm, of ``ids`` [B, S]:
    ``[B, S, D]``, each layer's picks per expert [L, E] and its balance
    term [L]."""
    eps = config["rms_norm_eps"]
    b, s = ids.shape
    x = params["embed"]["tokens"][ids]
    counts, balance = [], []
    for i, kind in enumerate(config["layer_types"]):
        lp = params["layers"][str(i)]
        x = x + attention(lp["attn"],
                          rms_norm(x, lp["input_norm"]["scale"], eps), kind,
                          config)
        h = rms_norm(x, lp["post_attn_norm"]["scale"], eps)
        out, mask, probs = experts(lp, h.reshape(b * s, -1), config)
        x = x + out.reshape(b, s, -1)
        picked = jnp.sum(mask, axis=0)
        counts.append(picked)
        share = jax.lax.stop_gradient(picked / jnp.sum(picked))
        balance.append(config["num_experts"]
                       * jnp.sum(share * jnp.mean(probs, axis=0)))
    return (rms_norm(x, params["final_norm"]["scale"], eps),
            jnp.stack(counts), jnp.stack(balance))


def loss_fn(params, batch, config):
    """``(loss, aux)`` as ``models/mellum.py::make_loss_fn``'s, of
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}``."""
    b, s = batch["inputs"].shape
    h, counts, balance = hidden_states(params, batch["inputs"], config)
    block = next(n for n in range(min(LOGIT_BLOCK, s), 0, -1) if s % n == 0)

    @jax.checkpoint
    def block_nll(args):               # [B, block, V] logits at a time
        h, targets = args
        logp = jax.nn.log_softmax(h @ params["head"]["kernel"], -1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))

    ce = jnp.sum(jax.lax.map(block_nll, (
        jnp.moveaxis(h.reshape(b, s // block, block, -1), 1, 0),
        jnp.moveaxis(batch["targets"].reshape(b, s // block, block), 1, 0)))
    ) / (b * s)
    # departure: each layer's own product, summed over layers; the
    # coefficient is assumed
    load_balance = jnp.sum(balance)
    loss = ce + config["router_aux_loss_coef"] * load_balance
    return loss, {"ce": ce, "load_balance": load_balance,
                  "expert_tokens": counts.astype(jnp.int32)}


def witness_grads(params, batch, config, names):
    """``loss_fn``'s value, its aux and its gradients with respect to the
    named leaves only (``"layers/0/attn/k/kernel"``): the whole backward pass
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
