"""Kimi-Linear: a decoder of two kinds of mixer, Kimi Delta Attention (a gated
delta rule with a per-channel decay, linear in the sequence) in three layers
of four and multi-head latent attention without positions in the fourth, with
a dense SwiGLU in the leading layer and sparse SwiGLU experts beside one shared
expert in the rest (Moonshot AI, ``model_type: kimi_linear``, arXiv:2510.26692;
Kimi-Linear-48B-A3B: 27 layers, 20 of them KDA, 256 experts of width 1024,
eight a token). The store's first recurrent mixer (``ops/kda.py``), its first
attention whose values are narrower than its keys (``ops/flash_attention.py``
at 192 / 128), and its first expert layer with a dense branch beside the
routed one. As LFM2's, the expert layer holds a share of the experts:
``num_experts`` of ``router_width``, from ``expert_start`` on, one chip of an
expert-parallel group without its exchange (``ops/moe.py``).

Pure functions over a parameter dict, as ``models/lfm2.py``; ``rms_norm``,
``dense_ffn``, ``mla_block`` and the checkpointed ``experts_of`` are
``models/blocks.py``'s.
A block is::

    x += mixer(rms_norm(x));  x += ffn(rms_norm(x))

and the equations of each part are written out in the plain reference's
docstring (``benchmark/families/kimi_reference.py``), which this module is
held to. What differs here is how they are computed:

- ``kda_block``: q, k and v each through the four causal taps and the SiLU
  of ``ops/gated_conv.py::conv_silu``, q and k L2-normalised a head;
  the log-decay ``-exp(A_log) * softplus((x Wfa) Wfb + dt_bias)``, the write
  strength ``sigmoid(x Wb)``; the rule itself in its chunked form
  (``ops/kda.py``, chunks of 64); the gated per-head RMSNorm; the out
  projection. Taps, normalisation, gates, decays, state and norms in f32, the
  projections in ``dtype``. Everything between the projections is recomputed in
  the backward pass but the rule's kernel call (one ``jax.checkpoint`` whose
  policy keeps what only that call can produce, ``ops/kda.py::KEPT``: the
  projections' outputs live on, 25 KB a token a layer in bf16, and the rule's
  output, states and inverses, 49 KB, where q, k, v behind the taps and the
  f32 decays would be 41 more and the chunks' internals 130; the plain form
  of the rule bears no names and is recomputed whole).
- ``mla_block`` (``models/blocks.py``'s, with neither of its two options): K
  and V expanded from the normalised 512-wide latent, the 64
  position-free channels every head shares broadcast to the 32 heads and
  concatenated behind the head's own 128 (one 192-wide operand: the kernel
  reads q and k at 192 and v at 128, ``ops/flash_attention.py``), scale
  ``192 ** -0.5``. No rotary embedding anywhere (``mla_use_nope``).
- ``moe_block``: sigmoid scores in f32, the top ``num_experts_per_token`` of
  ``score + expert_bias[layer]`` (the bias selects only), weights ``score /
  (sum of the picks' scores + 1e-20)`` times ``routed_scaling_factor``,
  dropless grouped SwiGLU over the held experts, recomputed in the backward
  pass as LFM2's, **plus the shared expert**, a SwiGLU every token passes,
  whole on every chip of the group (the shares of a layer add up to the uncut
  layer with it counted once).
- a final RMSNorm and an untied head.

Departures from the published model are the reference's (its docstring lists
them): the share, the low-rank width 128 of the two gates, no bias in any
projection, the bias rule, no auxiliary loss, one group of experts. What the
model does not compute, ``KimiLinearConfig.from_dict`` refuses.

The loss is the cross entropy alone. ``expert_bias`` [expert layers,
``router_width``] is state that the step updates by a rule of its own
(``ops/moe.py::balance_bias``); it enters ``loss_fn`` as an extra argument and
leaves in ``aux``, as LFM2's::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch, expert_bias)
    expert_bias = aux["expert_bias"]

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::KIMI_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ps_tpu.models.blocks import init_expert_bias  # noqa: F401 — re-export
from ps_tpu.models.blocks import (dense_ffn, experts_of, make_attn_fn,
                                  mla_block, rms_norm, token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.gated_conv import conv_silu_kernel
from ps_tpu.ops.kda import KEPT, kda


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names (``linear_attn_config``'s flattened with a ``kda_``
    prefix), but ``num_experts``: the experts held here, of ``router_width``
    published ones, from ``expert_start`` on."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216         # the dense layer's SwiGLU
    moe_intermediate_size: int = 1024     # ONE expert's, and the shared one's
    num_hidden_layers: int = 27
    kda_layers: Tuple[int, ...] = ()      # counted from 1, as published
    full_attn_layers: Tuple[int, ...] = ()
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_low_rank: int = 128              # assumed: the config gives none
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    router_width: int = 256
    num_experts: int = 256
    expert_start: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    bias_update_rate: float = 1e-3
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.num_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "KimiLinearConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("mla_use_nope", True), ("q_lora_rank", None),
                          ("num_expert_group", 1), ("topk_group", 1),
                          ("moe_router_activation_func", "sigmoid"),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu"), ("moe_layer_freq", 1),
                          ("num_nextn_predict_layers", 0),
                          ("rope_scaling", None)):
            if d.get(key, want) != want:
                raise ValueError(f"models/kimi_linear.py computes {key}="
                                 f"{want!r} only, not {d[key]!r}")
        if d.get("num_key_value_heads",
                 d["num_attention_heads"]) != d["num_attention_heads"]:
            raise ValueError("models/kimi_linear.py has one K/V head a "
                             "query head (both expanded from the latent)")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        linear = d["linear_attn_config"]
        kw.update(kda_layers=tuple(linear["kda_layers"]),
                  full_attn_layers=tuple(linear["full_attn_layers"]),
                  kda_num_heads=linear["num_heads"],
                  kda_head_dim=linear["head_dim"],
                  short_conv_kernel_size=linear["short_conv_kernel_size"])
        kw.setdefault("router_width", d["num_experts"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        layers = sorted(config.kda_layers + config.full_attn_layers)
        if layers != list(range(1, config.num_hidden_layers + 1)):
            raise ValueError(
                f"kda_layers {config.kda_layers} and full_attn_layers "
                f"{config.full_attn_layers} are not the layers 1.."
                f"{config.num_hidden_layers}, each once")
        return config


def init_params(key, config: KimiLinearConfig) -> Dict:
    """Normal(0, 0.02) weights and filters and unit norm scales, f32;
    ``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))`` a channel (the ``fla`` library's
    defaults). Jit it to make the tree on the device from the seed."""
    c = config
    d = c.hidden_size
    keys = iter(jax.random.split(key, 2 + 24 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(*shape):
        return {"kernel": w(*shape)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(f):
        return {"w1": lin(d, f), "w3": lin(d, f), "w2": lin(f, d)}

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "head": lin(d, c.vocab_size), "final_norm": ones()}
    for i in range(c.num_hidden_layers):
        lp = {"mixer_norm": ones(), "ffn_norm": ones()}
        if i + 1 in c.kda_layers:
            h, k, r = c.kda_num_heads, c.kda_head_dim, c.gate_low_rank
            dt = jnp.exp(jax.random.uniform(
                next(keys), (h * k,), jnp.float32, jnp.log(1e-3),
                jnp.log(1e-1)))
            lp["kda"] = {
                "q": lin(d, h * k), "k": lin(d, h * k), "v": lin(d, h * k),
                **{f"{n}_conv": w(h * k, c.short_conv_kernel_size)
                   for n in "qkv"},
                "f_a": lin(d, r), "f_b": lin(r, h * k),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "b": lin(d, h), "g_a": lin(d, r), "g_b": lin(r, h * k),
                "out_norm": ones(k), "out": lin(h * k, d)}
        else:
            h = c.num_attention_heads
            lp["attn"] = {
                "q": lin(d, h * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                "kv_a": lin(d, c.kv_lora_rank + c.qk_rope_head_dim),
                "kv_norm": ones(c.kv_lora_rank),
                "kv_b": lin(c.kv_lora_rank,
                            h * (c.qk_nope_head_dim + c.v_head_dim)),
                "out": lin(h * c.v_head_dim, d)}
        if i < c.first_k_dense_replace:
            lp["ffn"] = swiglu(c.intermediate_size)
        else:
            e, f = c.num_experts, c.moe_intermediate_size
            lp["moe"] = {"router": lin(d, c.router_width),
                         "gate": w(e, d, f), "up": w(e, d, f),
                         "down": w(e, f, d),
                         "shared": swiglu(f * c.num_shared_experts)}
        params[f"layer{i}"] = lp
    return params


@functools.partial(jax.checkpoint, static_argnums=(2, 3),
                   policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
def _mixer(projected, weights, heads: int, eps: float):
    """Everything of the KDA mixer between its projections: ``projected``
    the activations behind q, k, v [B, S, H * K], the two low-rank gates'
    inner sides [B, S, r] and the write strength's logits [B, S, H];
    ``weights`` the f32 leaves used here. Recomputed in the backward pass,
    but the rule's forward kernel call, whose named residuals are kept.
    Which device the kernels under it are traced for is in the checkpoint's
    key with the shapes (``ops/mosaic.py``)."""
    q, k, v, f_inner, g_inner, b_logits = projected
    batch, seq = q.shape[:2]

    def head_wise(x):
        return x.reshape(batch, seq, heads, -1)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    with jax.named_scope(phases.KDA_CONV):
        q, k, v = (head_wise(conv_silu_kernel(x, weights[f"{n}_conv"]))
                   for n, x in (("q", q), ("k", k), ("v", v)))
    dtype = v.dtype
    q, k = (unit(x.astype(jnp.float32)) for x in (q, k))
    q, k = (q * q.shape[-1] ** -0.5).astype(dtype), k.astype(dtype)
    decay = -jnp.exp(weights["A_log"])[:, None] * head_wise(jax.nn.softplus(
        jnp.dot(f_inner.astype(jnp.float32), weights["f_b"]["kernel"])
        + weights["dt_bias"]))
    beta = jax.nn.sigmoid(b_logits.astype(jnp.float32))
    with jax.named_scope(phases.KDA_CORE):
        o = kda(q, k, v, decay, beta)
    gate = head_wise(jnp.dot(g_inner.astype(jnp.float32),
                             weights["g_b"]["kernel"]))
    o = rms_norm(o.astype(jnp.float32), weights["out_norm"]["scale"], eps) \
        * jax.nn.sigmoid(gate)
    return o.astype(dtype).reshape(batch, seq, -1)


def kda_block(lp: Dict, x, config: KimiLinearConfig):
    """The KDA mixer on normed activations ``x`` [B, S, D]."""
    def proj(name):
        return x @ lp[name]["kernel"].astype(x.dtype)

    projected = tuple(proj(n) for n in ("q", "k", "v", "f_a", "g_a", "b"))
    inner = {n: lp[n] for n in ("q_conv", "k_conv", "v_conv", "f_b", "g_b",
                                "dt_bias", "A_log", "out_norm")}
    o = _mixer(projected, inner, config.kda_num_heads, config.rms_norm_eps)
    return o @ lp["out"]["kernel"].astype(x.dtype)


def moe_block(lp: Dict, x, config: KimiLinearConfig, bias):
    """The expert layer on normed activations ``x`` [B, S, D] with the
    layer's selection ``bias`` [router_width] or None: the held experts'
    part of the output plus the shared expert's [B, S, D], and the layer's
    ``Routing``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_token,
            renormalize=c.moe_renormalize, scoring="sigmoid", bias=bias,
            renorm_eps=1e-20, scaling=c.routed_scaling_factor, held=c.held)
    out = experts_of(tokens, lp["gate"], lp["up"], lp["down"], routing)
    with jax.named_scope(phases.MOE_SHARED):
        out = out + dense_ffn(lp["shared"], tokens)
    return out.reshape(b, s, d), routing


def apply(params: Dict, tokens, config: KimiLinearConfig, expert_bias=None,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, the list of each expert layer's ``Routing``)."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    routings = []
    for i in range(c.num_hidden_layers):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["mixer_norm"]["scale"], c.rms_norm_eps)
        if i + 1 in c.kda_layers:
            with jax.named_scope(phases.KDA):
                x = x + kda_block(lp["kda"], h, c)
        else:
            with jax.named_scope(phases.ATTN):
                x = x + mla_block(lp["attn"], h, c, attn_fn)
        h = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        if i < c.first_k_dense_replace:
            with jax.named_scope(phases.FFN):
                x = x + dense_ffn(lp["ffn"], h)
        else:
            bias = None if expert_bias is None else expert_bias[len(routings)]
            out, routing = moe_block(lp["moe"], h, c, bias)
            x = x + out
            routings.append(routing)
    return x, routings


def logits_of(params: Dict, hidden, config: KimiLinearConfig):
    """Final norm and the untied head: [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.rms_norm_eps)
    return h @ params["head"]["kernel"].astype(h.dtype)


def make_loss_fn(config: KimiLinearConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch, expert_bias) -> (loss, aux)`` for
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``). ``aux``: ``ce``;
    ``expert_tokens`` [expert layers, router_width], the step's pairs per
    expert over all of them; ``held_tokens`` [expert layers, num_experts],
    those computed here; ``expert_windows`` [expert layers], the windows of
    rows each layer ran (1 unless its held pairs overflowed the first);
    ``expert_bias``, the bias for the next step."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch, expert_bias):
        hidden, routings = apply(params, batch["inputs"], config, expert_bias,
                                 attn_fn)
        with jax.named_scope(phases.HEAD):
            ce = token_ce(logits_of(params, hidden, config),
                          batch["targets"])
        with jax.named_scope(phases.MOE_ROUTE):
            counts = jnp.stack([r.counts for r in routings])
            held = jnp.stack([r.group_sizes for r in routings])
            windows = jnp.stack([moe.live_windows(r) for r in routings])
            new_bias = moe.balance_bias(expert_bias, counts,
                                        config.bias_update_rate)
        return ce, {"ce": ce, "expert_tokens": counts, "held_tokens": held,
                    "expert_windows": windows, "expert_bias": new_bias}

    return loss_fn
