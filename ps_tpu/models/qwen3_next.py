"""Qwen3-Next: a decoder of two kinds of mixer, a gated delta rule whose decay
is one scalar a head and a token (Gated DeltaNet, linear in the sequence) in
three layers of four and gated softmax attention at head 256 in the fourth,
with sparse SwiGLU experts beside one shared expert under a sigmoid gate of
its own in every layer (Qwen, ``model_type: qwen3_next``;
Qwen3-Next-80B-A3B: 48 layers, 36 of them Gated DeltaNet, 512 experts of
width 512, ten a token). The store's second delta-rule mixer: where
Kimi-Linear's has a decay a channel from two low-rank gates, three filters
and as many key heads as value heads, this one has a decay a head, **one**
filter over the concatenated q, k and v channels and **16 key heads read by
32 value heads**, all through the same ``ops/kda.py``. Its first attention at
keys and values of 256 alike, eight query heads a K/V head, a rotation of the
first quarter of a head's channels only and a gate that the q projection
carries; its widest router (512, ten picks, softmax). As Kimi-Linear's, the
expert layer holds a share of the experts: ``num_experts`` of
``router_width``, from ``expert_start`` on, one chip of an expert-parallel
group without its exchange (``ops/moe.py``).

Pure functions over a parameter dict, as ``models/kimi_linear.py``;
``rms_norm``, ``dense_ffn``, ``rope``, ``LIVE_ROWS`` and ``token_ce`` are
``models/blocks.py``'s. A block is::

    x += mixer(norm(x));  x += moe(norm(x));  norm(x) = x / rms(x) * (1 + w)

(the residual stream's norms and the attention's q and k norms are
**zero-centred**: the parameter is ``w``, initialised at 0, and the scale is
``1 + w``), and the equations of each part are written out in the plain
reference's docstring (``benchmark/families/qwen3_next_reference.py``), which
this module is held to. What differs here is how they are computed:

- ``gdn_block``: one in-projection whose columns are laid out **a key head at
  a time** (q 128 | k 128 | v of its two value heads | z of its two value
  heads), as published, and a second for b | a (two and two a key head); the
  cuts regroup them into q | k | v over all heads for the one filter of four
  taps (``ops/gated_conv.py::conv_silu_kernel`` over 8,192 channels) and z a
  value head; q and k L2-normalised a head; the log-decay ``-exp(A_log) *
  softplus(a + dt_bias)`` and the write strength ``sigmoid(b)`` a value head;
  the rule in its chunked form (``ops/kda.py`` at ``g`` [B, S, H] and q, k of
  16 heads: at the published widths the scalar-decay Mosaic kernels, which
  read a key head once for its two value heads and one decay a head; the
  general rule on broadcast operands at the tests' widths); the per-head
  RMSNorm **first** and the gate ``silu(z)`` **after** it
  (``models/blocks.py::mamba_block`` gates first); the out projection. Taps,
  normalisation, gates, decays, state and norm in f32, the projections in
  ``dtype``.
- ``attention_block``: ``[q | gate] = x W_q`` a head at a time (256 | 256),
  q and k under their zero-centred norms, the first ``partial_rotary_factor``
  of the channels rotated (half-split pairs, ``blocks.rope`` over those
  channels alone) and the rest passed, the causal kernel at 256 / 256 with 16
  query heads on 2 K/V heads, ``sigmoid(gate)`` on its output, the out
  projection.
- ``moe_block``: softmax over all ``router_width`` in f32, the top
  ``num_experts_per_tok``, weights renormalised over all the picks
  (``norm_topk_prob``), dropless grouped SwiGLU over the held experts,
  **plus** ``sigmoid(x w_sg) * swiglu_shared(x)``, whole on every chip of the
  group (the shares of a layer add up to the uncut layer with it counted
  once).
- every layer is recomputed in the backward pass (one ``jax.checkpoint`` a
  layer) but for what its policy lists by name: ``ops/kda.py::KEPT`` (the
  rule's output, states and inverses, so its forward kernel runs once),
  ``ops/flash_attention.py::KEPT`` (the flash call's output and logsumexp),
  ``ops/moe.py::ROUTE_KEPT`` (the logits, the picks and the two
  permutations: the backward differentiates the routing the forward ran) and
  ``PRODUCTS_KEPT`` beside ``_layer``: the outputs of the mixers' first
  projections and the mixer's output, which the second norm reads.
- a final zero-centred norm and an untied head.

Departures from the published model are the reference's (its docstring lists
them): the share, no auxiliary loss, no prediction module. What the model
does not compute, ``Qwen3NextConfig.from_dict`` refuses.

The loss is the cross entropy alone and the model has no state beside its
parameters::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch)

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::QWEN3_NEXT_SCOPES``); they nest under the step's
``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models.blocks import (LIVE_ROWS, dense_ffn, make_attn_fn,
                                  rms_norm, rope, token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT as FLASH_KEPT
from ps_tpu.ops.gated_conv import conv_silu_kernel
from ps_tpu.ops.kda import KEPT as RULE_KEPT
from ps_tpu.ops.kda import kda

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, but ``num_experts``: the experts held here, of
    ``router_width`` published ones, from ``expert_start`` on."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    moe_intermediate_size: int = 512      # ONE expert's
    shared_expert_intermediate_size: int = 512
    router_width: int = 512
    num_experts: int = 512
    expert_start: int = 0
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """Every ``full_attention_interval``-th layer attends, the others
        run the delta rule (the published ``layer_types``' rule)."""
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.num_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "Qwen3NextConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("rope_scaling", None),
                          ("use_sliding_window", False),
                          ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu"), ("attention_bias", False),
                          ("num_nextn_predict_layers", 0)):
            if d.get(key, want) != want:
                raise ValueError(f"models/qwen3_next.py computes {key}="
                                 f"{want!r} only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["num_experts"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if "layer_types" in d and tuple(d["layer_types"]) \
                != config.layer_types:
            raise ValueError(
                f"layer_types {d['layer_types']} are not every "
                f"{config.full_attention_interval}-th layer attending")
        if config.linear_num_value_heads % config.linear_num_key_heads:
            raise ValueError(
                f"{config.linear_num_value_heads} value heads on "
                f"{config.linear_num_key_heads} key heads: no whole number "
                f"of readers a key head")
        if config.rotary_dim % 2 or not 0 < config.rotary_dim \
                <= config.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{config.partial_rotary_factor} of head_dim "
                             f"{config.head_dim} rotates no whole pairs")
        return config


def init_params(key, config: Qwen3NextConfig) -> Dict:
    """Normal(0, 0.02) weights and filter, the zero-centred norms' ``w`` 0
    and the gated head norm's scale 1, f32; ``A_log = log U(0, 16)`` and
    ``dt_bias = 1`` a value head (the published modelling file's; the draw
    is kept off 0, where the logarithm is not finite, by 1e-6). Jit it to
    make the tree on the device from the seed."""
    c = config
    d = c.hidden_size
    keys = iter(jax.random.split(key, 2 + 16 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(*shape):
        return {"kernel": w(*shape)}

    def zero(n=d):
        return {"w": jnp.zeros((n,), jnp.float32)}

    def swiglu(f):
        return {"w1": lin(d, f), "w3": lin(d, f), "w2": lin(f, d)}

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "head": lin(d, c.vocab_size), "final_norm": zero()}
    e, f = c.num_experts, c.moe_intermediate_size
    for i, kind in enumerate(c.layer_types):
        lp = {"mixer_norm": zero(), "ffn_norm": zero()}
        if kind == LINEAR:
            hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
            keys_w, values_w = (hk * c.linear_key_head_dim,
                                hv * c.linear_value_head_dim)
            lp["gdn"] = {
                "in_qkvz": lin(d, 2 * keys_w + 2 * values_w),
                "in_ba": lin(d, 2 * hv),
                "conv": w(2 * keys_w + values_w, c.linear_conv_kernel_dim),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (hv,), jnp.float32, 1e-6, 16.0)),
                "dt_bias": jnp.ones((hv,), jnp.float32),
                "out_norm": {"scale": jnp.ones((c.linear_value_head_dim,),
                                               jnp.float32)},
                "out": lin(values_w, d)}
        else:
            h, kv, dim = (c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim)
            lp["attn"] = {"q": lin(d, h * 2 * dim), "k": lin(d, kv * dim),
                          "v": lin(d, kv * dim), "out": lin(h * dim, d),
                          "q_norm": zero(dim), "k_norm": zero(dim)}
        lp["moe"] = {"router": lin(d, c.router_width), "gate": w(e, d, f),
                     "up": w(e, d, f), "down": w(e, f, d),
                     "shared": swiglu(c.shared_expert_intermediate_size),
                     "shared_gate": lin(d, 1)}
        params[f"layer{i}"] = lp
    return params


def zero_centred_norm(x, w, eps):
    """``x / rms(x) * (1 + w)``: ``blocks.rms_norm`` at the scale ``1 + w``
    (statistics and scale in f32, the result in ``x``'s dtype)."""
    return rms_norm(x, 1.0 + w, eps)


def gdn_block(lp: Dict, x, config: Qwen3NextConfig):
    """The Gated DeltaNet mixer on normed activations ``x`` [B, S, D]."""
    c = config
    b, s, _ = x.shape
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv, r = c.linear_key_head_dim, c.linear_value_head_dim, hv // hk
    qkvz = checkpoint_name(x @ lp["in_qkvz"]["kernel"].astype(x.dtype),
                           "gdn_qkvz").reshape(b, s, hk, -1)
    ba = checkpoint_name(x @ lp["in_ba"]["kernel"].astype(x.dtype),
                         "gdn_ba").reshape(b, s, hk, 2 * r)
    # a key head's columns: q | k | its r value heads' v | their z
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    with jax.named_scope(phases.KDA_CONV):
        mixed = conv_silu_kernel(
            jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], -1),
            lp["conv"])
    q, k, v = jnp.split(mixed, [hk * dk, 2 * hk * dk], axis=-1)
    dtype = v.dtype

    def unit(t):
        t = t.reshape(b, s, hk, dk).astype(jnp.float32)
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k = (unit(q) * dk ** -0.5).astype(dtype), unit(k).astype(dtype)
    beta = jax.nn.sigmoid(ba[..., :r].reshape(b, s, hv).astype(jnp.float32))
    decay = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
        ba[..., r:].reshape(b, s, hv).astype(jnp.float32) + lp["dt_bias"])
    with jax.named_scope(phases.KDA_CORE):
        o = kda(q, k, v.reshape(b, s, hv, dv), decay, beta)
    # the norm first, the gate after it
    o = rms_norm(o.astype(jnp.float32), lp["out_norm"]["scale"],
                 c.rms_norm_eps)
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv).astype(jnp.float32))
    return o.astype(dtype).reshape(b, s, -1) \
        @ lp["out"]["kernel"].astype(x.dtype)


def attention_block(lp: Dict, x, config: Qwen3NextConfig, attn_fn: Callable):
    """Gated grouped-query attention of the normed activations ``x``
    [B, S, D]."""
    c = config
    b, s, _ = x.shape
    dim, rot = c.head_dim, c.rotary_dim

    def proj(name, heads):
        return checkpoint_name(
            x @ lp[name]["kernel"].astype(x.dtype),
            f"attn_{name}").reshape(b, s, heads, -1)

    q, gate = jnp.split(proj("q", c.num_attention_heads), 2, axis=-1)
    q = zero_centred_norm(q, lp["q_norm"]["w"], c.rms_norm_eps)
    k = zero_centred_norm(proj("k", c.num_key_value_heads),
                          lp["k_norm"]["w"], c.rms_norm_eps)
    v = proj("v", c.num_key_value_heads)
    with jax.named_scope(phases.ATTN_ROPE):
        q, k = (jnp.concatenate([rope(t[..., :rot], c.rope_theta),
                                 t[..., rot:]], axis=-1) if rot < dim
                else rope(t, c.rope_theta) for t in (q, k))
    with jax.named_scope(phases.ATTN_FULL):
        a = attn_fn(q, k, v, causal=True)
    with jax.named_scope(phases.ATTN_GATE):
        a = (a.astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


def moe_block(lp: Dict, x, config: Qwen3NextConfig):
    """The expert layer on normed activations ``x`` [B, S, D]: the held
    experts' part of the output plus the gated shared expert's [B, S, D],
    and the layer's ``Routing``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scoring="softmax", held=c.held)
    out = moe.over_windows(
        LIVE_ROWS, routing, tokens,
        *(lp[n].astype(x.dtype) for n in ("gate", "up", "down")))
    with jax.named_scope(phases.MOE_SHARED):
        gate = jax.nn.sigmoid(
            (tokens @ lp["shared_gate"]["kernel"].astype(x.dtype)
             ).astype(jnp.float32))
        out = out + (gate * dense_ffn(lp["shared"], tokens)
                     ).astype(out.dtype)
    return out.reshape(b, s, d), routing


#: what a layer keeps beside the kernels' residuals and the routing (module
#: docstring), by the names the values bear where they are made: the outputs
#: of the mixers' first projections and the mixer's output
PRODUCTS_KEPT = ("gdn_qkvz", "gdn_ba", "attn_q", "attn_k", "attn_v",
                 "mixer_out")


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *RULE_KEPT, *FLASH_KEPT, *moe.ROUTE_KEPT,
                       *PRODUCTS_KEPT))
def _layer(lp: Dict, x, kind: str, config: Qwen3NextConfig,
           attn_fn: Callable):
    """One layer, recomputed in the backward pass: the stream out, the
    layer's counts over all experts and over the held ones and the windows
    of rows it ran. Which device the kernels under it are traced for is in
    the checkpoint's key with the shapes (``ops/mosaic.py``)."""
    eps = config.rms_norm_eps
    h = zero_centred_norm(x, lp["mixer_norm"]["w"], eps)
    if kind == LINEAR:
        with jax.named_scope(phases.KDA):
            a = gdn_block(lp["gdn"], h, config)
    else:
        with jax.named_scope(phases.ATTN):
            a = attention_block(lp["attn"], h, config, attn_fn)
    x = x + checkpoint_name(a, "mixer_out")
    out, routing = moe_block(
        lp["moe"], zero_centred_norm(x, lp["ffn_norm"]["w"], eps), config)
    return (x + out, routing.counts, routing.group_sizes,
            moe.live_windows(routing))


def apply(params: Dict, tokens, config: Qwen3NextConfig,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, each layer's pairs per expert over all of them [layers,
    router_width], over the held ones [layers, num_experts], and the windows
    of rows it ran [layers])."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    counts, held, windows = [], [], []
    for i, kind in enumerate(c.layer_types):
        x, *of_experts = _layer(params[f"layer{i}"], x, kind, c, attn_fn)
        for seen, one in zip((counts, held, windows), of_experts):
            seen.append(one)
    return x, jnp.stack(counts), jnp.stack(held), jnp.stack(windows)


def logits_of(params: Dict, hidden, config: Qwen3NextConfig):
    """Final norm and the untied head: [B, S, D] -> [B, S, V]."""
    h = zero_centred_norm(hidden, params["final_norm"]["w"],
                          config.rms_norm_eps)
    return h @ params["head"]["kernel"].astype(h.dtype)


def make_loss_fn(config: Qwen3NextConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch) -> (loss, aux)`` for pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``). ``aux``: ``ce``;
    ``expert_tokens`` [layers, router_width], the step's pairs per expert
    over all of them; ``held_tokens`` [layers, num_experts], those computed
    here; ``expert_windows`` [layers], the windows of rows each layer ran (1
    unless its held pairs overflowed the first)."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch):
        hidden, counts, held, windows = apply(params, batch["inputs"],
                                              config, attn_fn)
        with jax.named_scope(phases.HEAD):
            ce = token_ce(logits_of(params, hidden, config),
                          batch["targets"])
        return ce, {"ce": ce, "expert_tokens": counts, "held_tokens": held,
                    "expert_windows": windows}

    return loss_fn
