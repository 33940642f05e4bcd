"""Nemotron-H: a decoder in which every layer is ONE part, a mixer or a
feed-forward part alone, ``x += f(rms_norm(x))``, of three kinds that a
pattern string names: ``M`` a Mamba-2 mixer (a state-space scan with a scalar
decay a head), ``*`` grouped-query attention without positions, ``E`` sparse
experts that work in a latent narrower than the model beside one shared
expert, all ungated ``relu(.)**2`` (NVIDIA, ``model_type: nemotron_h``;
Nemotron-3-Super-120B-A12B: 88 layers, 40 ``M`` : 40 ``E`` : 8 ``*``, 512
experts of width 2,688 in a 1,024-wide latent, 22 a token). The store's first
state-space layers (``ops/ssd.py``), its first mixers that hold **a share of
their heads**, and its first experts that are not SwiGLU and do not work at
the model's width.

**The share.** One chip of a group that divides each layer: ``mamba_num_heads``
heads with ``n_groups`` B/C groups, ``num_attention_heads`` query heads on
``num_key_value_heads`` K/V heads and ``n_routed_experts`` of ``router_width``
experts are what is *held here* (``mamba_head_start``, ``attention_head_start``
and ``expert_start`` say which: a loader slices the uncut weights by them, and
``tests/test_nemotron_h.py`` does). A mixer's out projection then gives this
chip's part of a sum over the group and the expert layer its own experts'
part; the shares of all chips add up to the uncut layer, with what every chip
computes alike (the shared expert) counted once. Neither the sum nor the
exchange of tokens is here, and nothing stands in for them.

Pure functions over a parameter dict, as ``models/kimi_linear.py``;
``rms_norm`` and the expert layer's window (``WHOLE_WINDOW``) are
``models/blocks.py``'s. The equations of each part are written out in the
plain reference's docstring (``benchmark/families/nemotron_h_reference.py``),
which this module is held to. How they are computed here:

- ``mamba_block`` (``models/blocks.py``'s, which Granite-4.0-H calls too):
  ``[z | xBC | dt] = u W_in``; the x, B and C channels through the four
  causal taps, the bias and the SiLU of
  ``ops/gated_conv.py::conv_silu_kernel`` (at the cell's share, 16 heads of
  64 on one held group, the scan is two Mosaic calls over row-major operands
  and the taps in front of it are two more, so that XLA re-lays nothing
  between them, PR 72; the XLA form at a shape the taps' kernels do not
  take, the tests' sizes); ``dt = softplus(dt + dt_bias)``
  in f32; the scan in its chunked form (``ops/ssd.py``, chunks of
  ``chunk_size``) plus the skip ``D x`` over the flat channels; the gate
  first, then an RMSNorm over each group's channels (so a share's norm is
  the uncut mixer's over that group, exactly); the out projection.
- ``attention_block``: q on the held query heads, k and v on the held K/V
  heads, no position, no bias, scale ``head_dim ** -0.5``; with
  ``attn='flash'`` K and V enter the Pallas kernel at their own head count.
- ``moe_block``: sigmoid scores in f32 over all ``router_width`` experts read
  from the model-wide activations, the top ``num_experts_per_tok`` of ``score
  + expert_bias[layer]`` (the bias selects only), weights renormalised over all
  picks (+ 1e-20) times ``routed_scaling_factor``; the tokens projected to the
  latent; a window of the sorted pairs at a time (``ops/moe.py::over_windows``:
  three times an even load, 8,704 rows in the cell) dispatched, through the
  held experts' ``relu(z W1)**2 W2`` as grouped matmuls that do the whole
  window's work whatever is live, combined; projected back; **plus the
  shared expert** at the model's width, whole on every chip.
- a final RMSNorm and an untied head.

Every layer runs under one ``jax.checkpoint`` whose policy lists, by name,
what the layer keeps from its forward pass for its backward pass beside the
residual stream (8 KB a token a layer in bf16): whatever costs a matrix
product, a ``top_k``, a sort or a kernel call to make again.

- ``ops/flash_attention.py::KEPT``: the attention layer's flash output and
  logsumexp, the two residuals that only the forward kernel can produce (8.4
  MB in the cell);
- ``ops/moe.py::ROUTE_KEPT``: an expert layer's router logits (the product
  at ``Precision.HIGHEST``, six bf16 passes), its picks and the two
  permutations of the pairs (18 MB), so that the backward pass
  differentiates the routing the forward pass ran: on the chip a recomputed
  layer's bf16 input is not the forward's to the bit, and a token near a tie
  could pick another expert the second time;
- ``PRODUCTS_KEPT``, beside ``_layer``: the outputs of a layer's first
  matrix products (a mixer's in projection 38 MB, q / k / v 13 MB, the
  tokens in the latent 17 MB, the shared expert's ``x W1`` 88 MB) and the
  held experts' output in the latent (17 MB: without it the recomputation
  runs the experts' ``combine`` again only to hand ``latent_up`` its input).

0.90 GB over the cell's eleven layers, where the compiled step's peak moved
by 0.06e9 B (it lies in a layer's backward, which holds the same working set
either way). Everything else a layer makes is an elementwise pass, a filter
or a scan away from these and exists once, while that layer's gradient is
computed: an expert layer's ``[T, 22, 512]`` pick mask, a window's row
buffers at 2,688 wide, the shared expert's activation at 5,376. A name is
the identity where no policy lists it; with ``attn='full'`` no flash call
runs and ``KEPT`` names nothing. What fits is a property of this model's
compiled memory in its cell, which nothing in a layer's input shows: the
list is this file's constant, and a model with less room (Mellum, SDAR)
lists less.

What the model does not compute, ``NemotronHConfig.from_dict`` refuses.

The loss is the cross entropy alone. ``expert_bias`` [expert layers,
``router_width``] is state that the step updates by a rule of its own
(``ops/moe.py::balance_bias``); it enters ``loss_fn`` as an extra argument and
leaves in ``aux``, as LFM2's and Kimi-Linear's::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch, expert_bias)
    expert_bias = aux["expert_bias"]

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::NEMOTRON_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models import blocks
from ps_tpu.models.blocks import init_expert_bias  # noqa: F401 — re-export
from ps_tpu.models.blocks import (WHOLE_WINDOW, make_attn_fn, rms_norm,
                                  token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names; ``mamba_num_heads``, ``n_groups``,
    ``num_attention_heads``, ``num_key_value_heads`` and ``n_routed_experts``
    count what is held here (the module docstring's share)."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    router_width: int = 512
    n_routed_experts: int = 512
    expert_start: int = 0
    mamba_head_start: int = 0
    attention_head_start: int = 0
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688     # ONE expert's, in the latent
    moe_shared_expert_intermediate_size: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    bias_update_rate: float = 1e-3
    layer_norm_epsilon: float = 1e-5
    #: the depth ``rescale_prenorm_residual`` divides by the root of: the
    #: published model's, whatever is cut here
    rescale_depth: int = 88
    dtype: Any = jnp.bfloat16

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def num_expert_layers(self) -> int:
        return self.hybrid_override_pattern.count("E")

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.n_routed_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "NemotronHConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("mlp_hidden_act", "relu2"),
                          ("mamba_hidden_act", "silu"),
                          ("tie_word_embeddings", False),
                          ("num_nextn_predict_layers", 0),
                          ("attention_bias", False),
                          ("mamba_proj_bias", False), ("mlp_bias", False),
                          ("use_bias", False), ("use_conv_bias", True),
                          ("n_shared_experts", 1),
                          ("residual_in_fp32", False),
                          ("sliding_window", None)):
            if d.get(key, want) != want:
                raise ValueError(f"models/nemotron_h.py computes {key}="
                                 f"{want!r} only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["n_routed_experts"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        pattern = config.hybrid_override_pattern
        if len(pattern) != config.num_hidden_layers or set(pattern) - set(
                "ME*"):
            raise ValueError(
                f"{config.num_hidden_layers} layers of pattern {pattern!r}: "
                f"one of M (Mamba-2), E (experts), * (attention) a layer")
        if config.mamba_num_heads % config.n_groups \
                or config.num_attention_heads % config.num_key_value_heads:
            raise ValueError(
                f"{config.mamba_num_heads} Mamba heads on {config.n_groups} "
                f"groups, {config.num_attention_heads} query heads on "
                f"{config.num_key_value_heads} K/V heads: each must divide")
        return config


def init_params(key, config: NemotronHConfig) -> Dict:
    """Normal(0, 0.02) weights and filters, unit norm scales, zero filter
    bias, f32; ``dt_bias`` the inverse softplus of ``exp(U(log time_step_min,
    log time_step_max))`` floored at ``time_step_floor``, ``A_log = log U(1,
    16)`` and ``D = 1`` a head (mamba_ssm's defaults). The matrices that
    write into the residual stream after a non-linearity (a mixer's out
    projection, the experts' and the shared expert's second matrix) are
    divided by ``sqrt(rescale_depth)`` (``rescale_prenorm_residual``). Jit it
    to make the tree on the device from the seed."""
    c = config
    d = c.hidden_size
    keys = iter(jax.random.split(key, 2 + 8 * c.num_hidden_layers))
    shrink = 1.0 / math.sqrt(c.rescale_depth)

    def w(*shape, scale=1.0):
        return (0.02 * scale) * jax.random.normal(next(keys), shape,
                                                  jnp.float32)

    def lin(*shape, scale=1.0):
        return {"kernel": w(*shape, scale=scale)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "head": lin(d, c.vocab_size), "final_norm": ones()}
    for i, kind in enumerate(c.hybrid_override_pattern):
        lp: Dict = {"norm": ones()}
        if kind == "M":
            h, inner = c.mamba_num_heads, c.mamba_inner
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                next(keys), (h,), jnp.float32, math.log(c.time_step_min),
                math.log(c.time_step_max))), c.time_step_floor)
            lp["mamba"] = {
                "in_proj": lin(d, inner + c.conv_dim + h),
                "conv": {"kernel": w(c.conv_dim, c.conv_kernel),
                         "bias": jnp.zeros((c.conv_dim,), jnp.float32)},
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((h,), jnp.float32),
                "out_norm": ones(inner),
                "out_proj": lin(inner, d, scale=shrink)}
        elif kind == "*":
            q, kv = (n * c.head_dim for n in (c.num_attention_heads,
                                              c.num_key_value_heads))
            lp["attn"] = {"q": lin(d, q), "k": lin(d, kv), "v": lin(d, kv),
                          "out": lin(q, d, scale=shrink)}
        else:
            e, latent, f = (c.n_routed_experts, c.moe_latent_size,
                            c.moe_intermediate_size)
            fs = c.moe_shared_expert_intermediate_size
            lp["moe"] = {"router": lin(d, c.router_width),
                         "latent_down": lin(d, latent),
                         "latent_up": lin(latent, d),
                         "w1": w(e, latent, f),
                         "w2": w(e, f, latent, scale=shrink),
                         "shared": {"w1": lin(d, fs),
                                    "w2": lin(fs, d, scale=shrink)}}
        params[f"layer{i}"] = lp
    return params


def mamba_block(lp: Dict, x, config: NemotronHConfig):
    """The Mamba-2 mixer on normed activations ``x`` [B, S, D]
    (``models/blocks.py::mamba_block`` at the held heads and groups): the
    held heads' part of the sum after the out projection."""
    c = config
    return blocks.mamba_block(
        lp, x, heads=c.mamba_num_heads, head_dim=c.mamba_head_dim,
        groups=c.n_groups, state=c.ssm_state_size, chunk=c.chunk_size,
        eps=c.layer_norm_epsilon)


def attention_block(lp: Dict, x, config: NemotronHConfig, attn_fn: Callable):
    """Grouped-query attention without positions of the normed activations
    ``x`` [B, S, D]: the held heads' part of the sum after the out
    projection. K and V reach ``attn_fn`` at their own head count."""
    c = config
    b, s, _ = x.shape
    heads, kv_heads = c.num_attention_heads, c.num_key_value_heads

    def proj(name, n):
        projected = x @ lp[name]["kernel"].astype(x.dtype)
        return checkpoint_name(projected, f"attn_{name}").reshape(b, s, n, -1)

    q, k, v = proj("q", heads), proj("k", kv_heads), proj("v", kv_heads)
    a = attn_fn(q, k, v, causal=True)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


def relu2_ffn(lp: Dict, x):
    """``relu(x W1) ** 2 W2``; ``x W1`` bears the name 'shared_in'."""
    pre = checkpoint_name(x @ lp["w1"]["kernel"].astype(x.dtype), "shared_in")
    return jnp.square(jax.nn.relu(pre)) @ lp["w2"]["kernel"].astype(x.dtype)


def moe_block(lp: Dict, x, config: NemotronHConfig, bias):
    """The latent expert layer on normed activations ``x`` [B, S, D] with the
    layer's selection ``bias`` [router_width] or None: the held experts' part
    of the output plus the shared expert's [B, S, D], and the layer's
    ``Routing``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scoring="sigmoid", bias=bias,
            renorm_eps=1e-20, scaling=c.routed_scaling_factor, held=c.held)
    with jax.named_scope(phases.MOE_LATENT):
        latent = checkpoint_name(
            tokens @ lp["latent_down"]["kernel"].astype(x.dtype), "latent_in")
    latent = checkpoint_name(
        moe.over_windows(WHOLE_WINDOW, routing, latent,
                         lp["w1"].astype(x.dtype), None,
                         lp["w2"].astype(x.dtype)), "latent_out")
    with jax.named_scope(phases.MOE_LATENT):
        out = latent @ lp["latent_up"]["kernel"].astype(x.dtype)
    with jax.named_scope(phases.MOE_SHARED):
        out = out + relu2_ffn(lp["shared"], tokens)
    return out.reshape(b, s, d), routing


#: what a layer keeps beside the flash call's residuals and the routing
#: (module docstring), by the names the values bear where they are made: a
#: Mamba mixer's in projection, the attention's q, k and v, the tokens in
#: the experts' latent and the held experts' output there, the shared
#: expert's ``x W1``
PRODUCTS_KEPT = ("mamba_in", "attn_q", "attn_k", "attn_v", "latent_in",
                 "latent_out", "shared_in")


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *moe.ROUTE_KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, bias, kind: str, config: NemotronHConfig,
           attn_fn: Callable):
    """One layer, ``x + f(rms_norm(x))``, recomputed in the backward pass:
    the stream out and, of an expert layer, its counts over all experts and
    over the held ones and the windows of rows it ran (None of the
    others)."""
    h = rms_norm(x, lp["norm"]["scale"], config.layer_norm_epsilon)
    if kind == "M":
        with jax.named_scope(phases.MAMBA):
            return x + mamba_block(lp["mamba"], h, config), None, None, None
    if kind == "*":
        with jax.named_scope(phases.ATTN):
            return (x + attention_block(lp["attn"], h, config, attn_fn),
                    None, None, None)
    out, routing = moe_block(lp["moe"], h, config, bias)
    return (x + out, routing.counts, routing.group_sizes,
            moe.live_windows(routing))


def apply(params: Dict, tokens, config: NemotronHConfig, expert_bias=None,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, each expert layer's pairs per expert over all of them
    [expert layers, router_width], over the held ones [expert layers,
    n_routed_experts], and the windows of rows it ran [expert layers])."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    counts, held, windows = [], [], []
    for i, kind in enumerate(c.hybrid_override_pattern):
        bias = None
        if kind == "E" and expert_bias is not None:
            bias = expert_bias[len(counts)]
        x, *of_experts = _layer(params[f"layer{i}"], x, bias, kind, c,
                                attn_fn)
        if kind == "E":
            for seen, one in zip((counts, held, windows), of_experts):
                seen.append(one)
    return x, jnp.stack(counts), jnp.stack(held), jnp.stack(windows)


def logits_of(params: Dict, hidden, config: NemotronHConfig):
    """Final norm and the untied head: [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"],
                 config.layer_norm_epsilon)
    return h @ params["head"]["kernel"].astype(h.dtype)


def make_loss_fn(config: NemotronHConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch, expert_bias) -> (loss, aux)`` for
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``). ``aux``: ``ce``;
    ``expert_tokens`` [expert layers, router_width], the step's pairs per
    expert over all of them; ``held_tokens`` [expert layers,
    n_routed_experts], those computed here; ``expert_windows`` [expert
    layers], the windows of rows each layer ran (1 unless its held pairs
    overflowed the first); ``expert_bias``, the bias for the next step."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch, expert_bias):
        hidden, counts, held, windows = apply(
            params, batch["inputs"], config, expert_bias, attn_fn)
        with jax.named_scope(phases.HEAD):
            ce = token_ce(logits_of(params, hidden, config),
                          batch["targets"])
        with jax.named_scope(phases.MOE_ROUTE):
            new_bias = moe.balance_bias(expert_bias, counts,
                                        config.bias_update_rate)
        return ce, {"ce": ce, "expert_tokens": counts, "held_tokens": held,
                    "expert_windows": windows, "expert_bias": new_bias}

    return loss_fn
