"""Granite-4.0-H: a dense decoder in which every layer is TWO parts, a mixer
and a SwiGLU, each added to the residual stream under a multiplier, with a
Mamba-2 mixer in nine layers of ten and grouped-query attention without
positions in the tenth (IBM, ``model_type: granitemoehybrid``;
Granite-4.0-H-Micro: 40 layers, 36 ``mamba`` : 4 ``attention``, hidden 2,048,
64 Mamba heads of 64 on ONE B/C group over a state of 128 in chunks of 256, 32
query heads of 64 on 8 K/V heads, an 8,192-wide SwiGLU in every layer, no
experts, one tensor for the embedding and the head). The store's first dense
state-space decoder: its first mixers that are **whole** (every head of a
layer here: ``mamba_n_groups: 1`` puts one gated norm over all 4,096 channels,
so heads cannot be shared without a statistic across chips), and its first
model under Granite's four constants::

    x0 = embedding_multiplier * E[tokens]                          # 12
    h  = x + residual_multiplier * Mixer(rms_norm(x))              # 0.22
    y  = h + residual_multiplier * W_out(silu(a) * b)
         with [a | b] = rms_norm(h) W_in
    attention's scores times attention_multiplier          # 1/64, not 1/8
    logits = rms_norm(x_L) E^T / logits_scaling                    # 8

Pure functions over a parameter dict, as ``models/nemotron_h.py``;
``rms_norm``, ``token_ce``, the attention closure and **the Mamba-2 mixer**
(``mamba_block``, Nemotron-H's too) are ``models/blocks.py``'s. The equations
of each part are written out in the plain reference's docstring
(``benchmark/families/granite_h_reference.py``), which this module is held
to. How they are computed here:

- the mixer: ``blocks.mamba_block`` at all ``mamba_n_heads`` heads and
  ``mamba_n_groups`` groups, the scan in chunks of ``mamba_chunk_size``
  (``ops/ssd.py``: at the cell's shape one Mosaic call forward and one
  backward over all 64 heads, the decays and the states in VMEM; what lives
  between the two is the f32 states that entered each chunk, 134 MB inside
  one layer's backward). The element-wise stages around it take its layout
  (PR 72): ``mamba_block`` takes the 4,352 x, B and C channels through
  ``ops/gated_conv.py::conv_silu_kernel``'s two Mosaic calls, which write
  row-major what the scan's calls read row-major (the XLA form at a shape
  those kernels do not take, the tests' sizes), and the skip ``D x`` behind
  it is spelled over the flat 4,096 channels the scan writes. While this
  shape's scan was XLA's (until PR 59) the XLA taps were the faster, because
  that scan read ``x`` in three layouts (``ops/gated_conv.py``'s table);
- attention: q, k, v without bias or position; the attention closure scales
  by ``head_dim ** -0.5``, so q is multiplied by ``attention_multiplier *
  head_dim ** 0.5`` first (1/64 x 8 = 0.125, a power of two: exact in
  bfloat16), which puts the scores at ``attention_multiplier`` without
  touching the kernel; with ``attn='flash'`` K and V enter the Pallas kernel
  at their own head count;
- the SwiGLU: ``x W_in`` [.., 2 x 8,192] split in halves, gate first
  (``shared_mlp.input_linear`` / ``output_linear``); no expert branch exists
  at ``num_local_experts: 0``;
- the head: a final RMSNorm and the embedding transposed, the logits divided
  by ``logits_scaling``; the embedding's gradient is the sum of the lookup's
  and the head's.

Every layer runs under one ``jax.checkpoint`` whose policy lists, by name,
what the layer keeps from its forward pass for its backward pass beside the
residual stream (4 KB a token a layer in bf16). ``ops/flash_attention.py::
KEPT``: the attention layer's flash output and logsumexp, which only the
forward kernel can produce (34 MB + 1 MB in the cell). ``PRODUCTS_KEPT``,
beside ``_layer``: of the four values a layer names (a mixer's in projection
'mamba_in' 139 MB, the attention's q / k / v 50 MB, the SwiGLU's ``x W_in``
'ffn_in' 268 MB a layer at 8,192 tokens in bf16) **what the compiled peak
leaves room for**: 772M parameters take 12.37e9 B of the chip's 17.18e9 with
their moments and gradients. Kept: q / k / v and the SwiGLU's ``x W_in``, the
largest product a layer makes again (4.0 ms a layer). The scan's output is
not a fifth (it was until PR 59, 0.6e9 B): the recomputed layer runs the
scan's forward call again for the states its backward call reads, which
gives the output with them, and the step with the output kept and the
states made by a pass of their own ran 1.4% slower (``PERF.md`` section 6,
PR 59). 13.76e9 B at the run's peak, 80%. The mixer's in projection (2.1 ms a layer for 1.25e9 B more) is made
again.
The list is this file's
constant, argued from the compiled step's memory in ``PERF.md`` (PR 56): what
fits is a property of this model in its cell, which nothing in a layer's
input shows. A name is the identity where no policy lists it.

What the model does not compute, ``GraniteHConfig.from_dict`` refuses.

The loss is the next-token cross entropy alone; the model has no state of its
own beside its parameters (no router, no selection bias, no window)::

    step = store.make_step(make_loss_fn(config))
    loss, params = step(batch)

The phases a trace can tell apart are opened here and in ``mamba_block`` with
``jax.named_scope`` (``obs/phases.py::GRANITE_SCOPES``); they nest under the
step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models.blocks import (make_attn_fn, mamba_block, rms_norm,
                                  token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops.flash_attention import KEPT

KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class GraniteHConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names. ``time_step_*`` are not in it: mamba_ssm's
    defaults, which ``init_params`` draws ``dt_bias`` from."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_n_groups: int = 1
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def from_dict(cls, d: Dict) -> "GraniteHConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("num_local_experts", 0),
                          ("num_experts_per_tok", 0),
                          ("position_embedding_type", "nope"),
                          ("rope_scaling", None), ("attention_bias", False),
                          ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True),
                          ("tie_word_embeddings", True),
                          ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm")):
            if d.get(key, want) != want:
                raise ValueError(f"models/granite_h.py computes {key}="
                                 f"{want!r} only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw["layer_types"] = tuple(kw.get("layer_types", ()))
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        kinds = config.layer_types
        if len(kinds) != config.num_hidden_layers or set(kinds) - set(KINDS):
            raise ValueError(
                f"{config.num_hidden_layers} layers of layer_types "
                f"{list(kinds)}: one of {KINDS} a layer")
        if config.mamba_inner != config.mamba_expand * config.hidden_size:
            raise ValueError(
                f"mamba_n_heads {config.mamba_n_heads} x mamba_d_head "
                f"{config.mamba_d_head} is not mamba_expand "
                f"{config.mamba_expand} x hidden_size {config.hidden_size}")
        if config.mamba_n_heads % config.mamba_n_groups \
                or config.num_attention_heads % config.num_key_value_heads \
                or config.hidden_size % config.num_attention_heads:
            raise ValueError(
                f"{config.mamba_n_heads} Mamba heads on "
                f"{config.mamba_n_groups} groups, {config.num_attention_heads}"
                f" query heads on {config.num_key_value_heads} K/V heads over "
                f"a width of {config.hidden_size}: each must divide")
        return config


def init_params(key, config: GraniteHConfig) -> Dict:
    """Normal(0, 0.02) weights and filters, unit norm scales, zero filter
    bias, f32; ``dt_bias``, ``A_log`` and ``D`` as
    ``models/nemotron_h.py::init_params`` draws them (the inverse softplus of
    ``exp(U(log time_step_min, log time_step_max))`` floored at
    ``time_step_floor``, ``log U(1, 16)`` and 1 a head: mamba_ssm's defaults).
    One tensor, ``embed/tokens``, is the embedding and the head. Jit it to
    make the tree on the device from the seed."""
    c = config
    d = c.hidden_size
    keys = iter(jax.random.split(key, 1 + 8 * c.num_hidden_layers))

    def lin(*shape):
        return {"kernel": 0.02 * jax.random.normal(next(keys), shape,
                                                   jnp.float32)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params: Dict = {"embed": {"tokens": lin(c.vocab_size, d)["kernel"]},
                    "final_norm": ones()}
    for i, kind in enumerate(c.layer_types):
        lp: Dict = {"norm": ones(), "ffn_norm": ones(),
                    "ffn": {"w_in": lin(d, 2 * c.shared_intermediate_size),
                            "w_out": lin(c.shared_intermediate_size, d)}}
        if kind == "mamba":
            h, inner = c.mamba_n_heads, c.mamba_inner
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                next(keys), (h,), jnp.float32, math.log(c.time_step_min),
                math.log(c.time_step_max))), c.time_step_floor)
            lp["mamba"] = {
                "in_proj": lin(d, inner + c.conv_dim + h),
                "conv": {**lin(c.conv_dim, c.mamba_d_conv),
                         "bias": jnp.zeros((c.conv_dim,), jnp.float32)},
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (h,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((h,), jnp.float32),
                "out_norm": ones(inner),
                "out_proj": lin(inner, d)}
        else:
            kv = c.num_key_value_heads * c.head_dim
            lp["attn"] = {"q": lin(d, d), "k": lin(d, kv), "v": lin(d, kv),
                          "out": lin(d, d)}
        params[f"layer{i}"] = lp
    return params


def attention_block(lp: Dict, x, config: GraniteHConfig, attn_fn: Callable):
    """Grouped-query attention without positions of the normed activations
    ``x`` [B, S, D], the scores times ``attention_multiplier``: ``attn_fn``
    scales by ``head_dim ** -0.5``, q carries the rest. K and V reach
    ``attn_fn`` at their own head count."""
    c = config
    b, s, _ = x.shape

    def proj(name, n):
        projected = x @ lp[name]["kernel"].astype(x.dtype)
        return checkpoint_name(projected, f"attn_{name}").reshape(b, s, n, -1)

    q = proj("q", c.num_attention_heads)
    k, v = (proj(name, c.num_key_value_heads) for name in ("k", "v"))
    rest = c.attention_multiplier * math.sqrt(c.head_dim)
    a = attn_fn(q * jnp.asarray(rest, q.dtype), k, v, causal=True)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


def swiglu(lp: Dict, x, width: int):
    """``W_out(silu(a) * b)`` with ``[a | b] = x W_in``, which bears the name
    'ffn_in'."""
    both = checkpoint_name(x @ lp["w_in"]["kernel"].astype(x.dtype), "ffn_in")
    return (jax.nn.silu(both[..., :width]) * both[..., width:]) \
        @ lp["w_out"]["kernel"].astype(x.dtype)


#: what a layer keeps beside the flash call's residuals (module docstring), by
#: the names the values bear where they are made: the attention layer's q, k
#: and v and the SwiGLU's ``x W_in``. A mixer's in projection ('mamba_in') is
#: made again: 1.25e9 B over the nine layers
PRODUCTS_KEPT = ("attn_q", "attn_k", "attn_v", "ffn_in")


def _add(x, part, multiplier: float):
    """``x + multiplier * part``, the product and the sum in f32 (0.22 is no
    bfloat16 number) and one rounding to ``x``'s dtype."""
    return (x.astype(jnp.float32)
            + multiplier * part.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, kind: str, config: GraniteHConfig, attn_fn: Callable):
    """One layer, the mixer and the SwiGLU each behind its pre-norm and under
    the residual multiplier, recomputed in the backward pass."""
    c = config
    h = rms_norm(x, lp["norm"]["scale"], c.rms_norm_eps)
    if kind == "mamba":
        with jax.named_scope(phases.MAMBA):
            mixed = mamba_block(
                lp["mamba"], h, heads=c.mamba_n_heads, head_dim=c.mamba_d_head,
                groups=c.mamba_n_groups, state=c.mamba_d_state,
                chunk=c.mamba_chunk_size, eps=c.rms_norm_eps)
    else:
        with jax.named_scope(phases.ATTN):
            mixed = attention_block(lp["attn"], h, c, attn_fn)
    x = _add(x, mixed, c.residual_multiplier)
    h = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
    with jax.named_scope(phases.FFN):
        out = swiglu(lp["ffn"], h, c.shared_intermediate_size)
    return _add(x, out, c.residual_multiplier)


def apply(params: Dict, tokens, config: GraniteHConfig,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> final hidden states [B, S, D] before the
    final norm."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = (c.embedding_multiplier * x).astype(c.dtype)
    for i, kind in enumerate(c.layer_types):
        x = _layer(params[f"layer{i}"], x, kind, c, attn_fn)
    return x


def logits_of(params: Dict, hidden, config: GraniteHConfig):
    """Final norm, the embedding as the head, over ``logits_scaling``:
    [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.rms_norm_eps)
    logits = h @ params["embed"]["tokens"].astype(h.dtype).T
    return logits / jnp.asarray(config.logits_scaling, logits.dtype)


def make_loss_fn(config: GraniteHConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch) -> loss`` for pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn)``. ``attn`` is 'full' or 'flash'
    (``models/blocks.py::make_attn_fn``)."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch):
        hidden = apply(params, batch["inputs"], config, attn_fn)
        with jax.named_scope(phases.HEAD):
            return token_ce(logits_of(params, hidden, config),
                            batch["targets"])

    return loss_fn
