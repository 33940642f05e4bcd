"""LFM2-MoE: a decoder of two kinds of mixer, a double-gated short causal
convolution in most layers and grouped-query attention in every fourth, with
a dense SwiGLU in the leading layers and sparse SwiGLU experts in the rest
(Liquid AI, ``model_type: lfm2_moe``; LFM2-24B-A2B: 40 layers, 30 of them
``conv``, 64 experts of width 1536, four a token). The store's first stack
whose layers differ, and its first expert layer that holds a share of the
experts: ``num_experts`` of ``router_width``, from ``expert_start`` on, one
chip of an expert-parallel group without its exchange (``ops/moe.py``).

Pure functions over a parameter dict, as ``models/olmoe.py``; ``rms_norm``,
``rope``, ``dense_ffn`` and the checkpointed ``experts_of`` are
``models/blocks.py``'s. A block is::

    x += mixer(rms_norm(x));  x += ffn(rms_norm(x))

- ``conv`` mixer: ``h @ W_in`` [D, 3D], the two gates and the three causal
  taps (``ops/gated_conv.py``), ``@ W_out`` [D, D]. No position enters it.
- ``full_attention`` mixer: q on ``num_attention_heads``, k and v on
  ``num_key_value_heads`` (no bias), RMSNorm over each head's own width on q
  and k, RoPE, causal softmax attention with each K/V head serving a group
  of query heads, out projection. K and V go to the attention closure at
  their own head count (``models/blocks.py::make_attn_fn``: 'flash' reads
  head ``h // group`` where it lies, 'full' repeats them).
- dense feed-forward, layers below ``num_dense_layers``:
  ``W2(silu(W1 h) * W3 h)`` of width ``intermediate_size``.
- expert layer, the others: sigmoid scores in f32, the top
  ``num_experts_per_tok`` of ``score + expert_bias[layer]`` (the bias selects
  only), weights ``score / (sum of the picks' scores + 1e-6)`` times
  ``routed_scaling_factor``, dropless grouped SwiGLU over the held experts,
  recomputed in the backward pass (``jax.checkpoint`` around dispatch,
  experts and combine: the tokens and the routing are kept, the
  ``[T * k, D]`` and ``[T * k, F]`` row buffers are not).
- a final RMSNorm; the head is the embedding transposed.

The loss is the cross entropy alone (no auxiliary loss: the balance is the
bias's). ``expert_bias`` [expert layers, ``router_width``] is state that the
step updates by a rule of its own (``ops/moe.py::balance_bias``) from the
step's expert counts. It enters ``loss_fn`` as an extra argument and leaves
in ``aux``, as ResNet's ``batch_stats`` do::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch, expert_bias)
    expert_bias = aux["expert_bias"]

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::LFM2_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ps_tpu.models.blocks import init_expert_bias  # noqa: F401 — re-export
from ps_tpu.models.blocks import (dense_ffn, experts_of, make_attn_fn,
                                  rms_norm, rope, token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.gated_conv import gated_short_conv


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, but ``num_experts``: the experts held here, of
    ``router_width`` published ones, from ``expert_start`` on."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776        # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536     # ONE expert's
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    router_width: int = 64
    num_experts: int = 64
    expert_start: int = 0
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    bias_update_rate: float = 1e-3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.num_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "Lfm2Config":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("conv_bias", False), ("tie_word_embeddings", True)):
            if d.get(key, want) != want:
                raise ValueError(f"models/lfm2.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["num_experts"])
        rope_parameters = d.get("rope_parameters") or {}
        if rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("models/lfm2.py has plain RoPE only")
        kw["rope_theta"] = float(rope_parameters.get(
            "rope_theta", d.get("rope_theta", cls.rope_theta)))
        kw["layer_types"] = tuple(d["layer_types"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if len(config.layer_types) != config.num_hidden_layers or set(
                config.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"{config.num_hidden_layers} layers of types "
                             f"{config.layer_types}")
        return config


def init_params(key, config: Lfm2Config) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. Jit it to make the
    tree on the device from the seed."""
    c = config
    d, kv = c.hidden_size, c.num_key_value_heads * c.head_dim
    keys = iter(jax.random.split(key, 1 + 8 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "final_norm": ones()}
    for i, kind in enumerate(c.layer_types):
        lp = {"operator_norm": ones(), "ffn_norm": ones()}
        if kind == "conv":
            lp["conv"] = {"in_proj": {"kernel": w(d, 3 * d)},
                          "filter": w(d, c.conv_L_cache),
                          "out_proj": {"kernel": w(d, d)}}
        else:
            lp["attn"] = {"q": {"kernel": w(d, d)}, "k": {"kernel": w(d, kv)},
                          "v": {"kernel": w(d, kv)},
                          "out": {"kernel": w(d, d)},
                          "q_norm": ones(c.head_dim),
                          "k_norm": ones(c.head_dim)}
        if i < c.num_dense_layers:
            f = c.intermediate_size
            lp["ffn"] = {"w1": {"kernel": w(d, f)}, "w3": {"kernel": w(d, f)},
                         "w2": {"kernel": w(f, d)}}
        else:
            e, f = c.num_experts, c.moe_intermediate_size
            lp["moe"] = {"router": {"kernel": w(d, c.router_width)},
                         "gate": w(e, d, f), "up": w(e, d, f),
                         "down": w(e, f, d)}
        params[f"layer{i}"] = lp
    return params


def conv_block(lp: Dict, x):
    """The conv mixer on normed activations ``x`` [B, S, D]."""
    bcx = x @ lp["in_proj"]["kernel"].astype(x.dtype)
    with jax.named_scope(phases.CONV_GATE):
        y = gated_short_conv(bcx, lp["filter"])
    return y @ lp["out_proj"]["kernel"].astype(x.dtype)


def attention_block(lp: Dict, x, config: Lfm2Config, attn_fn: Callable):
    """Grouped-query attention of the normed activations ``x`` [B, S, D]:
    K and V reach ``attn_fn`` at their own head count."""
    c = config
    b, s, d = x.shape
    heads, kv_heads = c.num_attention_heads, c.num_key_value_heads

    def proj(name, n):
        return (x @ lp[name]["kernel"].astype(x.dtype)).reshape(b, s, n, -1)

    q = rms_norm(proj("q", heads), lp["q_norm"]["scale"], c.norm_eps)
    k = rms_norm(proj("k", kv_heads), lp["k_norm"]["scale"], c.norm_eps)
    v = proj("v", kv_heads)
    q, k = rope(q, c.rope_theta), rope(k, c.rope_theta)
    a = attn_fn(q, k, v, causal=True)
    return a.reshape(b, s, d) @ lp["out"]["kernel"].astype(x.dtype)


def moe_block(lp: Dict, x, config: Lfm2Config, bias):
    """The expert layer on normed activations ``x`` [B, S, D] with the
    layer's selection ``bias`` [router_width] or None: the held experts'
    part of the output [B, S, D] and the layer's ``Routing``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scoring="sigmoid", bias=bias,
            renorm_eps=1e-6, scaling=c.routed_scaling_factor, held=c.held)
    out = experts_of(tokens, lp["gate"], lp["up"], lp["down"], routing)
    return out.reshape(b, s, d), routing


def apply(params: Dict, tokens, config: Lfm2Config, expert_bias=None,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, the list of each expert layer's ``Routing``)."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    routings = []
    for i, kind in enumerate(c.layer_types):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["operator_norm"]["scale"], c.norm_eps)
        if kind == "conv":
            with jax.named_scope(phases.CONV):
                x = x + conv_block(lp["conv"], h)
        else:
            with jax.named_scope(phases.ATTN):
                x = x + attention_block(lp["attn"], h, c, attn_fn)
        h = rms_norm(x, lp["ffn_norm"]["scale"], c.norm_eps)
        if i < c.num_dense_layers:
            with jax.named_scope(phases.FFN):
                x = x + dense_ffn(lp["ffn"], h)
        else:
            bias = None
            if c.use_expert_bias and expert_bias is not None:
                bias = expert_bias[len(routings)]
            out, routing = moe_block(lp["moe"], h, c, bias)
            x = x + out
            routings.append(routing)
    return x, routings


def logits_of(params: Dict, hidden, config: Lfm2Config):
    """Final norm and the tied head: [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.norm_eps)
    return h @ params["embed"]["tokens"].astype(h.dtype).T


def make_loss_fn(config: Lfm2Config, attn: str = "full", **attn_kw):
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
            new_bias = expert_bias
            if config.use_expert_bias:
                new_bias = moe.balance_bias(expert_bias, counts,
                                            config.bias_update_rate)
        return ce, {"ce": ce, "expert_tokens": counts, "held_tokens": held,
                    "expert_windows": windows, "expert_bias": new_bias}

    return loss_fn
