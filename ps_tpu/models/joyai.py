"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``; 48B-A2.7B): a
DeepSeek-V3-shaped decoder. 40 layers at hidden 2,048, every one of them
multi-head latent attention **with positions**: 32 heads whose keys are 128
channels of their own from a 512-wide latent and 64 rotated channels all heads
share, q through a 1,536-wide latent and its norm, RoPE at theta 32e6 on
interleaved pairs (DeepSeek-V2, arXiv:2405.04434 section 2.1); a dense SwiGLU
7,168 wide in the leading layer, then 256 SwiGLU experts 768 wide, eight a
token by sigmoid scores and a selection bias, renormalised and scaled 2.5,
beside one shared expert; and **one prediction module for the token after
next** (DeepSeek-V3, arXiv:2412.19437 section 2.2): the store's first latent
attention with positions and a compressed q, and its first loss of two terms
whose second reads the embedding and the head a second time.

Pure functions over a parameter dict, as ``models/trinity.py``; the latent
attention (``mla_block``), ``rms_norm``, ``dense_ffn`` and the expert layer's
window (``WHOLE_WINDOW``) are ``models/blocks.py``'s. A layer is
``h = x + attn(norm1(x))``, ``y = h + ffn(norm2(h))``, and the equations of
each part are written out in the plain reference's docstring
(``benchmark/families/joyai_reference.py``), which this module is held to.
How they are computed here:

- ``blocks.mla_block`` with both of its options: the four latent projections
  and the two latent norms (``ps.attn/latent``), both rotations and the
  192-wide q and k (``ps.attn/rope``), one kernel call at keys of 192 and
  values of 128 (``ps.attn/full``).
- ``moe_block``: sigmoid scores in f32 over all ``router_width`` experts, the
  top ``num_experts_per_tok`` of ``score + expert_bias[layer]`` (the bias
  selects only), the picks' scores over their sum times
  ``routed_scaling_factor``; dropless grouped SwiGLU over the
  ``n_routed_experts`` held from ``expert_start`` on, on a window of rows
  fixed by the shapes (``HELD_ROWS_OVER_EVEN`` below), **plus the shared
  expert**, whole on every chip of the group.
- every layer is recomputed in the backward pass (one ``jax.checkpoint`` a
  layer) but its kernel call's output and logsumexp
  (``ops/flash_attention.py::KEPT``) and the routing's logits, picks and
  permutations (``ops/moe.py::ROUTE_KEPT``).
- the final RMSNorm once; its output feeds the head and the module.
- **the module** (``mtp_block``, ``ps.mtp``): at position ``i`` it joins the
  embedding of token ``i + 1`` and the main stack's normed output ``h_i``,
  each through a norm of its own, by ``eh_proj`` (``ps.mtp/join``); runs one
  expert layer of the shape above, with a row of the selection bias of its
  own; and ends in ``shared_head.norm`` and **the main model's head**. The
  embedding and the head are the main model's own leaves: their gradients are
  sums of two uses.
- each head pass (norm's output in, cross entropy out) under a
  ``jax.checkpoint`` of its own: between forward and backward a pass keeps its
  [B, S, D] input and no logit.

``batch = {"inputs": [B, S], "targets": [B, S]}`` is pre-shifted, as every
decoder's here: ``targets[i]`` is token ``i + 1``. The main loss is the mean
over all ``S`` positions of the cross entropy of position ``i`` against token
``i + 1``; the module reads ``targets[i]`` as its token ``i + 1`` and is scored
against ``targets[i + 1]``, token ``i + 2``, at positions ``0 .. S-2``: the
last position has no token after next, carries weight 0, and causality keeps
every counted position clear of it. ``loss = ce + mtp_loss_weight * mtp_ce``.

What the model does not compute, ``JoyaiConfig.from_dict`` refuses::

    step = store.make_step(make_loss_fn(config, attn="flash"), has_aux=True)
    loss, params, aux = step(batch, expert_bias)
    expert_bias = aux["expert_bias"]

The phases a trace can tell apart are opened here and in ``blocks.mla_block``
with ``jax.named_scope`` (``obs/phases.py::JOYAI_SCOPES``); they nest under the
step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ps_tpu.models.blocks import init_expert_bias  # noqa: F401 — re-export
from ps_tpu.models.blocks import (WHOLE_WINDOW, dense_ffn, make_attn_fn,
                                  mla_block, rms_norm)
from ps_tpu.obs import default_registry, phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, but ``n_routed_experts``: the experts held here,
    of ``router_width`` published ones, from ``expert_start`` on. The config
    gives no bias rate and no weight of the second loss: the benchmark file's
    ``assumed``. ``ep_size`` is read by nothing (how a deployment lays its
    experts out is no part of the mathematics)."""

    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168         # the dense layer's SwiGLU
    moe_intermediate_size: int = 768      # ONE expert's, and the shared one's
    num_hidden_layers: int = 40
    num_nextn_predict_layers: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rope_interleave: bool = True
    first_k_dense_replace: int = 1
    router_width: int = 256
    n_routed_experts: int = 256
    expert_start: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    bias_update_rate: float = 1e-3
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def num_expert_layers(self) -> int:
        """The rows of the selection bias: the main stack's expert layers,
        then the module's."""
        return (self.num_hidden_layers - self.first_k_dense_replace
                + self.num_nextn_predict_layers)

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.n_routed_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "JoyaiConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("rope_scaling", None), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("scoring_func", "sigmoid"), ("hidden_act", "silu"),
                          ("moe_layer_freq", 1)):
            if d.get(key, want) != want:
                raise ValueError(f"models/joyai.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        if d.get("num_nextn_predict_layers", 1) > 1:
            raise ValueError(
                f"models/joyai.py computes one prediction module, not "
                f"{d['num_nextn_predict_layers']}")
        if d.get("num_key_value_heads",
                 d["num_attention_heads"]) != d["num_attention_heads"]:
            raise ValueError("models/joyai.py has one K/V head a query "
                             "head (both expanded from the latent)")
        if d.get("q_lora_rank") is None:
            raise ValueError("models/joyai.py computes a compressed q: "
                             "q_lora_rank is not given")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["n_routed_experts"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if not 0 < config.first_k_dense_replace < config.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {config.first_k_dense_replace} "
                f"leaves no dense or no expert layer among "
                f"{config.num_hidden_layers}")
        return config


def init_params(key, config: JoyaiConfig) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. The module's
    embedding and head are the main model's: no leaf of its own for either.
    Jit it to make the tree on the device from the seed."""
    c = config
    d = c.hidden_size
    blocks = c.num_hidden_layers + c.num_nextn_predict_layers
    keys = iter(jax.random.split(key, 4 + 16 * blocks))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(*shape):
        return {"kernel": w(*shape)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    def swiglu(f):
        return {"w1": lin(d, f), "w3": lin(d, f), "w2": lin(f, d)}

    def layer(dense: bool):
        h = c.num_attention_heads
        lp = {"input_norm": ones(), "post_attn_norm": ones(),
              "attn": {
                  "q_a": lin(d, c.q_lora_rank), "q_norm": ones(c.q_lora_rank),
                  "q_b": lin(c.q_lora_rank,
                             h * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                  "kv_a": lin(d, c.kv_lora_rank + c.qk_rope_head_dim),
                  "kv_norm": ones(c.kv_lora_rank),
                  "kv_b": lin(c.kv_lora_rank,
                              h * (c.qk_nope_head_dim + c.v_head_dim)),
                  "out": lin(h * c.v_head_dim, d)}}
        if dense:
            lp["ffn"] = swiglu(c.intermediate_size)
        else:
            e, f = c.n_routed_experts, c.moe_intermediate_size
            lp["moe"] = {"router": lin(d, c.router_width),
                         "gate": w(e, d, f), "up": w(e, d, f),
                         "down": w(e, f, d),
                         "shared": swiglu(f * c.n_shared_experts)}
        return lp

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "head": lin(d, c.vocab_size), "final_norm": ones()}
    for i in range(c.num_hidden_layers):
        params[f"layer{i}"] = layer(i < c.first_k_dense_replace)
    if c.num_nextn_predict_layers:
        params["mtp"] = {"enorm": ones(), "hnorm": ones(),
                         "eh_proj": lin(2 * d, d), "layer": layer(False),
                         "norm": ones()}
    return params


#: The rows a share's layer moves at a time, in even loads of its held experts
#: (``tokens x top_k x held / router_width``), where ``ops/moe.py`` fixes 3
#: (``HELD_ROWS_OVER_EVEN``). ``models/sdar.py`` says why such a number is a
#: model's: under 0.02-normal weights and i.i.d. Zipf ids an attention layer's
#: output is nearly the same vector at every late position, so most tokens of
#: a layer pick the same eight experts, and each of the eight that is among the
#: held sixteen brings up to two even loads here (an even load is a 16th of a
#: layer's pairs, a popular expert an 8th). Two of eight among sixteen of 256
#: is one layer in eleven by the count of placements, a layer in a third of
#: the seeds with five expert layers: at 3 those would open a second window,
#: and the step would follow the seed. 4.25 holds two; three of eight is one
#: layer in a hundred. The grouped matmuls do the whole window's work, so the
#: room is paid for in every step: ``PERF.md`` section 6, PR 54.
HELD_ROWS_OVER_EVEN = 4.25


def window_rows(config: JoyaiConfig, tokens: int) -> int:
    """``ops/moe.py::window_rows`` at this model's ``HELD_ROWS_OVER_EVEN``."""
    c = config
    return moe.window_rows(tokens, c.num_experts_per_tok, c.n_routed_experts,
                           c.router_width, HELD_ROWS_OVER_EVEN)


def moe_block(lp: Dict, x, config: JoyaiConfig, bias):
    """The expert layer on normed activations ``x`` [B, S, D] with the
    layer's selection ``bias`` [router_width] or None: the held experts'
    part of the output plus the shared expert's [B, S, D], and the layer's
    ``Routing``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scoring="sigmoid", bias=bias,
            renorm_eps=1e-20, scaling=c.routed_scaling_factor, held=c.held)
    if routing.window is not None:      # a share: this model's window
        routing = routing._replace(window=jnp.arange(
            window_rows(c, b * s), dtype=jnp.int32))
    out = moe.over_windows(
        WHOLE_WINDOW, routing, tokens,
        *(lp[n].astype(x.dtype) for n in ("gate", "up", "down")))
    with jax.named_scope(phases.MOE_SHARED):
        out = out + dense_ffn(lp["shared"], tokens)
    return out.reshape(b, s, d), routing


@functools.partial(jax.checkpoint, static_argnums=(3, 4),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *moe.ROUTE_KEPT))
def _layer(lp: Dict, x, bias, config: JoyaiConfig, attn_fn: Callable):
    """One layer, recomputed in the backward pass: the stream out and, of an
    expert layer, its counts over all experts and over the held ones and the
    windows of rows it ran (None of the dense one)."""
    eps = config.rms_norm_eps
    with jax.named_scope(phases.ATTN):
        a = mla_block(lp["attn"], rms_norm(x, lp["input_norm"]["scale"], eps),
                      config, attn_fn)
    x = x + a
    h = rms_norm(x, lp["post_attn_norm"]["scale"], eps)
    if "ffn" in lp:
        with jax.named_scope(phases.FFN):
            return x + dense_ffn(lp["ffn"], h), None, None, None
    out, routing = moe_block(lp["moe"], h, config, bias)
    return (x + out, routing.counts, routing.group_sizes,
            moe.live_windows(routing))


def apply(params: Dict, tokens, config: JoyaiConfig, expert_bias=None,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (the main stack's hidden states [B, S, D]
    **after the final norm**: what the head reads and what the module is
    handed; and the list of each expert layer's (counts over all experts,
    over the held ones, windows of rows it ran))."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    of_experts = []
    for i in range(c.num_hidden_layers):
        bias = None
        if i >= c.first_k_dense_replace and expert_bias is not None:
            bias = expert_bias[len(of_experts)]
        x, *seen = _layer(params[f"layer{i}"], x, bias, c, attn_fn)
        if i >= c.first_k_dense_replace:
            of_experts.append(seen)
    return rms_norm(x, params["final_norm"]["scale"], c.rms_norm_eps), \
        of_experts


def mtp_block(params: Dict, hidden, next_tokens, config: JoyaiConfig,
              bias=None, attn_fn: Callable = None):
    """The prediction module on the main stack's normed output ``hidden``
    [B, S, D] and ``next_tokens`` [B, S] (token ``i + 1`` at position ``i``):
    its hidden states after ``shared_head.norm`` [B, S, D], ready for the main
    model's head, and its layer's (counts, held counts, windows)."""
    c = config
    mp = params["mtp"]
    eps = c.rms_norm_eps
    attn_fn = attn_fn or make_attn_fn("full")
    with jax.named_scope(phases.MTP_JOIN):
        # the main model's own embedding, read a second time
        e = jnp.take(params["embed"]["tokens"], next_tokens,
                     axis=0).astype(c.dtype)
        joined = jnp.concatenate(
            [rms_norm(e, mp["enorm"]["scale"], eps),
             rms_norm(hidden, mp["hnorm"]["scale"], eps)], axis=-1)
        u = joined @ mp["eh_proj"]["kernel"].astype(joined.dtype)
    u, *seen = _layer(mp["layer"], u, bias, c, attn_fn)
    return rms_norm(u, mp["norm"]["scale"], eps), seen


@jax.checkpoint
def head_ce(head, hidden, targets, weights):
    """One pass of the untied head: ``hidden`` [B, S, D] (a norm's output)
    -> the cross entropy against ``targets`` [B, S] in logsumexp form, the
    mean over the positions of ``weights`` [B, S] (0 or 1). Recomputed in the
    backward pass: between the two a pass keeps its operands and no logit."""
    logits = hidden @ head["kernel"].astype(hidden.dtype)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), -1)
    tok = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(weights * (lse - tok.astype(jnp.float32))) \
        / jnp.sum(weights)


def make_loss_fn(config: JoyaiConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch, expert_bias) -> (loss, aux)`` for
    pre-shifted ``batch = {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``). ``aux``, device values:
    ``loss`` itself, ``ce`` (the main head's, over all ``S`` positions) and
    ``mtp_ce`` (the module's, over ``mtp_positions`` = ``B (S - 1)``), its
    two terms, the second before its weight; ``expert_tokens`` [expert
    layers, router_width], the step's pairs per expert over all of them, the
    module's layer the last row; ``held_tokens`` [expert layers,
    n_routed_experts], those computed here; ``expert_windows`` [expert
    layers]; ``load_max_over_mean`` and ``held_pair_share`` (of the counts);
    ``dropped_tokens`` (pairs routed less pairs counted: 0, the window path
    drops none); ``live_pairs_per_step`` (the pairs computed here, all
    layers); ``expert_bias``, the bias for the next step."""
    attn_fn = make_attn_fn(attn, **attn_kw)
    c = config

    def loss_fn(params, batch, expert_bias):
        inputs, targets = batch["inputs"], batch["targets"]
        hidden, of_experts = apply(params, inputs, c, expert_bias, attn_fn)
        every = jnp.ones(targets.shape, jnp.float32)
        with jax.named_scope(phases.HEAD):
            ce = head_ce(params["head"], hidden, targets, every)
        loss, mtp_ce = ce, jnp.float32(0.0)
        counted = every.at[:, -1].set(0.0)
        if c.num_nextn_predict_layers:
            with jax.named_scope(phases.MTP):
                bias = None if expert_bias is None else expert_bias[-1]
                out, seen = mtp_block(params, hidden, targets, c, bias,
                                      attn_fn)
                of_experts.append(seen)
                # token i + 2 at position i; the last position has none
                after_next = jnp.concatenate(
                    [targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1)
                with jax.named_scope(phases.HEAD):
                    mtp_ce = head_ce(params["head"], out, after_next, counted)
            loss = ce + c.mtp_loss_weight * mtp_ce
        with jax.named_scope(phases.MOE_ROUTE):
            counts, held, windows = (jnp.stack(one)
                                     for one in zip(*of_experts))
            new_bias = moe.balance_bias(expert_bias, counts,
                                        c.bias_update_rate)
        routed = inputs.size * c.num_experts_per_tok * len(of_experts)
        total = jnp.sum(counts)
        return loss, {
            "loss": loss, "ce": ce, "mtp_ce": mtp_ce,
            "mtp_positions": jnp.sum(counted) * c.num_nextn_predict_layers,
            "expert_tokens": counts, "held_tokens": held,
            "expert_windows": windows,
            "load_max_over_mean": jnp.mean(
                jnp.max(counts, axis=-1) / jnp.mean(counts.astype(
                    jnp.float32), axis=-1)),
            "held_pair_share": jnp.sum(held) / total,
            "dropped_tokens": routed - total,
            "live_pairs_per_step": jnp.sum(held),
            "expert_bias": new_bias}

    return loss_fn


_mtp_ce = default_registry().gauge(
    "ps_joyai_mtp_ce",
    "cross entropy of the prediction module for the token after next, last "
    "step read")
_ce = default_registry().gauge(
    "ps_joyai_ce", "cross entropy of the main head, last step read")


def observe_losses(ce, mtp_ce) -> Tuple[float, float]:
    """``aux``'s two terms of a step, read to the host by whoever follows the
    run there and set on ``ps_joyai_ce`` and ``ps_joyai_mtp_ce``, side by side
    (the step raises nothing itself: ``models/bert.py::count_head_overflow``
    says why)."""
    ce, mtp_ce = float(ce), float(mtp_ce)
    _ce.set(ce)
    _mtp_ce.set(mtp_ce)
    return ce, mtp_ce
