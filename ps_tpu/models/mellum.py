"""Mellum (``model_type: mellum``): a decoder every layer of which is sparse,
64 SwiGLU experts and no shared one beside them, behind attention of two
kinds, three layers of four looking back over a window of ``sliding_window``
keys and the fourth over every earlier key, all of them rotated, the full
layers by a scaled table of their own (JetBrains; Mellum2-12B-A2.5B: 28
layers, hidden 2,304, 32 query heads of 128 on 4 K/V heads, a window of
1,024, experts of width 896, eight a token). The store's first expert layer
whose tokens cross chips: told a mesh, ``moe_block`` runs under ``shard_map``
over the mesh's data axis, each chip routes its own tokens over all the
experts and the pairs travel to the chips that hold their experts and back
(``ops/moe.py::over_trips``), the expert stacks read split by expert as the
store keeps them (``mellum_partition_rules``); told none, one device holds
every expert and the layer is OLMoE's. No dense layer, no shared expert, no
selection bias: nothing stands beside the routed experts.

Pure functions over a parameter dict, as ``models/trinity.py``; ``rms_norm``,
``rope`` and ``make_attn_fn`` are ``models/blocks.py``'s. A layer is::

    h = x + attn(norm1(x));  y = h + moe(norm2(h))

and the equations of each part are written out in the plain reference's
docstring (``benchmark/families/mellum_reference.py``), which this module is
held to. What differs here is how they are computed:

- ``attention_block``: q on ``num_attention_heads``, k and v on
  ``num_key_value_heads``, no bias; with ``qk_norm`` an RMSNorm over each q
  and k head's own width (assumed: the configuration's file says why); RoPE
  on q and k in every layer by ``rope_table(config, kind)``, plain in a
  ``sliding_attention`` layer and YaRN's blend in a ``full_attention`` one,
  whose cos and sin carry ``attention_factor``; ``window=sliding_window`` to
  the attention of a sliding layer. With ``attn='flash'`` K and V enter the
  kernel at their own head count, and a sliding layer's calls are the
  kernel's band step (a block against the window's keys before it and its
  own, and no other).
- ``moe_block``: softmax over all experts in f32, the top
  ``num_experts_per_tok``, their probabilities renormalised
  (``norm_topk_prob``), dropless grouped SwiGLU. Across chips the grouped
  matmuls run once a source and do a whole buffer's work whatever is live
  (``expected_rows``), so a step's time does not follow how the seed spread
  the popular experts over the chips (the fullest chip computes 1.1 to 2.6
  times an even share of a layer's pairs over sixteen seeds, PR 46). The
  chip that routed the tokens touches those buffers by contiguous copies
  alone: its random access is one gather of its own ``T x k`` pairs each
  way (``ops/moe.py::send`` / ``receive``), whatever the seed made of the
  split.
- every layer is recomputed in the backward pass (one ``jax.checkpoint`` a
  layer) but for the flash call's output and logsumexp
  (``ops/flash_attention.py::KEPT``). What the exchange received is not kept:
  the recomputation exchanges again (the layer's output is the expert
  branch's, which nothing in the layer reads, so the checkpoint's own
  recomputation drops it and ``over_trips``' rule computes each trip once
  more: six exchanges a layer a step, two of them forward).
- a final RMSNorm and an untied head, whose cross entropy runs over
  ``HEAD_BLOCK`` positions of every sequence at a time
  (``blocked_head_ce``, the mean of ``models/blocks.py::blocked_head_nll``).

The loss is the cross entropy plus ``router_aux_loss_coef`` times the sum
over layers of ``E * sum_e f_e P_e`` over the global batch
(``ops/moe.py::load_balance_loss``'s form, summed as OLMoE's). What the
model does not compute, ``MellumConfig.from_dict`` refuses::

    store = ps.KVStore(optimizer="adamw", placement="sharded",
                       partition_rules=mellum_partition_rules())
    step = store.make_step(make_loss_fn(config, mesh=ctx.mesh), has_aux=True)
    loss, params, aux = step(batch)

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::MELLUM_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ps_tpu.models.blocks import (blocked_head_nll, make_attn_fn, rms_norm,
                                  rope)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT
from ps_tpu.parallel.mesh import DATA_AXIS

WINDOWED, FULL = "sliding_attention", "full_attention"
#: positions of every sequence whose logits are formed at a time
HEAD_BLOCK = 2048


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names; ``rope_parameters`` as sorted items a layer type
    (a dataclass that ``jax.checkpoint`` takes as static is hashable)."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_parameters: Tuple = ()
    qk_norm: bool = True                  # assumed, see the module docstring
    router_aux_loss_coef: float = 0.001   # assumed
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_dict(cls, d: Dict) -> "MellumConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("num_nextn_predict_layers", 0),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu"),
                          ("use_sliding_window", True)):
            if d.get(key, want) != want:
                raise ValueError(f"models/mellum.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        other = set(d.get("mlp_layer_types", ())) - {"sparse"}
        if other or len(d.get("mlp_layer_types", d["layer_types"])) != len(
                d["layer_types"]):
            raise ValueError(
                "models/mellum.py computes a sparse layer for every layer, "
                f"not mlp_layer_types {d.get('mlp_layer_types')}")
        for kind, rp in d["rope_parameters"].items():
            if rp.get("rope_type", "default") not in ("default", "yarn"):
                raise ValueError(
                    "models/mellum.py rotates by rope_type 'default' or "
                    f"'yarn', not {rp['rope_type']!r} ({kind})")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw["layer_types"] = tuple(d["layer_types"])
        kw["rope_parameters"] = tuple(sorted(
            (kind, tuple(sorted(rp.items())))
            for kind, rp in d["rope_parameters"].items()))
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if len(config.layer_types) != config.num_hidden_layers or set(
                config.layer_types) - {WINDOWED, FULL}:
            raise ValueError(f"{config.num_hidden_layers} layers of types "
                             f"{config.layer_types}: models/mellum.py knows "
                             f"{WINDOWED!r} and {FULL!r}")
        missing = set(config.layer_types) - set(d["rope_parameters"])
        if missing:
            raise ValueError(f"rope_parameters has no entry for {missing}")
        return config


def mellum_partition_rules():
    """The expert stacks ``[E, .., ..]`` split by expert over the mesh's data
    axis, their moments with them: a split the model reads as it is stored
    (``parallel/sharding.py::gathered_sharding``). Every other leaf is the
    placement's (ZeRO-1)."""
    return [(r"layers/\d+/experts/w[123]$", (DATA_AXIS, None, None))]


def rope_table(config: MellumConfig, kind: str):
    """``(theta, inv_freq [head_dim / 2] or None, scale or None)`` of the
    layer type ``kind``, from ``rope_parameters``: the plain table, or YaRN's
    as ``transformers`` computes it (the interpolated frequencies
    ``theta^(-2i/d) / factor`` and the plain ones blended by a ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original positions; cos and sin times ``attention_factor``). The table
    is the same at every sequence length."""
    rp = dict(dict(config.rope_parameters)[kind])
    theta, dim = float(rp["rope_theta"]), config.head_dim
    if rp.get("rope_type", "default") == "default":
        return theta, None, None
    factor = float(rp["factor"])
    original = rp["original_max_position_embeddings"]
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def turns(n):   # the dimension that turns n times over the original span
        return dim * math.log(original / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(rp.get("beta_fast", 32))), 0)
    high = min(math.ceil(turns(rp.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv_freq = inter * ramp + extra * (1 - ramp)
    scale = rp.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return theta, jnp.asarray(inv_freq, jnp.float32), float(scale)


def init_params(key, config: MellumConfig) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. Jit it to make the
    tree on the device from the seed."""
    c = config
    d, f, e = c.hidden_size, c.moe_intermediate_size, c.num_experts
    q, kv = (n * c.head_dim for n in (c.num_attention_heads,
                                      c.num_key_value_heads))
    keys = iter(jax.random.split(key, 2 + 8 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(*shape):
        return {"kernel": w(*shape)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    layers = {}
    for i in range(c.num_hidden_layers):
        attn = {"q": lin(d, q), "k": lin(d, kv), "v": lin(d, kv),
                "out": lin(q, d)}
        if c.qk_norm:
            attn.update(q_norm=ones(c.head_dim), k_norm=ones(c.head_dim))
        layers[str(i)] = {
            "input_norm": ones(), "post_attn_norm": ones(), "attn": attn,
            "router": lin(d, e),
            "experts": {"w1": w(e, d, f), "w3": w(e, d, f), "w2": w(e, f, d)}}
    return {"embed": {"tokens": w(c.vocab_size, d)},
            "head": lin(d, c.vocab_size), "final_norm": ones(),
            "layers": layers}


def attention_block(lp: Dict, x, config: MellumConfig, kind: str,
                    attn_fn: Callable):
    """Grouped-query attention of the normed activations ``x`` [B, S, D], of
    the layer's ``kind``. K and V reach ``attn_fn`` at their own head
    count."""
    c = config
    b, s, _ = x.shape

    def proj(name, n):
        return (x @ lp[name]["kernel"].astype(x.dtype)).reshape(b, s, n, -1)

    q, k = proj("q", c.num_attention_heads), proj("k", c.num_key_value_heads)
    v = proj("v", c.num_key_value_heads)
    if c.qk_norm:
        q = rms_norm(q, lp["q_norm"]["scale"], c.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"]["scale"], c.rms_norm_eps)
    theta, inv_freq, scale = rope_table(c, kind)
    q = rope(q, theta, inv_freq=inv_freq, scale=scale)
    k = rope(k, theta, inv_freq=inv_freq, scale=scale)
    window = c.sliding_window if kind == WINDOWED else None
    with jax.named_scope(phases.ATTN_WINDOW if window else phases.ATTN_FULL):
        a = attn_fn(q, k, v, causal=True, window=window)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


def _exchanged(trip: moe.Trip, tokens, w1, w3, w2):
    """One trip of the exchange: ``ops/moe.py::over_trips``' ``layer``. The
    rows that arrive from each source are sorted by this chip's experts, so
    the grouped matmuls run once a source, each doing its whole buffer's
    work. Beside the trip's part of the output, the group sizes the matmuls
    were handed: what arrived, by source and expert."""
    with jax.named_scope(phases.MOE_DISPATCH):
        rows, sizes = moe.to_owners(moe.send(tokens, trip), trip.sizes,
                                    DATA_AXIS)
    with jax.named_scope(phases.MOE_EXPERT):
        rows = jnp.stack([
            moe.expert_ffn(rows[s], w1, w3, w2, sizes[s],
                           expected_rows=rows.shape[1])
            for s in range(rows.shape[0])])
    with jax.named_scope(phases.MOE_COMBINE):
        return moe.receive(moe.from_owners(rows, DATA_AXIS), trip), sizes


def _experts(tokens, router, w1, w3, w2, *, config: MellumConfig,
             chips: int):
    """The expert layer on one chip's ``tokens`` [T, D], its ``chips``-th of
    the stacks: the output [T, D] and, each with a leading axis of 1 (a row a
    chip), the pairs per expert over all of them [E], the router's mean
    probability of each [E] (the balance term's ``P``, with its gradient),
    the rows this chip's trips carried to the owners (itself among them) and
    to other chips, by its own routing; the rows its experts were handed,
    counted from the sizes that arrived; and the trips beyond the first."""
    c = config
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(tokens, router, c.num_experts_per_tok,
                            renormalize=c.norm_topk_prob, scoring="softmax")
        probs = jnp.mean(routing.probs, axis=0)
    stacks = tuple(w.astype(tokens.dtype) for w in (w1, w3, w2))
    if chips == 1:
        with jax.named_scope(phases.MOE_DISPATCH):
            rows = moe.dispatch(tokens, routing)
        with jax.named_scope(phases.MOE_EXPERT):
            rows = moe.expert_ffn(rows, *stacks, routing.group_sizes)
        with jax.named_scope(phases.MOE_COMBINE):
            out = moe.combine(rows, routing)
        sent = computed = jnp.sum(routing.group_sizes)
        moved = more = jnp.int32(0)
    else:
        out, arrived = moe.over_trips(_exchanged, routing, DATA_AXIS, tokens,
                                      *stacks)
        with jax.named_scope(phases.MOE_ROUTE):
            trips = moe.trips_of(routing, DATA_AXIS)
            # the sender's figures, from its routing; the owner's, from the
            # sizes the exchange delivered to its grouped matmuls
            to_owner = jnp.sum(moe.sent_rows(routing, trips, chips), axis=-1)
            sent = jnp.sum(to_owner)
            moved = sent - to_owner[jax.lax.axis_index(DATA_AXIS)]
            computed = jnp.sum(arrived)
            more = trips - 1
    return (out, routing.group_sizes[None], probs[None], sent[None],
            moved[None], computed[None], more[None])


def moe_block(lp: Dict, x, config: MellumConfig, mesh=None):
    """The expert layer on normed activations ``x`` [B, S, D]: its output
    [B, S, D] and ``_experts``' counts by chip. ``mesh``: the chips of its
    data axis share the layer, each its own sequences and its own
    ``num_experts / chips`` experts; None, or an axis of one: all here."""
    b, s, d = x.shape
    chips = mesh.shape[DATA_AXIS] if mesh is not None else 1
    args = (x.reshape(b * s, d), lp["router"]["kernel"],
            *(lp["experts"][n] for n in ("w1", "w3", "w2")))
    local = functools.partial(_experts, config=config, chips=chips)
    if chips == 1:
        out, *stats = local(*args)
    else:
        split = P(DATA_AXIS)
        # check_vma off: the trips' while_loop carries values that vary over
        # the axis beside ones that do not (the pmax'd count)
        out, *stats = shard_map(
            local, mesh=mesh, in_specs=(split, P(), split, split, split),
            out_specs=(split,) * 7, check_vma=False)(*args)
    return out.reshape(b, s, d), stats


@functools.partial(jax.checkpoint, static_argnums=(2, 3, 4, 5),
                   policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
def _layer(lp: Dict, x, kind: str, config: MellumConfig, attn_fn: Callable,
           mesh):
    """One layer, recomputed in the backward pass: the stream out and the
    expert layer's counts."""
    eps = config.rms_norm_eps
    with jax.named_scope(phases.ATTN):
        a = attention_block(lp["attn"],
                            rms_norm(x, lp["input_norm"]["scale"], eps),
                            config, kind, attn_fn)
    x = x + a
    out, stats = moe_block(
        lp, rms_norm(x, lp["post_attn_norm"]["scale"], eps), config, mesh)
    return x + out, stats


def apply(params: Dict, tokens, config: MellumConfig,
          attn_fn: Callable = None, mesh=None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, and by layer and chip: the pairs per expert [L, chips, E],
    the router's mean probabilities [L, chips, E], the rows sent, the rows
    sent to other chips, the rows computed and the further trips, each
    [L, chips])."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    stats = []
    for i, kind in enumerate(c.layer_types):
        x, of_experts = _layer(params["layers"][str(i)], x, kind, c, attn_fn,
                               mesh)
        stats.append(of_experts)
    return (x,) + tuple(jnp.stack(s) for s in zip(*stats))


def blocked_head_ce(hidden, head, targets, block):
    """``blocks.token_ce`` of ``hidden @ head`` for normed hidden states
    ``hidden`` [B, S, D] and a head [D, V]: the mean of
    ``blocks.blocked_head_nll``'s block sums (the blocked readout lives there
    since PR 63: Ouro's four weighted readouts call it too), the logits
    formed ``block`` positions of every sequence at a time, each block under
    a ``jax.checkpoint``: [B, block, V] logits and their cotangent live at
    once, not [B, S, V] (98,304 ids at 8,192 tokens a chip: 0.8e9 B for
    3.2e9)."""
    b, s, _ = hidden.shape
    return jnp.sum(blocked_head_nll(hidden, head, targets, block,
                                    summed=True)) / (b * s)


def make_loss_fn(config: MellumConfig, attn: str = "full", mesh=None,
                 **attn_kw):
    """``loss_fn(params, batch) -> (loss, aux)`` for pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, for ``KVStore.make_step(loss_fn,
    has_aux=True)``. ``attn`` is 'full' or 'flash'
    (``models/blocks.py::make_attn_fn``); ``mesh`` is the store's, whose
    data axis shares each expert layer (``moe_block``). ``aux``: ``ce`` and
    ``load_balance``, the loss's two terms; ``expert_tokens`` [L, E], the
    global batch's pairs per expert; ``sent_rows`` [L, chips], the rows a
    chip's trips carried to the owners, and ``exchange_rows`` [L, chips],
    those of them bound for other chips (the sender's figures, from its
    routing); ``received_rows`` [L, chips], the rows a chip's experts
    computed, counted from the group sizes the exchange delivered;
    ``exchange_trips`` [L], the trips beyond the first."""
    attn_fn = make_attn_fn(attn, **attn_kw)
    c = config

    def loss_fn(params, batch):
        hidden, counts, probs, sent, moved, computed, more = apply(
            params, batch["inputs"], c, attn_fn, mesh)
        with jax.named_scope(phases.HEAD):
            h = rms_norm(hidden, params["final_norm"]["scale"],
                         c.rms_norm_eps)
            seq = h.shape[1]
            ce = blocked_head_ce(
                h, params["head"]["kernel"], batch["targets"],
                HEAD_BLOCK if seq % HEAD_BLOCK == 0 else seq)
        with jax.named_scope(phases.MOE_ROUTE):
            # chips route as many tokens each: the global batch's share of
            # the pairs and mean probability, an expert
            expert_tokens = jnp.sum(counts, axis=1)
            share = expert_tokens.astype(jnp.float32) / jnp.sum(
                expert_tokens, axis=-1, keepdims=True)
            balance = jnp.sum(c.num_experts * jnp.sum(
                share * jnp.mean(probs, axis=1), axis=-1))
        loss = ce + c.router_aux_loss_coef * balance
        return loss, {"ce": ce, "load_balance": balance,
                      "expert_tokens": expert_tokens,
                      "sent_rows": sent, "exchange_rows": moved,
                      "received_rows": computed,
                      "exchange_trips": jnp.max(more, axis=1)}

    return loss_fn
