"""OLMoE: a decoder whose every layer's feed-forward is 64 SwiGLU experts,
eight of them a token (Muennighoff et al. 2024, arXiv:2409.02060;
``model_type: olmoe``). The store's first expert layer.

Pure functions over a parameter dict, the tree the store shards by key;
``rms_norm``, ``rope``, the attention closure and the loss are
``models/blocks.py``'s. A block is pre-norm attention (RMSNorm, q/k/v without
bias, RMSNorm over the whole q and k projections before the heads are split,
rotary positions, causal softmax attention, out projection) and a pre-norm
expert layer (``ops/moe.py``): router over all experts in f32, top-k of the
softmax *without* renormalisation, dropless grouped SwiGLU, no shared expert.
Embedding and head are untied. Parameters are f32; activations and matmuls
run in ``config.dtype``, the norms' statistics, the router, the softmaxes and
the loss in f32.

The loss is what the model was trained with::

    loss = ce + load_balance_coef * load_balance + z_loss_coef * z_loss

summed over layers for the two router terms, with
``aux = {ce, load_balance, z_loss, expert_tokens[E]}`` (``expert_tokens``
summed over layers too) leaving the fused step as device values:
``store.make_step(make_loss_fn(config), has_aux=True)``.

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::MOE_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from ps_tpu.models.blocks import make_attn_fn, rms_norm, rope, token_ce
from ps_tpu.obs import phases
from ps_tpu.ops import moe


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, plus the loss coefficients of the recipe."""

    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024     # the width of ONE expert
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    load_balance_coef: float = 0.01
    z_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_dict(cls, d: Dict) -> "OlmoeConfig":
        """From a ``config.json``-like dict; keys this model does not read
        (``hidden_act``, ``rope_scaling`` ...) are checked, not dropped in
        silence, where another value would change the mathematics."""
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("clip_qkv", None), ("rope_scaling", None),
                          ("tie_word_embeddings", False)):
            if d.get(key, want) != want:
                raise ValueError(f"models/olmoe.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        kv = d.get("num_key_value_heads", d["num_attention_heads"])
        if kv != d["num_attention_heads"]:
            raise ValueError("models/olmoe.py has plain multi-head attention")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        return cls(**kw)


def init_params(key, config: OlmoeConfig) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. Jit it to make the
    tree on the device from the seed."""
    c = config
    d, f, e = c.hidden_size, c.intermediate_size, c.num_experts

    keys = iter(jax.random.split(key, 2 + 8 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def ones():
        return jnp.ones((d,), jnp.float32)

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "final_norm": {"scale": ones()},
                    "head": {"kernel": w(d, c.vocab_size)}}
    for i in range(c.num_hidden_layers):
        params[f"layer{i}"] = {
            "attn_norm": {"scale": ones()},
            "attn": {"q": {"kernel": w(d, d)}, "k": {"kernel": w(d, d)},
                     "v": {"kernel": w(d, d)}, "out": {"kernel": w(d, d)},
                     "q_norm": {"scale": ones()},
                     "k_norm": {"scale": ones()}},
            "ffn_norm": {"scale": ones()},
            "moe": {"router": {"kernel": w(d, e)},
                    "gate": w(e, d, f), "up": w(e, d, f), "down": w(e, f, d)},
        }
    return params


def attention_block(lp: Dict, x, config: OlmoeConfig, attn_fn: Callable):
    """Attention of the normed activations ``x`` [B, S, D] -> [B, S, D]."""
    c = config
    b, s, d = x.shape
    heads = c.num_attention_heads

    def proj(name):
        return x @ lp[name]["kernel"].astype(x.dtype)

    q = rms_norm(proj("q"), lp["q_norm"]["scale"], c.rms_norm_eps)
    k = rms_norm(proj("k"), lp["k_norm"]["scale"], c.rms_norm_eps)
    v = proj("v")
    q, k, v = (t.reshape(b, s, heads, d // heads) for t in (q, k, v))
    a = attn_fn(rope(q, c.rope_theta), rope(k, c.rope_theta), v, causal=True)
    return a.reshape(b, s, d) @ lp["out"]["kernel"].astype(x.dtype)


def moe_block(lp: Dict, x, config: OlmoeConfig):
    """The expert layer on normed activations ``x`` [B, S, D]: the output
    [B, S, D] and the layer's ``Routing``. Not ``blocks.window_of``: every
    expert held, no window, the stacks cast inside ``ps.moe/expert``."""
    c = config
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(tokens, lp["router"]["kernel"],
                            c.num_experts_per_tok,
                            renormalize=c.norm_topk_prob)
    with jax.named_scope(phases.MOE_DISPATCH):
        rows = moe.dispatch(tokens, routing)
    with jax.named_scope(phases.MOE_EXPERT):
        rows = moe.expert_ffn(
            rows, *(lp[n].astype(x.dtype) for n in ("gate", "up", "down")),
            routing.group_sizes)
    with jax.named_scope(phases.MOE_COMBINE):
        out = moe.combine(rows, routing)
    return out.reshape(b, s, d), routing


def apply(params: Dict, tokens, config: OlmoeConfig,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, the list of each layer's ``Routing``)."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(c.dtype)
    routings = []
    for i in range(c.num_hidden_layers):
        lp = params[f"layer{i}"]
        h = rms_norm(x, lp["attn_norm"]["scale"], c.rms_norm_eps)
        with jax.named_scope(phases.ATTN):
            x = x + attention_block(lp["attn"], h, c, attn_fn)
        h = rms_norm(x, lp["ffn_norm"]["scale"], c.rms_norm_eps)
        out, routing = moe_block(lp["moe"], h, c)
        x = x + out
        routings.append(routing)
    return x, routings


def logits_of(params: Dict, hidden, config: OlmoeConfig):
    """Final norm and the untied head: [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.rms_norm_eps)
    return h @ params["head"]["kernel"].astype(h.dtype)


def make_loss_fn(config: OlmoeConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch) -> (loss, aux)`` for pre-shifted
    ``batch = {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``)."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch):
        hidden, routings = apply(params, batch["inputs"], config, attn_fn)
        with jax.named_scope(phases.HEAD):
            ce = token_ce(logits_of(params, hidden, config),
                          batch["targets"])
        with jax.named_scope(phases.MOE_ROUTE):
            load_balance = sum(moe.load_balance_loss(r) for r in routings)
            z_loss = sum(moe.router_z_loss(r) for r in routings)
        loss = (ce + config.load_balance_coef * load_balance
                + config.z_loss_coef * z_loss)
        return loss, {"ce": ce, "load_balance": load_balance,
                      "z_loss": z_loss,
                      "expert_tokens": sum(r.group_sizes for r in routings)}

    return loss_fn
