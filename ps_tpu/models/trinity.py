"""Trinity (``model_type: afmoe``): a decoder whose attention layers differ
in what they see and in whether they rotate, three of four looking back over
a window of ``sliding_window`` keys with rotary positions and the fourth over
every earlier key with no position at all, every one behind a sigmoid gate on
its output and every branch between two norms; a dense SwiGLU in the leading
layers and sparse SwiGLU experts beside one shared expert in the rest (Arcee
AI; Trinity-Mini: 32 layers, 32 query heads of 128 on 4 K/V heads, a window of
2,048, 128 experts of width 1,024, eight a token). The store's first attention
that sees a window (``ops/flash_attention.py``'s ``window=``) and its first
stack whose attention layers are of two kinds. As LFM2's and Kimi-Linear's,
the expert layer holds a share of the experts: ``num_experts`` of
``router_width``, from ``expert_start`` on, one chip of an expert-parallel
group without its exchange (``ops/moe.py``).

Pure functions over a parameter dict, as ``models/lfm2.py``; the three
SwiGLUs are ``models/blocks.py``'s ``dense_ffn``, and ``rms_norm``, ``rope``
and the expert layer's window (``WHOLE_WINDOW``) are its too. A layer is::

    h = x + norm2(attn(norm1(x)));  y = h + norm4(ffn(norm3(h)))

and the equations of each part are written out in the plain reference's
docstring (``benchmark/families/trinity_reference.py``), which this module is
held to. What differs here is how they are computed:

- the embedding is scaled by ``sqrt(hidden_size)`` in f32 (``mup_enabled``).
- ``attention_block``: q on ``num_attention_heads``, k and v on
  ``num_key_value_heads``, the gate as wide as q, no bias; RMSNorm over each
  q and k head's own width; in a ``sliding_attention`` layer RoPE on q and k
  and ``window=sliding_window`` to the attention, in a ``full_attention``
  layer neither; the output times ``sigmoid(gate)`` in f32; out projection.
  With ``attn='flash'`` K and V enter the kernel at their own head count, and
  a windowed layer's calls are the kernel's band step: a query block against
  the ``sliding_window`` keys before it and its own, and no other.
- ``moe_block``: sigmoid scores in f32, the top ``num_experts_per_tok`` of
  ``score + expert_bias[layer]`` (the bias selects only), weights ``score /
  (sum of the picks' scores + 1e-20)`` times ``route_scale``, dropless grouped
  SwiGLU over the held experts on a window of rows fixed by the shapes
  (``over_windows``), the grouped matmuls doing the whole window's work
  whatever is live (``expected_rows``), plus the shared expert, whole on every
  chip of the group.
- every layer is recomputed in the backward pass (one ``jax.checkpoint`` a
  layer) but for what its policy lists by name, which lives on between the
  two passes beside the stream: whatever costs a matrix product, a
  ``top_k``, a sort or a kernel call to make again. ``flash_attention.KEPT``:
  a flash call's output and logsumexp (136 MB a layer in the cell), so the
  recomputation does not run that kernel a second time.
  ``ops/moe.py::ROUTE_KEPT``: the router's logits, picks and the pairs' two
  permutations (10 MB), so the backward pass differentiates the routing the
  forward pass ran. ``PRODUCTS_KEPT``, beside ``_layer``: the outputs of the
  q, k, v and gate projections, q, k and v a head at a time and before the
  norm (302 MB); q and k again as the attention call reads them, normed and
  rotated, the backward kernels' operands (151 MB: the norm's backward
  reads its input, the kernels' its output, and a recomputed norm and
  rotation cost 3 ms a layer); the out projection's output and the
  feed-forward branch's, which ``post_attn_norm`` and ``post_mlp_norm`` read
  (67 MB each: without the second the recomputation runs the shared
  expert's last product and the experts' ``combine`` only for it). 2.9 GB
  over the cell's five layers, the compiled step's peak at 15.43e9 of
  17.18e9 B where it was 13.93e9 (``PERF.md`` section 6, PR 51, has each
  name's ms and bytes). The gated attention output is not kept: it took more
  in the forward pass than its recomputation costs. A name is the identity
  where no policy lists it. What fits is a property of this model's compiled
  memory in its cell, which nothing in a layer's input shows: the list is
  this file's constant.
- a final RMSNorm and an untied head.

Departures from the published model are the reference's (its docstring lists
them). What the model does not compute, ``TrinityConfig.from_dict`` refuses.

The loss is the cross entropy alone. ``expert_bias`` [expert layers,
``router_width``] is state that the step updates by a rule of its own
(``ops/moe.py::balance_bias``); it enters ``loss_fn`` as an extra argument and
leaves in ``aux``, as LFM2's::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch, expert_bias)
    expert_bias = aux["expert_bias"]

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::TRINITY_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models.blocks import init_expert_bias  # noqa: F401 — re-export
from ps_tpu.models.blocks import (WHOLE_WINDOW, dense_ffn, make_attn_fn,
                                  rms_norm, rope, token_ce)
from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT

WINDOWED, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, but ``num_experts``: the experts held here, of
    ``router_width`` published ones, from ``expert_start`` on."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144         # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024     # ONE expert's, and the shared one's
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    router_width: int = 128
    num_experts: int = 128
    expert_start: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3      # the selection bias's update rate
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.num_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "TrinityConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("n_group", 1), ("topk_group", 1),
                          ("num_expert_groups", 1), ("num_limited_groups", 1),
                          ("rope_scaling", None), ("score_func", "sigmoid"),
                          ("hidden_act", "silu"), ("mup_enabled", True),
                          ("tie_word_embeddings", False)):
            if d.get(key, want) != want:
                raise ValueError(f"models/trinity.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["num_experts"])
        kw["layer_types"] = tuple(d["layer_types"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if len(config.layer_types) != config.num_hidden_layers or set(
                config.layer_types) - {WINDOWED, FULL}:
            raise ValueError(f"{config.num_hidden_layers} layers of types "
                             f"{config.layer_types}: models/trinity.py knows "
                             f"{WINDOWED!r} and {FULL!r}")
        return config


def init_params(key, config: TrinityConfig) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. Jit it to make the
    tree on the device from the seed."""
    c = config
    d = c.hidden_size
    q, kv = (n * c.head_dim for n in (c.num_attention_heads,
                                      c.num_key_value_heads))
    keys = iter(jax.random.split(key, 2 + 12 * c.num_hidden_layers))

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
        lp = {"input_norm": ones(), "post_attn_norm": ones(),
              "pre_mlp_norm": ones(), "post_mlp_norm": ones(),
              "attn": {"q": lin(d, q), "k": lin(d, kv), "v": lin(d, kv),
                       "gate": lin(d, q), "out": lin(q, d),
                       "q_norm": ones(c.head_dim),
                       "k_norm": ones(c.head_dim)}}
        if i < c.num_dense_layers:
            lp["ffn"] = swiglu(c.intermediate_size)
        else:
            e, f = c.num_experts, c.moe_intermediate_size
            lp["moe"] = {"router": lin(d, c.router_width),
                         "gate": w(e, d, f), "up": w(e, d, f),
                         "down": w(e, f, d),
                         "shared": swiglu(f * c.num_shared_experts)}
        params[f"layer{i}"] = lp
    return params


def attention_block(lp: Dict, x, config: TrinityConfig, kind: str,
                    attn_fn: Callable):
    """Gated grouped-query attention of the normed activations ``x``
    [B, S, D], of the layer's ``kind``. K and V reach ``attn_fn`` at their
    own head count."""
    c = config
    b, s, _ = x.shape
    heads, kv_heads = c.num_attention_heads, c.num_key_value_heads

    def proj(name):
        return x @ lp[name]["kernel"].astype(x.dtype)

    def per_head(name, n):
        # named before the norm, whose own backward reads its input, and
        # after the reshape: a kept [B, S, 4096] takes the stream's layout,
        # tokens minor, and the norm over a head then costs a transposed f32
        # copy in the forward pass and another in the recomputation (PERF.md
        # section 6, PR 51); a kept [B, S, 32, 128] lies as the norm and the
        # kernel read it
        return checkpoint_name(proj(name).reshape(b, s, n, -1),
                               f"attn_{name}")

    q = rms_norm(per_head("q", heads), lp["q_norm"]["scale"], c.rms_norm_eps)
    k = rms_norm(per_head("k", kv_heads), lp["k_norm"]["scale"],
                 c.rms_norm_eps)
    v = per_head("v", kv_heads)
    gate = checkpoint_name(proj("gate"), "attn_gate")
    window = None
    if kind == WINDOWED:
        # positions and the window go together: a layer that sees every
        # earlier key rotates nothing
        q, k = rope(q, c.rope_theta), rope(k, c.rope_theta)
        window = c.sliding_window
    # as the attention call reads them: its backward kernels' operands
    q, k = checkpoint_name(q, "attn_q_read"), checkpoint_name(k, "attn_k_read")
    with jax.named_scope(phases.ATTN_WINDOW if window else phases.ATTN_FULL):
        a = attn_fn(q, k, v, causal=True, window=window)
    with jax.named_scope(phases.ATTN_GATE):
        a = (a.reshape(b, s, -1).astype(jnp.float32)
             * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return checkpoint_name(a @ lp["out"]["kernel"].astype(x.dtype),
                           "attn_out")


def moe_block(lp: Dict, x, config: TrinityConfig, bias):
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
            renormalize=c.route_norm, scoring="sigmoid", bias=bias,
            renorm_eps=1e-20, scaling=c.route_scale, held=c.held)
    out = moe.over_windows(
        WHOLE_WINDOW, routing, tokens,
        *(lp[n].astype(x.dtype) for n in ("gate", "up", "down")))
    with jax.named_scope(phases.MOE_SHARED):
        out = out + dense_ffn(lp["shared"], tokens)
    return out.reshape(b, s, d), routing


#: what a layer keeps beside the flash call's residuals and the routing
#: (module docstring), by the names the values bear where they are made: the
#: outputs of the attention's four projections, q and k again as the
#: attention call reads them, and what each branch hands its second norm
PRODUCTS_KEPT = ("attn_q", "attn_k", "attn_v", "attn_gate", "attn_q_read",
                 "attn_k_read", "attn_out", "ffn_out")


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *moe.ROUTE_KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, bias, kind: str, config: TrinityConfig,
           attn_fn: Callable):
    """One layer, both branches between their two norms, recomputed in the
    backward pass: the stream out and, of an expert layer, its counts over
    all experts and over the held ones and the windows of rows it ran (None
    of a dense one)."""
    eps = config.rms_norm_eps

    def norm(name, t):
        return rms_norm(t, lp[name]["scale"], eps)

    with jax.named_scope(phases.ATTN):
        a = attention_block(lp["attn"], norm("input_norm", x), config, kind,
                            attn_fn)
    x = x + norm("post_attn_norm", a)
    h = norm("pre_mlp_norm", x)
    if "ffn" in lp:
        with jax.named_scope(phases.FFN):
            out = checkpoint_name(dense_ffn(lp["ffn"], h), "ffn_out")
        return x + norm("post_mlp_norm", out), None, None, None
    out, routing = moe_block(lp["moe"], h, config, bias)
    out = checkpoint_name(out, "ffn_out")
    return (x + norm("post_mlp_norm", out), routing.counts,
            routing.group_sizes, moe.live_windows(routing))


def apply(params: Dict, tokens, config: TrinityConfig, expert_bias=None,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> (final hidden states [B, S, D] before the
    final norm, each expert layer's pairs per expert over all of them
    [expert layers, router_width], over the held ones [expert layers,
    num_experts], and the windows of rows it ran [expert layers])."""
    c = config
    attn_fn = attn_fn or make_attn_fn("full")
    # mup_enabled: the embedding times sqrt(hidden), in f32
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    x = (x * math.sqrt(c.hidden_size)).astype(c.dtype)
    counts, held, windows = [], [], []
    for i, kind in enumerate(c.layer_types):
        expert = i >= c.num_dense_layers
        bias = None
        if expert and expert_bias is not None:
            bias = expert_bias[len(counts)]
        x, *of_experts = _layer(params[f"layer{i}"], x, bias, kind, c,
                                attn_fn)
        if expert:
            for seen, one in zip((counts, held, windows), of_experts):
                seen.append(one)
    return x, jnp.stack(counts), jnp.stack(held), jnp.stack(windows)


def logits_of(params: Dict, hidden, config: TrinityConfig):
    """Final norm and the untied head: [B, S, D] -> [B, S, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.rms_norm_eps)
    return h @ params["head"]["kernel"].astype(h.dtype)


def make_loss_fn(config: TrinityConfig, attn: str = "full", **attn_kw):
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
        hidden, counts, held, windows = apply(
            params, batch["inputs"], config, expert_bias, attn_fn)
        with jax.named_scope(phases.HEAD):
            ce = token_ce(logits_of(params, hidden, config),
                          batch["targets"])
        with jax.named_scope(phases.MOE_ROUTE):
            new_bias = moe.balance_bias(expert_bias, counts,
                                        config.load_balance_coeff)
        return ce, {"ce": ce, "expert_tokens": counts, "held_tokens": held,
                    "expert_windows": windows, "expert_bias": new_bias}

    return loss_fn
