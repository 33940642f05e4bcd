"""SDAR (``model_type: sdar_moe``): a Qwen3-MoE decoder trained by block
diffusion (JetLM; SDAR-30B-A3B-Chat: 48 layers at hidden 2,048, 32 query
heads of 128 on 4 K/V heads with an RMSNorm over each q and k head, RoPE at
theta 1e6, 128 SwiGLU experts of width 768, eight a token by a softmax over
all of them, renormalised over the picks, no shared expert). The layer is the
family's; what the store had not run is how it is trained (Arriola et al.
2025, "Block Diffusion", the vectorised training step): a sequence ``x`` of
``L`` tokens is cut in blocks of ``B``; each block draws a noise level
``t_b``, each of its tokens is replaced by the mask id with probability
``t_b``, giving ``x~``; **both copies go through the stack at once**, each
at positions ``0..L-1``, and attention lets

- a clean query of block ``b`` see the clean keys of blocks ``<= b``;
- a noised query of block ``b`` see the clean keys of blocks ``< b`` and the
  noised keys of its own block, both directions;
- nobody see another block's noised keys.

The loss is the cross entropy of the noised copy's logits against ``x`` at
the masked positions, each weighted ``1 / t_b``, summed and divided by ``L``
(no shift: position ``i`` predicts token ``i``), plus
``router_aux_loss_coef`` times the layers' load-balancing terms. The noising
is data: ``batch = {"ids", "noised_ids", "weights"}``, each [B, L], whoever
holds the batch on the host draws it (``benchmark/families/sdar_step.py``).

Pure functions over a parameter dict, as ``models/trinity.py``; ``rms_norm``,
``rope`` and the expert layer's window (``WHOLE_WINDOW``) are
``models/blocks.py``'s. A layer is ``h = x + attn(norm1(x))``,
``y = h + moe(norm2(h))`` on the two streams stacked along the batch
([2 B, L, D], the clean copies first), and the equations of each part are
written out in the plain reference's docstring
(``benchmark/families/sdar_reference.py``), which this module is held to.
How they are computed here:

- ``attention_block``: projections, head norms and the rotation on both
  streams at once; then two calls of the attention over the **clean** K/V
  (``ps.attn/full``): the clean queries under the edge a block wide, and the
  noised queries under the strict one, which returns its logsumexp beside its
  output (``ops/flash_attention.py``'s ``edge_block=``, ``strict_edge=``,
  ``return_lse=``: the first block's noised queries see no clean key and get
  zeros and -1e30); then ``own_block`` (``ps.attn/inblock``,
  ``ops/own_block.py``): a noised query's scores over the ``B`` noised keys
  of its own block, merged with the kernel's part by the two logsumexps,
  all in f32. At heads of 128 on whole tiles of 128 positions (the cell) it
  is one Mosaic pass forward and one backward: ``B`` divides 128, so a tile
  holds whole blocks and the term is the tile's queries over the tile's keys
  on the MXU under the block-diagonal mask, q, k, v and the kernel's part
  read once and the merged rows written once; at every other shape
  (``own_block.path``) the same equations as f32 products and sums over
  ``[L / B, B, B]`` a head. The kernels see
  ``L (L + B) / 2 + L (L - B) / 2 = L ** 2`` pairs a head, not the
  ``2 L ** 2`` of a causal call over ``2 L``, and the clean-query x
  noised-key quarter is in no call.
- ``moe_block``: softmax scores in f32 over all ``router_width`` experts, the
  top ``num_experts_per_tok``, renormalised over all the picks whether held
  or not; dropless grouped SwiGLU over the ``num_experts`` held from
  ``expert_start`` on, on a window of rows fixed by the shapes
  (``ops/moe.py::over_windows``; 4.25 even loads of the held experts where
  ``ops/moe.py`` fixes 3: ``HELD_ROWS_OVER_EVEN`` below says why), on the
  tokens of both streams together.
- every layer is recomputed in the backward pass (one ``jax.checkpoint`` a
  layer) but for what its policy lists by name, as Trinity's: whatever costs
  a matrix product, a ``top_k``, a sort or a kernel call to make again.
  ``ops/flash_attention.py::KEPT``: the two flash calls' outputs and the
  strict one's logsumexp. ``ops/moe.py::ROUTE_KEPT``: the router's logits,
  the picks and the pairs' two permutations (10 MB a layer in the cell), so
  the backward pass differentiates the routing the forward pass ran.
  ``PRODUCTS_KEPT``, beside ``_layer``, under Trinity's names: the q, k and
  v projections' outputs a head at a time and before the norm (168 MB), q
  and k again as the calls and ``own_block`` read them, normed and rotated
  (151 MB); and ``attn_stream``, the stream once the out projection's output
  is added, which ``post_attn_norm`` reads (67 MB), so that the out
  projection's product is not made again. The name is on the sum and not on
  the product: XLA folds the sum into the product and rounds once, and a
  name on the product made it round the product to bf16 first and the sum
  again in every layer, a forward pass that one seed's router gradient did
  not forgive. 2.4 GB over the cell's six layers, 41 ms of a 685 ms step
  (``PERF.md`` section 6, PR 71, has each name's ms and bytes). The
  feed-forward branch adds to the stream with no norm behind it, so its
  output feeds nothing a backward pass reads and bears no name; the own
  block's merged rows bear none either (kept, they cost the forward pass
  more than their recomputation takes). A name is the identity where no
  policy lists it; the list is this file's constant, chosen from the
  measured table.
- a final RMSNorm and an untied head **on the noised stream alone**: the
  clean stream's last layer feeds the noised one's keys and nothing else.

What the model does not compute, ``SdarConfig.from_dict`` refuses::

    step = store.make_step(make_loss_fn(config, attn="flash"), has_aux=True)
    loss, params, aux = step(batch)

The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::SDAR_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models.blocks import WHOLE_WINDOW, rms_norm, rope
from ps_tpu.obs import default_registry, phases
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT
from ps_tpu.ops.own_block import own_block


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, but ``num_experts``: the experts held here, of
    ``router_width`` published ones, from ``expert_start`` on. The config
    gives no block length, mask id or auxiliary coefficient: the last three
    fields are the benchmark file's ``assumed``."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768      # ONE expert's
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    router_width: int = 128
    num_experts: int = 128
    expert_start: int = 0
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    block_length: int = 4
    mask_token_id: int = 151935
    router_aux_loss_coef: float = 1e-3
    dtype: Any = jnp.bfloat16

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_start, self.num_experts

    @classmethod
    def from_dict(cls, d: Dict) -> "SdarConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("use_sliding_window", False),
                          ("sliding_window", None), ("rope_scaling", None),
                          ("mlp_only_layers", []), ("decoder_sparse_step", 1),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if d.get(key, want) != want:
                raise ValueError(f"models/sdar.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("router_width", d["num_experts"])
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if 128 % config.block_length:
            raise ValueError(f"block_length {config.block_length} must "
                             f"divide 128, the kernels' narrowest tile")
        if not 0 <= config.mask_token_id < config.vocab_size:
            raise ValueError(f"mask_token_id {config.mask_token_id} lies "
                             f"outside the {config.vocab_size} ids")
        return config


def init_params(key, config: SdarConfig) -> Dict:
    """Normal(0, 0.02) weights and unit norm scales, f32. Jit it to make the
    tree on the device from the seed."""
    c = config
    d = c.hidden_size
    q, kv = (n * c.head_dim for n in (c.num_attention_heads,
                                      c.num_key_value_heads))
    keys = iter(jax.random.split(key, 2 + 8 * c.num_hidden_layers))

    def w(*shape):
        return 0.02 * jax.random.normal(next(keys), shape, jnp.float32)

    def lin(*shape):
        return {"kernel": w(*shape)}

    def ones(n=d):
        return {"scale": jnp.ones((n,), jnp.float32)}

    params: Dict = {"embed": {"tokens": w(c.vocab_size, d)},
                    "head": lin(d, c.vocab_size), "final_norm": ones()}
    e, f = c.num_experts, c.moe_intermediate_size
    for i in range(c.num_hidden_layers):
        params[f"layer{i}"] = {
            "input_norm": ones(), "post_attn_norm": ones(),
            "attn": {"q": lin(d, q), "k": lin(d, kv), "v": lin(d, kv),
                     "out": lin(q, d), "q_norm": ones(c.head_dim),
                     "k_norm": ones(c.head_dim)},
            "moe": {"router": lin(d, c.router_width), "gate": w(e, d, f),
                    "up": w(e, d, f), "down": w(e, f, d)}}
    return params


def _dense_edge(q, k, v, block: int, strict: bool, return_lse: bool):
    """``make_edge_attn('full')``: whole rows of the scores under the edge a
    block wide, K/V repeated for the query heads they serve. A row that sees
    no key gives zeros and a logsumexp of -1e30, as the kernel."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        * (q.shape[-1] ** -0.5)
    of = jnp.arange(q.shape[1]) // block
    seen = of[None, :] < of[:, None] if strict else of[None, :] <= of[:, None]
    s = jnp.where(seen, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    safe = jnp.where(total > 0, total, 1.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / safe).astype(v.dtype), v)
    if not return_lse:
        return out
    return out, jnp.transpose((m + jnp.log(safe))[..., 0], (0, 2, 1))


def make_edge_attn(attn: str = "full", **kw) -> Callable:
    """``fn(q, k, v, block, strict, return_lse)``: causal attention of ``q``
    [B, L, h, d] over ``k``, ``v`` [B, L, h_kv, d] under the edge ``block``
    positions wide, strict or not; the output [B, L, h, d] or, with
    ``return_lse``, the pair of it and the logsumexp [B, L, h]. 'flash' is
    the Pallas kernel, 'full' the dense form."""
    if attn == "full":
        return _dense_edge
    if attn != "flash":
        raise ValueError(f"models/sdar.py knows attn 'full' and 'flash', "
                         f"not {attn!r}")
    from ps_tpu.ops import flash_attention

    def flash_fn(q, k, v, block, strict, return_lse):
        return flash_attention(q, k, v, causal=True, edge_block=block,
                               strict_edge=strict, return_lse=return_lse,
                               **kw)

    return flash_fn


def attention_block(lp: Dict, x, config: SdarConfig, attn_fn: Callable):
    """Grouped-query attention of the normed activations ``x`` [2 B, L, D],
    the clean copies first and the noised ones after, under the
    block-diffusion mask."""
    c = config
    b2, s, _ = x.shape
    b = b2 // 2

    def per_head(name, n):
        # named a head at a time and before the norm, as Trinity's
        # (``models/trinity.py::attention_block`` says why)
        return checkpoint_name(
            (x @ lp[name]["kernel"].astype(x.dtype)).reshape(b2, s, n, -1),
            f"attn_{name}")

    q = rms_norm(per_head("q", c.num_attention_heads), lp["q_norm"]["scale"],
                 c.rms_norm_eps)
    k = rms_norm(per_head("k", c.num_key_value_heads), lp["k_norm"]["scale"],
                 c.rms_norm_eps)
    v = per_head("v", c.num_key_value_heads)
    # each copy at positions 0 .. L-1: the same rotation for both
    q, k = rope(q, c.rope_theta), rope(k, c.rope_theta)
    # as the two calls and ``own_block`` read them: their backward kernels'
    # operands
    q, k = checkpoint_name(q, "attn_q_read"), checkpoint_name(k, "attn_k_read")
    with jax.named_scope(phases.ATTN_FULL):
        clean = attn_fn(q[:b], k[:b], v[:b], c.block_length, False, False)
        earlier, lse = attn_fn(q[b:], k[:b], v[:b], c.block_length, True,
                               True)
    with jax.named_scope(phases.ATTN_INBLOCK):
        noised = own_block(q[b:], k[b:], v[b:], earlier, lse, c.block_length)
    a = jnp.concatenate([clean, noised], axis=0).reshape(b2, s, -1)
    return a @ lp["out"]["kernel"].astype(x.dtype)


#: The rows a share's layer moves at a time, in even loads of its held experts
#: (``tokens x top_k x held / router_width``), where ``ops/moe.py`` fixes 3
#: (``HELD_ROWS_OVER_EVEN``). Under 0.02-normal weights and i.i.d. Zipf ids the
#: first attention layer's output, the same weighted mean of the values at
#: every late position (norm about 6 by the init's arithmetic), outweighs a
#: token's own unscaled embedding row (0.9), so nearly every token of a layer
#: picks the same eight experts (the fullest holds 15.2 to 15.8 of the 16 times
#: the mean that all tokens would give it: my chip runs, PR 50). Each of the
#: eight that is among the sixteen held brings one even load: three of them
#: fill ``ops/moe.py``'s window to the row (held share 0.374 of a layer's pairs
#: where 0.375 is the window) and the stragglers open a second one, every step:
#: 6% of the layers by the count of placements, a layer in a third of the
#: seeds, and the step followed the seed (0.735 s against 0.712 to 0.718 at
#: nine seeds). 4.25 holds four of the eight with 4,096 rows to spare; five or
#: more of eight among sixteen of 128 is one layer in 1,400. The grouped
#: matmuls do the whole window's work, so the room is paid for in every step:
#: ``PERF.md`` section 6, PR 50, has the price.
HELD_ROWS_OVER_EVEN = 4.25


def window_rows(config: SdarConfig, tokens: int) -> int:
    """``ops/moe.py::window_rows`` at this model's ``HELD_ROWS_OVER_EVEN``:
    whole tiles of the grouped matmul, never more than the pairs there
    are."""
    c = config
    return moe.window_rows(tokens, c.num_experts_per_tok, c.num_experts,
                           c.router_width, HELD_ROWS_OVER_EVEN)


def moe_block(lp: Dict, x, config: SdarConfig):
    """The expert layer on normed activations ``x`` [2 B, L, D]: the held
    experts' part of the output and the layer's ``Routing``."""
    c = config
    b2, s, d = x.shape
    tokens = x.reshape(b2 * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        routing = moe.route(
            tokens, lp["router"]["kernel"], c.num_experts_per_tok,
            renormalize=c.norm_topk_prob, scoring="softmax", held=c.held)
    if routing.window is not None:      # a share: this model's window
        routing = routing._replace(window=jnp.arange(
            window_rows(c, b2 * s), dtype=jnp.int32))
    out = moe.over_windows(
        WHOLE_WINDOW, routing, tokens,
        *(lp[n].astype(x.dtype) for n in ("gate", "up", "down")))
    return out.reshape(b2, s, d), routing


#: what a layer keeps beside the flash calls' residuals and the routing
#: (module docstring), by the names the values bear where they are made: the
#: outputs of the attention's q, k and v projections, q and k again as the
#: calls read them, and the stream once the out projection's output is added
PRODUCTS_KEPT = ("attn_q", "attn_k", "attn_v", "attn_q_read", "attn_k_read",
                 "attn_stream")


@functools.partial(jax.checkpoint, static_argnums=(2, 3),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *moe.ROUTE_KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, config: SdarConfig, attn_fn: Callable):
    """One layer on both streams, recomputed in the backward pass: the
    stream out, the layer's counts over all experts and over the held ones,
    the windows of rows it ran and its load-balancing term."""
    eps = config.rms_norm_eps
    with jax.named_scope(phases.ATTN):
        a = attention_block(
            lp["attn"], rms_norm(x, lp["input_norm"]["scale"], eps), config,
            attn_fn)
    # the stream behind the attention branch, which ``post_attn_norm`` reads:
    # the sum bears the name, for a name on the out projection's output
    # alone rounds it to bf16 before the sum is rounded (module docstring)
    x = checkpoint_name(x + a, "attn_stream")
    out, routing = moe_block(
        lp["moe"], rms_norm(x, lp["post_attn_norm"]["scale"], eps), config)
    with jax.named_scope(phases.MOE_ROUTE):
        # over all router_width experts: the share's own group sizes are
        # the held ones'
        balance = moe.load_balance_loss(
            routing._replace(group_sizes=routing.counts))
    return (x + out, routing.counts, routing.group_sizes,
            moe.live_windows(routing), balance)


def apply(params: Dict, ids, noised_ids, config: SdarConfig,
          attn_fn: Callable = None):
    """``ids``, ``noised_ids`` [B, L] int32 -> (the noised copies' final
    hidden states [B, L, D] before the final norm, each layer's pairs per
    expert over all of them [layers, router_width], over the held ones
    [layers, num_experts], the windows of rows it ran [layers] and the sum
    of the layers' load-balancing terms)."""
    c = config
    attn_fn = attn_fn or make_edge_attn("full")
    if ids.shape[1] % c.block_length:
        raise ValueError(f"{ids.shape[1]} positions are no whole number of "
                         f"blocks of {c.block_length}")
    both = jnp.concatenate([ids, noised_ids], axis=0)
    x = jnp.take(params["embed"]["tokens"], both, axis=0).astype(c.dtype)
    counts, held, windows, balance = [], [], [], 0.0
    for i in range(c.num_hidden_layers):
        x, *of_experts, term = _layer(params[f"layer{i}"], x, c, attn_fn)
        balance = balance + term
        for seen, one in zip((counts, held, windows), of_experts):
            seen.append(one)
    return (x[ids.shape[0]:], jnp.stack(counts), jnp.stack(held),
            jnp.stack(windows), balance)


def logits_of(params: Dict, hidden, config: SdarConfig):
    """Final norm and the untied head: [B, L, D] -> [B, L, V]."""
    h = rms_norm(hidden, params["final_norm"]["scale"], config.rms_norm_eps)
    return h @ params["head"]["kernel"].astype(h.dtype)


def weighted_ce(logits, targets, weights):
    """``sum(weights * CE) / positions`` in logsumexp form
    (``blocks.token_ce`` with a weight a position): a position of weight 0
    carries no loss. Beside it, with no gradient, the plain mean of the CE
    over the positions that carry a weight (``aux``'s ``masked_ce``)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), -1)
    tok = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    nll = lse - tok.astype(jnp.float32)
    masked = weights > 0
    plain = jax.lax.stop_gradient(
        jnp.sum(jnp.where(masked, nll, 0.0)) / jnp.sum(masked))
    return jnp.sum(weights * nll) / targets.size, plain


def make_loss_fn(config: SdarConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch) -> (loss, aux)`` for ``batch = {"ids": [B,
    L] the clean tokens, "noised_ids": [B, L] the same with the mask id at
    the masked positions, "weights": [B, L] f32, 1 / t_b at a masked position
    and 0 elsewhere}``, for ``KVStore.make_step(loss_fn, has_aux=True)``.
    ``attn`` is 'full' or 'flash' (``make_edge_attn``). ``aux``, device
    values: ``loss`` itself, ``ce`` and ``load_balance`` (its two terms, the
    second before its coefficient); ``masked_ce``, the cross entropy at the
    masked positions as a plain mean, without the weights (the draw of the
    weights alone moves ``ce`` by 2.7% of itself from step to step at 8,192
    positions: a run is followed by this one); ``expert_tokens`` [layers,
    router_width], the step's pairs per expert over all of them;
    ``held_tokens`` [layers, num_experts], those computed here;
    ``expert_windows`` [layers];
    ``load_max_over_mean`` and ``held_pair_share`` (of the counts);
    ``dropped_tokens`` (pairs routed less pairs counted: 0, the window path
    drops none); ``masked_positions``, the positions that carry a weight."""
    attn_fn = make_edge_attn(attn, **attn_kw)
    c = config

    def loss_fn(params, batch):
        ids, weights = batch["ids"], batch["weights"]
        hidden, counts, held, windows, balance = apply(
            params, ids, batch["noised_ids"], c, attn_fn)
        with jax.named_scope(phases.HEAD):
            ce, masked_ce = weighted_ce(logits_of(params, hidden, c), ids,
                                        weights)
        loss = ce + c.router_aux_loss_coef * balance
        routed = 2 * ids.size * c.num_experts_per_tok * c.num_hidden_layers
        total = jnp.sum(counts)
        return loss, {
            "loss": loss, "ce": ce, "load_balance": balance,
            "expert_tokens": counts,
            "held_tokens": held, "expert_windows": windows,
            "load_max_over_mean": jnp.mean(
                jnp.max(counts, axis=-1) / jnp.mean(counts.astype(
                    jnp.float32), axis=-1)),
            "held_pair_share": jnp.sum(held) / total,
            "dropped_tokens": routed - total,
            "masked_positions": jnp.sum(weights > 0),
            "masked_ce": masked_ce}

    return loss_fn


_masked_share = default_registry().gauge(
    "ps_sdar_masked_share",
    "share of the last noised batch's positions that carry the mask id")


def observe_masked_share(weights) -> float:
    """The share of a host batch's positions that carry a weight, set on
    ``ps_sdar_masked_share``: taken on the host by whoever noises the batch
    there, beside ``aux``'s ``masked_positions`` (the step raises nothing
    itself: ``models/bert.py::count_head_overflow`` says why)."""
    share = float(np.count_nonzero(weights)) / np.size(weights)
    _masked_share.set(share)
    return share
