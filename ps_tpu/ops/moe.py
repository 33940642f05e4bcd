"""Dropless mixture-of-experts ops: route, dispatch, expert_ffn, combine.

One token picks ``top_k`` of ``E`` experts; a step therefore holds
``T * top_k`` token-expert pairs. The pairs are sorted by expert, the token
rows permuted into that order, and each expert's SwiGLU runs as one group of
three grouped matmuls (``jax.lax.ragged_dot``) over its contiguous rows. The
group sizes are data, the shapes are not: every pair is computed whatever the
imbalance, an expert may get no row at all, and there is no capacity factor
and no dropped token on this path.

On the TPU v5e, XLA lowers ``ragged_dot`` and both of its gradients to a
Mosaic grouped matmul of its own (``ragged-dot-none`` custom calls, tiles of
512 x 512 x 512; read from the optimized HLO compiled for a described v5e
and seen in the chip's traces), at the FLOPs of the pairs and not of ``E``
dense matmuls. XLA names those custom calls itself and drops the JAX
``op_name``, so a trace reader finds them by the instruction name
``%ragged-dot`` and not by a scope.

Permutations move rows with gathers in both directions: the backward pass of
a gather by a permutation is a gather by its inverse, which two
``custom_vjp`` rules state (``permute``, ``_dispatch``); left to autodiff it
would be a scatter-add of ``T * top_k`` rows.

**A layer that holds a share of the experts** (``route(..., held=(start,
count))``: one chip of an expert-parallel group, without its exchange). The
router keeps its published width and every token its ``top_k`` picks over all
``E``; the pairs whose expert is held are sorted to the front in expert
order, the grouped matmuls run over ``[count, D, F]`` stacks with the held
experts' group sizes, and ``combine`` gives every absent pair the weight 0:
the layer's output is the part of the whole layer's that its own experts
give, and the shares of all chips add up to it. Dropless still. The row
buffers keep their worst-case length: a token's picks are distinct experts,
so no more than ``count`` of them are held, and where that is fewer than
``top_k`` (22 picks on 8 held experts) ``route`` keeps each token's held
picks and as many absent ones as fill ``count`` places. Everything after it
sees a routing of ``min(top_k, count)`` picks a token and buffers of ``T *
min(top_k, count)`` rows. What the grouped matmul leaves in the rows past the
last group is unspecified, so those rows are masked with ``where`` (never by
a product: 0 x NaN) where they enter (``dispatch``, which also zeroes the
cotangent on its way back to the tokens) and where they leave (``combine``).

**The grouped matmuls' time follows the live rows**, not the buffer: on the
v5e 0.5-0.6 us a row over ``relu2`` experts of 1,024 x 2,688, forward and
backward (PR 39, fourteen seeds: the step's time against the held pairs), so
a share's step is as fast as its experts are unpopular at the seed.
``expert_ffn(..., expected_rows=R)`` makes it the same for every load up to
``R``: the last expert's group takes the zero rows after the live ones up to
``R`` (zeros in, zeros out, a zero gradient: no value changes by a bit).
With more live rows than ``R`` nothing is added and the time follows them
again. Dropless as ever: the buffers keep their length.

The rows need not be as wide as the router's input: ``route`` reads the
tokens the router was trained on, ``dispatch`` and ``combine`` move whatever
rows they are given (a model whose experts work in a latent hands them the
tokens' latent projection).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Routing(NamedTuple):
    """What ``route`` decides for ``T`` tokens, ``E`` experts, ``k`` picks."""

    logits: jax.Array       # [T, E] f32 router logits
    probs: jax.Array        # [T, E] f32 scores over all E (softmax or sigmoid)
    #: ``k`` below is ``top_k``, or the number of held experts where that is
    #: smaller
    weights: jax.Array      # [T, k] f32 weights of the picks
    experts: jax.Array      # [T, k] int32 picked experts, best first
    group_sizes: jax.Array  # [held] int32 pairs per held expert
    order: jax.Array        # [T * k] pair indices (t * k + j) sorted by expert
    inverse: jax.Array      # [T * k] position of pair (t * k + j) in that order
    #: [E] int32 pairs per expert over all E; sums to T * k. ``group_sizes``
    #: itself where every expert is held
    counts: jax.Array = None
    #: [T, k] bool, the pair's expert is held; None where every expert is
    live: jax.Array = None


@jax.custom_vjp
def permute(x, perm, inverse):
    """``x[perm]`` along axis 0 for a permutation ``perm`` whose inverse is
    ``inverse``; its cotangent is ``g[inverse]``, a gather too."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inverse):
    return permute(x, perm, inverse), (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


permute.defvjp(_permute_fwd, _permute_bwd)


def route(x, router, top_k: int, renormalize: bool = False, *,
          scoring: str = "softmax", bias=None, renorm_eps: float = 0.0,
          scaling: float = 1.0, held=None) -> Routing:
    """Router of ``x`` [T, D] with ``router`` [D, E]: logits and scores in
    f32 over all ``E`` experts, the ``top_k`` largest and their experts.
    ``scoring`` is 'softmax' or 'sigmoid'. ``bias`` [E], if given, is added
    to the scores for the selection only: it carries no gradient and never
    enters a weight (loss-free balancing, ``balance_bias``). The picks'
    scores are used as they are (``norm_topk_prob: false``); ``renormalize``
    divides them by their sum ``+ renorm_eps``, over all ``top_k`` picks
    whether or not their experts are held; ``scaling`` multiplies them
    (``routed_scaling_factor``). ``held = (start, count)`` says which
    experts this layer computes (module docstring); None is all of them.
    Where fewer experts are held than a token picks, the routing returned is
    of ``count`` picks a token, every held one among them.
    The matmul runs at the highest precision: 2 * T * D * E operations, and
    which expert a token goes to should not hang on a bf16 pass."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    num_experts = probs.shape[-1]
    select = jax.lax.stop_gradient(probs)
    if bias is not None:
        select = select + jax.lax.stop_gradient(bias).astype(select.dtype)
    _, experts = jax.lax.top_k(select, top_k)
    # the picks' probabilities through a 0/1 mask, so that their cotangent
    # is a dense product and not a scatter into [T, E]
    picked = jax.nn.one_hot(experts, num_experts, dtype=probs.dtype)
    weights = jnp.einsum("te,tke->tk", probs, picked)
    if renormalize:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + renorm_eps if renorm_eps else total)
    if scaling != 1.0:
        weights = weights * scaling
    flat = experts.reshape(-1)
    if held is None or tuple(held) == (0, num_experts):
        order = jnp.argsort(flat, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
        return Routing(logits, probs, weights, experts.astype(jnp.int32),
                       group_sizes, order, inverse, group_sizes)
    start, count = held
    if not 0 <= start <= start + count <= num_experts:
        raise ValueError(f"held experts {held} lie outside 0..{num_experts}")
    counts = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
    if count < top_k:
        # a token's picks are distinct, so at most ``count`` are held: its
        # held picks first (in their order), the row buffers ``count`` a token
        absent = (experts < start) | (experts >= start + count)
        kept = jnp.argsort(absent, axis=-1, stable=True)[:, :count]
        experts = jnp.take_along_axis(experts, kept, axis=-1)
        weights = jnp.take_along_axis(weights, kept, axis=-1)
        flat = experts.reshape(-1)
    # held pairs first, in expert order; the absent ones after them
    local = flat - start
    is_held = (local >= 0) & (local < count)
    order = jnp.argsort(jnp.where(is_held, local, count), stable=True)
    inverse = jnp.argsort(order)
    return Routing(logits, probs, weights, experts.astype(jnp.int32),
                   counts[start:start + count], order, inverse, counts,
                   is_held.reshape(experts.shape))


def balance_bias(bias, counts, rate: float):
    """Loss-free balancing (Wang et al. 2024, arXiv:2408.15664): the
    selection bias of each expert moves by ``rate`` towards the mean load,
    ``b_e += rate * sign(mean(c) - c_e)`` with ``c`` the step's pairs per
    expert over all ``E`` (last axis). A rule of its own, not the
    optimizer's: the bias has no gradient."""
    c = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)


def dispatch(x, routing: Routing):
    """Rows of ``x`` [T, D] in expert order: [T * k, D], each token's row
    once for each of its picks."""
    top_k = routing.experts.shape[-1]
    rows = _dispatch(x, routing.order, routing.inverse, top_k)
    if routing.live is None:
        return rows
    # rows past the last group belong to no held expert: zeros in, and on
    # the way back whatever the grouped matmuls' gradients left there is
    # dropped before it is summed into a token
    in_group = jnp.arange(rows.shape[0]) < jnp.sum(routing.group_sizes)
    return jnp.where(in_group[:, None], rows, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inverse, top_k):
    # pair t * k + j reads token t
    return jnp.take(x, order // top_k, axis=0)


def _dispatch_fwd(x, order, inverse, top_k):
    return _dispatch(x, order, inverse, top_k), inverse


def _dispatch_bwd(top_k, inverse, g):
    back = jnp.take(g, inverse, axis=0)
    return (jnp.sum(back.reshape(-1, top_k, g.shape[-1]), axis=1,
                    dtype=jnp.float32).astype(g.dtype), None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def expert_ffn(rows, gate, up, down, group_sizes, activation="swiglu",
               expected_rows=None):
    """Every expert's feed-forward over its group of ``rows`` [T * k, D],
    with ``gate``, ``up`` [E, D, F] and ``down`` [E, F, D], ``E`` the experts
    held. ``activation`` 'swiglu': ``(silu(rows @ gate[e]) * (rows @ up[e]))
    @ down[e]``; 'relu2', the ungated form (``up`` is None):
    ``relu(rows @ gate[e]) ** 2 @ down[e]``. Rows past ``sum(group_sizes)``
    come out unspecified. ``expected_rows``, for rows that are zero past the
    last group (as ``dispatch`` leaves a share's): the grouped matmuls do the
    work of at least that many rows whatever the groups' sizes (module
    docstring)."""
    if activation not in ("swiglu", "relu2") or (
            (activation == "relu2") != (up is None)):
        raise ValueError(f"expert_ffn: activation {activation!r} with "
                         f"{'no' if up is None else 'an'} up matrix")
    if expected_rows is not None:
        spare = min(expected_rows, rows.shape[0]) - jnp.sum(group_sizes)
        group_sizes = group_sizes.at[-1].add(jnp.maximum(spare, 0))
    g = jax.lax.ragged_dot(rows, gate, group_sizes)
    if up is None:
        hidden = jnp.square(jax.nn.relu(g))
    else:
        hidden = jax.nn.silu(g) * jax.lax.ragged_dot(rows, up, group_sizes)
    return jax.lax.ragged_dot(hidden, down, group_sizes)


def combine(rows, routing: Routing):
    """The experts' outputs ``rows`` [T * k, D] back in token order and
    summed over each token's picks with the router's weights: [T, D]."""
    t, top_k = routing.experts.shape
    back = permute(rows, routing.inverse, routing.order).reshape(t, top_k, -1)
    weights = routing.weights
    if routing.live is not None:
        back = jnp.where(routing.live[..., None], back, 0)
        weights = jnp.where(routing.live, weights, 0)
    out = jnp.einsum("tkd,tk->td", back, weights.astype(rows.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(rows.dtype)


def load_balance_loss(routing: Routing):
    """``E * sum_e(f_e * P_e)``: ``f_e`` the share of token-expert pairs that
    went to expert e (no gradient), ``P_e`` the mean router probability of e.
    1.0 when routing is uniform."""
    num_experts = routing.probs.shape[-1]
    f = routing.group_sizes.astype(jnp.float32) / routing.experts.size
    p = jnp.mean(routing.probs, axis=0)
    return num_experts * jnp.sum(f * p)


def router_z_loss(routing: Routing):
    """Mean squared logsumexp of the router logits."""
    return jnp.mean(jax.nn.logsumexp(routing.logits, axis=-1) ** 2)
