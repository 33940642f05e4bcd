"""Dropless mixture-of-experts ops: route, dispatch, expert_ffn, combine.

One token picks ``top_k`` of ``E`` experts; a step therefore holds
``T * top_k`` token-expert pairs. The pairs are sorted by expert, the token
rows permuted into that order, and each expert's SwiGLU runs as one group of
three grouped matmuls (``grouped_matmul.gmm``) over its contiguous rows. The
group sizes are data, the shapes are not: every pair is computed whatever the
imbalance, an expert may get no row at all, and there is no capacity factor
and no dropped token on this path.

The grouped matmuls are ``ops/grouped_matmul.py``'s: ``gmm`` and, from its
``custom_vjp``, the same kernel against the stacks read transposed (the rows'
gradient) and ``tgmm`` (the stacks' gradient, exact zeros for an expert with
no row). Mosaic calls of ours, at the FLOPs of the pairs and not of ``E``
dense matmuls, tiled from the shapes they are handed (``tiles(..)``: an
expert's whole matrix stays in VMEM across its row tiles of 256). They keep
the JAX ``op_name`` of where they are called, so a trace reader finds them
under ``ps.moe/expert`` like everything else there. Until PR 47 these were
``jax.lax.ragged_dot``, which XLA:TPU lowers to ``ragged-dot-none`` custom
calls of its own (tiles of 512 x 512 x 512, no scope): 20-29% of the MXU at
Mellum's shape, where ``gmm`` reads 76-83% (the table in
``grouped_matmul.py``).

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
give, and the shares of all chips add up to it. A token's picks are distinct
experts, so no more than ``count`` of them are held, and where that is fewer
than ``top_k`` (22 picks on 8 held experts) ``route`` keeps each token's held
picks and as many absent ones as fill ``count`` places: everything after it
sees a routing of ``k = min(top_k, count)`` picks a token and ``T * k`` sorted
pairs, of which the live ones, the held experts' groups, come first.

**A share moves a window of rows, not the pairs it could hold.** Of the
``T * k`` pairs an even load leaves a share ``T * top_k * count / E`` live
(an 8th, a 32nd, a 64th in the three cells that hold one), so ``dispatch``,
the experts and ``combine`` work on ``R = window_rows(..)`` rows of the
sorted pairs at a time: ``HELD_ROWS_OVER_EVEN`` times the even load in whole
tiles of the grouped matmul, a constant of the shapes and of nothing the
step can observe (24,576 / 6,144 / 8,704 of 65,536 in the LFM2, Kimi and
Nemotron cells). ``over_windows`` runs the first window always, so a step's
time does not follow how popular the held experts are at the seed, and the
windows behind it in a ``while_loop`` that is entered only when the live rows
overflow the first (the group sizes clipped to each window, the partial
outputs added): dropless still, by the same path, and no capacity factor.
Its ``custom_vjp`` computes a window again for its gradient, so a loop's
trips keep no residual. With every expert held the window is all the pairs
and ``over_windows`` is one call of the layer.

Inside a window, the row side (``dispatch``, and ``combine``'s cotangent)
gathers ``R`` rows by token and zeroes those past the live count with
``where`` (never by a product: 0 x NaN; what the grouped matmul leaves past
its last group is unspecified). The token side (``combine``, and
``dispatch``'s cotangent: "sum a token's live rows") gathers the ``R`` rows
into pair order, where a token's rows are at most ``k`` neighbours, sums each
run on the MXU (``_sum_rows``: a block of 128 rows times the block's 0 /
weight matrix, bf16 rows and weights, f32 accumulation) and gathers the
runs' first rows by token: ``R + T`` rows moved, no scatter. Timed alone on
the v5e at the three cells' shapes (PR 40): 1.55 / 0.32 / 0.26 ms against
3.06 / 1.50 / 0.47 for a sorted scatter-add of the same rows and 3.74 / 3.28
/ 1.53 for the gather of all ``T * k`` with its masked weighted sum.

**The grouped matmuls' time follows the live rows**, not the buffer (a row
tile past the last group is never visited): on the v5e 0.25 us a row over
``relu2`` experts of 1,024 x 2,688, forward and backward, 0.34 with the
forward computed again (PR 47's table: 2.2 ms the six calls over 8,704
rows; 0.5-0.6 us through ``ragged_dot``, PR 39, fourteen seeds: the step's
time against the held pairs).
``expert_ffn(..., expected_rows=R)`` makes it the same for every load up to
``R``: the last expert's group takes the zero rows after the live ones up to
``R`` (zeros in, zeros out, a zero gradient: no value changes by a bit). With
``R`` the window's length (Nemotron) the grouped matmuls do the whole
window's work whatever is live.

**A layer whose experts lie on the chips of a group, with its exchange**
(``over_trips``, under ``shard_map`` over the mesh axis that holds the expert
stacks split: chip ``c`` of ``n`` owns experts ``c * E / n`` onwards). Each
chip routes its own ``T`` tokens over all ``E`` experts; its ``T * k`` sorted
pairs are then sorted by owner too. What is sent: to each owner a buffer of
``C = exchange_rows(T, k, n)`` rows, the next ``C`` of that owner's segment of
the sorted pairs, zeros behind the live ones (``send``), and the ``E / n``
group sizes that go with them; one ``all_to_all`` each way (``to_owners``,
``from_owners``), the chip's own buffer among them. An owner runs the grouped
matmuls once a source over the ``[E / n, D, F]`` stacks it holds, on rows that
arrive sorted by its experts, and sends each source its results back in the
rows they came in, where ``receive`` sums them by token with the router's
weights. The sizes that arrived are what the grouped matmuls are handed, and
``over_trips`` returns their sum over the trips beside the output: the rows an
owner computed, counted from what the exchange carried and not from the
sender's routing (``sent_rows`` is the sender's figure; a model that reports
dropped pairs holds one against the other). How the buffers are sized: ``C``
is ``EXCHANGE_ROWS_OVER_EVEN`` times the rows an even load sends one owner
(``T * k / n``), in whole tiles of the grouped matmul, a constant of the
shapes; the constant's measurement stands beside it. What passes ``C`` is
taken by further trips of the same path, each of a small buffer
(``further_rows``: what passes the first is little): the first trip always
runs, the others in a ``while_loop`` whose count is the group's largest
(``trips_of``: a ``pmax``, every chip runs every collective as often as the
others), dropless still, no capacity factor. What the backward sends: the
transpose of an exchange is the exchange the other way, so the rows'
cotangents travel ``to_owners`` where the results came ``from_owners`` and
back where the rows went, by ``all_to_all``'s own transpose; ``send`` and
``receive`` have ``custom_vjp`` rules that are each other's shape, and
``over_trips``, as ``over_windows``, computes a trip again for its gradient:
between two layers only the tokens and the routing live on. The expert stacks'
gradients are whole where they are made (every row of an expert arrives at its
owner) and take no reduction over the axis.

**The source side of a trip moves the pairs a chip has, not the slots of its
buffers.** The ``n * C`` slots (196,608 in the Mellum cell) are three times
the ``T * k`` pairs (65,536), and how the pairs split over the owners follows
the seed, but a chip sends each of its pairs once whatever the split: so the
random access is over the pairs and the buffers are reached by contiguous
copies alone. A share's window (``_WindowIndex``) is the other case, its live
count follows the seed and the window is the bound, and shares nothing with
this path. ``send`` (``_fill``): the token rows in expert order, one gather of
``T * k`` rows as the one-chip ``dispatch`` makes; owner ``d``'s buffer is the
run of ``C`` rows from ``begins[d] + first`` of that array, where the trips
before stopped, with ``where(rank < to_owner[d], row, 0)`` behind the live
ones: a ``dynamic_slice`` an owner and a select (``_owner_runs``). ``receive``
(``_drain``): each pair's result is read from the slot that holds it, ``owner
* C + rank - first`` (``Trip.slot``): one gather of ``T * k`` rows out of the
buffers by token and pick, and the weighted sum over a token's ``k``
neighbours; a pair that travels in another trip reads zero (``Trip.here``), a
dead slot is never read, so the trips add up and what a grouped matmul left
past its last group stays out. The cotangents are the same two: ``send``'s is
``_drain`` without weights, ``receive``'s is ``_fill`` of the output's
cotangent times the pairs' weights, the weights' own from the rows' dot
products read back by slot. No key is sorted and no scatter is made: the
routing's ``order`` and ``inverse`` are all the index there is. Timed alone on
a v5e at the cell's shapes, with the trip's index each time (PR 48,
``tools/exchange_passes.py``): ``send`` 6.3 ms, ``receive`` 4.2, their
cotangents 3.7 and 9.0, where the sort of ``n * C`` keys with the gathers of
``n * C`` rows by token and into pair order read 6.0, 22.6, 21.0 and 10.0; the
gather of the pairs alone is 1.1 ms, the rest of ``send`` is XLA:TPU writing
the four runs out and reading them again to stack them (it fuses no slice at a
row offset it cannot see into what reads it: twice the bytes of one pass; the
runs written one by one into zeros read 5.6 ms alone and 10 ms a step more in
the cell, with 1.0e9 B more at the peak); ``receive`` by contiguous writes
back into sorted order and the one-chip ``combine`` read 13.5 ms, with
``_sum_rows`` 17.0. A further trip gathers the ``T * k`` rows again for its
``further_rows`` an owner (it is the same path; a trip in a hundred layers).

The rows need not be as wide as the router's input: ``route`` reads the
tokens the router was trained on, ``dispatch`` and ``combine`` move whatever
rows they are given (a model whose experts work in a latent hands them the
tokens' latent projection).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.obs import phases
from ps_tpu.ops.grouped_matmul import gmm


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
    #: [R] int32, the rows of ``order`` that ``dispatch`` moves and ``combine``
    #: brings back: 0 .. R-1 as ``route`` returns it, ``R = window_rows(..)``;
    #: None where every expert is held
    window: jax.Array = None


#: A share's expert layer moves this many times the rows an even load brings
#: it (tokens x picks x held / router width) at a time. PR 39 chose it from
#: fourteen seeds of the Nemotron cell, whose layers held 0.34 to 3.4 times an
#: even share of the pairs.
HELD_ROWS_OVER_EVEN = 3
#: what a window and an exchange buffer are whole multiples of: two row
#: tiles of the grouped matmul (one of XLA:TPU's, which the sizes date from)
GROUPED_MATMUL_ROWS = 512


def window_rows(tokens: int, top_k: int, held: int, num_experts: int,
                over_even: float = HELD_ROWS_OVER_EVEN) -> int:
    """``R``, the rows a layer that holds ``held`` of ``num_experts`` experts
    moves at a time: ``over_even`` (``HELD_ROWS_OVER_EVEN`` unless a model
    gives its own) times the even load in whole tiles of the grouped matmul,
    and never more than the ``tokens x min(top_k, held)`` pairs there are."""
    even = tokens * top_k * held / num_experts
    tiles = math.ceil(over_even * even / GROUPED_MATMUL_ROWS)
    return min(tokens * min(top_k, held), GROUPED_MATMUL_ROWS * tiles)


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


#: the names of what ``route`` makes that costs a product or a sort to make
#: again: the logits, the picks of ``top_k`` and the two permutations of the
#: pairs. A caller's ``jax.checkpoint`` keeps them with
#: ``save_only_these_names(*ROUTE_KEPT)``; under no checkpoint, or one whose
#: policy does not list them, the names are the identity
ROUTE_KEPT = ("route_logits", "route_experts", "route_order",
              "route_inverse")
_LOGITS, _EXPERTS, _ORDER, _INVERSE = ROUTE_KEPT


def _sorted_pairs(keys):
    """``order`` and ``inverse`` of the pairs sorted by ``keys``, stable,
    under their names of ``ROUTE_KEPT``."""
    order = checkpoint_name(jnp.argsort(keys, stable=True), _ORDER)
    return order, checkpoint_name(jnp.argsort(order), _INVERSE)


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
    of ``count`` picks a token, every held one among them. A share's routing
    carries the window of rows its layer moves at a time (``window_rows``).
    The matmul runs at the highest precision: 2 * T * D * E operations, and
    which expert a token goes to should not hang on a bf16 pass. The logits,
    the picks and the pairs' two permutations bear the names ``ROUTE_KEPT``:
    a layer's checkpoint that lists them runs the product (six bf16 passes),
    the ``top_k`` and the two sorts once a step and not again for the
    backward pass, which then differentiates the routing the forward pass
    ran; on the chip a recomputed layer's bf16 input is not the forward's to
    the bit, and a token near a tie may pick another expert the second
    time."""
    logits = checkpoint_name(
        jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), _LOGITS)
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
    experts = checkpoint_name(jax.lax.top_k(select, top_k)[1], _EXPERTS)
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
        order, inverse = _sorted_pairs(flat)
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
    order, inverse = _sorted_pairs(jnp.where(is_held, local, count))
    rows = window_rows(x.shape[0], top_k, count, num_experts)
    return Routing(logits, probs, weights, experts.astype(jnp.int32),
                   counts[start:start + count], order, inverse, counts,
                   is_held.reshape(experts.shape),
                   jnp.arange(rows, dtype=jnp.int32))


def balance_bias(bias, counts, rate: float):
    """Loss-free balancing (Wang et al. 2024, arXiv:2408.15664): the
    selection bias of each expert moves by ``rate`` towards the mean load,
    ``b_e += rate * sign(mean(c) - c_e)`` with ``c`` the step's pairs per
    expert over all ``E`` (last axis). A rule of its own, not the
    optimizer's: the bias has no gradient."""
    c = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)


def dispatch(x, routing: Routing):
    """Rows of ``x`` [T, D] in expert order, each token's row once for each
    of its picks: [T * k, D] where every expert is held, the ``R`` rows of
    ``routing``'s window where a share is (zeros past the live ones)."""
    if routing.live is None:
        top_k = routing.experts.shape[-1]
        return _dispatch(x, routing.order, routing.inverse, top_k)
    return _dispatch_window(x, _window_index(routing))


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


class _WindowIndex(NamedTuple):
    """Where one window's ``R`` rows come from and go to, ``T`` tokens of
    ``k`` picks. The row side is in expert order; the token side has the same
    rows in pair order (by token, then by pick), the dead ones last."""

    pair: jax.Array     # [R] int32 the pair of each row, t * k + j
    live: jax.Array     # [R] bool, the row belongs to a held expert's group
    by_pair: jax.Array  # [R] int32 the rows in pair order
    sorted: jax.Array   # [R] int32 their pairs, ascending; T * k in the dead
    start: jax.Array    # [T] int32 where a token's run begins in pair order
    here: jax.Array     # [T, k] bool, the pair has a live row in this window


def _window_index(routing: Routing) -> _WindowIndex:
    t, k = routing.experts.shape
    pairs, rows = routing.order.shape[0], routing.window.shape[0]
    first = routing.window[0]
    live_rows = jnp.sum(routing.group_sizes)
    # the last window may reach past the pairs: those rows are never live
    order = jnp.pad(routing.order, (0, -pairs % rows))
    pair = jax.lax.dynamic_slice_in_dim(order, first, rows)
    live = routing.window - first < live_rows
    in_order, by_pair = jax.lax.sort(
        (jnp.where(live, pair, pairs), routing.window - first), num_keys=1)
    row = routing.inverse.reshape(t, k) - first
    here = (row >= 0) & (row < jnp.minimum(live_rows, rows))
    count = jnp.sum(here, axis=-1, dtype=jnp.int32)
    return _WindowIndex(pair, live, by_pair, in_order,
                        jnp.cumsum(count) - count, here)


def _take_rows(x, index: _WindowIndex):
    """``x`` [T, D] -> [R, D]: each live row its token's row, zeros in the
    dead ones."""
    k = index.here.shape[-1]
    rows = jnp.take(x, index.pair // k, axis=0)
    return jnp.where(index.live[:, None], rows, 0)


#: rows of pair order summed by one product: the MXU's width
_RUN_BLOCK = 128


def _sum_rows(rows, index: _WindowIndex, weights=None):
    """``rows`` [R, D] -> [T, D]: the sum of each token's live rows, each
    times its pair's weight if ``weights`` [T, k] are given (at the rows'
    precision, the products accumulated in f32); a token with no live row
    here reads zero. ``R + T`` rows are gathered. First the rows into pair
    order, where a token's rows are neighbours, at most ``k`` of them. There
    a block of 128 rows times the 0 / weight matrix of "row j belongs to row
    i's token" puts a token's sum into each of its rows, on the MXU; a run
    that crosses into the next block finds its last rows in that block's
    head. Then the runs' first rows are gathered by token."""
    t, k = index.here.shape
    pairs, block = t * k, _RUN_BLOCK
    head = 16 * -(-(k - 1) // 16)   # whole bf16 tiles that hold k - 1 rows
    if head > block:
        raise ValueError(f"{k} picks a token do not fit a block of {block}")
    pad = (0, -index.sorted.shape[0] % block)
    in_order = jnp.pad(index.sorted, pad, constant_values=pairs)
    z = jnp.take(rows, jnp.pad(index.by_pair, pad), axis=0)
    # whatever the grouped matmuls left in the dead rows stays out
    z = jnp.where((in_order < pairs)[:, None], z, 0)
    z = z.reshape(-1, block, z.shape[-1])
    owner = (in_order // k).reshape(-1, block)
    scale = jnp.ones(owner.shape, rows.dtype)
    if weights is not None:
        scale = jnp.take(weights.reshape(-1).astype(rows.dtype),
                         jnp.minimum(in_order, pairs - 1)).reshape(owner.shape)

    def run_sums(their_owner, their_scale, their_rows):
        same = owner[:, :, None] == their_owner[:, None, :]
        return jnp.einsum(
            "bij,bjd->bid", jnp.where(same, their_scale[:, None, :], 0),
            their_rows, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def next_head(a, fill):
        return jnp.pad(a[1:, :head], ((0, 1),) + ((0, 0),) * (a.ndim - 1),
                       constant_values=fill)

    total = run_sums(owner, scale, z)
    if head:
        total = total + run_sums(next_head(owner, -1), next_head(scale, 0),
                                 next_head(z, 0))
    total = total.astype(rows.dtype).reshape(-1, total.shape[-1])
    first = jnp.take(total, index.start, axis=0, mode="clip")
    return jnp.where(jnp.any(index.here, axis=-1)[:, None], first, 0)


@jax.custom_vjp
def _dispatch_window(x, index: _WindowIndex):
    return _take_rows(x, index)


def _dispatch_window_fwd(x, index):
    return _take_rows(x, index), index


def _dispatch_window_bwd(index, g):
    return _sum_rows(g, index), None


_dispatch_window.defvjp(_dispatch_window_fwd, _dispatch_window_bwd)


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
    g = gmm(rows, gate, group_sizes)
    if up is None:
        hidden = jnp.square(jax.nn.relu(g))
    else:
        hidden = jax.nn.silu(g) * gmm(rows, up, group_sizes)
    return gmm(hidden, down, group_sizes)


def combine(rows, routing: Routing):
    """The experts' outputs ``rows`` (``dispatch``'s shape) back in token
    order and summed over each token's picks with the router's weights:
    [T, D]; of a share, the part its window's live rows give."""
    if routing.live is not None:
        return _combine_window(rows, routing.weights, _window_index(routing))
    t, top_k = routing.experts.shape
    back = permute(rows, routing.inverse, routing.order).reshape(t, top_k, -1)
    out = jnp.einsum("tkd,tk->td", back, routing.weights.astype(rows.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(rows.dtype)


@jax.custom_vjp
def _combine_window(rows, weights, index: _WindowIndex):
    return _sum_rows(rows, index, weights)


def _combine_window_fwd(rows, weights, index):
    return _combine_window(rows, weights, index), (rows, weights, index)


def _combine_window_bwd(res, g):
    rows, weights, index = res
    by_token = _take_rows(g, index).astype(jnp.float32)
    # the weights entered the sum at the rows' precision
    rounded = weights.astype(rows.dtype).astype(jnp.float32)
    scale = jnp.take(rounded.reshape(-1), index.pair)
    dots = jnp.sum(by_token * jnp.where(index.live[:, None], rows, 0), axis=-1)
    # a live row's pair is its own: R scalars set, not T * k gathered
    d_weights = jnp.zeros(weights.size, jnp.float32).at[
        jnp.where(index.live, index.pair, weights.size)].set(
            dots, mode="drop", unique_indices=True)
    return ((by_token * scale[:, None]).astype(rows.dtype),
            d_weights.reshape(weights.shape).astype(weights.dtype), None)


_combine_window.defvjp(_combine_window_fwd, _combine_window_bwd)


def num_windows(routing: Routing) -> int:
    """The windows a layer's pairs fill, from its shapes: 1 where every
    expert is held."""
    if routing.live is None:
        return 1
    return -(-routing.order.shape[0] // routing.window.shape[0])


def live_windows(routing: Routing):
    """The windows ``over_windows`` runs for this routing, int32: those that
    hold a live row, the first one always."""
    if routing.live is None:
        return jnp.int32(1)
    rows = routing.window.shape[0]
    return jnp.maximum((jnp.sum(routing.group_sizes) + rows - 1) // rows, 1)


def _window(routing: Routing, i) -> Routing:
    """``routing`` for the ``i``-th window of its sorted pairs: the group
    sizes clipped to the window's rows."""
    rows = routing.window.shape[0]
    first = i * rows
    ends = jnp.cumsum(routing.group_sizes)
    starts = ends - routing.group_sizes
    return routing._replace(
        group_sizes=(jnp.clip(ends, first, first + rows)
                     - jnp.clip(starts, first, first + rows)),
        window=first + jnp.arange(rows, dtype=jnp.int32))


def over_windows(layer, routing: Routing, *operands):
    """``layer(routing, *operands)`` -> [T, D] (dispatch, the experts,
    combine) over every window of ``routing`` that holds a live row, summed.
    One window, and ``layer`` is called once on ``routing`` as it is; of more
    the first always runs and a loop takes the others only while live rows
    are left (module docstring). ``layer`` reads nothing that needs a
    gradient but ``routing.weights`` and ``operands``."""
    if num_windows(routing) == 1:
        return layer(routing, *operands)
    return _over_windows(layer, routing, operands)


def _while_below(live, first, more):
    """``first`` joined by ``more(i)`` for ``i`` = 1, 2, ... below ``live``."""
    return jax.lax.while_loop(
        lambda s: s[0] < live,
        lambda s: (s[0] + 1, jax.tree.map(jnp.add, s[1], more(s[0]))),
        (jnp.int32(1), first))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _over_windows(layer, routing, operands):
    def run(i):
        return layer(_window(routing, i), *operands)

    return _while_below(live_windows(routing), run(0), run)


def _over_windows_fwd(layer, routing, operands):
    return _over_windows(layer, routing, operands), (routing, operands)


def _over_windows_bwd(layer, res, g):
    # a window is computed again for its gradient: between two layers only
    # the operands and the routing live on, and a loop's trips keep nothing
    routing, operands = res

    def pull(i):
        def run(weights, operands):
            window = _window(routing, i)._replace(weights=weights)
            return layer(window, *operands)

        return jax.vjp(run, routing.weights, operands)[1](g)

    d_weights, d_operands = _while_below(live_windows(routing), pull(0),
                                         pull)
    nothing = jax.tree.map(lambda _: None, routing)
    return nothing._replace(weights=d_weights), d_operands


_over_windows.defvjp(_over_windows_fwd, _over_windows_bwd)


# -- the exchange: the experts of a layer on the chips of a group ------------

#: A chip sends each owner this many times the rows an even load sends it
#: (tokens x picks / chips) in the first trip of the exchange, and the grouped
#: matmuls there do that buffer's work whatever is live. Measured on four
#: v5e chips at Mellum2's published widths, 8,192 Zipf(1.0) tokens a chip,
#: sixteen seeds of four layers (PR 46, ``tools/mellum_grad_check.py
#: --load-seeds``): under those ids nearly every token of a layer picks the
#: same eight experts (the fullest expert 5.4 to 8.0 times the mean, of 8
#: possible), so an owner's share is half an even one for each of them it
#: holds. The fullest pair of source and owner read 1.14 to 2.91 times even
#: (2.91, 2.55, 2.31, 2.23, 2.17, 2.11, 2.05 the largest of 64); at 2, seven
#: seeds of sixteen ran a second trip in some layer, at 3 none. Six of the
#: eight on one chip (3.0) is one layer in 120 by the count of placements.
EXCHANGE_ROWS_OVER_EVEN = 3
#: and this many times in each trip after the first: what passed the first
#: buffer is little (a layer at 3.1 times even is 1,600 rows over), so a
#: further trip is a small buffer's exchange and work, about a hundredth of
#: a step, and not a second pass of the first
FURTHER_ROWS_OVER_EVEN = 0.25


def exchange_rows(tokens: int, top_k: int, chips: int,
                  over_even: float = None) -> int:
    """``C``, the rows a chip sends each of the ``chips`` owners in the first
    trip: ``EXCHANGE_ROWS_OVER_EVEN`` times the even load (or ``over_even``
    times) in whole tiles of the grouped matmul, and never more than the
    ``tokens x top_k`` pairs there are."""
    if over_even is None:
        over_even = EXCHANGE_ROWS_OVER_EVEN
    even = tokens * top_k / chips
    tiles = math.ceil(over_even * even / GROUPED_MATMUL_ROWS)
    return min(tokens * top_k, GROUPED_MATMUL_ROWS * tiles)


def further_rows(tokens: int, top_k: int, chips: int) -> int:
    """The rows a chip sends each owner in a trip after the first."""
    return exchange_rows(tokens, top_k, chips, FURTHER_ROWS_OVER_EVEN)


class Trip(NamedTuple):
    """One trip of the exchange, on the chip that routed the ``T`` tokens:
    ``n`` owners, ``C`` rows each. Row ``j`` of owner ``d``'s buffer is the
    sorted pair at ``start[d] + j``."""

    sizes: jax.Array     # [n, E / n] int32 this trip's rows by owner and expert
    weights: jax.Array   # [T, k] the router's weights
    order: jax.Array     # [T * k] the routing's: pairs by owner and expert
    start: jax.Array     # [n] int32 where in that order an owner's run begins
    live: jax.Array      # [n, C] bool, the row is a pair of the owner's
    slot: jax.Array      # [T, k] int32 the pair's row of the [n * C]
    here: jax.Array      # [T, k] bool, the pair travels in this trip


def _to_owner(routing: Routing, chips: int):
    """Pairs per owner and expert [n, E / n], and where each owner's segment
    of the sorted pairs begins [n]."""
    counts = routing.group_sizes.reshape(chips, -1)
    to_owner = jnp.sum(counts, axis=-1)
    return counts, jnp.cumsum(to_owner) - to_owner


def _reach(routing: Routing, trips, chips: int):
    """How far into an owner's segment ``trips`` trips reach (``trips`` at
    least 1)."""
    t, k = routing.experts.shape
    return (exchange_rows(t, k, chips)
            + (trips - 1) * further_rows(t, k, chips))


def trips_of(routing: Routing, axis_name):
    """The trips ``over_trips`` runs for this routing, int32: those that the
    fullest pair of source and owner in the group needs, the first one
    always, the same number on every chip."""
    chips = jax.lax.axis_size(axis_name)
    t, k = routing.experts.shape
    rows, more = exchange_rows(t, k, chips), further_rows(t, k, chips)
    most = jnp.max(jnp.sum(routing.group_sizes.reshape(chips, -1), axis=-1))
    mine = 1 + (jnp.maximum(most - rows, 0) + more - 1) // more
    return jax.lax.pmax(mine, axis_name)


def sent_rows(routing: Routing, trips, chips: int):
    """The rows ``trips`` trips carry to each owner by expert [n, E / n], by
    the sender's reckoning from its routing alone. What the owners were in
    fact handed is ``over_trips``' second output, counted where it arrived."""
    counts, _ = _to_owner(routing, chips)
    ends = jnp.cumsum(counts, axis=-1)
    reach = _reach(routing, trips, chips)
    return jnp.minimum(ends, reach) - jnp.minimum(ends - counts, reach)


def _trip(routing: Routing, i, chips: int) -> Trip:
    """The ``i``-th trip of ``routing``'s sorted pairs to their owners: row
    ``j`` of owner ``d``'s buffer is place ``first + j`` of ``d``'s segment,
    ``first`` where the trips before it stopped. ``i`` the Python integer 0
    is the first trip, of ``exchange_rows`` rows an owner; anything else a
    further one (``i`` >= 1, traced), of ``further_rows``."""
    t, k = routing.experts.shape
    if isinstance(i, int) and i == 0:
        first, rows = 0, exchange_rows(t, k, chips)
    else:
        first, rows = _reach(routing, i, chips), further_rows(t, k, chips)
    counts, begins = _to_owner(routing, chips)
    owner = routing.experts // counts.shape[-1]
    rank = routing.inverse.reshape(t, k) - jnp.take(begins, owner)
    here = (rank >= first) & (rank < first + rows)
    ends = jnp.cumsum(counts, axis=-1)
    sizes = (jnp.clip(ends, first, first + rows)
             - jnp.clip(ends - counts, first, first + rows))
    place = first + jnp.arange(rows, dtype=jnp.int32)
    return Trip(sizes.astype(jnp.int32), routing.weights, routing.order,
                jnp.minimum(begins + first, t * k),
                place[None] < jnp.sum(counts, axis=-1)[:, None],
                jnp.where(here, owner * rows + rank - first, 0), here)


def _owner_runs(in_order, trip: Trip):
    """``in_order`` [T * k, ..], one entry a sorted pair -> [n, C, ..]: each
    owner's run of ``C`` from where its trip starts, zeros past its live
    entries. Slices and a select: nothing is gathered."""
    chips, rows = trip.live.shape
    rest = in_order.shape[1:]
    # a run may reach past the pairs, in its dead entries only
    padded = jnp.pad(in_order, ((0, rows),) + ((0, 0),) * len(rest))
    runs = jnp.stack([jax.lax.dynamic_slice_in_dim(padded, trip.start[d], rows)
                      for d in range(chips)])
    live = trip.live.reshape(trip.live.shape + (1,) * len(rest))
    return jnp.where(live, runs, 0)


def _fill(x, trip: Trip):
    """``x`` [T, D] -> [n, C, D]: every pair's token row in expert order
    (``_dispatch``'s gather of ``T * k`` rows), then each owner's run of
    them."""
    k = trip.here.shape[-1]
    in_order = jnp.take(x, trip.order // k, axis=0, mode="clip")
    return _owner_runs(in_order, trip)


def _drain(rows, trip: Trip, weights=None):
    """``rows`` [n, C, D] -> [T, D]: the sum of each token's rows that
    travelled in this trip, each times its pair's weight if ``weights``
    [T, k] are given (at the rows' precision, the products accumulated in
    f32). ``T * k`` rows are gathered, by pair from where they lie in the
    buffers; a dead row is never read, a pair of another trip reads zero."""
    t, k = trip.here.shape
    back = jnp.take(rows.reshape(-1, rows.shape[-1]), trip.slot.reshape(-1),
                    axis=0, mode="clip").reshape(t, k, -1)
    back = jnp.where(trip.here[..., None], back, 0)
    if weights is None:
        return jnp.sum(back, axis=1, dtype=jnp.float32).astype(rows.dtype)
    return jnp.einsum("tkd,tk->td", back, weights.astype(rows.dtype),
                      preferred_element_type=jnp.float32).astype(rows.dtype)


@jax.custom_vjp
def send(x, trip: Trip):
    """Rows of ``x`` [T, D] for the owners, [n, C, D]: each owner's next
    ``C`` pairs in expert order, zeros past the live ones."""
    return _fill(x, trip)


def _send_fwd(x, trip):
    return _fill(x, trip), trip


def _send_bwd(trip, g):
    return _drain(g, trip), None


send.defvjp(_send_fwd, _send_bwd)


def to_owners(rows, sizes, axis_name):
    """``rows`` [n, C, D] and their group ``sizes`` [n, E / n], one buffer an
    owner, exchanged: what the ``n`` sources sent this chip, by source."""
    with jax.named_scope(phases.MOE_EXCHANGE):
        return (jax.lax.all_to_all(rows, axis_name, 0, 0),
                jax.lax.all_to_all(sizes, axis_name, 0, 0))


def from_owners(rows, axis_name):
    """The owners' results [n, C, D] back to the chips whose rows they are."""
    with jax.named_scope(phases.MOE_EXCHANGE):
        return jax.lax.all_to_all(rows, axis_name, 0, 0)


@jax.custom_vjp
def receive(rows, trip: Trip):
    """The results ``rows`` [n, C, D] of the rows ``send`` made, summed by
    token with the router's weights: [T, D], the part this trip gives."""
    return _drain(rows, trip, trip.weights)


def _receive_fwd(rows, trip):
    return receive(rows, trip), (rows, trip)


def _receive_bwd(res, g):
    rows, trip = res
    weights = trip.weights
    by_token = _fill(g, trip).astype(jnp.float32)
    # the weights entered the sum at the rows' precision
    rounded = weights.astype(rows.dtype).astype(jnp.float32)
    scale = _owner_runs(jnp.take(rounded.reshape(-1), trip.order), trip)
    # what the grouped matmuls left in the dead rows stays out: 0 x NaN
    dots = jnp.sum(by_token * jnp.where(trip.live[..., None], rows, 0),
                   axis=-1)
    d_weights = jnp.where(trip.here, jnp.take(dots.reshape(-1), trip.slot), 0)
    nothing = jax.tree.map(lambda _: None, trip)
    return ((by_token * scale[..., None]).astype(rows.dtype),
            nothing._replace(weights=d_weights.astype(weights.dtype)))


receive.defvjp(_receive_fwd, _receive_bwd)


def over_trips(layer, routing: Routing, axis_name, *operands):
    """``layer(trip, *operands)`` -> ([T, D], the group sizes that arrived
    with the rows and that the experts were handed, int32 [n, E / n])
    (``send``, ``to_owners``, the experts, ``from_owners``, ``receive``) over
    every trip of the exchange that ``routing`` needs, both summed: the first
    always, the others in a loop (module docstring). The second output is the
    owner's own count of what the exchange carried to it, by source and
    expert, and has no gradient. Under ``shard_map`` over ``axis_name``,
    ``routing`` over all experts. ``layer`` reads nothing that needs a
    gradient but ``trip.weights`` and ``operands``."""
    if routing.live is not None:
        raise ValueError("over_trips: the routing is over all experts, and "
                         "the owners hold them between them")
    return _over_trips(layer, axis_name, routing, operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _over_trips(layer, axis_name, routing, operands):
    chips = jax.lax.axis_size(axis_name)

    def run(i):
        return layer(_trip(routing, i, chips), *operands)

    return _while_below(trips_of(routing, axis_name), run(0), run)


def _over_trips_fwd(layer, axis_name, routing, operands):
    return (_over_trips(layer, axis_name, routing, operands),
            (routing, operands))


def _over_trips_bwd(layer, axis_name, res, g):
    # as _over_windows_bwd: a trip is computed again for its gradient
    routing, operands = res
    chips = jax.lax.axis_size(axis_name)

    def pull(i):
        def run(weights, operands):
            trip = _trip(routing._replace(weights=weights), i, chips)
            return layer(trip, *operands)[0]

        return jax.vjp(run, routing.weights, operands)[1](g[0])

    d_weights, d_operands = _while_below(trips_of(routing, axis_name),
                                         pull(0), pull)
    nothing = jax.tree.map(lambda _: None, routing)
    return nothing._replace(weights=d_weights), d_operands


_over_trips.defvjp(_over_trips_fwd, _over_trips_bwd)


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
