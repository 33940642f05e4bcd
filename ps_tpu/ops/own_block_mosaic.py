"""SDAR's own-block term (``ops/own_block.py``) as two Mosaic (Pallas) calls,
one forward and one backward, a tile of ``TILE`` = 128 positions at a time.

``block`` divides 128, so a tile holds whole blocks and no block straddles
two: a noised query's ``block`` own keys are among its tile's 128, and the
term is dense attention of the tile's queries over the tile's keys under the
block-diagonal mask (``iota // block`` equal on both axes), merged with what
the strict flash call gave the row (``earlier``, ``lse``)::

    s       = q k^T * d ** -0.5          a tile, the MXU, f32 accumulation
    own_lse = logsumexp(s over the row's own block)
    total   = logaddexp(lse, own_lse)
    out     = exp(lse - total) * earlier + exp(s - total) v

``exp(s - total)`` is ``exp(own_lse - total) * exp(s - own_lse)``, the own
block's softmax times its share of the merge, taken in one exponential. The
off-diagonal scores are ``-inf`` before the maximum and the sum, so they are
in neither and their weight is 0 exactly; a row whose ``lse`` is -1e30 (the
first block: no earlier key) gets ``exp(lse - total) = 0`` and its own
block's softmax alone.

**The grid** is (sequences, K/V heads, tiles of 128 positions). A grid step
holds one K/V head's tile of ``k`` and ``v`` and the tile of **every query
head the K/V head serves** (``q``, ``earlier``, the output: ``[group, 128,
d]``; ``lse`` ``[group, 128]``, a position a lane), each read once and
written once: 0.86 MB a step at the cell's shape.

**Layout: the keys down the sublanes, the queries along the lanes**
(``s^T = k q^T``, ``[128, heads x 128]`` for the ``_TRIP`` heads of a trip
side by side). Everything a query has one of (the maximum, the sum, both
logsumexps, its weights in the merge) is then a row ``[1, heads x 128]``:
the reductions run over sublanes on the vector ALU, ``lse`` arrives as the
row it is, and the logarithms and exponentials of the merge cost a register
a head where a column costs sixteen (the first form of this file kept the
queries down the sublanes as ``flash_attention`` does: 550 bundles a head
forward and 1,225 backward in the compiler's final schedule, a third of the
vector operations of which were those columns; this one 220 and 450). The
one thing wanted down the sublanes is the earlier keys' weight against
``earlier`` ``[128, d]``: the row is put on the diagonal and summed over the
lanes (one term a row: exact). ``exp(s - total)^T v`` is a product with its
left operand transposed (``_dot``'s 'tn'), which Mosaic turns in the XLU.
A trip's heads are written level by level (one product, one mask, one
reduction over all of them), which is what lets the scheduler overlap them:
written head by head the same work takes the same bundles a head at one
head a trip as at four. ``_TRIP`` = 8 is the cell's whole group, so its body
has no loop; a wider group walks it eight heads a trip.

**The backward call** makes ``s`` and the weights again from the tile and
keeps nothing ``[.., 128, 128]`` in HBM. With ``P = exp(s - total)``, ``a =
exp(lse - total)`` and ``g`` the output's cotangent::

    dP   = g v^T                delta = a (g . earlier) + sum_j P dP
    dlse = a (g . earlier - delta)                 d earlier = a g
    dS   = P (dP - delta) d ** -0.5
    dq   = dS k         dk = sum over the group of dS^T q      dv = ... P^T g

all with the keys down the sublanes (``dP^T = v g^T``; ``g . earlier`` is the
diagonal of ``earlier g^T``, one more pass of the MXU), so ``dk`` and ``dv``
are plain products whose contracted rows are the trip's heads: the product
sums the group, two f32 scratches ``[128, d]`` sum the trips, and each is
written once a grid step. ``dq`` is the one transposed-left product.

**Precision.** Scores, both logsumexps, the weights, the weighted sums and
the merge are f32, as the XLA form has them. A product of two operands in the
caller's dtype (``k q^T``, ``v g^T``, ``earlier g^T``) is one pass when that
is bf16 (the products are exact, the sum f32) and at the highest precision
when it is f32. A product of f32 weights with an operand (``P v``, ``dS k``,
``dS^T q``, ``P^T g``) does **not** round the weights to bf16: against a
bf16 operand they go as three bf16 pieces that add up to them to the bit
(``_pieces``: 24 bits of mantissa are three times eight), every product
exact and the sums f32; against f32 at the highest precision.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 69).** The calls alone at
the cell's shape (q ``bf16[1, 8192, 32 on 4, 128]``, block 4; eight calls
chained in one program, median of five chains), ms:

| form | forward | backward |
|---|---|---|
| the XLA form (forward; forward and backward 4.29) | 1.50 | - |
| two heads a trip | 0.87 | 1.06 |
| four | 0.52 | 0.86 |
| **eight (the group: no loop)** | **0.43** | **0.75** |
| what the bytes allow at 819 GB/s | 0.27 | 0.46 |

Inside the cell's traced step (``tools/scope_table.py``, seed 6900000101) the
twelve forward calls take 0.39-0.46 ms and the six backward ones 0.67: 9.15
of the 9.35 ms under ``ps.attn/inblock`` where the XLA form took 42.76.
Eight heads a trip compile in 0.21 s (forward) and 0.47 s (backward) a call
for a described v5e where four take 0.14 and 0.24: 2.2 s more in a cold
compile of the cell's eighteen calls, nothing in a warm one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_tpu.ops import kda_mosaic

_F32 = jnp.float32
#: positions of one grid step: whole blocks of any length that divides it
TILE = 128
#: query heads written out in one trip of the loop over a K/V head's group
_TRIP = 8


def _dot(form: str, a, b):
    """``a @ b`` ('nn'), ``a @ b.T`` ('nt') or ``a.T @ b`` ('tn'), summed in
    f32 (``ops/kda_mosaic.py::_dot``): one pass where both operands are bf16
    (the products are exact), else both in f32 at the highest precision."""
    both = a.dtype == b.dtype == jnp.bfloat16
    return kda_mosaic._dot(form, jnp.bfloat16 if both else _F32, a, b)


def _pieces(w):
    """An f32 array as three bf16 ones that add up to it, to the bit: its
    first sixteen bits, the next sixteen of what is left, and the rest (24
    bits of mantissa are three times eight)."""
    def head(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(
            bits & jnp.uint32(0xFFFF0000), _F32)

    high = head(w)
    left = w - high
    mid = head(left)
    return tuple(x.astype(jnp.bfloat16) for x in (left - mid, mid, high))


def _weighted(form: str, w, x):
    """``_dot`` of f32 weights ``w`` with an operand ``x`` in the caller's
    dtype, the weights not rounded: against bf16 they go as their three bf16
    pieces, smallest first (every product exact, the sums f32); against f32
    at the highest precision."""
    if x.dtype != jnp.bfloat16:
        return _dot(form, w, x)
    return sum(_dot(form, piece, x) for piece in _pieces(w))


def _trip(group: int) -> int:
    """Heads of one trip: as many of ``_TRIP`` as divide the group."""
    return math.gcd(group, _TRIP)


def _tiles(heads: int, block: int):
    """Over a trip's [keys, heads x queries]: whether key and query lie in
    one block, and whether they are one position."""
    shape = (TILE, heads * TILE)
    key = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    query = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % TILE
    return key // block == query // block, key == query


def _side_by_side(rows):
    """[heads, TILE] -> [1, heads x TILE]: the heads' rows along the lanes."""
    return jnp.concatenate([rows[n:n + 1] for n in range(rows.shape[0])],
                           axis=1)


def _heads(ref, at, heads: int):
    """The trip's heads of a [group, TILE, d] block, one under the other."""
    return ref[at].reshape(heads * TILE, ref.shape[-1])


def _of(wide, n: int):
    """Head ``n``'s lanes of a trip's [.., heads x TILE]."""
    return wide[:, n * TILE:(n + 1) * TILE]


def _down(row, one, n: int):
    """Head ``n``'s part of a trip's row [1, heads x TILE] down the sublanes,
    [TILE, 1]: put on the diagonal and summed over the lanes, one term a
    row."""
    return jnp.sum(jnp.where(_of(one, n), _of(row, n), 0.0), axis=1,
                   keepdims=True)


def _scores(q, k, lse, same):
    """A trip's tiles, **the keys down the sublanes and the heads' queries
    side by side along the lanes** (``q`` [R, d], ``k`` [TILE, d], ``lse``
    [1, R]): the masked scores [TILE, R], and as rows [1, R] the merged
    logsumexp and the earlier keys' weight in the merge, ``exp(lse -
    total)``."""
    s = jnp.where(same, _dot("nt", k, q) * (q.shape[-1] ** -0.5), -jnp.inf)
    peak = jnp.max(s, axis=0, keepdims=True)
    own_lse = peak + jnp.log(jnp.sum(jnp.exp(s - peak), axis=0,
                                     keepdims=True))
    total = jnp.logaddexp(lse, own_lse)
    return s, total, jnp.exp(lse - total)


def _forward_kernel(q_ref, k_ref, v_ref, earlier_ref, lse_ref, out_ref, *,
                    block: int):
    group = q_ref.shape[0]
    heads = _trip(group)
    k, v = k_ref[...], v_ref[...]
    same, one = _tiles(heads, block)

    def trip(i, _):
        first = pl.multiple_of(i * heads, heads)
        at = pl.ds(first, heads)
        s, total, a = _scores(_heads(q_ref, at, heads), k,
                              _side_by_side(lse_ref[at]), same)
        own = jnp.exp(s - total)                         # P^T, 0 off the block
        for n in range(heads):
            out = _down(a, one, n) * earlier_ref[first + n].astype(_F32) \
                + _weighted("tn", _of(own, n), v)
            out_ref[first + n] = out.astype(out_ref.dtype)
        return _

    jax.lax.fori_loop(0, group // heads, trip, None)


def _backward_kernel(q_ref, k_ref, v_ref, earlier_ref, lse_ref, g_ref,
                     dq_ref, dk_ref, dv_ref, dearlier_ref, dlse_ref,
                     dk_sum, dv_sum, *, block: int):
    group = q_ref.shape[0]
    heads = _trip(group)
    k, v = k_ref[...], v_ref[...]
    same, one = _tiles(heads, block)
    scale = k.shape[-1] ** -0.5
    dk_sum[...] = jnp.zeros_like(dk_sum)
    dv_sum[...] = jnp.zeros_like(dv_sum)

    def trip(i, _):
        first = pl.multiple_of(i * heads, heads)
        at = pl.ds(first, heads)
        q, g = _heads(q_ref, at, heads), _heads(g_ref, at, heads)
        s, total, a = _scores(q, k, _side_by_side(lse_ref[at]), same)
        weights = jnp.exp(s - total)                     # P^T, 0 off the block
        into = _dot("nt", v, g)                          # dP^T = v g^T
        # g . earlier a query: the diagonal of earlier g^T
        through = jnp.sum(jnp.where(one, jnp.concatenate(
            [_dot("nt", earlier_ref[first + n], g_ref[first + n])
             for n in range(heads)], axis=1), 0.0), axis=0, keepdims=True)
        delta = a * through + jnp.sum(weights * into, axis=0, keepdims=True)
        dlse = a * (through - delta)
        ds = weights * (into - delta)                    # dS^T / scale
        dk_sum[...] += _weighted("nn", ds, q)            # summed over heads
        dv_sum[...] += _weighted("nn", weights, g)
        for n in range(heads):
            dlse_ref[pl.ds(first + n, 1), :] = _of(dlse, n)
            dq_ref[first + n] = (_weighted("tn", _of(ds, n), k)
                                 * scale).astype(dq_ref.dtype)
            dearlier_ref[first + n] = (
                _down(a, one, n) * g_ref[first + n].astype(_F32)
            ).astype(dearlier_ref.dtype)
        return _

    jax.lax.fori_loop(0, group // heads, trip, None)
    dk_ref[...] = (dk_sum[...] * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv_sum[...].astype(dv_ref.dtype)


def _specs(batch: int, kv_heads: int, group: int, seq: int, dim: int):
    """The grid and the block specs of a query-side operand [B, g, group, S,
    d], of a key-side one [B, g, S, d] and of the logsumexp [B, g, group,
    S]."""
    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return ((batch, kv_heads, seq // TILE),
            spec((None, None, group, TILE, dim),
                 lambda b, j, t: (b, j, 0, t, 0)),
            spec((None, None, TILE, dim), lambda b, j, t: (b, j, t, 0)),
            spec((None, None, group, TILE), lambda b, j, t: (b, j, 0, t)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def forward(q, k, v, earlier, lse, *, block: int, interpret: bool):
    """``q``, ``earlier`` [B, g, group, S, d] (a K/V head's query heads side
    by side, head-major), ``k``, ``v`` [B, g, S, d], ``lse`` [B, g, group, S]
    in f32 -> the merged output, as ``q``. ``S`` whole tiles of 128."""
    batch, kv_heads, group, seq, dim = q.shape
    grid, wide, narrow, row = _specs(batch, kv_heads, group, seq, dim)
    return pl.pallas_call(
        functools.partial(_forward_kernel, block=block),
        grid=grid, in_specs=[wide, narrow, narrow, wide, row], out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_PARAMS, interpret=interpret,
        name="own_block_forward")(q, k, v, earlier, lse)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def backward(q, k, v, earlier, lse, g, *, block: int, interpret: bool):
    """The cotangents of ``forward``'s five operands, each in its operand's
    shape and dtype, from the output's ``g``."""
    batch, kv_heads, group, seq, dim = q.shape
    grid, wide, narrow, row = _specs(batch, kv_heads, group, seq, dim)
    return pl.pallas_call(
        functools.partial(_backward_kernel, block=block),
        grid=grid, in_specs=[wide, narrow, narrow, wide, row, wide],
        out_specs=[wide, narrow, narrow, wide, row],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype)
                   for t in (q, k, v, earlier, lse)],
        scratch_shapes=[pltpu.VMEM((TILE, dim), _F32)] * 2,
        compiler_params=_PARAMS, interpret=interpret,
        name="own_block_backward")(q, k, v, earlier, lse, g)
