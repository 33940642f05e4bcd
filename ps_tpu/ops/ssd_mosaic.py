"""The chunked state-space scan of ``ops/ssd.py`` as Mosaic (Pallas) kernels:
one call forward and one backward over the operands as the mixer has them,
with a head's decay matrix ``L`` and every head's state in VMEM from the
first chunk of a sequence to the last.

**Row-major operands.** ``x`` and ``y`` are ``[B, T, H P]`` (a reshape of
``ssd``'s ``[B, T, H, P]``, free where the 4-D array is no argument of the
program), ``B`` and ``C`` ``[B, T, N]`` (one group), all in the caller's
dtype; no transposed or f32 copy of any of them is made. The steps ``dt``
[B, T, H] f32, one number a head and token, are re-laid once a call to
``[B, H / n, T, n]`` for the grid's blocks of ``n`` heads (XLA's; 2 MB at
Granite's shape, 34 MB as the chip tiles a last axis of eight: with the
token last instead, unpadded, the calls were 0.13 / 0.16 ms slower).

**The grid** is (sequences, chunks of ``CHUNK`` = 128 tokens, blocks of
``heads_a_step`` heads), every axis in order. A step holds the block's ``x``
``[Q, n P]``, the group's ``B`` and ``C`` ``[Q, N]`` and the block's states,
transposed and side by side ``[N, n P]`` f32 (2 MB for Granite's 64 heads, a
scratch indexed by the block; zeros at a sequence's first chunk). The
kernels' chunk is theirs: the recurrence gives the same result whatever the
chunk (the configurations' 256 and 128 are multiples of it), a ``[Q, Q]`` f32
``L`` is 16 vector registers at 128 and the whole file at 256, and the work
on ``L`` grows with ``Q`` a token (measured below).

**One chunk is one pure function**, ``_chunk``, as in ``ops/kda_mosaic.py``:
the forward kernel calls it, the backward kernel takes ``jax.vjp`` of it on
the blocks it has loaded, inside its body, walking the chunks from the last
to the first with the cotangent of the states in the scratch. With ``G`` the
log-decays ``dt A`` cumulated over the chunk (a product with a 0 / 1
triangle at the highest precision; transposed by a product with the
identity, which is exact, so that the column ``G_i`` and the row ``G_j`` are
the same numbers, ``G_i - G_i`` is 0 and two tokens' rounding cancels as in a
cumsum):

- over all the step's heads at once, what does not depend on ``L``: the read
  of the entering states ``(C S) o exp(G)`` and the chunk's own contribution
  to the states it leaves ``B^T (x o exp(G_Q - G) dt)``, one product each
  that fills the MXU's columns, the per-head numbers laid over their heads'
  lanes by selects (``_over_lanes``); ``C B^T`` once, the group's;
- a head at a time: ``exp(G_i - G_j)`` masked **before** the exponential
  (the exponents above the diagonal pass 88: ``ops/ssd.py``, "The decays
  never overflow"), times ``C B^T`` and ``dt_j``, rounded once to the MXU's
  dtype and multiplied with the head's ``x``. A head of 64 channels is half
  a lane tile: the product is taken against the whole tile of two heads and
  each head's half selected (the MXU is 128 columns wide either way, and no
  load, store or concatenation is off a tile's edge).

``dB`` and ``dC`` are summed over a block's heads by the transposition
itself and over the blocks in a scratch, written once a chunk; ``dA`` leaves
as one number a head and chunk and is summed by XLA. What the backward call
needs of the forward one is the state that entered each chunk
(``forward(keep=True)``: ``[B, T / Q, H / n, N, n P]`` f32, 134 MB at
Granite's shape). Both entry points are jitted, so a step's calls of one
shape are traced and lowered once.

**Precision** as ``ops/ssd.py`` states it: the cumulated sums, every
exponential, the carried state and all accumulation in f32; the products
with ``x``, ``B``, ``C`` and the state take their operands in ``x``'s dtype,
one pass (f32 operands: the highest precision). ``L o C B^T o dt`` is rounded
once and ``x`` enters its product as it is, where the XLA form rounds ``dt
x`` too.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 59). A call alone at 8,192
tokens in bf16, ten calls chained in one program (``tools/ssd_table.py``),
ms; beside it the XLA form (its gradient is forward and backward in one
program) and the least time ``ssd_cost`` gives a layer's forward and
backward at the chip's peaks; and the calls inside their cells' traced
steps.**

| | Granite: 64 heads of 64, state 128, chunk 256 | Nemotron-H's share: 16 heads, chunk 128 |
|---|---|---|
| the kernels, forward / backward | 0.792 / 1.595 | 0.250 / 0.465 |
| the XLA form, forward / gradient | 2.503 / 6.559 | 0.542 / 1.530 |
| ``ssd_cost``'s least, a layer | 0.433 | 0.120 |
| in the cell, forward / backward call | 0.702 / 1.428 | 0.177 / 0.373 |

What was tried at Granite's shape, forward / backward ms a call: 8, 16, 32
and 64 heads a step 0.782 / 1.579, 0.644 / 1.445, 0.599 / 1.362, 0.526 /
1.505 (a step's fixed cost against the unrolled body's size). ``_HEADS`` is 8
all the same: the body is unrolled over a step's heads, and traced and
lowered in every program that holds the calls, three in a benchmark run; at
32 heads that was 5-6 s of a cell's set-up (Granite's ``setup_s`` 61.5 ->
67.3 warm, Nemotron's 57.5 -> 61.1 at 16: an end-to-end metric with a bound
of 10%), at 8 the set-up's ``step0`` phase reads the parent's (28.7 against
28.5-29.4 s) for 2% of the Granite step. Chunks of 256 0.719 / 1.609 at 16
heads a step; the read and the own contribution a head at a time (``C`` and
``B`` scaled by a column a head) 0.647 / 1.410 against 0.653 / 1.438 all
heads at once: no difference; the cumulated sums, their exponentials and both
layouts made by XLA in front of the call 0.580 / 1.534 at 32 heads: slower
than the three small products in the kernel; the body with no ``L``, no read
and no own contribution 0.527 / 0.661 at 16: more than half of the forward
is a step's fixed cost, not the work on ``L``. A v5e core issues about one
vector operation a cycle on these bodies: the kernels are bound by the count
of vector operations, not by the MXU (three products a head) or by HBM (0.33
ms for the forward's bytes with the states).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_tpu.ops.kda_mosaic import _F32, _product

#: tokens of the kernels' own chunk, whatever the configuration's
CHUNK = 128
_LANES = 128
#: heads one grid step computes at most (module docstring: a larger block is
#: faster and costs every program that holds the calls its set-up)
_HEADS = 8


def heads_a_step(heads: int, width: int) -> int:
    """Heads one grid step computes: whole 128-lane tiles of ``x``, as many
    as divide the heads up to ``_HEADS`` (a step's fixed cost and the group's
    ``C B^T`` shared; the body is unrolled over them)."""
    return next(n for n in range(min(heads, _HEADS), 0, -1)
                if heads % n == 0 and (n * width) % _LANES == 0)


def takes(x, b, chunk: int) -> bool:
    """Whether operands of these shapes take the kernels: one B/C group, heads
    of 64 or 128 channels that fill whole 128-lane tiles, a state of whole
    tiles and the configuration's chunk a multiple of the kernels'. Read from
    the shapes and the dtype alone."""
    _, _, heads, width = x.shape
    return (b.shape[2] == 1 and width in (64, 128)
            and (heads * width) % _LANES == 0 and b.shape[3] % _LANES == 0
            and chunk % CHUNK == 0
            and x.dtype in (jnp.bfloat16, jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tiles(x, n):
    """``x`` [R, n * 128] cut into its ``n`` lane tiles; transposed by a
    concatenation (a slice's own transposition is a pad)."""
    return tuple(jnp.split(x, n, axis=1))


def _tiles_fwd(x, n):
    return _tiles(x, n), None


def _tiles_bwd(n, _, cts):
    return (jnp.concatenate(cts, axis=1),)


_tiles.defvjp(_tiles_fwd, _tiles_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _singles(m, axis):
    """``m`` [n, R] -> its ``n`` rows [1, R] (``axis`` 0), or ``m`` [R, n] ->
    its ``n`` columns [R, 1] (``axis`` 1); transposed by a sum of masked
    broadcasts (a slice's own transposition is a pad)."""
    return tuple(jax.lax.slice_in_dim(m, j, j + 1, axis=axis)
                 for j in range(m.shape[axis]))


def _singles_fwd(m, axis):
    return _singles(m, axis), None


def _singles_bwd(axis, _, cts):
    shape = [1, 1]
    shape[axis] = len(cts)
    at = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
    return (sum(jnp.where(at == j, ct, 0.0) for j, ct in enumerate(cts)),)


_singles.defvjp(_singles_fwd, _singles_bwd)


def _side_by_side(per_head, width: int):
    """A lane tile's heads' [R, 1] or [R, 128] each over its own ``width``
    lanes: [R, 128]."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // width
    tile = per_head[0]
    for k in range(1, len(per_head)):
        tile = jnp.where(lane == k, per_head[k], tile)
    return tile


def _over_lanes(m, width: int):
    """``m`` [R, n] a number a head -> [R, n P], each over its head's lanes."""
    per_head, per_tile = _singles(m, 1), _LANES // width
    return jnp.concatenate(
        [jnp.broadcast_to(
            _side_by_side(per_head[t:t + per_tile], width),
            (m.shape[0], _LANES))
         for t in range(0, len(per_head), per_tile)], axis=1)


def _chunk(x, dt, rate, b, c, state, *, width: int, mxu):
    """One chunk of the heads of a grid step, all in f32: ``x`` [Q, n P],
    ``dt`` [Q, n], ``rate`` [1, n], ``b`` and ``c`` [Q, N], ``state``
    [N, n P] the heads' transposed states entering the chunk, side by side
    -> (``y`` [Q, n P], the states leaving). The products with ``x``, ``b``,
    ``c`` and the state take their operands in ``mxu``."""
    q = x.shape[0]
    n_tiles, per_tile = x.shape[1] // _LANES, _LANES // width
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = i >= j
    # the log-decays cumulated with the token down the sublanes, then they
    # and the steps with the token along the lanes: the same numbers to the
    # bit (a product with the identity at the highest precision is exact),
    # so that ``G_i - G_i`` is 0 and two tokens' rounding cancels as in a
    # cumsum
    a = dt * rate
    eye = jnp.where(i == j, 1.0, 0.0)
    cum = _product("nn", _F32, jnp.where(lower, 1.0, 0.0), a)        # [Q, n]
    total = jnp.sum(a, axis=0, keepdims=True)                        # [1, n]
    cum_col, cum_row, dt_row = (
        _singles(cum, 1), _singles(_product("tn", _F32, cum, eye), 0),
        _singles(_product("tn", _F32, dt, eye), 0))
    scores = _product("nt", mxu, c, b)                  # C B^T, the group's
    # what does not depend on L, over all the step's heads at once: the read
    # of the entering states, and the chunk's own contribution to the states
    # it leaves, ``exp(G_Q) S + B^T (exp(G_Q - G) dt x)``
    read = _product("nn", mxu, c, state) * _over_lanes(jnp.exp(cum), width)
    own = _product("tn", mxu, b,
                   x * _over_lanes(jnp.exp(total - cum) * dt, width))
    ys = []
    for t, (x_t, read_t) in enumerate(zip(_tiles(x, n_tiles),
                                          _tiles(read, n_tiles))):
        inside = []
        for h in range(t * per_tile, (t + 1) * per_tile):
            # every exponent masked before it is taken
            decay = jnp.exp(jnp.where(lower, cum_col[h] - cum_row[h],
                                      -jnp.inf))
            inside.append(_product("nn", mxu, decay * scores * dt_row[h],
                                   x_t))
        ys.append(_side_by_side(inside, width) + read_t)
    return (jnp.concatenate(ys, axis=1),
            _over_lanes(jnp.exp(total), width) * state + own)


def _entering(carry):
    """This grid step's block of the scratch that carries every block's
    states (or their cotangents) over the chunk axis: zeros at a sequence's
    first step."""
    block = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        carry[block] = jnp.zeros(carry.shape[1:], _F32)

    return carry[block]


def _operands(*refs):
    return (ref[...].astype(_F32) for ref in refs)


def _forward_kernel(x_ref, dt_ref, rate_ref, b_ref, c_ref, y_ref, *rest,
                    width):
    """A grid step (sequence, chunk, block of heads): ``rest`` is the output
    block of the entering states (where the backward will want them) and the
    scratch of the states."""
    *kept, carry = rest
    state = _entering(carry)
    if kept:
        kept[0][...] = state
    y, carry[pl.program_id(2)] = _chunk(
        *_operands(x_ref, dt_ref, rate_ref, b_ref, c_ref), state,
        width=width, mxu=x_ref.dtype)
    y_ref[...] = y.astype(y_ref.dtype)


def _backward_kernel(x_ref, dt_ref, rate_ref, b_ref, c_ref, states_ref,
                     dy_ref, dx_ref, ddt_ref, drate_ref, db_ref, dc_ref,
                     carry, sums, *, width):
    """A grid step, the chunks walked from the last to the first: ``carry``
    the cotangents of the states leaving the chunk, ``sums`` the chunk's
    ``dB`` and ``dC`` summed over the blocks of heads."""
    block, blocks = pl.program_id(2), pl.num_programs(2)
    leaving = _entering(carry)

    @pl.when(block == 0)
    def _first():
        sums[...] = jnp.zeros_like(sums)

    _, transposed = jax.vjp(
        functools.partial(_chunk, width=width, mxu=x_ref.dtype),
        *_operands(x_ref, dt_ref, rate_ref, b_ref, c_ref, states_ref))
    dx, ddt_ref[...], drate_ref[...], db, dc, carry[block] = transposed(
        (dy_ref[...].astype(_F32), leaving))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    sums[0] += db
    sums[1] += dc

    @pl.when(block == blocks - 1)
    def _last():
        db_ref[...] = sums[0].astype(db_ref.dtype)
        dc_ref[...] = sums[1].astype(dc_ref.dtype)


def _specs(batch, seq, heads, width, state, reverse: bool):
    """The grid (sequences, chunks, blocks of heads) and the block specs of
    ``x`` [B, T, H P], of the steps re-laid [B, H / n, T, n], of the rates
    [H / n, 1, n], of ``B`` / ``C`` [B, T, N], of the states
    [B, T / Q, H / n, N, n P] and of a chunk's ``dA`` [B, T / Q, H / n, 1, n]."""
    n = seq // CHUNK
    per = heads_a_step(heads, width)

    def at(i):
        return n - 1 - i if reverse else i

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return (
        (batch, n, heads // per), per,
        spec((None, CHUNK, per * width), lambda b, i, h: (b, at(i), h)),
        spec((None, None, CHUNK, per), lambda b, i, h: (b, h, at(i), 0)),
        spec((None, 1, per), lambda b, i, h: (h, 0, 0)),
        spec((None, CHUNK, state), lambda b, i, h: (b, at(i), 0)),
        spec((None, None, None, state, per * width),
             lambda b, i, h: (b, at(i), h, 0, 0)),
        spec((None, None, None, 1, per), lambda b, i, h: (b, at(i), h, 0, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=2 ** 26)


def _relaid(dt, per):  # [B, T, H] -> [B, H / n, T, n]
    batch, seq, heads = dt.shape
    return jnp.transpose(dt.astype(_F32).reshape(batch, seq, heads // per,
                                                 per), (0, 2, 1, 3))


@functools.partial(jax.jit, static_argnames=("interpret", "keep"))
def forward(x, dt, a, b, c, *, interpret: bool, keep: bool):
    """``ops/ssd.py::ssd``'s operands (one group) -> ``y`` [B, T, H, P] in
    ``x``'s dtype and, where ``keep``, the transposed states that entered
    each chunk [B, T / Q, H / n, N, n P] in f32 (else ``()``)."""
    batch, seq, heads, width = x.shape
    state = b.shape[-1]
    grid, per, wide, steps, rates, group, states, _ = _specs(
        batch, seq, heads, width, state, reverse=False)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, width=width),
        grid=grid,
        in_specs=[wide, steps, rates, group, group],
        out_specs=[wide] + [states] * keep,
        out_shape=[jax.ShapeDtypeStruct((batch, seq, heads * width), x.dtype)]
        + [jax.ShapeDtypeStruct((batch, seq // CHUNK, heads // per, state,
                                 per * width), _F32)] * keep,
        scratch_shapes=[pltpu.VMEM((heads // per, state, per * width), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(x.reshape(batch, seq, -1), _relaid(dt, per),
      a.astype(_F32).reshape(-1, 1, per), b.reshape(batch, seq, -1),
      c.reshape(batch, seq, -1))
    return out[0].reshape(x.shape), tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def backward(x, dt, a, b, c, states, dy, *, interpret: bool):
    """The cotangents of the five operands, each in its operand's shape and
    dtype, from the entering states ``forward`` kept and ``y``'s cotangent;
    ``dB`` and ``dC`` summed over the group's heads inside the call."""
    batch, seq, heads, width = x.shape
    state = b.shape[-1]
    grid, per, wide, steps, rates, group, entering, rate_sums = _specs(
        batch, seq, heads, width, state, reverse=True)
    flat, group_shape = (batch, seq, heads * width), (batch, seq, state)
    relaid = _relaid(dt, per)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_backward_kernel, width=width),
        grid=grid,
        in_specs=[wide, steps, rates, group, group, entering, wide],
        out_specs=[wide, steps, rate_sums, group, group],
        out_shape=[jax.ShapeDtypeStruct(flat, x.dtype),
                   jax.ShapeDtypeStruct(relaid.shape, _F32),
                   jax.ShapeDtypeStruct(
                       (batch, seq // CHUNK, heads // per, 1, per), _F32),
                   jax.ShapeDtypeStruct(group_shape, b.dtype),
                   jax.ShapeDtypeStruct(group_shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((heads // per, state, per * width), _F32),
                        pltpu.VMEM((2, CHUNK, state), _F32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(x.reshape(flat), relaid, a.astype(_F32).reshape(-1, 1, per),
      b.reshape(group_shape), c.reshape(group_shape), states,
      dy.reshape(flat))
    ddt = jnp.transpose(ddt, (0, 2, 1, 3)).reshape(dt.shape)
    return (dx.reshape(x.shape), ddt.astype(dt.dtype),
            jnp.sum(da, axis=(0, 1)).reshape(a.shape).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape))
