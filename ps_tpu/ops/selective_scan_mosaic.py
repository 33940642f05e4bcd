"""Mamba-1's selective scan (``ops/selective_scan.py``) as two Mosaic (Pallas)
calls, one forward and one backward, over the operands as the mixer has them,
with a block of channels' state in VMEM from a sequence's first token to its
last::

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t        h_{-1} = 0  [N, C]
    y_t = sum_n h_t C_t

token by token, as the XLA form: no quotient and no cumulated product of
decays is formed, every exponential is taken (as ``exp2`` of ``dt (A log2
e)``: the chip's exponential is a power of two, and the product with ``log2
e`` a token and state is then made once a call), the state, the decays and
every sum in f32; ``x``, ``B`` and ``C`` are read in the caller's dtype and
``y`` leaves in f32. ``D x`` and ``dD`` stay XLA's, outside.

**Layout.** The state is ``[N, C]``, a state a sublane and a channel a lane:
16 states of 128 channels are two vector registers, and ``x`` ``[B, S, C]``
and ``dt`` arrive with the channels on the lanes as the mixer's products
leave them, so nothing is re-laid in front of the calls but ``B`` and ``C``
(``[B, S, N]`` -> ``[B, N, S]``, 0.5 MB each in the cell) and ``A`` (``[C, N]``
-> ``[N, C]`` times ``log2 e``). What a token needs besides is its row of
``dt`` and of ``dt x`` over the sublanes (a sublane permutation of the
register that holds eight tokens' rows) and its ``B_t`` and ``C_t`` over the
lanes: ``_over_lanes`` lays a tile's ``B`` and ``C`` out as ``[T, N, 128]`` in
VMEM once a tile of tokens, at the first block of channels (a select of the
token's lane and a sum over the lanes, the XLU's), and every block reads
them from there. The sum over the states a token (``y``; in the backward
``dt``'s and ``x``'s cotangents) is a sum over sublanes: eight tokens'
``[8, 128]`` products are stored one under the other and read back at a stride
of eight rows, so that a register holds one sublane of all eight, and seven
adds give the eight sums in their rows (``_summed``), where a reduction a
token rotates and adds three times and must then be put in its row.

**The grid** is (sequences, tiles of ``TILE`` tokens, blocks of ``lanes``
channels), every axis in order. Every block's state is a scratch ``[C /
lanes, N, lanes]`` indexed by the block, zeros at a sequence's first tile
(``_entering``). A grid step walks its tile eight tokens a trip of a
``fori_loop``; inside a trip the tokens are written out, and a token's
update runs over the block's lane tiles side by side: four independent chains
of two registers, which is what fills the vector ALU's four slots (a chain
alone waits on its own multiply and add). The forward call writes ``y`` and,
where the gradient is asked (``keep``), the state that entered each tile,
``[S / TILE, B, N, C]`` f32: 21 MB a layer in the cell, the only thing the
backward call needs of the forward one beside the operands.

**The backward call** walks the tiles from the last to the first. A grid
step first makes the tile's states again from the entering one (``again``:
the forward's update, each state stored as it is made, ``[TILE + 1, N,
lanes]`` f32 in VMEM, 8.4 MB), then runs the transposed recurrence from the
tile's last token to its first with the state's cotangent in the carry::

    g_t  = dy_t (x) C_t + a_{t+1} g_{t+1}          a_t = exp(dt_t A)
    dC_t = sum_c dy_t h_t       dB_t = sum_c g_t (dt_t x_t)
    e_t  = a_t g_t h_{t-1}      dA = sum_t e_t dt_t
    d(dt_t) = sum_n e_t A + x_t sum_n g_t B_t      dx_t = dt_t sum_n g_t B_t

``dB`` and ``dC`` are kept a lane (``sums`` ``[2, T, N, 128]``) over a block's
lane tiles and over the blocks, and summed over the lanes once a tile at the
last block; ``dA`` leaves a tile and block and XLA sums the tiles.

**What bounds them** is the vector ALU, not the EUP, the HBM or the MXU: in
the compiler's final bundles (``--xla_jf_dump_llo_text`` on a described v5e)
the forward trip is 161 bundles for eight tokens of four lane tiles with 560
vector-ALU operations, 3.48 of the 4 slots a bundle (17.5 a token and tile:
eight multiplies, four adds, two permutations, two pops of the EUP's result,
the rest moves), the EUP 0.40 of its one slot, loads 0.76 of 3, stores 0.27
of 1; the backward trips 460 bundles at 3.57 of 4. A grid step moves 1.3 MB
forward (1.6 us at the HBM's 819 GB/s) under 3.4 us of bundles at 1.5 GHz;
on the chip the forward call takes 2.59 ms in the cell's step where its 3.3e6
bundles of trips alone are 2.2, the backward 6.88 where its 9.4e6 are 6.3
(``ops/selective_scan.py``'s table; ``PERF.md`` section 6, PR 66).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_tpu.ops.ssd_mosaic import _F32, _entering

_LANES = 128
_ROWS = 8
#: tokens of ``B`` and ``C`` laid over the lanes in one trip
_TRIP = 32
#: tokens of one grid step: the chunk whose entering state is kept
TILE = 256
#: channels of one grid step at most
_BLOCK = 512


def lanes(channels: int) -> int:
    """Channels of one grid step: the widest of 512, 256 and 128 that
    divides ``channels`` (0 where none does)."""
    return next((n for n in (_BLOCK, 256, _LANES) if channels % n == 0), 0)


def _over_lanes(groups, wides):
    """Each of ``groups`` [N, T], a token a lane -> its ``wides`` [T, N, 128]:
    token ``t``'s N numbers, each over a whole register row (the sum over the
    lanes of the token's lane alone: one term, exact). ``_TRIP`` tokens of
    every group a trip, written out: each is a select, a reduction and a
    broadcast in the XLU's pipeline, and a trip waits for its last."""
    lane = jax.lax.broadcasted_iota(jnp.int32, groups[0].shape, 1)
    values = [group[...].astype(_F32) for group in groups]

    def trip(i, _):
        for t in (i * _TRIP + s for s in range(_TRIP)):
            for value, wide in zip(values, wides):
                wide[t] = jnp.broadcast_to(jnp.sum(
                    jnp.where(lane == t, value, 0.0), axis=1, keepdims=True),
                    wide.shape[1:])
        return _

    jax.lax.fori_loop(0, lane.shape[1] // _TRIP, trip, None)


def _row(rows, s: int, n: int):
    """Row ``s`` of ``rows`` [8, 128] over ``n`` sublanes."""
    return jnp.broadcast_to(rows[s:s + 1], (n, _LANES))


def _halves(p):
    """``p`` [N, 128] -> [8, 128]: the sum of its whole registers."""
    return sum(p[r:r + _ROWS] for r in range(0, p.shape[0], _ROWS))


def _summed(parts, j):
    """``parts`` [tiles, 64, 128], eight tokens' [8, 128] a lane tile one under
    the other -> [8, 128] of tile ``j`` whose row ``s`` is token ``s``'s summed
    over its sublanes: eight loads at a stride of eight rows (row ``r`` of
    every token) and seven adds, where a reduction a token rotates and adds
    three times and has to be put in its row."""
    return sum(parts[j, pl.ds(r, _ROWS, stride=_ROWS), :]
               for r in range(_ROWS))


def _tiles(wide: int):
    return [slice(j, j + _LANES) for j in range(0, wide, _LANES)]


def _forward_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, y_ref, *rest):
    """A grid step (sequence, tile of tokens, block of channels): ``rest`` is
    the output block of the entering state (where the backward will want it),
    the scratch of every block's state, those of ``B`` and ``C`` over the
    lanes and that of eight tokens' ``h C`` before the sum over the states."""
    *kept, carry, bb, cb, parts = rest
    block = pl.program_id(2)
    state = _entering(carry)
    if kept:
        kept[0][...] = state

    @pl.when(block == 0)
    def _group():
        _over_lanes((bt_ref, ct_ref), (bb, cb))

    n, tiles = state.shape[0], _tiles(state.shape[1])

    def group(g, hs):
        base = pl.multiple_of(g * _ROWS, _ROWS)
        rows = pl.ds(base, _ROWS)
        hs = list(hs)
        dt = [dt_ref[rows, at] for at in tiles]
        u = [dt[j] * x_ref[rows, at].astype(_F32)
             for j, at in enumerate(tiles)]
        for s in range(_ROWS):
            b_s, c_s = bb[base + s], cb[base + s]
            for j, at in enumerate(tiles):
                hs[j] = jnp.exp2(_row(dt[j], s, n) * a_ref[:, at]) * hs[j] \
                    + _row(u[j], s, n) * b_s
                parts[j, s * _ROWS:(s + 1) * _ROWS, :] = _halves(hs[j] * c_s)
        for j, at in enumerate(tiles):
            y_ref[rows, at] = _summed(parts, j)
        return tuple(hs)

    hs = jax.lax.fori_loop(0, x_ref.shape[0] // _ROWS, group,
                           tuple(state[:, at] for at in tiles))
    carry[block] = jnp.concatenate(hs, axis=1)


def _backward_kernel(x_ref, dt_ref, a_ref, bt_ref, ct_ref, states_ref, dy_ref,
                     dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                     carry, states, bb, cb, sums, steps, rates):
    """A grid step, the tiles walked from the last to the first: ``carry`` the
    cotangent of the state leaving the tile, ``states`` the state before each
    of the tile's tokens and after its last, made again from the entering
    one, ``sums`` the tile's ``dB`` and ``dC`` a lane, summed over the blocks
    of channels, ``steps`` and ``rates`` eight tokens' ``g B`` and ``e A``
    before their sums over the states."""
    block, blocks = pl.program_id(2), pl.num_programs(2)
    leaving = _entering(carry)
    n, tiles = leaving.shape[0], _tiles(leaving.shape[1])
    groups = x_ref.shape[0] // _ROWS

    @pl.when(block == 0)
    def _group():
        _over_lanes((bt_ref, ct_ref), (bb, cb))
        sums[...] = jnp.zeros_like(sums)

    def again(g, hs):
        base = pl.multiple_of(g * _ROWS, _ROWS)
        rows = pl.ds(base, _ROWS)
        hs = list(hs)
        dt = [dt_ref[rows, at] for at in tiles]
        u = [dt[j] * x_ref[rows, at].astype(_F32)
             for j, at in enumerate(tiles)]
        for s in range(_ROWS):
            b_s = bb[base + s]
            for j, at in enumerate(tiles):
                states[base + s, :, at] = hs[j]
                hs[j] = jnp.exp2(_row(dt[j], s, n) * a_ref[:, at]) * hs[j] \
                    + _row(u[j], s, n) * b_s
        return tuple(hs)

    entering = states_ref[...]
    states[x_ref.shape[0]] = jnp.concatenate(jax.lax.fori_loop(
        0, groups, again, tuple(entering[:, at] for at in tiles)), axis=1)

    def back(i, carried):
        base = pl.multiple_of((groups - 1 - i) * _ROWS, _ROWS)
        rows = pl.ds(base, _ROWS)
        gs, das = list(carried[0]), list(carried[1])
        dt = [dt_ref[rows, at] for at in tiles]
        dy = [dy_ref[rows, at] for at in tiles]
        u = [dt[j] * x_ref[rows, at].astype(_F32)
             for j, at in enumerate(tiles)]
        for s in reversed(range(_ROWS)):
            t = base + s
            b_s, c_s = bb[t], cb[t]
            db, dc = sums[0, t], sums[1, t]
            for j, at in enumerate(tiles):
                dt_s, u_s, dy_s = (_row(r[j], s, n) for r in (dt, u, dy))
                rate = a_ref[:, at]
                decay = jnp.exp2(dt_s * rate)
                dc = dc + dy_s * states[t + 1, :, at]
                g = dy_s * c_s + gs[j]
                db = db + g * u_s
                steps[j, s * _ROWS:(s + 1) * _ROWS, :] = _halves(g * b_s)
                gs[j] = decay * g
                e = gs[j] * states[t, :, at]
                das[j] = das[j] + e * dt_s
                rates[j, s * _ROWS:(s + 1) * _ROWS, :] = _halves(e * rate)
            sums[0, t], sums[1, t] = db, dc
        for j, at in enumerate(tiles):
            into = _summed(steps, j)
            dx_ref[rows, at] = (dt[j] * into).astype(dx_ref.dtype)
            ddt_ref[rows, at] = math.log(2.0) * _summed(rates, j) \
                + x_ref[rows, at].astype(_F32) * into
        return tuple(gs), tuple(das)

    gs, das = jax.lax.fori_loop(
        0, groups, back,
        (tuple(leaving[:, at] for at in tiles),
         tuple(jnp.zeros((n, _LANES), _F32) for _ in tiles)))
    carry[block] = jnp.concatenate(gs, axis=1)
    da_ref[...] = jnp.concatenate(das, axis=1)

    @pl.when(block == blocks - 1)
    def _last():
        db_ref[...] = jnp.sum(sums[0], axis=-1).astype(db_ref.dtype)
        dc_ref[...] = jnp.sum(sums[1], axis=-1).astype(dc_ref.dtype)


def _specs(batch, seq, channels, state, reverse: bool):
    """The grid (sequences, tiles of tokens, blocks of channels) and the block
    specs of ``x`` [B, S, C], of the rates [N, C], of ``B`` / ``C`` with the
    token last [B, N, S] and first [B, S, N], and of a tile's state
    [S / TILE, B, N, C]."""
    n, wide = seq // TILE, lanes(channels)

    def at(i):
        return n - 1 - i if reverse else i

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    return (
        (batch, n, channels // wide), wide,
        spec((None, TILE, wide), lambda b, i, k: (b, at(i), k)),
        spec((state, wide), lambda b, i, k: (0, k)),
        spec((None, state, TILE), lambda b, i, k: (b, 0, at(i))),
        spec((None, TILE, state), lambda b, i, k: (b, at(i), 0)),
        spec((None, None, state, wide), lambda b, i, k: (at(i), b, 0, k)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=2 ** 26)


def _rates(a):  # [C, N] -> [N, C], for ``exp2``
    return a.astype(_F32).T * math.log2(math.e)


def _token_last(group):  # [B, S, N] -> [B, N, S]
    return jnp.swapaxes(group, 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret", "keep"))
def forward(x, dt, a, b, c, *, interpret: bool, keep: bool):
    """``x`` [B, S, C], ``dt`` [B, S, C] f32, ``a`` [C, N], ``b``, ``c``
    [B, S, N] -> ``y`` [B, S, C] in f32 (without the ``D x`` skip) and, where
    ``keep``, the state that entered each tile [S / TILE, B, N, C] in f32
    (else ``()``). ``S`` whole tiles, ``C`` whole blocks."""
    batch, seq, channels = x.shape
    state = a.shape[1]
    grid, wide, block, rates, group, _, states = _specs(
        batch, seq, channels, state, reverse=False)
    out = pl.pallas_call(
        _forward_kernel,
        grid=grid,
        in_specs=[block, block, rates, group, group],
        out_specs=[block] + [states] * keep,
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32)]
        + [jax.ShapeDtypeStruct((seq // TILE, batch, state, channels),
                                _F32)] * keep,
        scratch_shapes=[pltpu.VMEM((channels // wide, state, wide), _F32)]
        + [pltpu.VMEM((TILE, state, _LANES), _F32)] * 2
        + [pltpu.VMEM((wide // _LANES, _ROWS * _ROWS, _LANES), _F32)],
        compiler_params=_PARAMS, interpret=interpret, name="s6_forward",
    )(x, dt.astype(_F32), _rates(a), _token_last(b), _token_last(c))
    return out[0], tuple(out[1:])


@functools.partial(jax.jit, static_argnames=("interpret",))
def backward(x, dt, a, b, c, states, dy, *, interpret: bool):
    """The cotangents of the five operands, each in its operand's shape and
    dtype, from the entering states ``forward`` kept and ``y``'s cotangent
    (f32); ``dB`` and ``dC`` summed over the channels inside the call, ``dA``
    over the tiles and sequences by XLA."""
    batch, seq, channels = x.shape
    state = a.shape[1]
    grid, wide, block, rates, group, sums, entering = _specs(
        batch, seq, channels, state, reverse=True)
    dx, ddt, da, db, dc = pl.pallas_call(
        _backward_kernel,
        grid=grid,
        in_specs=[block, block, rates, group, group, entering, block],
        out_specs=[block, block, entering, sums, sums],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(states.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((channels // wide, state, wide), _F32),
                        pltpu.VMEM((TILE + 1, state, wide), _F32)]
        + [pltpu.VMEM((TILE, state, _LANES), _F32)] * 2
        + [pltpu.VMEM((2, TILE, state, _LANES), _F32)]
        + [pltpu.VMEM((wide // _LANES, _ROWS * _ROWS, _LANES), _F32)] * 2,
        compiler_params=_PARAMS, interpret=interpret, name="s6_backward",
    )(x, dt.astype(_F32), _rates(a), _token_last(b), _token_last(c), states,
      dy.astype(_F32))
    return (dx, ddt.astype(dt.dtype),
            jnp.sum(da, axis=(0, 1)).T.astype(a.dtype), db, dc)
