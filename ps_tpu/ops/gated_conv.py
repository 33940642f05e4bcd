"""The double-gated short causal convolution of LFM2's ``conv`` layers.

Between the mixer's two projections, over ``bcx = h @ W_in`` [B, S, 3 * D]::

    B, C, X = split(bcx, 3)             # three gates' worth of channels
    u = B * X                           # the input gate
    v_t = sum_j w[:, j] * u_{t-(L-1)+j} # depthwise, causal, zero left pad
    y = C * v                           # the output gate

with ``w`` [D, L] one filter of ``L`` taps a channel (``conv_L_cache``, 3 in
the published models; ``conv_bias`` false). No position enters it, and
output ``t`` reads inputs ``t-L+1 .. t`` only. All of it is element-wise work
over ``[tokens, D]`` arrays: bandwidth's, between two matmuls that are the
MXU's.

Written as one ``jax.custom_vjp`` in plain ``jax.numpy`` so that XLA makes
one fusion of each direction and nothing but ``bcx`` and ``w`` is kept for
the backward pass, which recomputes ``u`` and ``v`` (left to autodiff, B, X,
C, u and v would each be a residual of ``[tokens, D]``). By its shapes the
forward reads three ``D``-wide arrays and writes one, the backward reads
four and writes three: 11 x tokens x D x itemsize bytes a layer, which
``benchmark/families/lfm2_step.py::conv_gate_bytes`` counts. The products
and the taps' sum run in f32 and the results are cast to ``bcx``'s dtype.

The taps themselves (``causal_taps``) are public, and so is the other short
convolution built on them, ``conv_silu``: a depthwise causal filter, an
optional bias and a SiLU, with one rule in each direction. Kimi Delta
Attention runs it on q, k and v (four taps, no bias, ``models/kimi_linear.py``)
and the Mamba-2 mixer on its x, B and C channels (four taps and a bias,
``models/blocks.py::mamba_block``: Granite-4.0-H's and Nemotron-H's).

**``conv_silu`` has two realisations of one rule, a name each: its caller's
neighbours say which.** By its shapes a forward pass reads ``x`` and writes
``y``, 2 passes of ``tokens x C x itemsize``; a backward pass reads ``x`` and
``dy`` and writes ``dx``, 3 passes. A layer's ``jax.checkpoint`` runs the
forward twice: 7 passes a layer, 0.50 GB at Granite's [8192, 4352] in bf16.
Until PR 57 neither pass came near that: ``shift`` spells a move by one to
three rows as ``concatenate(zeros, u[:, :-by])`` of ``x`` **already cast to
f32**, and XLA:TPU does not fuse a slice that starts off the 8-row sublane
tile of an f32 array into the loop fusion that reads it: it writes each
shifted operand out. Compiled for a described v5e at ``bf16[1, 8192, 4352]``
with a bias (ISSUE 57): the forward kept three ``f32[1, 819x, 4352]`` slices
(428 MB of temporaries, 1.71 GB accessed where ``x`` and ``y`` are 0.143),
the gradient seven such arrays (571 MB, 3.35 GB accessed where ``x``, ``dy``
and ``dx`` are 0.214).

*The XLA form* (``conv_silu``; what Phi-4-mini-flash's Mamba-1 mixer calls,
what ``models/blocks.py::mamba_block`` called until PR 72, and every shape
the kernels do not take). One copy of ``x`` padded by
``taps - 1`` rows **in its own dtype**, static slices of it, each cast after
it is cut (``_moved_copies``): the pad is a producer the loop fusion takes
in and the slices are read in place. Same values to the bit
(``tests/test_gated_conv.py``); compiled as above the forward accesses
0.143 GB with no temporary, the gradient 0.429 GB with ``dz`` in f32 as its
one temporary (143 MB), at all three cells' shapes
(``tests/test_chip_compile.py``). The gain is the backward's: alone, the
forward call takes what it took (the fusion is bound by its sublane
shuffles, not by its bytes). Its place is where XLA's own fusions read the
result: until PR 59 Granite's scan (``ops/ssd.py``'s XLA form) took ``xs``
in three layouts, and XLA wrote each from a taps fusion of its own; a custom
call pins one row-major result and the copies into the other layouts then
cost what the kernel saved (the table's third row, in brackets). Since PR 59
the scan at the cells' shapes is a Mosaic call that reads ``xs`` row-major,
and since PR 72 ``mamba_block`` calls ``conv_silu_kernel``: no cell's scan
is XLA's, and Nemotron's share was faster with the Mosaic taps in front of
that one too (the table's last row).

*The Mosaic calls* (``conv_silu_kernel``: ``forward`` / ``backward``; what
``models/kimi_linear.py::_mixer`` and ``models/qwen3_next.py`` call, whose q,
k and v go on to the delta rule's kernels row-major, and since PR 72
``mamba_block``, whose x, B and C go on to the scan's Mosaic calls in
Granite's and Nemotron's cells; at shapes ``path`` accepts: whole 128-lane
tiles of channels, whole tiles of rows, at least one block). A grid step holds
a ``[rows, lanes]`` block of one sequence (``tiles``: the widest multiple of
128 up to 512 lanes that divides ``C``, rows to 512 Ki elements; 2,048 x 256
at 4,352 and 1,280 channels, 1,024 x 512 at 4,096) and, through a second
``BlockSpec`` on the same operand, the tile of rows before it (zeros at a
sequence's start: no row of one sequence reads another's); the backward
also the tile after it, of ``x`` and of ``dy``. The block is cast once into
an f32 scratch behind that tile; the body walks it 128 rows x 128 lanes at
a time, and a move by ``k`` rows is ``pltpu.roll`` along the sublanes of the
8 + 128 rows and an aligned slice (Mosaic refuses a load at a dynamic row
that is no multiple of 8, and a fully unrolled body of static ones compiles
five times as long). The four multiply-adds, the bias, the SiLU, ``dz = dy
sigmoid(z) (1 + z (1 - sigmoid(z)))`` and the transposed taps of ``dz`` (a
second walk, over a scratch of ``dz`` that reaches one tile past the block)
run in f32 and the result is cast once; ``dw`` and ``db`` are sums of whole
registers carried in VMEM along the grid's row blocks and batch
(``arbitrary``; the lane tiles ``parallel``) and folded to a row each at
the last step, so they differ from the XLA form's in the last bits.
Residuals are ``x``, ``w`` and ``b`` in both. ``interpret`` is a static
argument of ``forward`` / ``backward`` and of the ``custom_vjp``, which
``conv_silu_kernel`` fills from ``ops/mosaic.py::interpret``. Under a mesh
of several devices the
calls are not wrapped in ``shard_map`` as ``ops/kda.py``'s are: no cell runs
that mixer across chips yet.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 57, and PR 72 where said):
a call alone at ``bf16[1, 8192, C]``, four taps, twenty calls chained in one
program (``tools/taps_table.py``), forward / backward in ms; and the taps'
scope inside its cell's step, a step's calls together, with the step beside
it.**

| form | 4,352 with a bias (Granite) | 4,096 (Kimi) | 1,280 with a bias (Nemotron) | in the cell: taps / step, ms |
|---|---|---|---|---|
| ``shift`` on the f32 cast (before PR 57) | 1.06 / 4.43 | 0.99 / 3.90 | 0.26 / 0.95 | Granite 50.06 / 467.18, Kimi 53.72 / 424.76, Nemotron 2.94 / 236.26 |
| **the XLA form** | 1.06 / 2.17 | 0.99 / 1.88 | 0.26 / 0.34 | Granite 20.40 / 443.03 in front of the XLA scan (PR 57), 21.02 / 409.80 in front of the scan's Mosaic calls (PR 72's parent), Kimi 14.66 / 374.64, Nemotron 3.25 / 235.94 (PR 57), 2.64 / 228.73 (PR 72's parent) |
| **the Mosaic calls** (467 / 403 GB/s of the bytes above at 4,352) | 0.31 / 0.53 | 0.29 / 0.52 | 0.12 / 0.18 | **Granite 9.01 / 392.36 and Nemotron 1.53 / 227.39 in front of the scan's Mosaic calls (my chip runs, PR 72: what ``mamba_block`` runs; 27 calls a Granite step, 0.263 forward, 0.473 backward; the ``pad`` that joins the three cotangents into the backward call's ``dy`` stands outside the scope, under ``ps.mamba``: +1.92 ms a Granite step and +0.74 a Nemotron step, so taps and pad together 10.93 and 2.27)**; in front of the XLA scan (PR 57) Granite 9.02 / 471.91 (the scan's scope 51.44 -> 70.65 and the gate's 30.02 -> 43.6: copies of ``f32[1,8192,4096]`` and ``f32[1,32,256,1,64,64]`` into the layouts the einsums read) and Nemotron 1.54 / 234.29; **Kimi 11.62 / 363.37** |

The chip's plain elementwise pass over the forward's bytes (``silu`` alone)
takes 0.17 ms at 4,352 channels: the kernel's forward stands at 0.31, bound
by its body (v5e's VPU has no bf16 and one store slot a cycle; 32 / 64 /
128 / 256 rows a walk: 0.54 / 0.46 / 0.43 / 0.44 ms with a carried copy in
the loop), not by the block's shape (512 x 256 to 4,096 x 256 and 128 to
4,352 lanes all within 5%). A form that leaves the SiLU and ``dz`` to XLA
and keeps only the taps in Mosaic was slower than either in all three cells
(474.70 / 374.90 / 234.67), and the XLA forward under the Mosaic backward
slower still in Granite's (481.41).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_tpu.ops import mosaic


def shift(u, by: int):
    """``u`` [B, S, D] moved ``by`` positions to the right along S (to the
    left where negative), zeros coming in."""
    if by == 0:
        return u
    pad = jnp.zeros_like(u[:, :abs(by)])
    if by > 0:
        return jnp.concatenate([pad, u[:, :-by]], axis=1)
    return jnp.concatenate([u[:, -by:], pad], axis=1)


def causal_taps(u, w, sign: int = 1):
    """``sum_j w[:, j] * u_{t - sign * (L-1-j)}`` for ``u`` [B, S, D] and
    ``w`` [D, L]: the depthwise causal filter (``sign`` 1; zero left pad, no
    bias) or its transpose (-1). The public helper of both short-convolution
    mixers: ``gated_short_conv`` below (LFM2's three taps between two gates)
    and ``models/kimi_linear.py``'s four taps before a SiLU on q, k and v."""
    taps = w.shape[-1]
    return sum(w[:, j] * shift(u, sign * (taps - 1 - j))
               for j in range(taps))


@jax.custom_vjp
def gated_short_conv(bcx, w):
    """``bcx`` [B, S, 3 * D], ``w`` [D, L] -> ``C * conv(B * X)`` [B, S, D]
    in ``bcx``'s dtype."""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (c * causal_taps(b * x, w.astype(jnp.float32))).astype(bcx.dtype)


def _fwd(bcx, w):
    return gated_short_conv(bcx, w), (bcx, w)


def _bwd(res, dy):
    bcx, w = res
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    wf = w.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    u = b * x
    dv = dy * c
    du = causal_taps(dv, wf, -1)
    taps = w.shape[-1]
    dw = jnp.stack([jnp.sum(dv * shift(u, taps - 1 - j), axis=(0, 1))
                    for j in range(taps)], axis=-1)
    dbcx = jnp.concatenate([du * x, dy * causal_taps(u, wf), du * b], axis=-1)
    return dbcx.astype(bcx.dtype), dw.astype(w.dtype)


gated_short_conv.defvjp(_fwd, _bwd)


def _moved_copies(u, taps: int, sign: int = 1):
    """``[u_{t - sign * (taps-1-j)} for j]`` in f32 for ``u`` [B, S, D]:
    static slices of ONE copy of ``u`` padded by ``taps - 1`` rows of zeros
    **in ``u``'s own dtype**, each cast after it is cut. The values are
    ``shift``'s; the spelling is what XLA:TPU fuses: a pad is a producer its
    loop fusion takes in, and a slice of it at a row off the sublane tile is
    then read in place, where ``concatenate(zeros, u[:, :-by])`` of an f32
    ``u`` is written out whole, once a tap."""
    pad = (taps - 1, 0) if sign > 0 else (0, taps - 1)
    padded = jnp.pad(u, ((0, 0), pad, (0, 0)))
    seq = u.shape[1]
    return [padded[:, start:start + seq].astype(jnp.float32)
            for start in (range(taps) if sign > 0
                          else range(taps - 1, -1, -1))]


def _pre_activation(x, w, b):
    """(z, the moved copies of x) in f32, the taps summed in ``causal_taps``'
    order."""
    moved = _moved_copies(x, w.shape[-1])
    z = sum(w[:, j] * xj for j, xj in enumerate(moved))
    return (z if b is None else z + b.astype(jnp.float32)), moved


@jax.custom_vjp
def _conv_silu(x, w, b):
    z, _ = _pre_activation(x, w.astype(jnp.float32), b)
    return jax.nn.silu(z).astype(x.dtype)


def _conv_silu_fwd(x, w, b):
    return _conv_silu(x, w, b), (x, w, b)


def _conv_silu_bwd(res, dy):
    x, w, b = res
    wf = w.astype(jnp.float32)
    z, moved = _pre_activation(x, wf, b)
    gate = jax.nn.sigmoid(z)
    dz = dy.astype(jnp.float32) * gate * (1 + z * (1 - gate))
    dw = jnp.stack([jnp.sum(dz * xj, axis=(0, 1)) for xj in moved], axis=-1)
    db = None if b is None else jnp.sum(dz, axis=(0, 1)).astype(b.dtype)
    dx = sum(wf[:, j] * dzj
             for j, dzj in enumerate(_moved_copies(dz, w.shape[-1], -1)))
    return dx.astype(x.dtype), dw.astype(w.dtype), db


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


# ---- the same rule as one Mosaic call a direction -------------------------

#: rows of the f32 tile: a halo is one such tile, of which ``taps - 1`` rows
#: are read, and ``[w | b]`` travels as one tile of ``_TILE`` rows
_TILE = 8
#: elements of one grid step's block of ``x``
_BLOCK = 512 * 1024
#: lanes of a block at most, and rows and lanes of what the body holds in
#: registers at a time
_LANES, _ROWS, _VREG = 512, 128, 128


def _sublanes(itemsize: int) -> int:
    """Rows of a tile in memory: 8 of four bytes, 16 of two."""
    return _TILE * 4 // itemsize


def path(x, w) -> str:
    """Which realisation ``conv_silu_kernel`` takes for ``x`` [B, S, C] and
    ``w`` [C, taps]: ``"kernel"`` (the Mosaic calls) where the channels fill
    whole 128-lane tiles, the sequence whole tiles of ``x``'s dtype, the taps
    and the bias one tile of rows, and a sequence at least one grid step's
    block (under that a call is one step with nothing to overlap, and the
    plain form's temporary is a few MB), else ``"plain"`` (the XLA form,
    ``conv_silu``). Read from the shapes alone; whether a caller wants the
    kernels at all is which of the two functions it calls."""
    _, seq, channels = x.shape
    tiled = channels % _VREG == 0 and seq % _sublanes(x.dtype.itemsize) == 0
    whole = seq * channels >= _BLOCK and w.shape[-1] < _TILE
    return "kernel" if tiled and whole else "plain"


def tiles(seq: int, channels: int, itemsize: int):
    """(rows, lanes) of one grid step's block: the widest multiple of 128
    lanes up to ``_LANES`` that divides the channels, and the most rows that
    divide the sequence, are whole tiles and keep the block to ``_BLOCK``
    elements."""
    lanes = max(n for n in range(_VREG, min(channels, _LANES) + 1, _VREG)
                if channels % n == 0)
    step = _sublanes(itemsize)
    fit = [n for n in range(step, seq + 1, step)
           if seq % n == 0 and n * lanes <= _BLOCK]
    return (max(fit) if fit else step), lanes


def _chunks(rows: int) -> int:
    """Rows the body takes at a time: ``_ROWS`` or the next half of it that
    divides the block's."""
    return next(n for n in (_ROWS >> k for k in range(5)) if rows % n == 0)


def _moved(v, by: int):
    """``v`` [8 + n, lanes] f32 -> its rows ``8 - by .. 8 - by + n``: the
    ``n`` rows behind the leading tile moved ``by`` down, that tile supplying
    what comes in: a rotation along the sublanes in VMEM (``pltpu.roll``;
    Mosaic refuses a load at a row that is no multiple of 8 unless it is
    static) and an aligned slice."""
    return (pltpu.roll(v, by, 0) if by else v)[_TILE:]


def _fill(xs_ref, before_ref, x_ref, first):
    """The f32 scratch ``xs`` = [the halo tile before | the block's rows]:
    zeros for the halo at the sequence's start."""
    before = before_ref[...].astype(jnp.float32)[-_TILE:]
    xs_ref[:_TILE, :] = jnp.where(first, 0.0, before)
    xs_ref[_TILE:_TILE + x_ref.shape[0], :] = x_ref[...].astype(jnp.float32)


def _taps_of(wb_ref, lanes, taps: int):
    """The filter's rows and the bias's of ``[w | b]`` on these lanes."""
    return ([wb_ref[j:j + 1, lanes] for j in range(taps)],
            wb_ref[taps:taps + 1, lanes])


def _pre(v, w, bias, taps: int):
    """(z, the moved copies of x) for the rows behind ``v``'s leading tile."""
    moved = [_moved(v, taps - 1 - j) for j in range(taps)]
    z = bias + sum(wj * xj for wj, xj in zip(w, moved))
    return z, moved


def _forward_kernel(before_ref, x_ref, wb_ref, y_ref, xs_ref, *, taps):
    rows, width = x_ref.shape
    _fill(xs_ref, before_ref, x_ref, pl.program_id(2) == 0)
    chunk = _chunks(rows)
    for lane in range(0, width, _VREG):
        lanes = slice(lane, lane + _VREG)
        w, bias = _taps_of(wb_ref, lanes, taps)

        def body(t, _):
            r = pl.multiple_of(t * chunk, chunk)
            z, _ = _pre(xs_ref[pl.ds(r, chunk + _TILE), lanes], w, bias, taps)
            y_ref[pl.ds(r, chunk), lanes] = (
                z * jax.nn.sigmoid(z)).astype(y_ref.dtype)

        jax.lax.fori_loop(0, rows // chunk, body, None)


def _backward_kernel(before_ref, x_ref, after_ref, dy_ref, dy_after_ref,
                     wb_ref, dx_ref, dwb_ref, xs_ref, dzs_ref, acc_ref, *,
                     taps):
    rows, width = x_ref.shape
    b, i = pl.program_id(1), pl.program_id(2)
    last = i == pl.num_programs(2) - 1
    _fill(xs_ref, before_ref, x_ref, i == 0)
    # the tile after the block: x's for z there, dy's zero past the end
    xs_ref[_TILE + rows:, :] = after_ref[...].astype(jnp.float32)[:_TILE]
    dy_after = jnp.where(
        last, 0.0, dy_after_ref[...].astype(jnp.float32)[:_TILE])

    @pl.when((b == 0) & (i == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    chunk = _chunks(rows)

    def fold(v):  # [rows, lanes] -> [8, lanes]: whole registers added
        return jnp.sum(v.reshape(-1, _TILE, v.shape[-1]), axis=0)

    for lane in range(0, width, _VREG):
        lanes = slice(lane, lane + _VREG)
        w, bias = _taps_of(wb_ref, lanes, taps)

        def dz_of(r, n, dy):
            z, moved = _pre(xs_ref[pl.ds(r, n + _TILE), lanes], w, bias, taps)
            gate = jax.nn.sigmoid(z)
            dz = dy * gate * (1 + z * (1 - gate))
            dzs_ref[pl.ds(r, n), lanes] = dz
            return dz, moved

        def first(t, sums):
            r = pl.multiple_of(t * chunk, chunk)
            dz, moved = dz_of(r, chunk,
                              dy_ref[pl.ds(r, chunk), lanes].astype(jnp.float32))
            return tuple(s + fold(dz * m)
                         for s, m in zip(sums, moved + [1.0]))

        zero = jnp.zeros((_TILE, _VREG), jnp.float32)
        sums = jax.lax.fori_loop(0, rows // chunk, first, (zero,) * (taps + 1))
        for j, s in enumerate(sums):
            acc_ref[j, :, lanes] += s
        dz_of(rows, _TILE, dy_after[:, lanes])

        def second(t, _):
            r = pl.multiple_of(t * chunk, chunk)
            u = dzs_ref[pl.ds(r, chunk + _TILE), lanes]
            n = chunk + _TILE
            dx = sum(w[j] * (pltpu.roll(u, n - (taps - 1 - j), 0)
                             if j < taps - 1 else u)[:chunk]
                     for j in range(taps))
            dx_ref[pl.ds(r, chunk), lanes] = dx.astype(dx_ref.dtype)

        jax.lax.fori_loop(0, rows // chunk, second, None)

    @pl.when((b == pl.num_programs(1) - 1) & last)
    def _():
        for j in range(_TILE):
            dwb_ref[j:j + 1, :] = (
                jnp.sum(acc_ref[j], axis=0, keepdims=True) if j <= taps
                else jnp.zeros((1, width), jnp.float32))


def _packed(w, b):
    """``[w | b]`` as the kernels read it: [8, C] f32, a tap a row, then the
    bias's row (zeros without one), then zeros."""
    channels, taps = w.shape
    rows = [w.astype(jnp.float32).T,
            jnp.zeros((_TILE - taps, channels), jnp.float32)]
    if b is not None:
        rows[1] = rows[1].at[0].set(b.astype(jnp.float32))
    return jnp.concatenate(rows, axis=0)


def _specs(x, block):
    """The grid's extent over (row blocks, lane tiles) and the block specs of
    an ``x``-shaped operand in blocks of ``block`` = (rows, lanes), of the
    tile before one and of the tile after it (clamped at the sequence's ends,
    where the kernels mask them), and of ``[w | b]``; ``at(g)`` reads (batch,
    lane tile, row block) off a grid index."""
    _, seq, channels = x.shape
    rows, lanes = block
    halo = _sublanes(x.dtype.itemsize)
    per = rows // halo

    def make(at):
        def block(shape, index):
            return pl.BlockSpec(shape, lambda *g: index(*at(*g)))

        return (
            block((None, rows, lanes), lambda b, c, i: (b, i, c)),
            block((None, halo, lanes),
                  lambda b, c, i: (b, jnp.maximum(i * per - 1, 0), c)),
            block((None, halo, lanes),
                  lambda b, c, i: (b, jnp.minimum((i + 1) * per,
                                                  seq // halo - 1), c)),
            block((_TILE, lanes), lambda b, c, i: (0, c)))

    return (seq // rows, channels // lanes), make


def _vmem(blocks: int, scratch: int, rows: int, lanes: int, itemsize: int):
    """The kernels' VMEM limit: ``blocks`` double-buffered blocks of the
    operands' dtype and ``scratch`` f32 ones, and as much again."""
    return 2 * rows * lanes * (2 * blocks * itemsize + 4 * scratch) + 2 ** 22


def forward(x, w, b, *, interpret: bool):
    """``silu(taps(x) + b)`` [B, S, C] in ``x``'s dtype: one call, its grid
    (batch, lane tiles, row blocks)."""
    block = tiles(x.shape[1], x.shape[2], x.dtype.itemsize)
    return _forward(x, w, b, block, interpret)


# jitted so that a step's calls of one shape are traced and lowered once
# (Kimi's thirty-six: four seconds of its set-up otherwise); the block and
# ``interpret`` are static arguments, so the cache holds every choice made
@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(x, w, b, block, interpret):
    (n_rows, n_lanes), make = _specs(x, block)
    rows, lanes = block
    block, before, _, packed = make(lambda b, c, i: (b, c, i))
    return pl.pallas_call(
        functools.partial(_forward_kernel, taps=w.shape[-1]),
        grid=(x.shape[0], n_lanes, n_rows),
        in_specs=[before, block, packed], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_TILE + rows, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_vmem(2, 1, rows, lanes, x.dtype.itemsize)),
        interpret=interpret,
    )(x, x, _packed(w, b))


def backward(x, w, b, dy, *, interpret: bool):
    """(dx [B, S, C] in ``x``'s dtype, d[w | b] [8, C] f32) of ``forward``:
    one call, its grid (lane tiles, batch, row blocks), the sums of ``dw``
    and ``db`` carried in VMEM along the last two."""
    block = tiles(x.shape[1], x.shape[2], x.dtype.itemsize)
    return _backward(x, w, b, dy, block, interpret)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _backward(x, w, b, dy, block, interpret):
    (n_rows, n_lanes), make = _specs(x, block)
    rows, lanes = block
    block, before, after, packed = make(lambda c, b, i: (b, c, i))
    return pl.pallas_call(
        functools.partial(_backward_kernel, taps=w.shape[-1]),
        grid=(n_lanes, x.shape[0], n_rows),
        in_specs=[before, block, after, block, after, packed],
        out_specs=[block, packed],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((_TILE, x.shape[-1]), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((2 * _TILE + rows, lanes), jnp.float32),
            pltpu.VMEM((_TILE + rows, lanes), jnp.float32),
            pltpu.VMEM((_TILE, _TILE, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem(3, 2, rows, lanes, x.dtype.itemsize)),
        interpret=interpret,
    )(x, x, x, dy, dy, _packed(w, b))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_kernel(x, w, b, interpret):
    return forward(x, w, b, interpret=interpret)


def _conv_silu_kernel_fwd(x, w, b, interpret):
    return forward(x, w, b, interpret=interpret), (x, w, b)


def _conv_silu_kernel_bwd(interpret, res, dy):
    x, w, b = res
    taps = w.shape[-1]
    dx, dwb = backward(x, w, b, dy.astype(x.dtype), interpret=interpret)
    db = None if b is None else dwb[taps].astype(b.dtype)
    return dx, dwb[:taps].T.astype(w.dtype), db


_conv_silu_kernel.defvjp(_conv_silu_kernel_fwd, _conv_silu_kernel_bwd)


def conv_silu(x, w, b=None):
    """``silu`` of the depthwise causal convolution of ``x`` [B, S, C] with
    the filter ``w`` [C, taps] (zero left pad) plus the bias ``b`` [C] if
    there is one, in f32, the result in ``x``'s dtype, each sequence of the
    batch on its own. One rule in each direction as ``gated_short_conv``'s:
    only ``x``, ``w`` and ``b`` are kept, and the backward pass is the
    transposed taps, not autodiff's pads and slices of a concatenation.
    The XLA form, for a caller whose neighbours are XLA's
    (``models/phi4flash.py``'s Mamba-1 mixer; until PR 59 Granite's scan,
    whose einsums took the result in three layouts that XLA wrote from this
    function's own fusions; module docstring: what each form costs where)."""
    return _conv_silu(x, w, b)


def conv_silu_kernel(x, w, b=None):
    """``conv_silu`` for a caller whose neighbours are Mosaic calls
    (``models/kimi_linear.py``: q, k and v go on to the KDA kernels,
    row-major as a custom call writes them; ``models/blocks.py::mamba_block``:
    x, B and C go on to the scan's, ``ops/ssd_mosaic.py``): the Mosaic calls
    where ``path`` says the shapes take them (in interpret mode off the chip,
    ``ops/mosaic.py::interpret``), the XLA form elsewhere."""
    if path(x, w) == "kernel":
        return _conv_silu_kernel(x, w, b, mosaic.interpret())
    return _conv_silu(x, w, b)
