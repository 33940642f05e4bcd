"""Grouped matmul as Pallas TPU kernels: the three products of an expert
layer and their gradients (``ops/moe.py::expert_ffn``).

``gmm(lhs [M, K], rhs [E, K, N], group_sizes [E]) -> [M, N]``: rows
``offsets[e] .. offsets[e + 1]`` of ``lhs`` times ``rhs[e]``, the groups
contiguous and in order (``jax.lax.ragged_dot``'s contract, and until PR 47
its call: XLA:TPU tiles that 512 x 512 x 512, re-reads the stacks once a
row tile and sits at the chip's ridge). Rows past ``sum(group_sizes)`` come
out unspecified.

Design:

- THE WALK (``_visits``). The grid's middle axis does not count row tiles, it
  counts visits: a row tile is visited once for each group that has a row in
  it, groups in order, so a tile that a boundary cuts is visited once a side,
  under a row mask; tiles wholly inside a group, which is most of them, take
  a path without the mask. The groups' offsets and each visit's group and row
  tile are scalar-prefetch operands, computed by XLA from ``group_sizes``
  before the call; the index maps read them. The grid is the most visits
  there can be (row tiles + groups - 1); the visits past the live ones repeat
  the last one's block indices, so they copy nothing, and compute nothing.
  A row tile past the last group is never visited: a call's time follows the
  rows it is handed in groups, not the buffer.
- ``gmm``: grid (column tiles, visits, depth tiles), depth innermost, f32
  accumulation (in VMEM scratch where the depth is cut, else the product is
  rounded and written as it comes). A stack's block index changes only where
  the group does, so a matrix that fits is read once a group. The rows'
  cotangent is the same kernel with the stacks read transposed: the index map
  swaps the block's axes and the product contracts the last axis of both
  (``transpose_rhs``), no ``[E, N, K]`` copy in HBM.
- ``tgmm``, the stacks' cotangent: grid (column tiles, depth tiles, visits),
  visits innermost; ``lhs[rows].T @ g[rows]`` contracts the row axis of both
  tiles into an f32 ``[tk, tn]`` accumulator, zeroed at a group's first visit
  and written at its last. A group with no row is visited once all the same,
  for the zeroing and the write: **its gradient is exact zeros**, not what
  the buffer held. A boundary tile masks both sides with ``where``: a row of
  no group may hold anything (what ``gmm`` left past its last group), and
  0 x NaN is NaN.
- THE TILES (``tiles``), from the operands' shapes alone. ``tk``, ``tn``: an
  expert's whole ``[k, n]`` matrix where a grid step then fits
  ``_VMEM_BUDGET`` by ``vmem_bytes``' count (25 MiB: 2,304 x 896, 2,048 x
  1,024, 2,304 x 1,024 and 1,024 x 2,688 do, at 18.9 to 24.6 MiB, and their
  transposes), else the widest divisors that fit, the columns cut before the
  depth: a column tile walks all the visits before the next, so a group's
  block is still read once, and the rows twice; a depth tile would bring a
  group's blocks in turn at every row tile. LFM2's 2,048 x 1,536 (27.5 MiB)
  is cut so, into two column tiles of 768. With the matrix resident a row
  tile moves ``tm * (k + n)`` elements for ``2 * tm * k * n`` FLOPs, 645
  FLOP/B at Mellum's shape against the chip's ridge of 240.
  ``tm``: the power of two under the mean rows a group, between 128 and 256.
  256 is the table's: at every shape it is as fast as 512 or faster, by 3-5%
  where groups are long (less of a boundary tile is thrown away, a shorter
  first copy before the first product) and by 10-40% where they are short
  (OLMoE's 64 groups, Kimi's 2,048 live rows on 8), and 128 gains nothing
  on it.
- THE SCOPE a call asks Mosaic for is its own count and a quarter more
  (``_vmem_limit``), 18 to 31 MiB at the cells' shapes; the 16 MiB Mosaic
  scopes by default is under two buffers of one 2,048 x 1,024 matrix, its
  accumulator and a row tile. Not a fixed large one: XLA keeps arrays of the
  program around a call in the VMEM the calls leave it, and what a call
  scopes it cannot use. With 96 MiB asked for everywhere, LFM2's 96 MiB row
  buffer lost its place there and that cell's gathers took 10 ms a step
  more than the grouped matmuls returned (``PERF.md`` section 6, PR 47).
- GRADIENT: ``gmm``'s ``custom_vjp`` keeps rows, stacks and sizes, what
  autodiff of ``ragged_dot`` kept, and returns the cotangents in the
  operands' dtypes. Same arithmetic as before in other tiles: operands in
  their dtype on the MXU, f32 accumulation, one rounding.
- Off the chip the calls run in interpret mode (``ops/mosaic.py::interpret``,
  asked while ``_gmm`` and ``tgmm`` are traced: their ``jax.jit`` keys the
  device it reads beside the shapes), so the CPU tests run the kernels' own
  code (``tests/test_grouped_matmul.py``, against ``ragged_dot`` and its
  autodiff); ``tests/test_chip_compile.py`` compiles all three for a
  described v5e at the six cells' shapes. ``_gmm`` and
  ``tgmm`` are jitted so that a model's layers trace and lower each shape
  once (interpret mode's cost on the CPU is the trace); the call keeps the
  scope it is made under (``.../ps.moe/expert/jit(_gmm)/gmm/pallas_call``).
- Under a mesh the calls must be inside ``shard_map`` (Mosaic kernels are not
  partitioned automatically), as Mellum's expert layer is; on one chip there
  is nothing to partition.

**Measured (TPU v5e, jax 0.9.0 / libtpu 0.0.34; my chip runs, PR 47,
``tools/gmm_table.py --rows 128,256`` at seed 0 with the row tile forced, the
matrix whole and 96 MiB scoped; bf16; one call's device time = (a chain of 9
calls in one program - 1 call) / 8, median of 5)**: ms and share of the
MXU's 197 TFLOP/s on the rows handed in groups. ``ragged_dot``'s columns are
the call itself and its autodiff's two. Group sizes: 'padded' a third of the
rows live on Zipf(1) sizes, the last group taking the zero rows behind them
(``expected_rows``); 'live' the same with the rows behind in no group (8,192
of 24,576 and 2,048 of 6,144 rows are computed); 'zipf' every row live.
The first line of a pair is into the expert (gate, up), the second out of it
(down). LFM2's rows at the tiles ``tiles(..)`` now gives it, two column
tiles, stand under the table::

                                   ragged_dot    tm=128      tm=256      tm=512
    Mellum 49,152 x (2,304, 896) x 16 padded
      forward                      4.35  24%   1.32  78%   1.24  83%   1.30  79%
      rows' gradient               3.66  28%   1.32  78%   1.27  81%   1.32  78%
      stacks' gradient             5.30  19%   1.40  73%   1.32  78%   1.35  76%
      forward    (896, 2,304)      3.61  29%   1.32  78%   1.27  81%   1.29  80%
      rows' gradient               4.42  23%   1.33  77%   1.24  83%   1.29  80%
      stacks' gradient             5.06  20%   1.40  74%   1.33  78%   1.37  75%
    OLMoE 65,536 x (2,048, 1,024) x 64 zipf
      forward                      2.91  48%   2.19  64%   1.92  73%   2.18  64%
      rows' gradient               3.17  44%   2.21  63%   2.00  70%   2.19  64%
      stacks' gradient             3.25  43%   2.34  60%   2.14  65%   2.37  59%
      forward    (1,024, 2,048)    3.16  44%   2.20  64%   2.02  69%   2.19  64%
      rows' gradient               2.93  48%   2.14  65%   1.95  72%   2.19  64%
      stacks' gradient             3.27  43%   2.34  60%   2.16  65%   2.36  59%
    LFM2 24,576 x (2,048, 1,536) x 8 live
      forward                      0.50  53%   0.41  64%   0.40  65%   0.45  58%
      rows' gradient               0.49  53%   0.39  67%   0.39  66%   0.45  58%
      stacks' gradient             0.55  48%   0.44  60%   0.43  61%   0.50  53%
      forward    (1,536, 2,048)    0.51  51%   0.41  64%   0.40  66%   0.46  57%
      rows' gradient               0.51  51%   0.38  68%   0.38  69%   0.45  59%
      stacks' gradient             0.54  49%   0.43  60%   0.44  60%   0.49  54%
    Trinity 49,152 x (2,048, 1,024) x 16 padded
      forward                      1.56  67%   1.33  79%   1.27  82%   1.32  79%
      rows' gradient               1.70  61%   1.34  78%   1.27  82%   1.32  79%
      stacks' gradient             1.66  63%   1.39  75%   1.34  78%   1.36  77%
      forward    (1,024, 2,048)    1.65  63%   1.34  78%   1.28  82%   1.33  79%
      rows' gradient               1.62  65%   1.34  78%   1.28  82%   1.32  79%
      stacks' gradient             1.69  62%   1.40  75%   1.32  79%   1.37  77%
    Nemotron 8,704 x (1,024, 2,688) x 8 padded
      forward                      0.67  36%   0.38  65%   0.35  69%   0.37  65%
      rows' gradient               0.60  41%   0.35  70%   0.35  69%   0.40  62%
      stacks' gradient             0.85  29%   0.39  62%   0.38  63%   0.44  55%
      forward    (2,688, 1,024)    0.61  40%   0.35  69%   0.35  69%   0.39  62%
      rows' gradient               0.67  36%   0.37  67%   0.38  64%   0.39  62%
      stacks' gradient             0.77  32%   0.39  63%   0.40  62%   0.43  57%
    Kimi 6,144 x (2,304, 1,024) x 8 live
      forward                      0.21  24%   0.15  33%   0.13  38%   0.19  26%
      rows' gradient               0.21  23%   0.13  37%   0.13  39%   0.18  28%
      stacks' gradient             0.26  19%   0.15  32%   0.15  34%   0.20  25%
      forward    (1,024, 2,304)    0.22  23%   0.14  36%   0.12  41%   0.17  29%
      rows' gradient               0.19  26%   0.15  32%   0.13  39%   0.16  31%
      stacks' gradient             0.24  20%   0.15  33%   0.14  34%   0.20  25%
    LFM2 at the tiles it has now, (256, 2,048, 768) and (256, 1,536, 1,024):
      forward                      0.48  54%               0.40  66%
      rows' gradient               0.50  52%               0.38  68%
      stacks' gradient             0.53  49%               0.42  62%
      forward    (1,536, 2,048)    0.48  55%               0.39  67%
      rows' gradient               0.49  53%               0.37  70%
      stacks' gradient             0.53  49%               0.43  61%

``ragged_dot`` is slower at every shape and every pass, Kimi's 256 rows a
group included, so no shape keeps it. What holds the short shapes under the
long ones' 80%: a group's first row tile waits for its matrix (4-6 MB, 5-7 us
at the HBM's peak against 5 us of products a tile of 256), which 64 groups
of 1,024 rows pay once in four tiles and 16 groups of 3,072 hardly at all;
and each call computes the walk from the sizes first, a few small fusions of
XLA's (the chain includes them), which weigh on a call of 0.13 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ps_tpu.ops import mosaic

# What one grid step may hold in VMEM by ``vmem_bytes``' count. A call asks
# Mosaic for its own count and a quarter more (``_vmem_limit``: Mosaic's
# temporaries, the f32 product before it is rounded, the boundary tiles'
# masks), not for a fixed large scope: XLA keeps arrays of the program around
# the call in the VMEM the calls leave it (LFM2's ``bf16[24576, 2048]`` row
# buffer, 96 MiB of the v5e's 128, lives there), and a call that scopes 96
# MiB evicts them (PERF.md section 6, PR 47: 10 ms a step of that cell's
# gathers). Mosaic's default scope, 16 MiB, is under two buffers of one
# 2,048 x 1,024 matrix, its accumulator and a row tile.
_VMEM_BUDGET = 25 * 2 ** 20


#: the rows of a tile: no shorter (the MXU's width), no longer
_MIN_ROWS = 128
_MAX_ROWS = 256


def _lanes(width: int) -> int:
    return pl.cdiv(width, 128) * 128


def vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM one grid step keeps live, the larger of the two kernels': the
    blocks double-buffered by the pipeline and the f32 accumulator. ``gmm``
    holds a ``[tm, tk]`` row tile, a ``[tk, tn]`` block of a stack and a
    ``[tm, tn]`` output with its accumulator; ``tgmm`` two row tiles
    ``[tm, tk]`` and ``[tm, tn]`` and a ``[tk, tn]`` output with its
    accumulator."""
    rows = 2 * tm * (_lanes(tk) + _lanes(tn)) * itemsize
    stack = 2 * tk * _lanes(tn) * itemsize
    return rows + stack + 4 * max(tm, tk) * _lanes(tn)


def _vmem_limit(tm: int, tk: int, tn: int, itemsize: int) -> int:
    return vmem_bytes(tm, tk, tn, itemsize) * 5 // 4


def _divisors(size: int):
    """``size`` itself, then its divisors that are multiples of 128, widest
    first."""
    return [size] + [b for b in range(size - size % 128, 0, -128)
                     if b < size and size % b == 0]


def tiles(m: int, k: int, n: int, groups: int, itemsize: int):
    """``(tm, tk, tn)`` of both kernels, from the operands' shapes alone.
    ``tk``, ``tn``: the whole ``[k, n]`` matrix of a group where a grid step
    then fits ``_VMEM_BUDGET``, so that a stack is read once a group and not
    once a row tile; else the widest divisors (multiples of 128) that fit,
    the columns cut first: a column tile walks all the rows before the next
    and reads each group's block once, where a depth tile would bring a
    group's blocks in turn at every row tile. ``tm``: the power of two
    under the mean rows a group, between ``_MIN_ROWS`` and ``_MAX_ROWS``: a
    tile that a boundary cuts is computed once a group it holds, so tiles as
    long as the groups would double the work."""
    mean = max(m // max(groups, 1), 1)
    tm = min(max(2 ** (mean.bit_length() - 1), _MIN_ROWS), _MAX_ROWS)
    tm = min(tm, pl.cdiv(m, 16) * 16)
    for tk in _divisors(k):
        for tn in _divisors(n):
            if vmem_bytes(tm, tk, tn, itemsize) <= _VMEM_BUDGET:
                return tm, tk, tn
    raise ValueError(f"no tile of a [{k}, {n}] matrix fits {_VMEM_BUDGET} B "
                     f"of VMEM beside {tm} rows, itemsize {itemsize}")


def _visits(group_sizes, m: int, tm: int, empty_groups: bool):
    """The grid's walk over row tiles and groups, as scalar-prefetch
    operands: the groups' row offsets ``[E + 1]``, and for each of the
    ``tiles_m + E - 1`` grid steps its group and its row tile, and ``[1]``
    how many of the steps are live. A row tile is visited once a group that
    has a row in it, groups in order; ``empty_groups``: a group with no row
    is visited once all the same (``tgmm`` writes its zeros then). The
    steps past the live ones repeat the last one's blocks, so they copy
    nothing."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tiles_m = pl.cdiv(m, tm)
    first = jnp.minimum(starts // tm, tiles_m - 1)
    count = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1,
                      1 if empty_groups else 0)
    upto = jnp.cumsum(count)
    live = upto[-1]
    steps = tiles_m + sizes.shape[0] - 1
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                       jnp.maximum(live - 1, 0))
    # how many groups end their visits at or before this step: one fusion,
    # where a binary search is a loop of XLA's in front of every call
    group = jnp.minimum(
        jnp.sum(step[:, None] >= upto[None, :], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1)
    tile = jnp.take(first, group) + step - jnp.take(upto - count, group)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, group, jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32),
            live.reshape(1).astype(jnp.int32))


def _rows_of_group(offsets, group, tile, tm: int):
    """Whether the group holds the whole row tile, and ``mask(width)``, the
    ``[tm, width]`` mask of the tile's rows that are the group's."""
    start, end = offsets[group], offsets[group + 1]
    first = tile * tm
    whole = (start <= first) & (first + tm <= end)

    def mask(width: int):
        row = first + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
        return (row >= start) & (row < end)

    return whole, mask


def _depth_mask(x, axis: int, live):
    """``x`` with everything past ``live`` along ``axis`` zeroed: the last
    depth block's overhang, which holds whatever the copy brought."""
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(at < live, x, jnp.zeros_like(x))


def _gmm_kernel(offsets, groups, row_tiles, live, lhs_ref, rhs_ref, out_ref,
                *acc, tm: int, k: int, tk: int, transpose_rhs: bool):
    step, depth = pl.program_id(1), pl.program_id(2)
    depths = pl.cdiv(k, tk)

    def product():
        lhs, rhs = lhs_ref[...], rhs_ref[...]
        if k % tk:
            lhs = _depth_mask(lhs, 1, k - depth * tk)
            rhs = _depth_mask(rhs, 1 if transpose_rhs else 0, k - depth * tk)
        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        return jax.lax.dot_general(lhs, rhs, contract,
                                   preferred_element_type=jnp.float32)

    def write(value):
        whole, mask = _rows_of_group(offsets, groups[step], row_tiles[step],
                                     tm)

        @pl.when(whole)
        def _inside():
            out_ref[...] = value().astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _boundary():
            # the other groups' rows of this tile: theirs stay, or come
            out_ref[...] = jnp.where(
                mask(out_ref.shape[-1]), value(),
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    @pl.when(step < live[0])
    def _live():
        if depths == 1:
            write(product)
            return
        acc_ref, = acc

        @pl.when(depth == 0)
        def _first():
            acc_ref[...] = product()

        @pl.when(depth > 0)
        def _more():
            acc_ref[...] += product()

        @pl.when(depth == depths - 1)
        def _last():
            write(lambda: acc_ref[...])


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tiling"))
def _gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool, tiling=None):
    """``gmm`` without its gradient; ``transpose_rhs``: ``rhs`` is
    ``[E, N, K]`` and is read transposed by the index map and contracted on
    its last axis, no ``[E, K, N]`` copy in HBM."""
    m, k = lhs.shape
    e, n = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    if rhs.shape[2 if transpose_rhs else 1] != k or group_sizes.shape != (e,):
        raise ValueError(f"gmm: rows {lhs.shape} on stacks {rhs.shape}"
                         f"{' transposed' if transpose_rhs else ''} in "
                         f"groups {group_sizes.shape}")
    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    tm, tk, tn = tiling or tiles(m, k, n, e, dtype.itemsize)
    depths = pl.cdiv(k, tk)
    scalars = _visits(group_sizes, m, tm, empty_groups=False)

    def rows(j, s, d, offsets, groups, row_tiles, live):
        return row_tiles[s], d

    def stack(j, s, d, offsets, groups, row_tiles, live):
        return (groups[s], j, d) if transpose_rhs else (groups[s], d, j)

    def out(j, s, d, offsets, groups, row_tiles, live):
        return row_tiles[s], j

    kernel = functools.partial(_gmm_kernel, tm=tm, k=k, tk=tk,
                               transpose_rhs=transpose_rhs)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), scalars[1].shape[0], depths),
            in_specs=[
                pl.BlockSpec((tm, tk), rows),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), stack)],
            out_specs=pl.BlockSpec((tm, tn), out),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if depths > 1 else [])),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, tk, tn, dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + e * k * n + m * n)),
        interpret=mosaic.interpret(),
        name="gmm_transposed" if transpose_rhs else "gmm",
    )(*scalars, lhs, rhs)


def _tgmm_kernel(offsets, groups, row_tiles, live, lhs_ref, g_ref, out_ref,
                 acc_ref, *, tm: int):
    step, steps = pl.program_id(2), pl.num_programs(2)
    group = groups[step]
    first = (step == 0) | (groups[jnp.maximum(step - 1, 0)] != group)
    last = (step == steps - 1) | (groups[jnp.minimum(step + 1, steps - 1)]
                                  != group)

    @pl.when(first)
    def _zero():
        # a group with no row is visited for this and for the write below
        acc_ref[...] = jnp.zeros_like(acc_ref)

    whole, mask = _rows_of_group(offsets, group, row_tiles[step], tm)
    some = (step < live[0]) & (offsets[group + 1] > offsets[group])

    def add(lhs, g):
        acc_ref[...] += jax.lax.dot_general(
            lhs, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(some & whole)
    def _inside():
        add(lhs_ref[...], g_ref[...])

    @pl.when(some & jnp.logical_not(whole))
    def _boundary():
        # both sides: a row of no group may hold anything, 0 x NaN
        def mine(ref):
            return jnp.where(mask(ref.shape[-1]), ref[...],
                             jnp.zeros_like(ref))

        add(mine(lhs_ref), mine(g_ref))

    @pl.when(last)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiling",))
def tgmm(lhs, g, group_sizes, *, tiling=None):
    """``lhs [M, K]``, ``g [M, N]`` -> ``[E, K, N]``: for each group the
    transposed product of its rows, ``lhs[rows].T @ g[rows]``, accumulated
    in f32 over the group's row tiles; the stacks' cotangent of ``gmm``. A
    group with no row gets exact zeros: every output block is written."""
    (m, k), n = lhs.shape, g.shape[1]
    e, = group_sizes.shape
    if g.shape[0] != m:
        raise ValueError(f"tgmm: rows {lhs.shape} against {g.shape}")
    dtype = jnp.result_type(lhs.dtype, g.dtype)
    lhs, g = lhs.astype(dtype), g.astype(dtype)
    tm, tk, tn = tiling or tiles(m, k, n, e, dtype.itemsize)
    scalars = _visits(group_sizes, m, tm, empty_groups=True)

    def left(j, d, s, offsets, groups, row_tiles, live):
        return row_tiles[s], d

    def right(j, d, s, offsets, groups, row_tiles, live):
        return row_tiles[s], j

    def out(j, d, s, offsets, groups, row_tiles, live):
        return groups[s], d, j

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((e, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), scalars[1].shape[0]),
            in_specs=[pl.BlockSpec((tm, tk), left),
                      pl.BlockSpec((tm, tn), right)],
            out_specs=pl.BlockSpec((None, tk, tn), out),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(tm, tk, tn, dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + m * n * pl.cdiv(k, tk) + e * k * n)),
        interpret=mosaic.interpret(),
        name="tgmm",
    )(*scalars, lhs, g)


@jax.custom_vjp
def gmm(lhs, rhs, group_sizes):
    """``lhs [M, K]`` in ``E`` contiguous groups of rows, each times its
    matrix of ``rhs [E, K, N]``: ``[M, N]`` in the operands' dtype, f32
    accumulation. Rows past ``sum(group_sizes)`` come out unspecified, and
    so does their cotangent."""
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False)


def _gmm_fwd(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _gmm(g, rhs, group_sizes, transpose_rhs=True)
    d_rhs = tgmm(lhs, g, group_sizes)
    return d_lhs.astype(lhs.dtype), d_rhs.astype(rhs.dtype), None


gmm.defvjp(_gmm_fwd, _gmm_bwd)
