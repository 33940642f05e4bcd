"""The rotary rotation as one pass over its operand.

``models/blocks.py::rope``'s halves branch on ``x`` [.., S, d], angles
``a[s, i] = s * inv_freq[i]`` in f32::

    xf = x.astype(f32);  x1, x2 = split(xf, 2)
    y = (xf * [cos a, cos a] + [-x2, x1] * [sin a, sin a]).astype(x.dtype)

Element-wise work between a projection (or a head's norm) and the attention
kernel: the HBM's, not the MXU's. Written out in ``jax.numpy`` XLA:TPU does
not make one fusion of it. Compiled for a described v5e inside Ouro's step
(ISSUE 64; ``bf16[1, 8192, 16, 128]``, 33.6 MB a tensor), one layer
application has, for q and again for k:

| pass | what XLA writes (read again by the next op) | MB written |
|---|---|---|
| forward, and again in the recomputation | the projection's product as ``f32[1,8192,16,128]`` (``rope`` asks for ``x.astype(f32)``); ``-x2`` and ``x1`` as two ``f32[1,8192,16,64]`` (half of each 128-lane tile empty: 134 MB as tiled); the rotated ``bf16[16,8192,128]`` the flash call reads | 67 + 67 (134) + 34 |
| transposed (dq, dk) | ``dy * sin`` as ``f32[1,8192,16,128]``; its two halves ``f32[1,8192,16,64]``; the sum cast to ``bf16[1,8192,16,128]`` | 67 + 67 (134) + 34 |

against 34 MB read and 34 MB written if the rotation were one pass. A
``jnp.roll`` in place of the split and the concatenate changes nothing:
XLA:TPU lowers it to two slices and a concatenate and stores what feeds them.
In VMEM the same move is one rotation of the lanes (``pltpu.roll`` by
``d / 2``, which for ``d / 2`` is its own inverse).

**``rotate``** is that pass as a Mosaic call: ``x`` [B, h, S, d] **head-major**
(what ``ops/flash_attention.py`` hands its kernels, and the layout the
projection's product has on the chip, so the transposition in front of the
call is a bitcast), ``cos`` / ``sin`` [S, d] in f32 with ``rotate_half``'s
sign folded into ``sin`` (``[-sin a, +sin a]``, ``tables``). A grid step holds
``[heads, rows, d]`` of one sequence (``tiles``) and the ``[rows, d]`` of the
tables that go with those rows; the row blocks are the grid's slow axis and
the head blocks its fast one, so **a block of the tables is fetched once a
row block and not once a head** (at 2 x 4 B a channel of table against 2 B
of operand, a fetch a head would triple the call's bytes). The block is cast
to f32 in VMEM, rotated, multiplied and summed in f32 (``blocks.rope``'s
arithmetic, nothing rounded earlier: the same values to the bit in f32,
``tests/test_rope_kernel.py``) and cast once.

The rotation is orthogonal, so the cotangent is the same pass on ``dy``:
``dx = dy * cos + roll(dy * sin)``, the body's other order of the roll and
the product (``transposed``). A ``jax.custom_vjp`` whose only residuals are
the tables, which are the step's constants; the tables get no cotangent
(they are made from ``arange`` and ``theta``).

**``path``** says from the shapes alone where ``blocks.rope`` takes the
call: ``"kernel"`` where a head is one 128-lane tile, the sequence whole row
blocks and the halves are rotated; ``"plain"`` (the ``jax.numpy`` form, its
trace untouched) for heads of 64 (LFM2: half a tile a row, and a roll by 32
of 64 lanes is no lane rotation of a register), a rotated slice of 64
channels (Qwen3-Next), the interleaved pairs (``mla_block``: Kimi, JoyAI) and
every narrow width of the tests and rehearsals.

Under ``ps_tpu.init``'s mesh the call runs inside ``shard_map``, batch over
'data' and heads over 'model' wherever the axis exists and divides, the
tables replicated, as ``ops/flash_attention.py`` runs its own: GSPMD cannot
partition a Mosaic call.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 64).** A call alone, forty
chained in one program (``chiprun_out/pr64/rope_table.json``), ms forward /
transposed, beside the expression on the same head-major operand:

| ``x`` (bf16) | the expression | ``rotate``, blocks of (8 heads, 512 rows) |
|---|---|---|
| ``[1, 16, 8192, 128]`` (Ouro's q, k) | 0.790 | 0.056 / 0.064 (a chain of 34 MB arrays stays in the chip's fast memory, ``S(1)`` in the compiled layouts: the body's pace, 1,351 GB/s of the call's 75.5 MB, not the HBM's) |
| ``[1, 32, 16384, 128]`` (Trinity's q; SDAR's at ``[2, 32, 8192, 128]``) | 3.104 | 0.445 / 0.448 (641 GB/s of 285 MB: the HBM's) |
| ``[1, 4, 16384, 128]`` (their k) | 0.117 | 0.047 / 0.049 |
| ``[2, 16, 4096, 128]`` (OLMoE's q, k) | 0.789 | 0.054 / 0.064 |

Blocks from (4 heads, 256 rows) to (4, 2048) and (16, 512) read 0.444-0.453
ms on the large operand (0.498 at 128 Ki elements) and 0.052-0.075 on
Ouro's: ``_ROWS`` and ``_BLOCK`` are within 8% of the best in every row.
Inside the cells' steps (traced, parent and change in one call) the
rotation's calls take 17.9 ms a step in Ouro's (192 calls; the step 1279.7
-> 1176.9 ms), 3.6 in Trinity's (16; 552.4 -> 532.4), 0.36 in OLMoE's (4;
110.2 -> 107.1); ``PERF.md`` section 6 (PR 64) has the pairs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ps_tpu.ops import mosaic
from ps_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

#: lanes of a register: the head width the kernel takes
_LANES = 128
#: rows of one grid step's block and of the tables' block beside it
_ROWS = 512
#: elements of one grid step's block of ``x``: heads x rows x lanes
_BLOCK = 512 * 1024


def path(x, interleaved: bool = False) -> str:
    """Which realisation ``models/blocks.py::rope`` takes for ``x``
    [B, S, h, d]: ``"kernel"`` (``rotate``) where a head is one 128-lane
    tile, the sequence is whole blocks of ``_ROWS`` rows and the halves are
    rotated against each other, else ``"plain"`` (the ``jax.numpy`` form).
    Read from the shapes alone."""
    whole = x.shape[-1] == _LANES and x.shape[1] % _ROWS == 0
    return "kernel" if whole and not interleaved else "plain"


def tables(angles, scale=None):
    """``cos``, ``sin`` [S, d] in f32 for ``rotate`` from ``angles``
    [S, d / 2]: the halves' tables side by side, ``rotate_half``'s sign in
    ``sin``, both times ``scale`` where one is given."""
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)
    sin = jnp.sin(angles)
    sin = jnp.concatenate([-sin, sin], -1)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    return cos, sin


def tiles(heads: int, seq: int):
    """(heads, rows) of one grid step's block: ``_ROWS`` rows (a shorter
    sequence whole) of as many heads as divide ``heads`` and keep the block
    to ``_BLOCK`` elements."""
    rows = min(_ROWS, seq)
    fit = [n for n in range(1, heads + 1)
           if heads % n == 0 and n * rows * _LANES <= _BLOCK]
    return max(fit), rows


def _body(x_ref, cos_ref, sin_ref, out_ref, *, transposed: bool):
    x = x_ref[...].astype(jnp.float32)          # [heads, rows, d]
    cos, sin = cos_ref[...], sin_ref[...]       # [rows, d]
    half = x.shape[-1] // 2
    if transposed:
        y = x * cos + pltpu.roll(x * sin, half, 2)
    else:
        y = x * cos + pltpu.roll(x, half, 2) * sin
    out_ref[...] = y.astype(out_ref.dtype)


def _call(x, cos, sin, transposed: bool, interpret: bool):
    batch, heads, seq, dim = x.shape
    block, rows = tiles(heads, seq)
    operand = pl.BlockSpec((None, block, rows, dim),
                           lambda b, r, h: (b, h, r, 0))
    table = pl.BlockSpec((rows, dim), lambda b, r, h: (r, 0))
    return pl.pallas_call(
        functools.partial(_body, transposed=transposed),
        grid=(batch, seq // rows, heads // block),
        in_specs=[operand, table, table], out_specs=operand,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="rope_transposed" if transposed else "rope",
        interpret=interpret)(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x, cos, sin, interpret):
    return _call(x, cos, sin, False, interpret)


def _rotate_fwd(x, cos, sin, interpret):
    return _call(x, cos, sin, False, interpret), (cos, sin)


def _rotate_bwd(interpret, kept, dy):
    return _call(dy, *kept, True, interpret), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def rotate(x, cos, sin):
    """``x`` [B, h, S, d] head-major with ``d`` one 128-lane tile, ``cos`` and
    ``sin`` [S, d] in f32 as ``tables`` makes them -> ``x`` rotated, in
    ``x``'s dtype: ``x * cos + roll(x, d / 2) * sin`` in f32, one read of
    ``x`` and one write. ``S`` must be whole blocks of ``tiles``' rows, and
    those whole tiles of ``x``'s dtype. Off the chip the kernel runs in
    interpret mode (``ops/mosaic.py::interpret``); under ``ps_tpu.init``'s
    mesh inside ``shard_map``."""
    batch, heads, seq, dim = x.shape
    rows = tiles(heads, seq)[1]
    if dim != _LANES or seq % rows or rows % (32 // x.dtype.itemsize):
        raise ValueError(
            f"rotate: {x.dtype}{list(x.shape)}: heads of {_LANES} channels "
            f"and a sequence of whole blocks of {rows} rows, themselves "
            f"whole tiles")
    if cos.shape != (seq, dim) or sin.shape != (seq, dim):
        raise ValueError(f"rotate: tables {cos.shape}, {sin.shape} for "
                         f"{seq} positions of {dim} channels")
    interpret = mosaic.interpret()

    def run(x, cos, sin):
        return _rotate(x, cos, sin, interpret)

    from ps_tpu import api

    mesh = api.current_context().mesh if api.is_initialized() else None
    if mesh is None:
        return run(x, cos, sin)

    def axis(name, n):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and n % size == 0 else None

    spec = P(axis(DATA_AXIS, batch), axis(MODEL_AXIS, heads), None, None)
    # check_vma off for the reason flash_attention gives
    return shard_map(run, mesh=mesh, in_specs=(spec, P(), P()),
                     out_specs=spec, check_vma=False)(x, cos, sin)
