"""``ops/gated_conv.py::conv_silu``'s two realisations: the XLA form against
the spelling it replaced (``causal_taps`` on ``shift``, to the bit), the
Mosaic calls in interpret mode against that form and against a
token-by-token loop, and the function that says which shapes take them. The block of a grid step
is made small here (``_BLOCK``, ``_LANES``: the test steers them, the
program has no option) so that toy shapes span several row blocks and lane
tiles; nothing at a cell's shape runs here (``tests/test_chip_compile.py``
compiles those for the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_tpu.ops import gated_conv
from ps_tpu.ops.gated_conv import (causal_taps, conv_silu, conv_silu_kernel,
                                   path, shift, tiles)

#: batch, sequence, channels, taps, bias, dtype, (block elements, lanes at
#: most) -> the (rows, lanes) of a grid step they give
CASES = {
    "128 channels, four row blocks": (
        (1, 64, 128, 4, True, jnp.float32, (16 * 128, 512)), (16, 128)),
    "no bias": ((1, 64, 128, 4, False, jnp.float32, (16 * 128, 512)),
                (16, 128)),
    "three taps, 384 channels in one tile": (
        (1, 48, 384, 3, True, jnp.float32, (16 * 384, 512)), (16, 384)),
    "384 channels in three tiles": (
        (1, 32, 384, 4, False, jnp.float32, (16 * 128, 256)), (16, 128)),
    "1,280 channels in five tiles, bf16": (
        (1, 64, 1280, 4, True, jnp.bfloat16, (32 * 256, 512)), (32, 256)),
    "two sequences, bf16, no bias": (
        (2, 64, 256, 4, False, jnp.bfloat16, (16 * 256, 512)), (16, 256)),
    "two sequences of one block each": (
        (2, 32, 128, 4, True, jnp.float32, (32 * 128, 512)), (32, 128)),
}


def _plain(x, w, b):
    z = causal_taps(x.astype(jnp.float32), w)
    return jax.nn.silu(z if b is None else z + b).astype(x.dtype)


def _by_token(x, w, b, rows: int):
    """The first ``rows`` outputs of each sequence, one token at a time."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    taps = w.shape[1]
    z = np.zeros((x.shape[0], rows, x.shape[2]))
    for t in range(rows):
        for j in range(taps):
            if t - (taps - 1 - j) >= 0:
                z[:, t] += w[:, j] * x[:, t - (taps - 1 - j)]
    z += 0.0 if b is None else np.asarray(b, np.float64)
    return z / (1 + np.exp(-z))


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_plain_form(case, monkeypatch):
    """Value, ``dx``, ``dw`` and ``db`` of the two Mosaic calls in interpret
    mode against the plain form's (f32: to 1e-5 of the largest entry, the
    sums of ``dw`` and ``db`` being taken block by block; bf16: to one
    rounding of the result); the halo crosses every block's edge; a
    sequence's first rows read nothing of the sequence before it."""
    (batch, seq, channels, taps, bias, dtype, (block, lanes)), want = \
        CASES[case]
    monkeypatch.setattr(gated_conv, "_BLOCK", block)
    monkeypatch.setattr(gated_conv, "_LANES", lanes)
    rng = np.random.default_rng(len(case))
    x = jnp.asarray(rng.normal(size=(batch, seq, channels)), dtype)
    w = jnp.asarray(0.5 * rng.normal(size=(channels, taps)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(channels,)), jnp.float32) if bias \
        else None
    dy = jnp.asarray(rng.normal(size=x.shape), dtype)
    assert path(x, w) == "kernel"
    assert tiles(seq, channels, x.dtype.itemsize) == want

    y, vjp = jax.vjp(conv_silu_kernel, x, w, b)
    ref, ref_vjp = jax.vjp(_plain, x, w, b)
    assert y.dtype == x.dtype and y.shape == x.shape
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    names = ("y", "dx", "dw", "db")
    got_all = (y, *vjp(dy))
    want_all = (ref, *ref_vjp(dy))
    for name, got, ref_one in zip(names, got_all, want_all):
        if ref_one is None:
            assert got is None, name
            continue
        assert got.dtype == ref_one.dtype and got.shape == ref_one.shape
        ref_one = np.asarray(ref_one, np.float32)
        loose = tol if name in ("y", "dx") else 1e-5
        np.testing.assert_allclose(np.asarray(got, np.float32), ref_one,
                                   atol=loose * np.abs(ref_one).max(),
                                   rtol=0, err_msg=name)
    first = _by_token(x, w, b, 3)
    np.testing.assert_allclose(np.asarray(y[:, :3], np.float64), first,
                               atol=tol * np.abs(first).max(), rtol=0)


def test_a_sequences_first_rows_read_nothing_of_the_one_before(monkeypatch):
    """Batch 2: the second sequence's outputs are what it gives alone, to
    the bit, whatever stands in the first (whose last rows are the halo block
    the index map would reach without its clamp and the kernel's mask)."""
    monkeypatch.setattr(gated_conv, "_BLOCK", 16 * 128)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 48, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 4)), jnp.float32)
    alone = conv_silu_kernel(x[1:], w)
    for other in (x[0], 1e6 * jnp.ones_like(x[0])):
        both = conv_silu_kernel(jnp.stack([other, x[1]]), w)
        np.testing.assert_array_equal(np.asarray(both[1]),
                                      np.asarray(alone[0]))


@pytest.mark.parametrize("shape, taps, dtype, want", [
    ((1, 8192, 4352), 4, jnp.bfloat16, "kernel"),   # Granite's x, B and C
    ((1, 8192, 4096), 4, jnp.bfloat16, "kernel"),   # Kimi's q, k and v
    ((1, 8192, 1280), 4, jnp.bfloat16, "kernel"),   # Nemotron-H's share
    ((2, 128, 96), 4, jnp.float32, "plain"),        # lanes not whole
    ((2, 4100, 128), 4, jnp.float32, "plain"),      # rows not whole
    ((2, 128, 256), 4, jnp.float32, "plain"),       # under one block
    ((1, 8192, 4352), 8, jnp.bfloat16, "plain"),    # taps and bias > a tile
], ids=["granite", "kimi", "nemotron", "96-channels", "4100-rows",
        "under-a-block", "eight-taps"])
def test_the_shapes_alone_choose_the_realisation(shape, taps, dtype, want):
    x = jax.ShapeDtypeStruct(shape, dtype)
    w = jax.ShapeDtypeStruct((shape[-1], taps), jnp.float32)
    assert path(x, w) == want
    if want == "kernel":
        rows, lanes = tiles(shape[1], shape[2], x.dtype.itemsize)
        assert shape[1] % rows == 0 and shape[2] % lanes == 0
        assert lanes % 128 == 0 and rows * lanes <= gated_conv._BLOCK


@pytest.mark.parametrize("shape, form, want", [
    ((2, 24, 96), conv_silu_kernel, "plain"),   # a toy under the kernels' name
    ((1, 4096, 128), conv_silu, "kernel"),      # a kernel's shape, and a
], ids=["toy", "no-word"])                      # caller of the XLA form's
def test_the_xla_form_is_what_the_others_take(shape, form, want):
    """No Mosaic call in the trace, value or gradient."""
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    w = jax.ShapeDtypeStruct((shape[-1], 4), jnp.float32)
    assert path(x, w) == want
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(form(x, w)), argnums=(0, 1)))(x, w)
    assert "pallas_call" not in str(jaxpr)


def _old_spelling(x, w, b):
    """``conv_silu``'s two rules as they stood before PR 57: ``causal_taps``
    and ``shift`` on ``x`` cast to f32 first."""
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    z = causal_taps(xf, wf)
    z = z if b is None else z + b.astype(jnp.float32)

    def backward(dy):
        gate = jax.nn.sigmoid(z)
        dz = dy.astype(jnp.float32) * gate * (1 + z * (1 - gate))
        taps = w.shape[-1]
        dw = jnp.stack([jnp.sum(dz * shift(xf, taps - 1 - j), axis=(0, 1))
                        for j in range(taps)], axis=-1)
        db = None if b is None else jnp.sum(dz, axis=(0, 1))
        return causal_taps(dz, wf, -1).astype(x.dtype), dw, db

    return jax.nn.silu(z).astype(x.dtype), backward


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("taps, bias", [(4, True), (4, False), (3, True)],
                         ids=["four-taps", "no-bias", "three-taps"])
def test_the_xla_form_is_the_spelling_it_replaced_to_the_bit(taps, bias,
                                                             dtype):
    """One copy of ``x`` padded in its own dtype and cut by static slices
    (what XLA:TPU fuses) gives, value and all three gradients, what
    ``concatenate(zeros, u[:, :-by])`` of the f32 cast gave: the cast of a
    zero is a zero and the taps are summed in the same order."""
    rng = np.random.default_rng(taps)
    x = jnp.asarray(rng.normal(size=(2, 40, 96)), dtype)
    w = jnp.asarray(rng.normal(size=(96, taps)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(96,)), jnp.float32) if bias else None
    dy = jnp.asarray(rng.normal(size=x.shape), dtype)
    y, vjp = jax.vjp(conv_silu, x, w, b)
    want, backward = _old_spelling(x, w, b)
    for got, ref in zip((y, *vjp(dy)), (want, *backward(dy))):
        if ref is None:
            assert got is None
            continue
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))
