"""Mamba-1's selective scan as Mosaic calls (``ps_tpu/ops/
selective_scan_mosaic.py``, through ``ops/selective_scan.py::selective_scan``
where ``path`` says ``"kernel"``), the kernels' own bodies interpreted on the
CPU: values and all six gradients against the XLA form and against the
recurrence written out token by token, at a length the tile of tokens does not
divide, two sequences in a batch; what ``path`` takes and what it leaves as
it was.

Tolerances. In f32 both sides run the same recurrence in the same order; the
kernels take ``exp2`` of ``dt (A log2 e)`` where the others take ``exp`` of
``dt A``, and sum the states and the tokens in another order: 2e-5 of the
largest entry, ``tests/test_selective_scan.py``'s (seen: 1e-6). With ``x``,
``B`` and ``C`` in bf16 their three gradients leave in bf16: half a unit in
the last of eight bits, 4e-3.
"""

import jax
import jax.numpy as jnp
import pytest

from jaxpr_tools import equations, primitives
from ps_tpu.ops import selective_scan as module
from ps_tpu.ops import selective_scan_mosaic
from ps_tpu.ops.selective_scan import path, selective_scan
from test_selective_scan import TOL, _inputs, _rel, token_by_token

NAMES = ("x", "dt", "A", "B", "C", "D")
#: 300 tokens are a tile of 256 and 44 of the next; two sequences; two lane
#: tiles of channels in f32 and one in bf16
SHAPES = {"float32": dict(seq=300, channels=256, state=16),
          "bfloat16": dict(seq=300, channels=128, state=16)}


def _operands(dtype):
    x, dt, a, b, c, d, weights = _inputs(**SHAPES[dtype])
    low = jnp.dtype(dtype)
    return (x.astype(low), dt, a, b.astype(low), c.astype(low), d), weights


def _xla(*o):
    return module._xla(*o[:5], module.CHUNK, module.UNROLL) \
        + o[5] * o[0].astype(jnp.float32)


def _reference(*o):
    return token_by_token(*(t.astype(jnp.float32) for t in o))


@pytest.fixture(scope="module", params=sorted(SHAPES))
def results(request):
    """(dtype, {form: (y, its six gradients)}): every form once a dtype."""
    operands, weights = _operands(request.param)
    assert path(operands[0], operands[2]) == "kernel"

    def both(form):
        def weighed(*o):
            y = form(*o)
            return jnp.sum(weights * y), y

        (_, y), gradients = jax.value_and_grad(
            weighed, argnums=range(6), has_aux=True)(*operands)
        return y, gradients

    return request.param, {
        name: both(form)
        for name, form in (("kernel", selective_scan), ("xla", _xla),
                           ("token by token", _reference))}


@pytest.mark.parametrize("against", ["xla", "token by token"])
def test_the_kernels_values_are_the_recurrences(results, against):
    _, forms = results
    got, want = forms["kernel"][0], forms[against][0]
    assert got.dtype == jnp.float32 and got.shape == want.shape
    assert _rel(got, want) <= TOL


@pytest.mark.parametrize("against", ["xla", "token by token"])
@pytest.mark.parametrize("operand", range(6), ids=NAMES)
def test_the_kernels_gradients_are_the_recurrences(results, against,
                                                   operand):
    dtype, forms = results
    got, want = forms["kernel"][1][operand], forms[against][1][operand]
    assert got.shape == want.shape and got.dtype == want.dtype
    rounded = dtype == "bfloat16" and got.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), want.astype(jnp.float32)) <= (
        4e-3 if rounded else TOL)


@pytest.mark.parametrize("decay", [1e-3, 40.0])
def test_no_decay_is_too_weak_or_too_strong_for_the_kernels(decay):
    """A decay of ``exp(-40)`` a token, or none to speak of, over a tile of
    128 tokens: the kernels form no quotient and no cumulated product of
    decays either, so nothing overflows and nothing is lost; the gradients
    stay finite."""
    (x, dt, a, b, c, d), weights = _operands("float32")
    a = -decay * jnp.ones_like(a)
    got, transposed = jax.vjp(selective_scan, x, dt, a, b, c, d)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, token_by_token(x, dt, a, b, c, d)) <= TOL
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in transposed(weights))


def test_every_sequence_starts_from_a_zero_state(results):
    """The second sequence of the batch gives behind the first what the
    recurrence gives it alone: the state's scratch is zeroed at a sequence's
    first tile."""
    dtype, forms = results
    operands, _ = _operands(dtype)
    alone = _reference(*(t[1:] if t.ndim == 3 else t for t in operands))
    assert _rel(forms["kernel"][0][1:], alone) <= TOL


@pytest.mark.parametrize("channels,state,dtype,want", [
    (5120, 16, jnp.bfloat16, "kernel"), (256, 16, jnp.float32, "kernel"),
    (384, 8, jnp.float32, "kernel"), (128, 64, jnp.bfloat16, "kernel"),
    (24, 4, jnp.float32, "xla"), (200, 16, jnp.float32, "xla"),
    (256, 12, jnp.float32, "xla"), (256, 4, jnp.float32, "xla"),
    (256, 128, jnp.float32, "xla"), (256, 16, jnp.float16, "xla")],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_path_is_read_from_the_shapes(channels, state, dtype, want):
    x = jax.ShapeDtypeStruct((1, 64, channels), dtype)
    assert path(x, jax.ShapeDtypeStruct((channels, state), jnp.float32)) \
        == want


def test_a_shape_the_kernels_refuse_is_traced_as_it_was():
    """24 channels on 4 states: ``selective_scan``'s jaxpr is the XLA form's
    and the skip's, equation for equation, and holds no Mosaic call."""
    *operands, _ = _inputs(64)
    got = jax.make_jaxpr(selective_scan)(*operands)
    assert "pallas_call" not in primitives(got)
    assert str(got) == str(jax.make_jaxpr(_xla)(*operands))


def test_one_state_a_tile_lives_between_the_kernels_passes():
    """What the gradient's trace keeps from the forward call beside the
    operands: the state that entered each tile of 256 tokens,
    [tiles, B, N, C]. Two Mosaic calls, no loop over tokens outside them."""
    (x, dt, a, b, c, d), weights = _operands("float32")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(weights * selective_scan(*o)),
        argnums=range(6)))(x, dt, a, b, c, d)
    found = primitives(jaxpr)
    assert "scan" not in found and "while" not in found
    calls = [eqn for eqn in equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 2
    batch, seq, channels = x.shape
    tiles = -(-seq // selective_scan_mosaic.TILE)
    assert [v.aval.shape for v in calls[0].outvars] == [
        (batch, tiles * selective_scan_mosaic.TILE, channels),
        (tiles, batch, a.shape[1], channels)]
