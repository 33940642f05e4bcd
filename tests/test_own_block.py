"""SDAR's own-block term as Mosaic calls (``ps_tpu/ops/own_block_mosaic.py``,
through ``ops/own_block.py::own_block`` where ``path`` says ``"kernel"``), the
kernels' own bodies interpreted on the CPU: the value and the five gradients
(q, k, v, ``earlier``, ``lse``) against the XLA form and against one dense f32
softmax over the earlier keys' logsumexp and the tile's scores under the
explicit block-diagonal mask; blocks of 4, 32 and 128, groups of 8, 4 and 1
query heads a K/V head, two sequences of two tiles; what ``path`` takes and
what it leaves as it was.

Every case holds rows of three kinds beside the ordinary ones: the first
block's (``lse`` = -1e30 and ``earlier`` = 0, as the strict flash call leaves
a row that saw no key), rows whose earlier keys outweigh the own block by
``exp(30)`` and rows whose own block outweighs them by as much.

Tolerances, as shares of the reference's largest entry. In f32 both sides
compute the same sums in another order: 2e-5 (seen: 1.3e-6). With bf16
operands the output and four of the gradients leave in bf16 on both sides,
each rounded on its own: one unit in the last of eight bits, 8e-3 (seen:
3.5e-3); ``lse``'s gradient is f32 on both sides and held to 2e-5 against the
XLA form, which rounds the output's cotangent to bf16 as the kernels do.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import equations, primitives
from ps_tpu.ops import own_block as module
from ps_tpu.ops import own_block_mosaic
from ps_tpu.ops.own_block import own_block, path

NAMES = ("q", "k", "v", "earlier", "lse")
TOL, ROUNDED = 2e-5, 8e-3
BATCH, SEQ, DIM = 2, 256, 128
#: dtype, query heads a K/V head, block, heads a trip of the kernels' loop
#: (None: the module's): the cell's group of 8 at its block of 4; a group of
#: four walked two heads a trip; one query head a K/V head on two K/V heads
CASES = {"f32-group8-block4": ("float32", 8, 4, None),
         "f32-group4-block32-trips": ("float32", 4, 32, 2),
         "f32-group1-block128": ("float32", 1, 128, None),
         "bf16-group8-block32": ("bfloat16", 8, 32, None),
         "bf16-group1-block4": ("bfloat16", 1, 4, None)}


def _operands(dtype, group, block):
    rng = np.random.default_rng(block + group)
    kv_heads = 2 if group == 1 else 1
    heads = kv_heads * group

    def normal(*shape, to=dtype):
        return jnp.asarray(rng.normal(0, 1, shape), to)

    q, earlier = (normal(BATCH, SEQ, heads, DIM) for _ in range(2))
    k, v = (normal(BATCH, SEQ, kv_heads, DIM) for _ in range(2))
    lse = normal(BATCH, SEQ, heads, to=jnp.float32)
    # the first block saw no earlier key; the second tile's first block is
    # far outweighed by its earlier keys, its second far outweighs them
    lse = lse.at[:, :block].set(-1e30)
    earlier = earlier.at[:, :block].set(0)
    lse = lse.at[:, 128:128 + block].add(30.0)
    lse = lse.at[:, 192:192 + block].add(-30.0)
    weights = normal(BATCH, SEQ, heads, DIM, to=jnp.float32)
    return (q, k, v, earlier, lse), weights


def _dense(q, k, v, earlier, lse, block):
    """One softmax a query over [the earlier keys together, at ``lse``; the
    sequence's keys under the explicit block-diagonal mask], all in f32."""
    q, k, v, earlier = (t.astype(jnp.float32) for t in (q, k, v, earlier))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    of = jnp.arange(q.shape[1]) // block
    own = of[:, None] == of[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * (q.shape[-1] ** -0.5)
    logits = jnp.concatenate([jnp.transpose(lse, (0, 2, 1))[..., None],
                              jnp.where(own, s, -jnp.inf)], axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    return (jnp.transpose(p[..., :1], (0, 2, 1, 3)) * earlier
            + jnp.einsum("bhqk,bkhd->bqhd", p[..., 1:], v,
                         precision="highest"))


@pytest.fixture(scope="module", params=sorted(CASES))
def results(request):
    """(dtype, {form: (the output, its five gradients)}): every form once a
    case."""
    dtype, group, block, trip = CASES[request.param]
    operands, weights = _operands(dtype, group, block)
    assert path(operands[0], operands[1], block) == "kernel"

    def both(form):
        def weighed(*o):
            y = form(*o, block)
            return jnp.sum(weights * y.astype(jnp.float32)), y

        (_, y), gradients = jax.jit(jax.value_and_grad(
            weighed, argnums=range(5), has_aux=True))(*operands)
        return y, gradients

    ours = own_block_mosaic._TRIP
    own_block_mosaic._TRIP = trip or ours
    try:
        kernel = both(own_block)
    finally:
        own_block_mosaic._TRIP = ours
    return dtype, {"kernel": kernel, "xla": both(module._xla),
                   "dense": both(_dense)}


def _rel(got, want):
    got, want = (t.astype(jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("against", ["xla", "dense"])
def test_the_kernels_values_are_the_merged_softmax(results, against):
    dtype, forms = results
    got, want = forms["kernel"][0], forms[against][0]
    assert got.dtype == jnp.dtype(dtype) and got.shape == want.shape
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    assert _rel(got, want) <= (TOL if dtype == "float32" else ROUNDED)


@pytest.mark.parametrize("against", ["xla", "dense"])
@pytest.mark.parametrize("operand", range(5), ids=NAMES)
def test_the_kernels_gradients_are_the_merged_softmaxs(results, against,
                                                       operand):
    dtype, forms = results
    got, want = forms["kernel"][1][operand], forms[against][1][operand]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    # the dense form's output is f32, so its cotangent is not rounded to bf16
    # on the way in as the other two's is: ``lse``'s gradient follows it
    rounded = got.dtype == jnp.bfloat16 or (
        dtype == "bfloat16" and against == "dense")
    assert _rel(got, want) <= (ROUNDED if rounded else TOL)


def test_the_first_block_gets_its_own_softmax_alone(results):
    """A row whose ``lse`` is -1e30 takes nothing of ``earlier`` and gives
    neither ``earlier`` nor ``lse`` a gradient, whatever stands there."""
    _, forms = results
    _, (_, _, _, dearlier, dlse) = forms["kernel"]
    block = 4  # every case's first four rows lie in its first block
    assert not np.any(np.asarray(dearlier[:, :block].astype(jnp.float32)))
    assert not np.any(np.asarray(dlse[:, :block]))


@pytest.mark.parametrize("shape,kv_heads,block,dtype,want", [
    ((1, 8192, 32, 128), 4, 4, jnp.bfloat16, "kernel"),
    ((2, 256, 8, 128), 8, 128, jnp.float32, "kernel"),
    ((1, 128, 4, 256), 2, 32, jnp.bfloat16, "kernel"),
    ((1, 128, 4, 64), 2, 4, jnp.bfloat16, "xla"),      # half a lane tile
    ((2, 256, 4, 32), 2, 4, jnp.float32, "xla"),       # tests/test_sdar.py
    ((1, 192, 4, 128), 2, 4, jnp.bfloat16, "xla"),     # a tile and a half
    ((1, 512, 4, 128), 2, 256, jnp.bfloat16, "xla"),   # a block of two tiles
    ((1, 384, 4, 128), 2, 48, jnp.bfloat16, "xla"),    # straddles a tile
    ((1, 128, 4, 128), 2, 4, jnp.float16, "xla")],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_path_is_read_from_the_shapes(shape, kv_heads, block, dtype,
                                          want):
    q = jax.ShapeDtypeStruct(shape, dtype)
    k = jax.ShapeDtypeStruct((*shape[:2], kv_heads, shape[3]), dtype)
    assert path(q, k, block) == want


def test_operands_of_two_dtypes_take_the_xla_form():
    q = jax.ShapeDtypeStruct((1, 128, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 128, 2, 128), jnp.float32)
    assert path(q, k, 4) == "xla"


def test_a_mesh_of_several_chips_takes_the_xla_form(monkeypatch):
    """GSPMD cannot partition a Mosaic call and this one runs under no
    ``shard_map``: ``path`` reads the context's mesh, as the selective
    scan's."""
    from types import SimpleNamespace

    from ps_tpu import api

    q = jax.ShapeDtypeStruct((1, 128, 4, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 128, 2, 128), jnp.bfloat16)
    for chips, want in ((4, "xla"), (1, "kernel")):
        monkeypatch.setattr(api, "_context", SimpleNamespace(
            mesh=SimpleNamespace(size=chips)))
        assert path(q, k, 4) == want


def test_a_shape_the_kernels_refuse_is_traced_as_it_was():
    """Heads of 32 channels (``tests/test_sdar.py``'s): ``own_block``'s jaxpr
    is the XLA form's, equation for equation, and holds no Mosaic call."""
    rng = np.random.default_rng(0)
    q, k, v, earlier = (jnp.asarray(rng.normal(0, 1, (1, 128, heads, 32)),
                                    jnp.float32) for heads in (4, 2, 2, 4))
    lse = jnp.zeros((1, 128, 4), jnp.float32)
    got = jax.make_jaxpr(lambda *o: own_block(*o, 4))(q, k, v, earlier, lse)
    assert "pallas_call" not in primitives(got)
    assert str(got) == str(jax.make_jaxpr(lambda *o: module._xla(*o, 4))(
        q, k, v, earlier, lse))


def test_the_gradient_is_two_calls_that_keep_the_operands_alone():
    """The gradient's trace: one forward call and one backward call, no other
    product, and nothing [.., 128, 128] or f32 of q's size between them: the
    backward call reads the five operands and the output's cotangent."""
    (q, k, v, earlier, lse), weights = _operands("bfloat16", 8, 4)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *o: jnp.sum(weights * own_block(*o, 4).astype(jnp.float32)),
        argnums=range(5)))(q, k, v, earlier, lse)
    found = primitives(jaxpr)
    assert "dot_general" not in found and "while" not in found
    calls = [eqn for eqn in equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert [eqn.params["name"] for eqn in calls] == [
        "own_block_forward", "own_block_backward"]
    head_major = (BATCH, 1, 8, SEQ, DIM)
    assert [v.aval.shape for v in calls[1].invars] == [
        head_major, (BATCH, 1, SEQ, DIM), (BATCH, 1, SEQ, DIM), head_major,
        (BATCH, 1, 8, SEQ), head_major]
    assert [(v.aval.shape, str(v.aval.dtype)) for v in calls[1].outvars] == [
        (head_major, "bfloat16"), ((BATCH, 1, SEQ, DIM), "bfloat16"),
        ((BATCH, 1, SEQ, DIM), "bfloat16"), (head_major, "bfloat16"),
        ((BATCH, 1, 8, SEQ), "float32")]
