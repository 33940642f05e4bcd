"""``ops/rope.py``: the rotary rotation as one Mosaic pass, interpreted on the
CPU, held to ``models/blocks.py::rope``'s ``jax.numpy`` expression: values,
cotangents, and where ``path`` sends each cell's call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_tpu.models import blocks
from ps_tpu.ops import rope as rotary

THETA = 1e6
SHAPES = [(1, 256, 4, 128), (2, 512, 16, 128)]
#: no table: ``theta``'s frequencies; Mellum's: a table of its own (YaRN's
#: blend falls as this ramp does) and cos / sin times ``attention_factor``
TABLES = {"theta": {},
          "mellum": {"inv_freq": 10000.0 ** -np.linspace(0, 1.3, 64),
                     "scale": 1.2772588722239782}}


def _operand(shape, dtype, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _rope(route, **kw):
    """``blocks.rope`` with ``path`` answering ``route`` whatever the shape,
    jitted: the kernel's rotation (``rotate`` on the head-major view, its
    tables from ``tables``) or the plain expression. Jitted both, so that
    XLA's CPU backend contracts the two products and the sum alike."""
    def run(x):
        saved = rotary.path
        rotary.path = lambda x, interleaved=False: route
        try:
            return blocks.rope(x, THETA, **{
                k: jnp.asarray(v, jnp.float32) if k == "inv_freq" else v
                for k, v in kw.items()})
        finally:
            rotary.path = saved

    return jax.jit(run)


def _assert_same(got, want, dtype):
    """To the bit in f32; within one bf16 ulp of the larger in bf16."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got, want)
    else:
        room = 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))
        assert np.all(np.abs(got - want) <= room)


@pytest.mark.parametrize("tables", sorted(TABLES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", SHAPES, ids=["256x4", "2x512x16"])
def test_the_kernel_is_the_plain_rotation(shape, dtype, tables):
    """``rotate`` on the head-major view gives ``blocks.rope``'s expression,
    [B, S, h, d] in and out, under ``theta``'s frequencies and under a table
    and a factor as Mellum's YaRN hands them."""
    x = _operand(shape, dtype)
    got = _rope("kernel", **TABLES[tables])(x)
    assert got.shape == x.shape and got.dtype == x.dtype
    _assert_same(got, _rope("plain", **TABLES[tables])(x), dtype)


@pytest.mark.parametrize("tables", sorted(TABLES))
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", SHAPES, ids=["256x4", "2x512x16"])
def test_the_kernels_cotangent_is_the_plain_rotations(shape, dtype, tables):
    """``jax.vjp`` through the ``custom_vjp`` (the same pass on ``dy``, the
    roll behind the product) against ``jax.vjp`` of the expression."""
    x, dy = _operand(shape, dtype), _operand(shape, dtype, seed=1)

    def cotangent(route):
        rope = _rope(route, **TABLES[tables])
        return jax.jit(lambda x, dy: jax.vjp(rope, x)[1](dy)[0])(x, dy)

    got = cotangent("kernel")
    assert got.shape == x.shape and got.dtype == x.dtype
    _assert_same(got, cotangent("plain"), dtype)


def test_a_rotation_keeps_every_pairs_length_and_the_tables_take_nothing():
    """The rotation is orthogonal (what lets the cotangent be the same pass):
    each pair ``(j, j + d / 2)`` keeps its length. The tables are the step's
    constants: the rule hands them no cotangent."""
    x = _operand((1, 4, 256, 128), jnp.float32)
    angles = jnp.arange(256, dtype=jnp.float32)[:, None] * (
        THETA ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))[None]
    cos, sin = rotary.tables(angles)
    y = rotary.rotate(x, cos, sin)

    def lengths(t):
        return np.asarray(t[..., :64] ** 2 + t[..., 64:] ** 2)

    np.testing.assert_allclose(lengths(y), lengths(x), rtol=1e-5, atol=1e-6)
    dcos, dsin = jax.grad(lambda c, s: jnp.sum(rotary.rotate(x, c, s)),
                          (0, 1))(cos, sin)
    assert not np.any(np.asarray(dcos)) and not np.any(np.asarray(dsin))


#: every call of ``blocks.rope`` in the benchmark's cells: [B, S, h, d] as the
#: model hands it (a four-chip cell's at the global batch), interleaved or not
CALLS = {
    "ouro-2.6b.s8192.b1.zipf q, k": ((1, 8192, 16, 128), False, "kernel"),
    "olmoe-1b-7b.s4096.zipf q, k": ((2, 4096, 16, 128), False, "kernel"),
    "trinity-mini.s16384.b1.zipf windowed q":
        ((1, 16384, 32, 128), False, "kernel"),
    "trinity-mini.s16384.b1.zipf windowed k":
        ((1, 16384, 4, 128), False, "kernel"),
    "sdar-30b-a3b.s8192.b1.zipf.bd4 q": ((2, 8192, 32, 128), False, "kernel"),
    "sdar-30b-a3b.s8192.b1.zipf.bd4 k": ((2, 8192, 4, 128), False, "kernel"),
    "mellum2-12b-a2.5b.s8192.b1.zipf.x4 q":
        ((4, 8192, 32, 128), False, "kernel"),
    "mellum2-12b-a2.5b.s8192.b1.zipf.x4 k":
        ((4, 8192, 4, 128), False, "kernel"),
    "lfm2-24b-a2b.s8192.zipf q (heads of 64)":
        ((2, 8192, 32, 64), False, "plain"),
    "lfm2-24b-a2b.s8192.zipf k": ((2, 8192, 8, 64), False, "plain"),
    "qwen3-next-80b-a3b.s8192.b1.zipf q (64 of 256 channels)":
        ((1, 8192, 16, 64), False, "plain"),
    "qwen3-next-80b-a3b.s8192.b1.zipf k": ((1, 8192, 2, 64), False, "plain"),
    "kimi-linear-48b-a3b.s8192.b1.zipf q_pe (interleaved)":
        ((1, 8192, 32, 64), True, "plain"),
    "joyai-llm-flash.s8192.b1.zipf k_pe (interleaved)":
        ((1, 8192, 1, 64), True, "plain"),
    "interleaved pairs on a whole tile": ((1, 8192, 16, 128), True, "plain"),
    "the rehearsals' and the tests' heads of 16":
        ((2, 4096, 2, 16), False, "plain"),
    "a sequence that is no whole row block":
        ((1, 8192 + 128, 16, 128), False, "plain"),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_path_at_every_cells_call(call):
    """Where the mechanism engages, read from the shapes alone."""
    shape, interleaved, want = CALLS[call]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert rotary.path(x, interleaved) == want
    if want == "kernel":
        heads, rows = rotary.tiles(shape[2], shape[1])
        assert shape[2] % heads == 0 and shape[1] % rows == 0


def test_blocks_rope_takes_the_kernel_by_the_shape_alone_and_the_plain_trace_stands():
    """At a cell's kind of shape ``blocks.rope``'s trace holds the Mosaic call
    and no split; at a narrow head it is the expression it always was, with
    or without the arguments at their defaults."""
    wide = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda x: blocks.rope(x, THETA))(wide))
    assert "pallas_call" in text and "split" not in text
    narrow = jax.ShapeDtypeStruct((1, 512, 2, 64), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda x: blocks.rope(x, THETA))(narrow))
    assert "pallas_call" not in text and "split" in text
    pairs = str(jax.make_jaxpr(
        lambda x: blocks.rope(x, THETA, interleaved=True))(wide))
    assert "pallas_call" not in pairs


def test_rotate_refuses_what_it_cannot_tile():
    cos = sin = jnp.zeros((256, 128), jnp.float32)
    with pytest.raises(ValueError, match="heads of 128"):
        rotary.rotate(jnp.zeros((1, 2, 256, 64), jnp.bfloat16),
                      cos[:, :64], sin[:, :64])
    with pytest.raises(ValueError, match="whole blocks"):
        rotary.rotate(jnp.zeros((1, 2, 8192 + 128, 128), jnp.bfloat16),
                      cos, sin)
    with pytest.raises(ValueError, match="tables"):
        rotary.rotate(jnp.zeros((1, 2, 512, 128), jnp.bfloat16), cos, sin)


def test_under_a_mesh_the_kernel_runs_sharded_and_agrees():
    """GSPMD cannot partition a Mosaic call: under ``ps.init``'s mesh the
    rotation and its cotangent run in ``shard_map``, batch over 'data' and
    heads over 'model' where they divide, replicated where they do not."""
    import ps_tpu as ps

    x, dy = (_operand((2, 512, 4, 128), jnp.bfloat16, seed)
             for seed in (0, 1))

    def value_and_cotangent():
        y, back = jax.vjp(lambda x: blocks.rope(x, THETA), x)
        return y, back(dy)[0]

    assert rotary.path(x) == "kernel"
    want = jax.jit(value_and_cotangent)()
    for mesh_shape in ({"data": 2, "model": 4}, {"data": 8}):
        ps.init(backend="tpu", mesh_shape=mesh_shape)  # 8 cannot divide B
        try:
            got = jax.jit(value_and_cotangent)()
        finally:
            ps.shutdown()
        for a, b in zip(got, want):
            _assert_same(a, b, jnp.float32)
