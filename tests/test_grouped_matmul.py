"""``ps_tpu/ops/grouped_matmul.py`` on the CPU, in interpret mode (the
kernels' own code), against ``jax.lax.ragged_dot`` and its autodiff."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_tpu.ops import grouped_matmul as gm

#: name -> (rows, k, n, group sizes, forced tiles or None for ``tiles(..)``)
CASES = {
    "even groups": (512, 256, 384, [128] * 4, None),
    "zipf-like groups": (640, 256, 256, [301, 147, 99, 51, 29, 13], (128, 256, 256)),
    "groups smaller than a tile": (256, 128, 256, [3, 5, 60, 1, 7, 90, 2, 88],
                                   (128, 128, 256)),
    "a boundary inside a tile": (512, 256, 128, [200, 312], (256, 256, 128)),
    "empty groups in the middle and at the end":
        (512, 256, 384, [100, 0, 300, 0, 0, 112, 0, 0], (128, 256, 384)),
    "every group but one empty": (256, 128, 128, [0, 0, 256, 0], None),
    "a padded last group": (768, 256, 128, [90, 0, 40, 638], (256, 256, 128)),
    "live rows short of the buffer": (768, 256, 128, [90, 0, 40, 100],
                                      (256, 256, 128)),
    "k and n that are no multiple of the tile":
        (384, 896, 200, [100, 184, 100], (128, 256, 128)),
    "rows that are no multiple of the tile":
        (500, 256, 896, [100, 0, 300, 100], (128, 128, 384)),
}


def operands(case, dtype, seed=0):
    m, k, n, sizes, tiling = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (m, k), dtype)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), dtype) * 0.1
    g = jax.random.normal(keys[2], (m, n), dtype)
    return lhs, rhs, g, jnp.asarray(sizes, jnp.int32), tiling


def close(got, want, dtype, scale=1.0):
    # bf16: both round an f32 accumulation once, in another order of sums
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol * scale, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gmm_and_both_gradients_are_ragged_dots(case, dtype):
    lhs, rhs, g, sizes, tiling = operands(case, dtype)
    live = int(jnp.sum(sizes))
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    got = gm._gmm(lhs, rhs, sizes, transpose_rhs=False, tiling=tiling)
    assert got.dtype == want.dtype and got.shape == want.shape
    close(got[:live], want[:live], dtype, scale=4.0)
    # the rows' cotangent: the stacks read transposed, none copied
    want = jax.vjp(lambda x: jax.lax.ragged_dot(x, rhs, sizes), lhs)[1](g)[0]
    got = gm._gmm(g, rhs, sizes, transpose_rhs=True,
                  tiling=tiling and (tiling[0], tiling[2], tiling[1]))
    assert got.dtype == want.dtype and got.shape == want.shape
    close(got[:live], want[:live], dtype, scale=4.0)
    want = jax.vjp(lambda w: jax.lax.ragged_dot(lhs, w, sizes), rhs)[1](g)[0]
    got = gm.tgmm(lhs, g, sizes, tiling=tiling)
    assert got.dtype == want.dtype and got.shape == want.shape
    close(got, want, dtype, scale=32.0)
    for e in np.flatnonzero(np.asarray(sizes) == 0):
        # exactly: no grid step computes there, the kernel writes the zeros
        assert not np.asarray(got[e], np.float32).any(), e


@pytest.mark.parametrize("case", ["zipf-like groups",
                                  "empty groups in the middle and at the end",
                                  "live rows short of the buffer"])
def test_the_custom_vjp_is_ragged_dots_autodiff(case):
    lhs, rhs, g, sizes, _ = operands(case, jnp.float32)
    live = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)

    def loss(product, lhs, rhs):
        out = jnp.where(live, product(lhs, rhs, sizes), 0)
        return jnp.sum(jnp.tanh(out) * g)

    want = jax.grad(loss, argnums=(1, 2))(jax.lax.ragged_dot, lhs, rhs)
    got = jax.jit(jax.grad(loss, argnums=(1, 2)), static_argnums=0)(
        gm.gmm, lhs, rhs)
    close(jnp.where(live, got[0], 0), jnp.where(live, want[0], 0),
          jnp.float32, scale=4.0)
    close(got[1], want[1], jnp.float32, scale=32.0)
    for e in np.flatnonzero(np.asarray(sizes) == 0):
        assert not np.asarray(got[1][e]).any(), e


def test_rows_of_no_group_never_reach_a_stacks_gradient():
    """What stands past the last group may be anything (the product before
    it left it unspecified): a NaN there is no NaN in ``tgmm``."""
    lhs, rhs, g, sizes, tiling = operands("live rows short of the buffer",
                                          jnp.float32)
    live = int(jnp.sum(sizes))
    dirty = gm.tgmm(lhs.at[live:].set(jnp.nan), g.at[live:].set(jnp.inf),
                    sizes, tiling=tiling)
    np.testing.assert_array_equal(np.asarray(dirty),
                                  np.asarray(gm.tgmm(lhs, g, sizes,
                                                     tiling=tiling)))


def test_the_walk_visits_a_tile_once_a_group_that_has_a_row_in_it():
    sizes = jnp.asarray([100, 0, 300, 0, 0, 112, 0, 0], jnp.int32)
    offsets, groups, row_tiles, live = gm._visits(sizes, 512, 128, False)
    assert offsets.tolist() == [0, 100, 100, 400, 400, 400, 512, 512, 512]
    assert int(live[0]) == 6
    assert groups.tolist()[:6] == [0, 2, 2, 2, 2, 5]
    assert row_tiles.tolist()[:6] == [0, 0, 1, 2, 3, 3]
    # the steps behind the live ones repeat the last: nothing is copied
    assert set(groups.tolist()[6:]) == {5} and set(
        row_tiles.tolist()[6:]) == {3}
    assert groups.shape == (4 + 8 - 1,)
    # tgmm's walk: the empty groups too, once each, in order
    _, groups, row_tiles, live = gm._visits(sizes, 512, 128, True)
    assert int(live[0]) == 11
    assert groups.tolist() == [0, 1, 2, 2, 2, 2, 3, 4, 5, 6, 7]
    assert row_tiles.tolist() == [0, 0, 0, 1, 2, 3, 3, 3, 3, 3, 3]


#: the six expert cells' grouped matmuls: rows, a row's width, an expert's
#: width, groups (``tools/gmm_table.py::CELLS``)
CELLS = {"mellum2-12b-a2.5b.s8192.b1.zipf.x4": (49152, 2304, 896, 16),
         "olmoe-1b-7b.s4096.zipf": (65536, 2048, 1024, 64),
         "lfm2-24b-a2b.s8192.zipf": (24576, 2048, 1536, 8),
         "trinity-mini.s16384.b1.zipf": (49152, 2048, 1024, 16),
         "nemotron-3-super-120b-a12b.s8192.b1.zipf": (8704, 1024, 2688, 8),
         "kimi-linear-48b-a3b.s8192.b1.zipf": (6144, 2304, 1024, 8)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tiles_hold_an_experts_whole_matrix_at_the_cells_shapes(cell):
    """But LFM2's, the largest (2,048 x 1,536: 27.5 MiB by the count), which
    is cut in two along its columns: whole, its calls' scope would push the
    cell's 96 MiB row buffer out of the VMEM XLA keeps it in. The depth is
    whole everywhere, so a group's block is read once a column tile."""
    m, d, f, groups = CELLS[cell]
    for k, n in ((d, f), (f, d)):
        tm, tk, tn = gm.tiles(m, k, n, groups, 2)
        assert tk == k
        assert tn == (n // 2 if cell.startswith("lfm2") else n)
        assert tm == gm._MAX_ROWS
        assert gm.vmem_bytes(tm, tk, tn, 2) <= gm._VMEM_BUDGET
        assert gm._vmem_limit(tm, tk, tn, 2) < 32 * 2 ** 20


def test_tiles_cut_a_matrix_that_does_not_fit_and_follow_the_groups():
    tm, tk, tn = gm.tiles(65536, 8192, 8192, 8, 2)
    assert 8192 % tk == 0 and 8192 % tn == 0 and tk % 128 == 0
    assert tk == 8192 and tn < 8192    # the columns are cut first
    assert gm.vmem_bytes(tm, tk, tn, 2) <= gm._VMEM_BUDGET
    # short groups, short tiles: a boundary tile is computed once a group
    assert gm.tiles(8192, 512, 512, 64, 2)[0] == 128
    assert gm.tiles(16384, 512, 512, 64, 2)[0] == 256
    assert gm.tiles(65536, 512, 512, 8, 2)[0] == gm._MAX_ROWS
    with pytest.raises(ValueError, match="no tile"):
        gm.tiles(4096, 2 ** 20 + 1, 2 ** 20 + 1, 8, 4)


def test_operands_that_do_not_fit_are_refused():
    rows, stacks = jnp.zeros((64, 32)), jnp.zeros((4, 48, 16))
    with pytest.raises(ValueError, match="gmm"):
        gm.gmm(rows, stacks, jnp.zeros(4, jnp.int32))
    with pytest.raises(ValueError, match="gmm"):
        gm.gmm(rows, jnp.zeros((4, 32, 16)), jnp.zeros(3, jnp.int32))
    with pytest.raises(ValueError, match="tgmm"):
        gm.tgmm(rows, jnp.zeros((32, 16)), jnp.zeros(4, jnp.int32))
