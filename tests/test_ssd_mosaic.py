"""The state-space scan's Mosaic calls (``ps_tpu/ops/ssd_mosaic.py``, in
interpret mode on the CPU: the kernels' own code) behind ``ops/ssd.py::ssd``,
against the token-by-token recurrence of the plain reference
(``benchmark/families/nemotron_h_reference.py::selective_scan``) and against
the XLA form that stays for the shapes the kernels do not take.

Tolerances. In f32 both sides differ in the order of their sums and in where
the cumulated log-decays round (1e-5 of the largest entry for the values, ten
times that for the gradients, as ``tests/test_nemotron_h.py`` holds the XLA
form, and thirty times for ``dA``, four numbers that are each a sum over
every token of terms that cancel: 1.0e-4 seen on one seed of five where the
XLA form shows 2.2e-5, under 1.1e-5 on the others); in bf16 the products' operands are rounded, 5% of the largest entry
(``test_ssd_is_causal_and_keeps_bf16_in_bf16_out``'s).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from benchmark.families import nemotron_h_reference as reference
from ps_tpu.ops import ssd as ssd_module
from ps_tpu.ops import ssd_mosaic
from ps_tpu.ops.ssd import ssd

F32_TOL, BF16_TOL = 1e-5, 0.05
NAMES = "x dt A B C".split()
#: a gradient's tolerance over its value's
LOOSER = {"x": 10, "dt": 10, "A": 30, "B": 10, "C": 10}
#: (heads, groups, P, N, chunk, dtype, batch, tokens, takes the kernels)
CASES = {
    # Granite-4.0-H-Micro's whole mixer: eight blocks of eight heads, two of
    # the kernels' chunks in the configuration's one
    "granite": (64, 1, 64, 128, 256, jnp.bfloat16, 1, 256, True),
    # Nemotron-H's share (two blocks of eight heads), two sequences
    "nemotron": (16, 1, 64, 128, 128, jnp.bfloat16, 2, 256, True),
    "f32": (4, 1, 64, 128, 128, jnp.float32, 2, 384, True),
    "f32_wide_heads": (2, 1, 128, 128, 256, jnp.float32, 1, 256, True),
    "f32_one_block_of_six": (6, 1, 64, 256, 128, jnp.float32, 1, 256, True),
    # what the kernels leave to the XLA form
    "two_groups": (4, 2, 64, 128, 128, jnp.float32, 2, 256, False),
    "rehearse": (4, 1, 16, 16, 32, jnp.float32, 2, 128, False),
    "short_chunk": (4, 1, 64, 128, 64, jnp.float32, 1, 128, False)}


def _rel(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _inputs(heads, groups, width, state, dtype, batch, seq, seed=0,
            steps=(1e-3, 1e-1)):
    """Steps and rates in the range the configurations initialise them to
    (``time_step`` 0.001 .. 0.1, ``A`` -1 .. -16)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, seq, heads, width))
    dt = rng.uniform(*steps, size=(batch, seq, heads))
    a = -rng.uniform(1.0, 16.0, size=(heads,))
    b, c = (rng.normal(size=(batch, seq, groups, state)) for _ in range(2))
    return [jnp.asarray(t, d) for t, d in zip(
        (x, dt, a, b, c), (dtype, jnp.float32, jnp.float32, dtype, dtype))]


def _recurrence(x, dt, a, b, c):
    per_group = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(t, per_group, axis=2) for t in (b, c))
    return jax.vmap(reference.selective_scan, in_axes=(0, 0, None, 0, 0))(
        *(t.astype(jnp.float32) for t in (x, dt)), a,
        *(t.astype(jnp.float32) for t in (b, c)))


def _value_and_grads(f, args, weights):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weights),
        argnums=range(5)))(*args)


@pytest.mark.parametrize("case", CASES)
def test_the_scan_is_the_recurrence_and_the_xla_form(case):
    """Value and all five gradients (B's and C's summed over the group's
    heads inside the call), whichever realisation the shapes take."""
    *sizes, chunk, dtype, batch, seq, kernel = CASES[case]
    args = _inputs(*sizes, dtype, batch, seq)
    assert ssd_mosaic.takes(args[0], args[3], chunk) is kernel
    tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=args[0].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ssd(*args, chunk=chunk)
        assert got.shape == args[0].shape and got.dtype == dtype
        _, grads = _value_and_grads(functools.partial(ssd, chunk=chunk),
                                    args, weights)
        for other in (_recurrence,
                      functools.partial(ssd_module._ssd_plain, chunk=chunk)):
            assert _rel(got, other(*args)) <= tol
            _, wanted = _value_and_grads(other, args, weights)
            for name, g, w, arg in zip(NAMES, grads, wanted, args):
                assert g.shape == arg.shape and g.dtype == arg.dtype, name
                assert _rel(g, w) <= LOOSER[name] * tol, (name, _rel(g, w))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_strong_decays_over_a_whole_chunk_stay_finite(dtype):
    """``exp(A_log)`` 16 at a step of 0.1 loses 205 nats over one of the
    kernels' chunks and 410 over the configuration's: the exponents above the
    diagonal pass 88 and are masked before they are taken, so the value and
    the five gradients are finite, and the recurrence's."""
    x, dt, a, b, c = _inputs(2, 1, 64, 128, dtype, 1, 256, seed=3)
    dt, a = jnp.full_like(dt, 0.1), jnp.full_like(a, -16.0)
    gap = -np.cumsum(np.asarray(dt[0, :, 0] * a[0]))
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(gap.astype(np.float32))).any()
    weights = jnp.ones(x.shape, jnp.float32)
    args = (x, dt, a, b, c)
    with jax.default_matmul_precision("highest"):
        (value, grads), (want, wanted) = (
            _value_and_grads(f, args, weights)
            for f in (functools.partial(ssd, chunk=256), _recurrence))
    tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
    assert np.isfinite(float(value)) and abs(float(value) - float(want)) \
        <= 10 * tol * abs(float(want))
    for name, g, w in zip(NAMES, grads, wanted):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) <= LOOSER[name] * tol, (name, _rel(g, w))


def test_no_token_reads_a_later_one_and_no_sequence_another():
    """A change at token ``t`` moves no output before ``t``, across the
    kernels' chunks too; and each sequence of a batch starts from a zero
    state: it is its own call's to the bit."""
    x, dt, a, b, c = _inputs(4, 1, 64, 128, jnp.float32, 2, 384)
    whole = ssd(x, dt, a, b, c, chunk=128)
    changed = ssd(x.at[:, 200:].set(0.0), dt.at[:, 200:].set(1.0), a,
                  b.at[:, 200:].set(1.0), c.at[:, 200:].set(-1.0), chunk=128)
    np.testing.assert_array_equal(np.asarray(whole[:, :200]),
                                  np.asarray(changed[:, :200]))
    assert float(jnp.max(jnp.abs(whole[:, 200:] - changed[:, 200:]))) > 0
    for i in range(2):
        alone = ssd(x[i:i + 1], dt[i:i + 1], a, b[i:i + 1], c[i:i + 1],
                    chunk=128)
        np.testing.assert_array_equal(np.asarray(whole[i]),
                                      np.asarray(alone[0]))


def test_a_pad_of_zero_steps_changes_nothing_before_it():
    """``ssd``'s promise to a caller who pads: tokens of ``dt`` 0 at the end
    move no output before them and no gradient of the tokens before them."""
    x, dt, a, b, c = _inputs(2, 1, 64, 128, jnp.float32, 1, 256, seed=5)
    weights = jnp.asarray(np.random.default_rng(2).normal(size=x.shape),
                          jnp.float32).at[:, 128:].set(0.0)
    padded = (x, dt.at[:, 128:].set(0.0), a, b, c)
    short = (x[:, :128], dt[:, :128], a, b[:, :128], c[:, :128])
    (_, grads), (_, wanted) = (
        _value_and_grads(functools.partial(ssd, chunk=128), args, w)
        for args, w in ((padded, weights), (short, weights[:, :128])))
    for name, g, w in zip(NAMES, grads, wanted):
        assert bool(jnp.all(jnp.isfinite(g))), name
        cut = g if name == "A" else g[:, :128]
        assert _rel(cut, w) <= F32_TOL, (name, _rel(cut, w))


def _calls(jaxpr) -> int:
    """``pallas_call``s of a jaxpr, those of its sub-jaxprs too."""
    count = 0
    for eqn in jaxpr.eqns:
        count += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _calls(sub)
    return count


@pytest.mark.parametrize("case,calls", [("granite", 2), ("nemotron", 2),
                                        ("rehearse", 0), ("two_groups", 0)])
def test_the_shapes_alone_choose_the_realisation(case, calls):
    """The cells' shapes trace to one Mosaic call forward and one backward,
    a ``rehearse`` configuration's and two groups to none; and no argument,
    no name and nothing of the environment is asked (``ssd``'s signature is
    the five operands and the chunk)."""
    *sizes, chunk, dtype, batch, seq, kernel = CASES[case]
    args = _inputs(*sizes, dtype, batch, seq)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ssd(*a, chunk=chunk).astype(jnp.float32)),
        argnums=range(5)))(*args)
    assert _calls(jaxpr.jaxpr) == calls == 2 * kernel
    per = ssd_mosaic.heads_a_step(sizes[0], sizes[2]) if kernel else None
    assert per == {"granite": 8, "nemotron": 8}.get(case)


def test_a_layers_calls_of_one_shape_are_traced_once():
    """Nine layers must not cost nine lowerings: a second call at the same
    shapes adds nothing to the two entry points' caches."""
    args = _inputs(2, 1, 64, 128, jnp.float32, 1, 128, seed=7)

    def grad(*args):
        return jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=128)),
                        argnums=(0, 1))(*args)

    grad(*args)
    sizes = (ssd_mosaic.forward._cache_size(),
             ssd_mosaic.backward._cache_size())
    grad(*(2.0 * t for t in args))
    assert sizes == (ssd_mosaic.forward._cache_size(),
                     ssd_mosaic.backward._cache_size())


@pytest.mark.parametrize("kept", [(), ("scan_out",)],
                         ids=["nothing_kept", "output_kept"])
def test_the_scan_under_a_layers_checkpoint(kept):
    """As ``models/blocks.py::mamba_block`` runs it, inside a layer's
    ``jax.checkpoint`` whose policy keeps nothing of it (the forward call runs
    again for the entering states), and as a caller may who names its output:
    the gradients are the unwrapped call's to the bit."""
    args = _inputs(2, 1, 64, 128, jnp.float32, 1, 256, seed=11)

    def layer(*args):
        return jnp.sum(jnp.tanh(checkpoint_name(
            ssd(*args, chunk=128), "scan_out")))

    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    wanted = jax.grad(layer, argnums=range(5))(*args)
    grads = jax.grad(jax.checkpoint(layer, policy=policy),
                     argnums=range(5))(*args)
    for g, w in zip(grads, wanted):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
