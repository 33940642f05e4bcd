"""Mamba-1's selective scan (``ps_tpu/ops/selective_scan.py``), its XLA form,
against the recurrence written out token by token, at small sizes on the CPU:
values and all six gradients, at lengths the chunk divides and does not, at
several chunk lengths and unrolls; and what the chunked form keeps for its
backward pass. Every shape here (24 channels on 4 states) is one the Mosaic
kernels refuse (``path``), so ``selective_scan`` is the XLA form;
``tests/test_selective_scan_mosaic.py`` has the kernels.

Tolerances. Both sides compute in f32 and differ in nothing but where a
chunk's boundary puts a ``jax.checkpoint``: values agree to a few f32
roundoffs of their largest entry, gradients to 2e-5 of theirs (seen: 5e-6;
``A``'s are sums over every token of terms of either sign).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import primitives
from ps_tpu.ops.selective_scan import CHUNK, UNROLL, path, selective_scan

TOL = 2e-5


def token_by_token(x, dt, a, b, c, d):
    """``h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) outer B_t``, ``y_t = h_t C_t +
    D x_t``, one token of one sequence at a time."""
    def one(x, dt, b, c):
        def token(h, args):
            x_t, dt_t, b_t, c_t = args
            h = jnp.exp(dt_t[:, None] * a) * h \
                + (dt_t * x_t)[:, None] * b_t[None]
            return h, h @ c_t + d * x_t

        return jax.lax.scan(token, jnp.zeros(a.shape, jnp.float32),
                            (x, dt, b, c))[1]

    return jax.vmap(one)(x, dt, b, c)


def _inputs(seq, batch=2, channels=24, state=4, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (normal(batch, seq, channels),
            jax.nn.softplus(normal(batch, seq, channels)),
            -jnp.exp(normal(channels, state)), normal(batch, seq, state),
            normal(batch, seq, state), normal(channels),
            normal(batch, seq, channels))


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


@pytest.mark.parametrize("seq,chunk,unroll", [
    (64, 16, 4), (50, 16, 4), (50, 7, 7), (50, 64, 16), (33, 8, 3)],
    ids=lambda v: str(v))
def test_the_chunked_scan_is_the_token_by_token_recurrence(seq, chunk,
                                                           unroll):
    """Values and the gradients of all six operands, whether or not the
    chunk divides the sequence (a padded token has ``dt`` 0 and passes the
    state on unchanged) and whatever the unroll."""
    *operands, weights = _inputs(seq)

    def chunked(*a):
        return selective_scan(*a, chunk=chunk, unroll=unroll)

    assert _rel(chunked(*operands), token_by_token(*operands)) <= TOL
    got = jax.grad(lambda *a: jnp.sum(weights * chunked(*a)),
                   argnums=range(6))(*operands)
    want = jax.grad(lambda *a: jnp.sum(weights * token_by_token(*a)),
                    argnums=range(6))(*operands)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert g.shape == w.shape and _rel(g, w) <= TOL, name


def test_the_scan_reads_bfloat16_operands_in_float32():
    """x, B and C in the compute dtype, the steps in f32 as the mixer hands
    them over: the result is f32 and is the f32 scan of the rounded
    operands."""
    x, dt, a, b, c, d, _ = _inputs(48)
    low = [t.astype(jnp.bfloat16) for t in (x, b, c)]
    got = selective_scan(low[0], dt, a, low[1], low[2], d, chunk=16)
    assert got.dtype == jnp.float32
    want = token_by_token(low[0].astype(jnp.float32), dt, a,
                          low[1].astype(jnp.float32),
                          low[2].astype(jnp.float32), d)
    assert _rel(got, want) <= TOL


@pytest.mark.parametrize("decay", [1e-3, 40.0])
def test_no_decay_is_too_weak_or_too_strong_for_a_chunk(decay):
    """A decay of ``exp(-40)`` a token, or none to speak of, over chunks of
    32: no quotient of cumulated decays is formed, so nothing overflows and
    nothing is lost."""
    x, dt, a, b, c, d, _ = _inputs(64)
    a = -decay * jnp.ones_like(a)
    got = selective_scan(x, dt, a, b, c, d, chunk=32)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _rel(got, token_by_token(x, dt, a, b, c, d)) <= TOL


def test_one_state_a_chunk_lives_between_the_passes():
    """What the XLA form's gradient's trace keeps from the forward pass
    beside the operands: the state that entered each chunk, [chunks, B, N,
    C], and no array with a token axis and a state axis at once."""
    seq, chunk = 64, 16
    *operands, weights = _inputs(seq)
    assert path(operands[0], operands[2]) == "xla"
    batch, _, channels = operands[0].shape
    state = operands[2].shape[1]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(weights * selective_scan(*a, chunk=chunk)),
        argnums=range(6)))(*operands)
    scans = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "scan"]
    assert len(scans) == 2                       # forward, backward
    kept = [v.aval.shape for v in scans[0].outvars]
    assert (seq // chunk, batch, state, channels) in kept
    assert not [shape for shape in kept
                if len(shape) == 5 or (len(shape) == 4 and shape[-2:] == (
                    state, channels) and shape[0] * shape[1] >= seq)]
    assert "scan" in primitives(jaxpr)


def test_the_defaults_are_the_measured_ones():
    assert UNROLL <= CHUNK and CHUNK % UNROLL == 0
