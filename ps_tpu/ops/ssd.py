"""Mamba-2's state-space duality (Dao & Gu 2024, arXiv:2405.21060), chunk by
chunk.

A head keeps a state ``S`` [P, N] in f32, zero before the first token, and a
token does (``dt`` the step, > 0; ``A`` the head's decay rate, < 0; ``B`` and
``C`` [N] shared by the heads of a group)::

    S  = exp(dt_t * A) * S + dt_t * outer(x_t, B_t)
    y_t = S @ C_t

The plain reference (``benchmark/families/nemotron_h_reference.py``) runs
exactly that, token by token. This module is the **chunked form**, the normal
path: with ``a = dt * A`` the log-decays (<= 0, one scalar a head and token,
where ``ops/kda.py``'s rule has one a channel and a delta correction with an
inverse), ``G`` their sum cumulated inside a chunk of ``Q`` tokens (128) and
``S0`` the state entering it,

    L[i, j] = exp(G_i - G_j)                       j <= i, else 0
    Y  = (L o (C B^T)) (dt x)  +  (exp(G) C) S0^T            # [Q, P]
    S' = exp(G_Q) S0 + ((exp(G_Q - G) dt x))^T B             # [P, N]

so that ``C B^T`` (a group's, shared by its heads), ``L``, the product inside
the chunk and each chunk's own contribution to the state are batched matmuls
over all chunks at once, and what reads ``S0`` is a ``lax.scan`` over the
chunks that carries ``S``: 64 dependent steps at 8,192 tokens instead of
8,192.

**The decays never overflow.** Every exponent that is used is a sum of
log-decays over a span of tokens, so <= 0; ``G_i - G_j`` above the diagonal
is positive and can pass 88 (``exp(A_log)`` 16 and a step of 0.1 lose 205
nats over a chunk), so it is masked *before* the exponential, as
``ops/kda.py::masked_exp`` does: no ``0 * inf`` in the value or in the
gradient. A scalar decay makes ``L`` a [Q, Q] matrix a head: no sub-blocks
are needed, where KDA's per-channel decay forces them.

**Two realisations of the chunked form, chosen by the operands' shapes and
dtype alone** (``ssd_mosaic.takes``; no argument, name or environment
variable enters it).

*The Mosaic calls* (``ops/ssd_mosaic.py``; both cells' shapes: Granite-4.0-H's
whole mixer, 64 heads of 64 on one B/C group of state 128 in chunks of 256,
and Nemotron-H's share of 16 heads in chunks of 128, in bf16): one call
forward and one backward behind a ``jax.custom_vjp``, over ``x`` as the mixer
has it, ``[B, T, H P]`` row-major. The grid walks the sequence in chunks of
128 tokens (sub-chunks of the configuration's: the recurrence is the same
whatever the chunk), all heads' ``[P, N]`` states stay in a VMEM scratch, a
head's ``L`` is built on the tile and dies in the product that reads it, and
``dB`` / ``dC`` are summed over the group's heads inside the call. What lives
from the forward call to the backward one is the f32 states that entered each
chunk (134 MB at Granite's shape): under a layer's ``jax.checkpoint`` the
forward call runs again for them and gives the output with them, so a layer's
policy keeps nothing of the scan (``PERF.md`` section 6, PR 59: the three
ways that were measured). Off the chip the same kernels run in interpret mode
(``ops/mosaic.py::interpret``). Under a mesh of several devices the calls are
not wrapped in ``shard_map`` as ``ops/kda.py``'s are: no cell runs this mixer
across chips.

*The XLA form* (``_ssd_plain``; every other shape: more than one group, heads
that do not fill 128-lane tiles, a chunk that is no multiple of 128, the
``rehearse`` configurations). Differentiated by autodiff. ``L`` is ``4 Q^2``
bytes a head a chunk in f32 and made over all heads and chunks at once (537
MB a copy at Granite's shape, of which the gradient compiled to 0.49e9 B of
temporaries), each chunk's contribution to the state is written, carried by
a ``while`` over the chunks and read back, and the einsums read ``x`` in
three layouts that XLA writes from fusions of its own: at the Granite cell it
took 62.2 ms of a 443 ms step where the kernels take 31.7 (``PERF.md``
section 6, PR 59).

Precision, in both: the cumulated sums, the decays and the carried state in
f32; the products take their operands in ``x``'s dtype (the configuration's
compute dtype: one bf16 pass on the MXU; f32 operands at the highest
precision in the kernels) and accumulate in f32. The XLA form rounds ``dt x``
and ``L o C B^T`` each before their product; the kernels round ``L o C B^T o
dt`` once and take ``x`` as it is.

``benchmark/families/nemotron_h_step.py::ssd_cost`` counts the operations and
bytes of the chunked form from the configuration's shapes, whatever
implements it; ``nemo.ssd_roofline`` and ``kernel.ssd_roofline`` are its
share of what the scan's scope takes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ps_tpu.ops import mosaic, ssd_mosaic
from ps_tpu.ops.kda import masked_exp


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_kernel(x, dt, A, B, C, interpret):
    return ssd_mosaic.forward(x, dt, A, B, C, interpret=interpret,
                              keep=False)[0]


def _ssd_kernel_fwd(x, dt, A, B, C, interpret):
    y, (states,) = ssd_mosaic.forward(x, dt, A, B, C, interpret=interpret,
                                      keep=True)
    return y, (x, dt, A, B, C, states)


def _ssd_kernel_bwd(interpret, res, dy):
    return ssd_mosaic.backward(*res, dy, interpret=interpret)


_ssd_kernel.defvjp(_ssd_kernel_fwd, _ssd_kernel_bwd)


def ssd(x, dt, A, B, C, *, chunk: int = 128):
    """``x`` [B, T, H, P], ``dt`` [B, T, H] the steps (> 0, f32), ``A`` [H]
    the decay rates (< 0), ``B`` and ``C`` [B, T, G, N] with ``G`` dividing
    ``H`` (head ``h`` reads group ``h // (H / G)``) -> ``y`` [B, T, H, P] in
    ``x``'s dtype: the recurrence of the module docstring from a zero state,
    each sequence of the batch on its own, without the ``D x`` skip (the
    caller's). ``T`` must be a multiple of ``chunk``: a sequence is not
    padded here (a pad of ``dt`` 0 tokens at the end changes no output before
    it and is the caller's to add and cut)."""
    seq, heads, groups = x.shape[1], x.shape[2], B.shape[2]
    if seq % chunk or heads % groups or B.shape != C.shape:
        raise ValueError(
            f"ssd: {seq} tokens in chunks of {chunk}, {heads} heads on "
            f"B {B.shape} and C {C.shape}: the chunk must divide the "
            f"sequence and the groups the heads")
    if ssd_mosaic.takes(x, B, chunk):
        return _ssd_kernel(x, dt, A, B, C, mosaic.interpret())
    return _ssd_plain(x, dt, A, B, C, chunk)


def _ssd_plain(x, dt, A, B, C, chunk: int):
    """The XLA form, for every shape the kernels do not take."""
    seq, heads, groups = x.shape[1], x.shape[2], B.shape[2]
    batch, width, state_dim = x.shape[0], x.shape[3], B.shape[3]
    n, per_group = seq // chunk, heads // groups
    mxu = x.dtype
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    def by_head(t, *tail):     # [B, T, H, ...] -> [B, n, g, r, Q, ...]
        t = t.reshape(batch, n, chunk, groups, per_group, *tail)
        return jnp.moveaxis(t, 2, 4)

    def by_group(t):           # [B, T, G, N] -> [B, n, g, Q, N]
        return jnp.moveaxis(t.reshape(batch, n, chunk, groups, state_dim),
                            2, 3).astype(mxu)

    dt = by_head(dt.astype(jnp.float32))
    cum = jnp.cumsum(dt * A.astype(jnp.float32).reshape(
        groups, per_group, 1), axis=-1)                  # G [B, n, g, r, Q]
    dx = by_head(x.astype(jnp.float32), width) * dt[..., None]   # dt x, f32
    b_in, c_in = by_group(B), by_group(C)
    # inside a chunk: (L o C B^T)(dt x)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = masked_exp(cum[..., :, None] - cum[..., None, :], lower)
    scores = mm("bngis,bngjs->bngij", c_in, b_in)        # [B, n, g, Q, Q]
    inside = mm("bngrij,bngrjp->bngrip",
                (decay * scores[:, :, :, None]).astype(mxu), dx.astype(mxu))
    # each chunk's own contribution to the state it leaves, and its decay
    last = cum[..., -1:]
    to_end = (dx * jnp.exp(last - cum)[..., None]).astype(mxu)
    own = mm("bngrjp,bngjs->bngrps", to_end, b_in)       # [B, n, g, r, P, N]
    whole = jnp.exp(last[..., 0])                        # [B, n, g, r]

    def carry(state, per_chunk):
        own_c, whole_c = per_chunk
        return whole_c[..., None, None] * state + own_c, state

    zero = jnp.zeros((batch, groups, per_group, width, state_dim),
                     jnp.float32)
    _, entering = jax.lax.scan(
        carry, zero, (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    # the chunk's read of the state that entered it
    read = mm("bngis,bngrps->bngrip", c_in,
              jnp.moveaxis(entering, 0, 1).astype(mxu))
    y = inside + read * jnp.exp(cum)[..., None]          # [B, n, g, r, Q, P]
    return jnp.moveaxis(y, 4, 2).reshape(batch, seq, heads, width).astype(
        x.dtype)


