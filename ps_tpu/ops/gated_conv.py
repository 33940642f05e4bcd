"""The double-gated short causal convolution of LFM2's ``conv`` layers.

Between the mixer's two projections, over ``bcx = h @ W_in`` [B, S, 3 * D]::

    B, C, X = split(bcx, 3)             # three gates' worth of channels
    u = B * X                           # the input gate
    v_t = sum_j w[:, j] * u_{t-(L-1)+j} # depthwise, causal, zero left pad
    y = C * v                           # the output gate

with ``w`` [D, L] one filter of ``L`` taps a channel (``conv_L_cache``, 3 in
the published models; ``conv_bias`` false). No position enters it, and
output ``t`` reads inputs ``t-L+1 .. t`` only. All of it is element-wise work
over ``[tokens, D]`` arrays: bandwidth's, between two matmuls that are the
MXU's.

Written as one ``jax.custom_vjp`` in plain ``jax.numpy`` so that XLA makes
one fusion of each direction and nothing but ``bcx`` and ``w`` is kept for
the backward pass, which recomputes ``u`` and ``v`` (left to autodiff, B, X,
C, u and v would each be a residual of ``[tokens, D]``). By its shapes the
forward reads three ``D``-wide arrays and writes one, the backward reads
four and writes three: 11 x tokens x D x itemsize bytes a layer, which
``benchmark/families/lfm2_step.py::conv_gate_bytes`` counts. The products
and the taps' sum run in f32 and the results are cast to ``bcx``'s dtype.

The taps themselves (``causal_taps``) are public, and so is the other short
convolution built on them, ``conv_silu``: a depthwise causal filter, an
optional bias and a SiLU, with one rule in each direction. Kimi Delta
Attention runs it on q, k and v (four taps, no bias, ``models/kimi_linear.py``)
and the Mamba-2 mixer on its x, B and C channels (four taps and a bias,
``models/nemotron_h.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shift(u, by: int):
    """``u`` [B, S, D] moved ``by`` positions to the right along S (to the
    left where negative), zeros coming in."""
    if by == 0:
        return u
    pad = jnp.zeros_like(u[:, :abs(by)])
    if by > 0:
        return jnp.concatenate([pad, u[:, :-by]], axis=1)
    return jnp.concatenate([u[:, -by:], pad], axis=1)


def causal_taps(u, w, sign: int = 1):
    """``sum_j w[:, j] * u_{t - sign * (L-1-j)}`` for ``u`` [B, S, D] and
    ``w`` [D, L]: the depthwise causal filter (``sign`` 1; zero left pad, no
    bias) or its transpose (-1). The public helper of both short-convolution
    mixers: ``gated_short_conv`` below (LFM2's three taps between two gates)
    and ``models/kimi_linear.py``'s four taps before a SiLU on q, k and v."""
    taps = w.shape[-1]
    return sum(w[:, j] * shift(u, sign * (taps - 1 - j))
               for j in range(taps))


@jax.custom_vjp
def gated_short_conv(bcx, w):
    """``bcx`` [B, S, 3 * D], ``w`` [D, L] -> ``C * conv(B * X)`` [B, S, D]
    in ``bcx``'s dtype."""
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    return (c * causal_taps(b * x, w.astype(jnp.float32))).astype(bcx.dtype)


def _fwd(bcx, w):
    return gated_short_conv(bcx, w), (bcx, w)


def _bwd(res, dy):
    bcx, w = res
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    wf = w.astype(jnp.float32)
    dy = dy.astype(jnp.float32)
    u = b * x
    dv = dy * c
    du = causal_taps(dv, wf, -1)
    taps = w.shape[-1]
    dw = jnp.stack([jnp.sum(dv * shift(u, taps - 1 - j), axis=(0, 1))
                    for j in range(taps)], axis=-1)
    dbcx = jnp.concatenate([du * x, dy * causal_taps(u, wf), du * b], axis=-1)
    return dbcx.astype(bcx.dtype), dw.astype(w.dtype)


gated_short_conv.defvjp(_fwd, _bwd)


@jax.custom_vjp
def _conv_silu(x, w, b):
    taps = causal_taps(x.astype(jnp.float32), w.astype(jnp.float32))
    if b is not None:
        taps = taps + b.astype(jnp.float32)
    return jax.nn.silu(taps).astype(x.dtype)


def _conv_silu_fwd(x, w, b):
    return _conv_silu(x, w, b), (x, w, b)


def _conv_silu_bwd(res, dy):
    x, w, b = res
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    z = causal_taps(xf, wf)
    if b is not None:
        z = z + b.astype(jnp.float32)
    gate = jax.nn.sigmoid(z)
    dz = dy.astype(jnp.float32) * gate * (1 + z * (1 - gate))
    taps = w.shape[-1]
    dw = jnp.stack([jnp.sum(dz * shift(xf, taps - 1 - j), axis=(0, 1))
                    for j in range(taps)], axis=-1)
    db = None if b is None else jnp.sum(dz, axis=(0, 1)).astype(b.dtype)
    return causal_taps(dz, wf, -1).astype(x.dtype), dw.astype(w.dtype), db


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x, w, b=None):
    """``silu`` of the depthwise causal convolution of ``x`` [B, S, C] with
    the filter ``w`` [C, taps] (zero left pad) plus the bias ``b`` [C] if
    there is one, in f32, the result in ``x``'s dtype. One rule in each
    direction as ``gated_short_conv``'s: only ``x``, ``w`` and ``b`` are
    kept, and the backward pass is the transposed taps, not autodiff's pads
    and slices of a concatenation."""
    return _conv_silu(x, w, b)
