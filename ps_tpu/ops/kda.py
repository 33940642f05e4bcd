"""Kimi Delta Attention's recurrence (arXiv:2510.26692), chunk by chunk.

A head keeps a state ``S`` [K, V] in f32, zero before the first token, and a
token does (``g`` the per-channel log-decay, <= 0; ``beta`` the write
strength)::

    S *= exp(g_t)[:, None]
    u  = beta_t * (v_t - S^T k_t)     # the delta rule: what k_t read, corrected
    S += outer(k_t, u)
    o_t = S^T q_t

i.e. ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
v_t^T``. The plain references (``tests/kimi_reference.py``) run exactly that,
token by token; 8,192 dependent steps of rank-one updates leave the MXU idle.
This module is the **chunked form**, the normal path: with ``G`` the decays
cumulated inside a chunk of ``C`` tokens (64) and ``S0`` the state entering
it,

    A[s, r] = beta_s sum_c k_s[c] k_r[c] exp(G_s[c] - G_r[c])     r <  s
    B[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    U = (I + A)^-1 (beta v) - (I + A)^-1 (beta k exp(G)) S0    # the chunk's u
    O = (q exp(G)) S0 + B U
    S' = Diag(exp(G_C)) S0 + (k exp(G_C - G))^T U

so that everything that does not read ``S0`` (A, B, the inverse and its two
products) is batched matmuls over all chunks at once, and what reads it is a
``lax.scan`` over the chunks that carries ``S``: three small matmuls a chunk,
128 dependent steps at 8,192 tokens instead of 8,192.

**The decays never overflow.** ``exp(G_s - G_r)`` is <= 1, but a matmul needs
it as a product of a factor of s and one of r, and ``exp(-G_r)`` alone is not
an f32 once a channel has lost 88 nats (``exp(A_log)`` 16 and a softplus of
0.1 lose 102 over a chunk). So a chunk is cut into sub-blocks of 16 tokens
(as the published kernels do). Between two sub-blocks both factors are taken
to the boundary ``b`` in front of the later one: ``exp(G_s - G_b)`` and
``exp(G_b - G_r)``, both <= 1 whatever the decay. Inside a sub-block every
pair gets its own ``exp(G_s - G_r)`` ([16, 16, K] a block: a quarter of the
exponentials of whole-chunk pairs, and at 16 tokens no assumption about how
fast a channel may decay is needed). Masked entries are masked in the
exponent too, so no gradient is 0 x inf.

**The inverse.** ``I + A`` is unit lower triangular, ``A^C = 0``, so
``(I + A)^-1 = prod_i (I + (-A)^(2^i))``, i < log2 C: five squarings and five
products of [C, C] matrices at the highest precision, batched over every chunk
and head, in place of ``solve_triangular``'s forward substitution (the table).

**Differentiable by autodiff**, not a ``custom_vjp``: the backward of the
chunked form is four more recurrences to derive, and forty lines of
``jax.numpy`` that equal the token-by-token reference are differentiated
right by construction (``tests/test_kimi_linear.py`` holds all five
gradients to the recurrence's). The whole op is under ``jax.checkpoint``:
between the layers only ``q, k, v, g, beta`` live on (40 KB a token a layer
where the chunk's internals would be 130), and the batched part runs in
groups of ``GROUP`` chunks, each under ``jax.checkpoint`` again, so that the
[16, 16, K] pair tensors of one group are the most that is alive (34 MB at
32 heads and two chunks) and not those of all 128 chunks (2.1 GB).

Precision: every array in f32 (the inputs may be bf16: they are the
configuration's compute dtype), the state carried in f32; the matmuls at the
default precision (one bf16 pass on the MXU with f32 accumulation) but the
inverse's, whose errors compound.

Plain XLA. ``benchmark/families/kimi_step.py::kda_core_cost`` counts the
operations and bytes of this form from the shapes; a Pallas kernel that keeps
``S`` in VMEM across the chunks is ROADMAP R3's.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 34: the op alone at
[1, 8192, 32, 128], bf16 q / k / v, median of 5 calls on the host clock, and
inside the fused step from the cell's traces, four layers)**:

| what | forward, ms | forward + backward, ms | ``kimi.kda_core_ms`` / ``step.device_ms`` |
|---|---|---|---|
| squarings, groups of 16 chunks | 15.81 | 72.68 | 346.26 / 799.58 |
| ``solve_triangular``, groups of 16 | 23.79 | 94.29 | not run |
| squarings, all 128 chunks one group | 21.93 | 86.02 | not run |
| squarings, groups of 8 / 4 / **2** / 1 | 14.28 / 14.32 / **14.38** / 15.99 | 64.32 / 52.13 / **49.79** / 51.45 | - / 288.18 / **266.71** / 281.95 of 734.02 / **706.16** / 715.98 |
| sub-blocks of 8, groups of 4 / 2 | 14.42 / 14.41 | 52.20 / 49.71 | not run |
| the scan unrolled 4 chunks a step, groups of 2 | not run | not run | 265.96 / 710.03 |

Small groups win because XLA then keeps a group's intermediates in the
faster memory space (``S(1)`` in the optimized HLO). Of the 50.65 ms of
device time of a call (groups of 4) the four fusions over the pair tensors
are 10.6; the rest is a long tail of the two loops' slices, copies and small
matmuls, none over 1.1 ms: the op is bound by its loops, not by one fusion,
at 1.6-2.1% of its roofline (HBM-bound, 5.6 ms a step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: tokens of a sub-block, inside which every pair gets its own decay
SUB = 16
#: chunks whose batched part is computed (and, in the backward pass,
#: recomputed) together
GROUP = 2
_HIGHEST = jax.lax.Precision.HIGHEST


def _masked_exp(x, keep):
    """``exp(x)`` where ``keep``, else 0, with no overflow and no NaN in the
    gradient behind the mask."""
    return jnp.where(keep, jnp.exp(jnp.where(keep, x, 0.0)), 0.0)


def _unit_lower_inverse_times(a, rhs):
    """``(I + a)^-1 rhs`` for strictly lower triangular ``a`` [..., C, C]:
    ``prod_i (I + (-a)^(2^i))`` applied to ``rhs``."""
    c = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    power = -a
    inverse = jnp.eye(c, dtype=a.dtype) + power
    for _ in range((c - 1).bit_length() - 1):
        power = mm(power, power)
        inverse = inverse + mm(inverse, power)
    return mm(inverse, rhs)


@jax.checkpoint
def _chunk_internals(q, k, v, g, beta):
    """What a chunk computes without the state, for chunks [..., C, *]
    (``beta`` [..., C]): ``q exp(G)``, B, the inverse's two products,
    ``k exp(G_C - G)`` and ``exp(G_C)``."""
    c = q.shape[-2]
    nb = c // SUB
    lead = q.shape[:-2]

    def blocks(x):
        return x.reshape(*lead, nb, SUB, x.shape[-1])

    cum = jnp.cumsum(g, axis=-2)                                # G [.., C, K]
    # G at the boundary in front of each sub-block
    edge = jnp.concatenate(
        [jnp.zeros_like(cum[..., :1, :]),
         cum[..., SUB - 1::SUB, :][..., :-1, :]], axis=-2)     # [.., nb, K]
    cum_b = blocks(cum)
    inside = jnp.exp(cum_b - edge[..., None, :])                # <= 1
    kb = blocks(k)
    # keys in front of sub-block i, carried to its boundary: [.., nb, C, K]
    before = (jnp.arange(c) < SUB * jnp.arange(nb)[:, None])[..., None]
    carried = k[..., None, :, :] * _masked_exp(
        edge[..., :, None, :] - cum[..., None, :, :], before)
    # every pair of one sub-block: [.., nb, SUB, SUB, K]
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[..., None]
    pair = _masked_exp(cum_b[..., :, None, :] - cum_b[..., None, :, :], lower)
    same_block = jnp.eye(nb, dtype=q.dtype)

    def against_keys(x):
        """``sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c])``, s <= t: [.., C, C]."""
        xb = blocks(x)
        across = jnp.einsum("...iac,...isc->...ias", xb * inside, carried)
        within = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * pair,
                         axis=-1)                              # [.., nb, a, b]
        within = jnp.einsum("...iab,ij->...iajb", within, same_block)
        return (across + within.reshape(*lead, nb, SUB, c)).reshape(
            *lead, c, c)

    strict = 1.0 - jnp.eye(c, dtype=q.dtype)
    a = beta[..., None] * against_keys(k) * strict
    decay = jnp.exp(cum)
    last = cum[..., -1:, :]
    w = _unit_lower_inverse_times(
        a, jnp.concatenate([beta[..., None] * v,
                            beta[..., None] * k * decay], axis=-1))
    return (q * decay, against_keys(q), w[..., :v.shape[-1]],
            w[..., v.shape[-1]:], k * jnp.exp(last - cum),
            jnp.exp(last[..., 0, :]))


def _carry_state(state, internals):
    """One chunk of the scan: the state in, the chunk's outputs and the state
    out. ``state`` [B, H, K, V]."""
    q_decayed, b, w_v, w_k, k_to_end, decay_to_end = internals
    u = w_v - w_k @ state
    out = q_decayed @ state + b @ u
    state = decay_to_end[..., None] * state \
        + jnp.swapaxes(k_to_end, -1, -2) @ u
    return state, out


def _kda(q, k, v, g, beta, chunk: int):
    b, t, h, width = q.shape
    n = t // chunk

    def chunks(x):  # [B, T, H, *] -> [N, B, H, C, *]
        x = x.astype(jnp.float32).reshape(b, n, chunk, h, -1)
        return jnp.transpose(x, (1, 0, 3, 2, 4))

    args = (chunks(q), chunks(k), chunks(v), chunks(g),
            chunks(beta[..., None])[..., 0])
    group = next(s for s in range(min(GROUP, n), 0, -1) if n % s == 0)
    internals = jax.lax.map(
        lambda xs: _chunk_internals(*xs),
        tuple(x.reshape(n // group, group, *x.shape[1:]) for x in args))
    internals = tuple(x.reshape(n, *x.shape[2:]) for x in internals)
    state = jnp.zeros((b, h, width, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(_carry_state, state, internals)
    out = jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(b, t, h, v.shape[-1])
    return out.astype(v.dtype)


def kda(q, k, v, g, beta, *, chunk: int = 64, checkpoint: bool = True):
    """``q``, ``k`` [B, T, H, K], ``v`` [B, T, H, V], ``g`` [B, T, H, K] the
    log-decays (<= 0, f32), ``beta`` [B, T, H] -> ``o`` [B, T, H, V] in
    ``v``'s dtype: the recurrence of the module docstring from a zero state,
    each sequence of the batch on its own. ``T`` must be a multiple of
    ``chunk`` and ``chunk`` of ``SUB``: a sequence is not padded here (a pad
    of ``beta`` 0, ``g`` 0 tokens at the end changes no output before it and
    is the caller's to add and cut). ``checkpoint=False`` leaves the
    recomputation to a caller that has a wider ``jax.checkpoint`` of its own
    around the call (``models/kimi_linear.py``): two nested ones would run
    the forward pass three times."""
    t = q.shape[1]
    if chunk % SUB or t % chunk:
        raise ValueError(f"kda: {t} tokens in chunks of {chunk}, sub-blocks "
                         f"of {SUB}: each must divide the one before")
    run = functools.partial(_kda, chunk=chunk)
    return (jax.checkpoint(run) if checkpoint else run)(q, k, v, g, beta)
