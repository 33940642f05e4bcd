"""Kimi Delta Attention's recurrence (arXiv:2510.26692), chunk by chunk.

A head keeps a state ``S`` [K, V] in f32, zero before the first token, and a
token does (``g`` the per-channel log-decay, <= 0; ``beta`` the write
strength)::

    S *= exp(g_t)[:, None]
    u  = beta_t * (v_t - S^T k_t)     # the delta rule: what k_t read, corrected
    S += outer(k_t, u)
    o_t = S^T q_t

i.e. ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
v_t^T``. The plain reference (``benchmark/families/kimi_reference.py``) runs
exactly that, token by token; 8,192 dependent steps of rank-one updates leave
the MXU idle.
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
The kernels take the same product with its factors in the other order (they
are polynomials in ``A`` and commute), which makes a level one product
(``kda_mosaic._inverses``; the last table).

**Three realisations, chosen by shape** (``path``): where a head's keys and
values fill whole 128-lane tiles and the chunk is 64 (the benchmark's cells:
32 heads of 128), the rule runs as Mosaic kernels of ``ops/kda_mosaic.py``
behind a ``custom_vjp``: the state stays in VMEM across the chunks of a
head, a chunk's internals live and die there, the operands are read as the
projections write them ([B, T, H * K], no transposed f32 copy), and the
forward rule keeps for the backward, beside its operands, only the state
entering each chunk and the chunk's inverse. Which kernels is read from the
decay's shape: ``g`` [B, T, H, K], **a decay a channel** (Kimi-Linear), takes
the per-channel kernels (``_chunk``: the decays by halving levels); ``g``
[B, T, H], **one decay a head and a token** (Qwen3-Next's gated delta rule),
takes the scalar-decay kernels (``_scalar_chunk``) at the operands' own
shapes: ``exp(G_t - G_s)`` is then one [C, C] matrix a head that scales the
entries of ``K K^T`` and ``Q K^T``, which are one [2C, K] x [K, C] product a
**key** head, read once by the ``r`` value heads that follow one another
(value head ``h`` reads key head ``h // r``); no decay a channel, no repeated
key head and none of autodiff's sums back exist in HBM. Everything else (the
tests' models at widths 16 and 32) runs the plain XLA form below on
operands broadcast to the general rule's shapes, which is also the kernels'
second oracle beside the token-by-token recurrence. One algorithm, the same
arithmetic classes ("Precision"), no switch to set.

**The residuals' names (``KEPT``).** Under a ``jax.checkpoint`` around the
call none of ``(q, k, v, g, beta, states, inverses)`` lives from forward to
backward, and the backward pass runs the forward kernel again only to have
the states, the inverses and the output ``o`` (which whatever follows the
call is recomputed from): the operands are a projection and a few
elementwise passes away, these three are the whole call away. So
``_kda_kernel_fwd`` passes the three through ``checkpoint_name`` before they
enter the residual tuple, as ``ops/flash_attention.py::_flash_vjp_fwd``
does its two, and a checkpoint whose policy is
``save_only_these_names(*KEPT)`` keeps them and recomputes no forward call:
``models/kimi_linear.py::_mixer`` says so for the mixer. At 32 heads of 128
that is 49 KB a token a layer more between forward and backward (``o`` in
bf16 8 KB, the states in f32 32 KB, the inverses 8 KB: 403 MB a layer at
8,192 tokens) and one forward call a layer less (11.4 ms when PR 44 read
it, 7.7 since PR 62). Under no
checkpoint, or one that does not list them, the names are identity. The
plain form has no ``custom_vjp`` and no names: a caller's policy recomputes
it whole.

**The plain form is differentiable by autodiff**: forty lines of
``jax.numpy`` that equal the token-by-token reference are differentiated
right by construction (``tests/test_kimi_linear.py`` holds all five
gradients of both realisations to the recurrence's). The whole op belongs
under the caller's wider ``jax.checkpoint`` (``kda`` has none of its own):
under a policy-less one only ``q, k, v, g, beta`` live on between
forward and backward (40 KB a token a layer where the chunk's internals
would be 130), under one that lists ``KEPT`` those and the kernels' three
(89 KB), and the plain form's batched part runs in groups of ``GROUP``
chunks, each under ``jax.checkpoint`` again, so that the [16, 16, K] pair
tensors of one group are the most that is alive (34 MB at 32 heads and two
chunks) and not those of all 128 chunks (2.1 GB).

Precision: every array in f32 (the inputs may be bf16: they are the
configuration's compute dtype), the state carried in f32; the matmuls at the
default precision (one bf16 pass on the MXU with f32 accumulation) but the
inverse's, whose errors compound. The kernels keep each class: what is an
f32 product on the VPU here (the pairs inside a sub-block, the cumulated
sums) is a highest-precision product on the MXU there, and Mosaic reads no
``jax.default_matmul_precision``, so ``kda`` hands the kernels the operand
dtype of their default-class products (``_mxu_dtype``).

``benchmark/families/kimi_step.py::kda_core_cost`` counts the operations and
bytes of the chunked form from the shapes (the plain form's: the kernels'
six-pass products, their halving levels and what they keep for the backward
are not in it).

**Measured (TPU v5e, jax 0.9.0): the op alone at [1, 8192, 32, 128], bf16
q / k / v, and inside the fused step from the cell's traces, four layers.**
The plain form (my chip runs, PR 34; median of 5 calls on the host clock):

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
matmuls, none over 1.1 ms: the plain form is bound by its loops, not by one
fusion, at 1.6-2.1% of its roofline (HBM-bound, 5.6 ms a step).

The kernels (my chip runs, PR 35; four calls chained in one program, so a
call is 12 ms or more of device work behind one dispatch; the last column
from the cell's traces). "Forward + backward" is the forward that keeps the
states and the backward, what ``jax.grad`` of the op runs, and all the
cell's step runs under a checkpoint that keeps ``KEPT`` (a policy-less one
runs the forward a second time, and 5.0 ms a layer of copies and casts of
its [8192, 4096] operands with it: the last two rows):

| what | forward, ms | forward + backward, ms | ``kimi.kda_core_ms`` / ``step.device_ms`` |
|---|---|---|---|
| the plain form, this harness | 13.60 | 53.57 | 266.70 / 706.17 (ledger, PR 34) |
| ``jax.vjp`` of the whole chunk in the body, Mosaic's six-pass products for the run sums, 1 / 2 / 4 heads a step | 16.21 / 15.78 / 15.52 | 50.69 / 49.80 / 49.33 | not run |
| + ``_solve``'s own rule with the inverse kept (the squarings are not transposed), the run sums in three exact passes, 2 heads | 12.07 | 26.03 | 145.38 / 568.25 |
| the same with the masks handed in as tables, not made of iotas | 12.33 | 26.64 | not run |
| **as landed: 4 heads a step** (2: 12.11 / 26.16) | **11.91** | **25.92** | **144.00 / 566.70** |
| the same kernels, the mixer's checkpoint keeping ``KEPT`` (PR 44): 8 calls a step where there were 12, each at its time inside the step | 11.39 | 24.58 | **99.30 / 430.36** (my chip run, PR 44; 143.99 / 495.80 on the parent beside it) |
| **the inverses of a step's four heads taken together, six [64, 64] x [64, 128] products a chain (PR 62)**: alone 8.27 (11.92 on the parent beside it), the backward 14.62 (14.63) | **7.73** | 20.92 | **84.64 / 348.79** (my chip run, PR 62; the ledger's PR 61 line reads 99.30 / 363.33) |

Where a call's time goes (ablations of the 2-head form, forward / forward +
backward, ms): the inverse's ten [64, 64] products at the highest precision
5.3 / 5.3 (forward only: the backward loads it), the four levels inside a
sub-block 1.6 / 7.2, the run sums 1.0 / 3.8, the products with the state
1.0 / 2.8, the solve 0.3 / 1.8, the two levels across sub-blocks 0.1 / 1.3,
the exponentials under 0.3, and 1.7 / 5.0 that is left with all of these
taken out (blocks in and out, casts, a grid step's fixed cost). A [64, 64]
product costs 104 cycles at the highest precision and 54 in one bf16 pass,
and two independent chains take twice one: throughput, not latency, so more
heads a step buy only the step's fixed cost (0.6 us). The six terms of a
highest-precision product written by hand over three bf16 pieces cost what
Mosaic's own do (0.94 against 1.11 us for the inverse). **What PR 62 found
of that reading:** two chains took twice one because the scheduler ran them
one after the other, not because a product fills the MXU. A product is its
weights' pushes, six passes and their pops in a row, about 200 cycles before
the next product of the chain can start, and the ten products of an inverse
are six such steps deep (five squarings and the last update; an update runs
beside the next squaring): 1,223 cycles an inverse, measured. Six products
[64, 64] x [64, 128] in ``_inverse`` alone, the issue's form, are as deep
and read 1,203 with a concatenation a level (a wide product 241 cycles by
the difference, where two narrow ones are 244: the count bought nothing),
1,250 carried as ``[X | P]``, 1,174 as ``[P | X]`` (no lane moves), 1,369
with the rows stacked instead, ``[X; P] @ P``; in one bf16 pass the ten cost
737 and the six 882, so the depth and not the passes. With the chains of a
step's heads issued level by level the same six products cost 367 cycles an
inverse (four chains, this kernel: 61 a product) and 398 (the scalar
kernels'), 0.39 and 0.42 us.

The scalar-decay kernels (my chip runs, PR 61, ``tools/gdn_table.py``: q and
k ``bf16[1, 8192, 16, 128]`` read by 32 value heads, ``g`` and ``beta``
``f32[1, 8192, 32]``; four calls chained in one program; the last column from
the Qwen3-Next cell's traces, three layers, where a call reads 8.15 forward
and 5.17 backward inside the step, 4.42 and 4.86 since PR 62):

| what | forward, ms | backward, ms | forward + backward, ms | ``decoder.kda_core_ms`` / ``step.device_ms`` |
|---|---|---|---|---|
| the per-channel kernels on operands broadcast for them (the decay to 128 channels, 16 key heads repeated to 32, autodiff's sums back: before PR 61) | 13.10 | - | 28.15 | 87.15 / 280.83 |
| the scalar body, two key heads with their four value heads a step (PR 61) | 8.62 | 5.85 | 14.00 | 40.75 / 236.68 |
| the same without the inverse (``I - A`` in its place) | 3.14 | 5.84 | - | not run |
| without the exponents' run sum (the masked decays themselves in its place) | 8.23 | 4.72 | - | not run |
| without the solve's products | 7.45 | 4.90 | - | not run |
| six wide products in ``_inverse`` alone, the chains still one after the other (PR 62; the parent 8.57 beside it): a concatenation a level / carried ``[X | P]`` / ``[P | X]`` | 8.50 / 8.71 / 8.34 | - | - | not run |
| a key head's two readers' chains level by level, ten narrow / six ``[P | X]`` products | 6.55 / 5.63 | 5.44 | - | 30.99 / 226.91 |
| **as landed (PR 62): the step's four chains level by level, six ``[P | X]`` products each** (the parent 8.58 / 5.87 beside it) | **4.85** | **5.43** | **9.91** | **28.60 / 224.41** |
| the same without the inverse | 3.12 | 5.45 | - | not run |
| without the exponents' run sum | 4.41 | 4.70 | - | not run |
| without the solve's products | 3.57 | 4.18 | - | not run |

Of PR 61's forward call 5.5 ms were the inverse's ten [64, 64] products at
the highest precision (64%; the backward loads it), 1.2 / 1.0 the solve, 0.4
/ 1.1 the exponents' three exact passes and their transposition, and 1.5 /
3.8 everything else: the pair product a key head, the three products with
the state, blocks in and out. Of PR 62's 4.85 the inverses are 1.7 (36%),
the solve 1.3 / 1.3, the run sum 0.4 / 0.8; the backward's 0.4 came with the
body's two loops (a key head's readers' products side by side, no inverse
among them). ``gdn_core_cost``'s least time for a layer is 0.66 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ps_tpu.ops import kda_mosaic, mosaic
from ps_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

#: tokens of a sub-block, inside which every pair gets its own decay (and
#: its own f32 product: the kernels' split of the arithmetic classes too)
SUB = kda_mosaic.SUB
#: chunks whose batched part is computed (and, in the backward pass,
#: recomputed) together
GROUP = 2
_HIGHEST = jax.lax.Precision.HIGHEST
#: the names of what only the kernels' forward call can produce (its output,
#: the state entering each chunk, each chunk's inverse): a caller's
#: ``jax.checkpoint`` keeps them with ``save_only_these_names(*KEPT)``
KEPT = ("kda_out", "kda_states", "kda_inverses")


def masked_exp(x, keep):
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
    carried = k[..., None, :, :] * masked_exp(
        edge[..., :, None, :] - cum[..., None, :, :], before)
    # every pair of one sub-block: [.., nb, SUB, SUB, K]
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[..., None]
    pair = masked_exp(cum_b[..., :, None, :] - cum_b[..., None, :, :], lower)
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


def path(q, k, v, chunk: int, g=None) -> str:
    """Which realisation of the rule operands of these shapes take, read from
    the shapes alone. Where a head's keys and values fill whole 128-lane
    tiles and the chunk is the kernels' 64, the Mosaic kernels of
    ``ops/kda_mosaic.py``: ``"scalar_kernel"`` for ``g`` [B, T, H], one decay
    a head, at the operands' own shapes (``q`` and ``k`` may have fewer
    heads than ``v``), ``"kernel"`` for a decay a channel (``g`` [B, T, H, K]
    or none given), on broadcast operands. Else ``"plain"``, this module's
    XLA form, on broadcast operands too."""
    lanes = q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
    whole = q.shape[1] % chunk == 0 and q.shape == k.shape
    if not (lanes and whole and chunk == 64):
        return "plain"
    return "scalar_kernel" if g is not None and g.ndim == 3 else "kernel"


def _mxu_dtype():
    """The operand dtype of the kernels' default-class products: bf16 (one
    pass, what the chip's default precision makes of the plain form's) unless
    the caller's ``jax.default_matmul_precision`` asks for more."""
    asked = jax.config.jax_default_matmul_precision
    return (jnp.bfloat16 if asked in (None, "default", "bfloat16")
            else jnp.float32)


def _calls(g):
    """The kernels' forward and backward for a decay of ``g``'s shape."""
    return ((kda_mosaic.scalar_forward, kda_mosaic.scalar_backward)
            if g.ndim == 3 else (kda_mosaic.forward, kda_mosaic.backward))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernel(q, k, v, g, beta, chunk, mxu, interpret):
    return _calls(g)[0](q, k, v, g, beta, chunk=chunk, mxu=mxu,
                        interpret=interpret, keep=False)[0]


def _kda_kernel_fwd(q, k, v, g, beta, chunk, mxu, interpret):
    out, kept = _calls(g)[0](q, k, v, g, beta, chunk=chunk, mxu=mxu,
                             interpret=interpret, keep=True)
    # named on the variables the backward reads, as ``_flash_vjp_fwd`` does
    out, states, inverses = map(checkpoint_name, (out, *kept), KEPT)
    return out, (q, k, v, g, beta, (states, inverses))


def _kda_kernel_bwd(chunk, mxu, interpret, res, do):
    return _calls(res[3])[1](*res, do, chunk=chunk, mxu=mxu,
                             interpret=interpret)


_kda_kernel.defvjp(_kda_kernel_fwd, _kda_kernel_bwd)


def _under_mesh(run, q, g):
    """``run`` inside ``shard_map`` over the mesh ``ps_tpu.init`` built, if
    any: batch over 'data' and heads over 'model' wherever the axis exists
    and divides (what it does not divide is computed replicated), as
    ``ops/flash_attention.py`` runs its kernels: GSPMD cannot partition a
    Mosaic call, and the plain form it replaces at these shapes could be.
    The heads are split by ``q``'s, so a key head stays with the value heads
    that read it; ``g`` is split as it comes, [B, T, H] or [B, T, H, K]."""
    from ps_tpu import api

    if not api.is_initialized() or api.current_context().mesh is None:
        return run
    mesh = api.current_context().mesh

    def axis(name, n):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and n % size == 0 else None

    wide = P(axis(DATA_AXIS, q.shape[0]), None, axis(MODEL_AXIS, q.shape[2]),
             None)
    specs = (wide,) * 3 + (P(*wide[:g.ndim]), P(*wide[:3]))
    # check_vma off for the reason flash_attention gives: jax 0.9.0 types
    # a kernel's VMEM scratch as unvarying
    return shard_map(run, mesh=mesh, in_specs=specs, out_specs=wide,
                     check_vma=False)


def kda(q, k, v, g, beta, *, chunk: int = 64):
    """``q``, ``k`` [B, T, H, K], ``v`` [B, T, H, V], ``g`` [B, T, H, K] the
    log-decays (<= 0, f32), ``beta`` [B, T, H] -> ``o`` [B, T, H, V] in
    ``v``'s dtype: the recurrence of the module docstring from a zero state,
    each sequence of the batch on its own. ``T`` must be a multiple of
    ``chunk`` and ``chunk`` of ``SUB``: a sequence is not padded here (a pad
    of ``beta`` 0, ``g`` 0 tokens at the end changes no output before it and
    is the caller's to add and cut). The recomputation is the caller's, who
    has a wider ``jax.checkpoint`` of its own around the call
    (``models/kimi_linear.py``). That caller's policy decides what of the
    kernels lives from forward to backward: ``save_only_these_names(*KEPT)``
    keeps the output, states and inverses (49 KB a token a layer at 32 heads
    of 128) and the forward call runs once; no policy keeps nothing and it
    runs twice. ``path`` says which realisation runs; off the chip the same
    kernels run in interpret mode (``ops/mosaic.py::interpret``), and under
    ``ps_tpu.init``'s mesh they run in ``shard_map`` (``_under_mesh``).

    Two special cases of the rule (``models/qwen3_next.py``'s gated delta
    rule is both): ``g`` [B, T, H], **one decay a head and a token**, is that
    decay in each of the head's ``K`` channels; ``q``, ``k`` [B, T, H / r, K],
    **fewer key heads than value heads**, are each read by the ``r`` value
    heads that follow one another (value head ``h`` reads key head
    ``h // r``). At the kernels' shapes a scalar decay takes kernels written
    for it, which read the operands as they come and return ``dq``, ``dk``
    [B, T, H / r, K] and ``dg`` [B, T, H] summed in VMEM
    (``path``: ``"scalar_kernel"``). Everywhere else (a decay a channel on
    fewer key heads, the plain form) both are broadcast in front of the call
    and computed as the general rule, exactly, and autodiff sums the
    cotangents back: ``dg`` over the channels, ``dq`` and ``dk`` over a key
    head's readers."""
    heads = v.shape[2]
    if heads % q.shape[2] or q.shape[2] != k.shape[2]:
        raise ValueError(f"kda: {q.shape[2]} query and {k.shape[2]} key "
                         f"heads for {heads} value heads: equal, and a "
                         f"divisor of the value heads")
    t = q.shape[1]
    if chunk % SUB or t % chunk:
        raise ValueError(f"kda: {t} tokens in chunks of {chunk}, sub-blocks "
                         f"of {SUB}: each must divide the one before")
    route = path(q, k, v, chunk, g)
    if route != "scalar_kernel":
        if q.shape[2] != heads:
            q, k = (jnp.repeat(x, heads // x.shape[2], axis=2)
                    for x in (q, k))
        if g.ndim == 3:
            g = jnp.broadcast_to(g[..., None], (*g.shape, q.shape[-1]))
    if route == "plain":
        run = functools.partial(_kda, chunk=chunk)
    else:
        run = _under_mesh(
            functools.partial(_kda_kernel, chunk=chunk, mxu=_mxu_dtype(),
                              interpret=mosaic.interpret()), q, g)
    return run(q, k, v, g, beta)
