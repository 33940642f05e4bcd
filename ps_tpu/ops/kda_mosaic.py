"""The chunked gated delta rule of ``ops/kda.py`` as Mosaic (Pallas) kernels:
the state of a head stays in VMEM across the chunks of its sequence, and
everything a chunk computes on the way (cumulated decays, A, B, the inverse
and its products) lives and dies in VMEM too.

**One chunk is one pure function**, ``_chunk``: the chunk's q, k, v, g, beta
and the state entering it -> the chunk's outputs and the state leaving it,
each a list with one entry a head. The forward kernel calls it once a grid
step, on all the step's heads (their inverses are taken together, below);
the backward kernel takes ``jax.vjp`` of it a head at a time on the blocks
it has loaded, inside the kernel's body, so
the backward is the forward's own transposition and no second derivation
(every op of ``_chunk`` lowers in Mosaic in both directions: products,
elementwise, masks from iotas, concatenations). Four pieces carry a
transposition rule of their own: the products (``_product``: explicit
operand dtype in both directions), the splits (a slice transposes to a pad),
the run sums (three exact passes in both directions) and the solve (below).

**The decays by halving, not by pairs.** ``sum_c x_t[c] k_s[c] exp(G_t[c] -
G_s[c])`` needs each decay as a factor of t times a factor of s, both <= 1.
Two tokens t > s of a chunk lie in different halves of exactly one block of
2^l tokens (l = 1 .. 6): at that level both are referred to the last token
``m`` of the lower half, ``exp(G_t - G_m)`` and ``exp(G_m - G_s)``, each a
sum of log-decays over a run of tokens and so <= 1 whatever the decay (no
``exp`` of a positive number anywhere, and nothing assumed about how fast a
channel decays). A level is then one product [2C, K] x [K, C] (q's and k's
rows stacked) under the mask of its blocks, in place of the plain form's
[16, 16, K] pair tensors and their 2 x 1,024 lane reductions a chunk: eight
[C, K] exponentials where the pairs take sixty-four. The exponents
themselves are products of 0 / 1 run matrices with ``g`` (the cumulated sums
of the plain form, but each taken over its own run, so no difference of two
large cumulated decays is ever formed: the kernels sit at 4e-7 of the
token-by-token recurrence where the plain form sits at 6e-6).

**The inverse is computed once a pass and never transposed.** ``(I + A)^-1``
``= prod_i (I + P^(2^i))``, ``P = -A``, the plain form's product
(``_inverses``); the solve ``W = (I + A)^-1 R`` has the rule
``dR = (I + A)^-T dW``, ``dA = -dR W^T`` (two products where the squarings'
own transposition is twenty), and the forward that runs inside the backward
pass keeps the inverse [C, C] beside the state entering the chunk, so the
backward kernel loads both and computes neither.

**A chain of products costs its depth, so the chains of a grid step are
taken together and a level is one product.** The plain form's level is a
squaring and an update, ``P <- P P``, ``X <- X + X P``: ten [64, 64]
products a chunk, six of them one after another (the five squarings, then
the last update). Every factor is a polynomial in ``A``, so the factors
commute and the update may as well be ``X <- X + P X``: it then shares its
left operand with the next squaring and both are ``P @ [P | X] =
[P^2 | P X]``, one [64, 64] x [64, 128] product a level, six a chain, from
``[-A | I]``. The power stands in the left lanes, where a left operand is
read from, so no level moves a lane (with ``[X | P]`` every level rotates
64 lanes twice, and a rotation waits in the chain). Mosaic's scheduler lays
a product out as its weights' pushes, its six passes and their pops one
after another (about 200 cycles at 940 MHz, as many for the wide product as
for the narrow one: 1,223 cycles for the ten products and 1,174 for the six
alone, my chip runs, PR 62), and does not reach across the thousands of
operations between one head's chain and the next head's: a step's chains
ran one after the other, each waiting for its own results. So ``_chunk``
and ``_scalar_chunk`` make ``A`` for all their heads first and hand
``_inverses`` the list: it walks the levels once and issues every chain's
product at a level side by side, which the scheduler does overlap. With a
step's four chains together an inverse costs 367 cycles (398 in the scalar
kernels) where it cost 1,223, with two together 576 (``ops/kda.py``'s
tables). The order of
two commuting factors is all that changed of the arithmetic: the kept
inverses are the parent's to 1.5e-8 of their largest entry on the chip.

**Arithmetic classes** (``ops/kda.py``, "Precision"): state, decays,
exponentials, A, B, the inverse in f32. The levels inside a sub-block of
``SUB`` tokens (the plain form's f32 products on the VPU) and the inverse
with its products run on the MXU at the highest precision (six passes), the
run sums in three passes that are exact (0 / 1 times the three bf16 pieces
of an f32); the levels across sub-blocks and the products with the state are
``mxu``-class: operands rounded to bf16, one pass, f32 accumulation (what
XLA's default precision does on the chip to the plain form's), or f32 at the
highest precision where the caller's ``jax.default_matmul_precision`` asks
for it. Mosaic reads no such context (f32 operands and no ``precision`` give
one pass), so the class is a static argument and each product names its own
(``_product``): its backward rounds the cotangent as the default precision
would, and keeps the result in f32.

**One decay a head: a second chunk body, chosen by the decay's shape**
(``_scalar_chunk`` and the kernels below it, for ``g`` [B, T, H];
``ops/kda.py::path``). With one log-decay a token for all of a head's
channels, ``exp(G_t - G_s)`` comes out of the sum over the channels: it is
one [C, C] matrix ``D`` a value head, and ``A = beta * strict(K K^T * D)``,
``B = lower(Q K^T * D)``. So ``K K^T`` and ``Q K^T`` are one
[2C, K] x [K, C] product at the highest precision **a key head**, made once
and scaled by each of the value heads that read the key head (a grid step
holds whole key heads with all their readers, and the backward's ``dq`` and
``dk`` are summed over the readers in VMEM); no halving levels, no
sub-blocks and no [C, K] exponentials: every exponent of a causal pair is
<= 0 as it stands. The exponents ``G_t - G_s`` are still run sums of their
own and not differences of two cumulated decays: ``through @ (g * before)``,
a 0 / 1 matrix times the head's decays masked to the tokens after ``s``
(``_run_sums``: three exact passes), 0 on and above the diagonal, so the
mask is in the exponent where it is made. ``g`` comes as ``beta`` does, a
row of C a chunk and a head ([B, H, N, 1, C]), ``q exp(G)`` and
``k exp(G_C - G)`` are row scalings by [C, 1] columns summed on the VPU, and
``dg`` leaves as such a row. From ``A`` and ``B`` on the body is
``_chunk``'s, piece for piece (``_inverses``, ``_solve``, the three products
with the state), in the same arithmetic classes; what it keeps for the
backward has the per-channel kernels' shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: tokens of a sub-block: pairs inside one are f32-class products
SUB = 16
_F32 = jnp.float32
_FORMS = {"nn": (1, 0), "nt": (1, 1), "tn": (0, 0)}


def _dot(form: str, dtype, a, b):
    """``a @ b`` ('nn'), ``a @ b.T`` ('nt') or ``a.T @ b`` ('tn') with both
    operands in ``dtype`` (f32: the highest precision), accumulated in f32."""
    ca, cb = _FORMS[form]
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (((ca,), (cb,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _product(form, dtype, a, b):
    return _dot(form, dtype, a, b)


def _product_fwd(form, dtype, a, b):
    return _dot(form, dtype, a, b), (a, b)


def _product_bwd(form, dtype, res, ct):
    a, b = res
    dot = functools.partial(_dot, dtype=dtype)
    if form == "nn":
        return dot("nt", a=ct, b=b), dot("tn", a=a, b=ct)
    if form == "nt":
        return dot("nn", a=ct, b=b), dot("tn", a=ct, b=a)
    return dot("nt", a=b, b=ct), dot("nn", a=a, b=ct)


_product.defvjp(_product_fwd, _product_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _halves(x, axis):
    """``x`` cut in two along ``axis``; transposed by a concatenation (a
    slice's own transposition is a pad)."""
    return tuple(jnp.split(x, 2, axis=axis))


def _halves_fwd(x, axis):
    return _halves(x, axis), None


def _halves_bwd(axis, _, cts):
    return (jnp.concatenate(cts, axis=axis),)


_halves.defvjp(_halves_fwd, _halves_bwd)


def _thirds(x):
    """f32 ``x`` [R, N] as three bf16 pieces side by side [R, 3 N] whose sum
    is ``x`` to the last bit: 0 / 1 times a piece is exact on the MXU in
    one pass."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, low], axis=1)


def _whole(pieces):
    n = pieces.shape[1] // 3
    return pieces[:, :n] + pieces[:, n:2 * n] + pieces[:, 2 * n:]


@jax.custom_vjp
def _run_sums(runs, g):
    """``runs`` [R C, C] stacked 0 / 1 matrices (bf16), ``g`` [C, K] f32 ->
    the R sums ``run @ g`` [C, K], each accumulated in f32 from exact
    products: three passes for all of them, where a product of two f32
    operands at the highest precision takes six for each."""
    c = g.shape[0]
    sums = _whole(_dot("nn", jnp.bfloat16, runs, _thirds(g)))
    return tuple(sums[i * c:(i + 1) * c] for i in range(runs.shape[0] // c))


def _run_sums_fwd(runs, g):
    return _run_sums(runs, g), runs


def _run_sums_bwd(runs, cts):
    d_g = _whole(_dot("tn", jnp.bfloat16, runs,
                      _thirds(jnp.concatenate(cts, axis=0))))
    return jnp.zeros_like(runs), d_g


_run_sums.defvjp(_run_sums_fwd, _run_sums_bwd)


def _inverses(mats, eye):
    """``(I + a)^-1 = prod_i (I + (-a)^(2^i))`` for each strictly lower
    triangular ``a`` [C, C] of ``mats`` (``a^C = 0``), at the highest
    precision. A level is one product 2 C columns wide, ``P @ [P | X] =
    [P^2 | P X]``: the next power, and what the level adds to the inverse so
    far (from ``[-a | I]``; the first level's ``P I`` and the last one's
    square ride along). The chains of ``mats`` are taken level by level, one's
    product in flight while another's operands are made (the module
    docstring has why, and what each choice costs on the chip)."""
    c = eye.shape[0]
    left = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) < c
    boths = [jnp.concatenate([-a, eye], axis=1) for a in mats]
    for _ in range((c - 1).bit_length()):
        news = [_dot("nn", _F32, both[:, :c], both) for both in boths]
        boths = [jnp.where(left, new, both + new)
                 for new, both in zip(news, boths)]
    return [both[:, c:] for both in boths]


@jax.custom_vjp
def _solve(a, rhs, inverse):
    """``(I + a)^-1 rhs`` given ``inverse = (I + a)^-1``: with ``w`` the
    result, ``d rhs = inverse^T dw`` and ``da = -d rhs w^T`` (two products,
    where the squarings' own transposition is twenty)."""
    return _dot("nn", _F32, inverse, rhs)


def _solve_fwd(a, rhs, inverse):
    w = _solve(a, rhs, inverse)
    return w, (inverse, w)


def _solve_bwd(res, dw):
    inverse, w = res
    d_rhs = _dot("tn", _F32, inverse, dw)
    return -_dot("nt", _F32, d_rhs, w), d_rhs, jnp.zeros_like(inverse)


_solve.defvjp(_solve_fwd, _solve_bwd)


def _chunk(qs, ks, vs, gs, betas, states_t, inverses=None, *, mxu):
    """One chunk of some heads, a list an operand. For each head ``q``, ``k``,
    ``g`` [C, K], ``v`` [C, V], ``beta`` [1, C], all f32; ``state_t`` [V, K]
    the transposed state entering the chunk; ``inverse`` [C, C] the chunk's
    ``(I + A)^-1`` where an earlier pass kept it -> for each head (``o``
    [C, V], the transposed state leaving, ``inverse``), as three lists. What
    comes before the inverses is computed for every head, then the inverses
    together (``_inverses``), then the rest."""
    c = qs[0].shape[0]
    levels = range(1, c.bit_length())
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    stacked_t = jax.lax.broadcasted_iota(jnp.int32, (2 * c, c), 0) % c
    stacked_r = jax.lax.broadcasted_iota(jnp.int32, (2 * c, c), 1)
    eye = jnp.where(t == r, 1.0, 0.0)

    def upper(token, level):  # in the upper half of its block of 2^level
        return ((token >> (level - 1)) & 1) == 1

    # [t, r] is 1 where g_r is in the sum of row t: a level's log-decays run
    # from the last token m of the lower half of a block up to t (t above
    # it) or from t up to m (t in the lower half); then G's and G_C - G's
    runs = [((t >> (level - 1)) == (r >> (level - 1)))
            & (upper(t, level) == (r <= t)) for level in levels] \
        + [r <= t, r > t]
    runs = jnp.concatenate(
        [jnp.where(run, 1.0, 0.0).astype(jnp.bfloat16) for run in runs],
        axis=0)
    pairs = []
    for q, k, g, beta in zip(qs, ks, gs, betas):
        *sums, cum, to_end = _run_sums(runs, g)
        beta = jnp.sum(eye * beta, axis=1, keepdims=True)         # [C, 1]
        qk = jnp.concatenate([q, k], axis=0)
        products = jnp.zeros((2 * c, c), _F32)
        for level, run in zip(levels, sums):
            factor = jnp.exp(run)                                 # <= 1
            above = jnp.where(upper(row, level), factor, 0.0)
            level_pairs = _product(
                "nt", _F32 if (1 << level) <= SUB else mxu,
                qk * jnp.concatenate([above, above], axis=0),
                k * (factor - above))
            if (1 << level) < c:
                level_pairs = jnp.where(
                    (stacked_t >> level) == (stacked_r >> level),
                    level_pairs, 0.0)
            products = products + level_pairs
        b, a = _halves(products, 0)
        b = b + eye * jnp.sum(q * k, axis=1, keepdims=True)
        pairs.append((beta, beta * a, b, cum, to_end))
    if inverses is None:
        inverses = _inverses(
            [jax.lax.stop_gradient(a) for _, a, *_ in pairs], eye)
    outs, states = [], []
    for q, k, v, g, state_t, (beta, a, b, cum, to_end), inverse in zip(
            qs, ks, vs, gs, states_t, pairs, inverses):
        decay = jnp.exp(cum)
        w_v, w_k = _halves(_solve(a, jnp.concatenate(
            [beta * v, beta * k * decay], axis=1), inverse), 1)
        read_k, read_q = _halves(_product(
            "nt", mxu, jnp.concatenate([w_k, q * decay], axis=0), state_t), 0)
        u = w_v - read_k
        outs.append(read_q + _product("nn", mxu, b, u))
        states.append(
            state_t * jnp.exp(jnp.sum(g, axis=0, keepdims=True))
            + _product("tn", mxu, u, k * jnp.exp(to_end)))
    return outs, states, inverses


def _head(ref, j: int, heads: int):
    """Head ``j``'s lanes of a block [C, heads * width]."""
    width = ref.shape[1] // heads
    return slice(None), slice(j * width, (j + 1) * width)


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                    heads, mxu):
    """A grid step: one chunk of ``heads`` heads. ``rest`` is the output
    blocks of the states and the inverses (where the backward will want
    them) and the scratch that carries the states over the chunk axis."""
    *kept, carry = rest

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        carry[...] = jnp.zeros_like(carry)

    every = range(heads)
    if kept:
        for j in every:
            kept[0][j] = carry[j]
    outs, states, inverses = _chunk(
        *([ref[_head(ref, j, heads)].astype(_F32) for j in every]
          for ref in (q_ref, k_ref, v_ref, g_ref)),
        [beta_ref[j] for j in every], [carry[j] for j in every], mxu=mxu)
    for j, out, state, inverse in zip(every, outs, states, inverses):
        carry[j] = state
        if kept:
            kept[1][j] = inverse
        o_ref[_head(o_ref, j, heads)] = out.astype(o_ref.dtype)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                     inverses_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                     dbeta_ref, carry, *, heads, mxu):
    """A grid step: one chunk of ``heads`` heads, a head at a time, the
    chunks walked from the last to the first; ``carry`` the cotangent of the
    state leaving the chunk."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        carry[...] = jnp.zeros_like(carry)

    for j in range(heads):
        inverse = inverses_ref[j]
        _, transposed = jax.vjp(
            lambda *a: _chunk(*a, [inverse], mxu=mxu)[:2],
            *([ref[_head(ref, j, heads)].astype(_F32)]
              for ref in (q_ref, k_ref, v_ref, g_ref)),
            [beta_ref[j]], [states_ref[j]])
        *cotangents, (dbeta_ref[j],), (carry[j],) = transposed(
            ([do_ref[_head(do_ref, j, heads)].astype(_F32)], [carry[j]]))
        for ref, (ct,) in zip((dq_ref, dk_ref, dv_ref, dg_ref), cotangents):
            ref[_head(ref, j, heads)] = ct.astype(ref.dtype)


def heads_a_step(heads: int) -> int:
    """Heads one grid step computes: their inverses' chains are taken
    together (``_inverses``), and one step's fixed cost is shared."""
    return next(n for n in (4, 2, 1) if heads % n == 0)


def _specs(b, t, h, width, v_width, chunk, reverse: bool):
    """The grid (batch, groups of heads, chunks) and the block specs of a
    [B, T, H * width] operand (as the projections write it: no transposed
    copy is made), of one at the values' width, of ``beta``, of the states
    and of the inverses, those three [B, H, N, ...]."""
    n = t // chunk
    per = heads_a_step(h)

    def at(i):
        return n - 1 - i if reverse else i

    def tokens(lanes):
        return pl.BlockSpec((None, chunk, per * lanes),
                            lambda b_, h_, i: (b_, at(i), h_),
                            memory_space=pltpu.VMEM)

    def a_head(*block):
        return pl.BlockSpec((None, per, None) + block,
                            lambda b_, h_, i: (b_, h_, at(i), 0, 0),
                            memory_space=pltpu.VMEM)

    return ((b, h // per, n), per, tokens(width), tokens(v_width),
            a_head(1, chunk), a_head(v_width, width), a_head(chunk, chunk))


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _rows(beta, chunk):  # [B, T, H] -> [B, H, N, 1, C]
    b, t, h = beta.shape
    return jnp.transpose(beta, (0, 2, 1)).reshape(b, h, t // chunk, 1, chunk)


def forward(q, k, v, g, beta, *, chunk, mxu, interpret, keep: bool):
    """``ops/kda.py::kda``'s operands -> ``o`` [B, T, H, V] in ``v``'s dtype
    and, where ``keep``, what the backward wants of every chunk: the
    transposed state entering it [B, H, N, V, K] and its ``(I + A)^-1``
    [B, H, N, C, C], both f32 (else ``()``)."""
    b, t, h, width = q.shape
    v_width = v.shape[-1]
    n = t // chunk
    grid, per, wide, v_wide, row, state, square = _specs(
        b, t, h, width, v_width, chunk, reverse=False)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, heads=per, mxu=mxu),
        grid=grid,
        in_specs=[wide, wide, v_wide, wide, row],
        out_specs=[v_wide] + [state, square] * keep,
        out_shape=[jax.ShapeDtypeStruct((b, t, h * v_width), v.dtype)]
        + [jax.ShapeDtypeStruct((b, h, n, v_width, width), _F32),
           jax.ShapeDtypeStruct((b, h, n, chunk, chunk), _F32)] * keep,
        scratch_shapes=[pltpu.VMEM((per, v_width, width), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
    )(q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
      g.reshape(b, t, -1), _rows(beta.astype(_F32), chunk))
    return out[0].reshape(b, t, h, v_width), tuple(out[1:])


def backward(q, k, v, g, beta, kept, do, *, chunk, mxu, interpret):
    """The cotangents of the five operands, each in its operand's dtype and
    shape, from what ``forward`` kept and the output's cotangent."""
    b, t, h, width = q.shape
    v_width = v.shape[-1]
    grid, per, wide, v_wide, row, state, square = _specs(
        b, t, h, width, v_width, chunk, reverse=True)
    flat = [x.reshape(b, t, -1) for x in (q, k, v, g)]
    rows = _rows(beta.astype(_F32), chunk)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, heads=per, mxu=mxu),
        grid=grid,
        in_specs=[wide, wide, v_wide, wide, row, state, square, v_wide],
        out_specs=[wide, wide, v_wide, wide, row],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in flat + [rows]],
        scratch_shapes=[pltpu.VMEM((per, v_width, width), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
    )(*flat, rows, *kept, do.reshape(b, t, -1))
    dbeta = jnp.transpose(dbeta.reshape(b, h, t), (0, 2, 1))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta.astype(beta.dtype))


# -- one decay a head, a key head read by several value heads -------------------

def _scalar_chunk(qs, ks, vs, gs, betas, states_t, inverses=None, *, mxu):
    """One chunk of some key heads and the value heads that read them, the
    decay one scalar a head and a token. For each key head ``q``, ``k``
    [C, K] f32, read once; for each value head (value head ``i`` reads key
    head ``i // readers``) ``v`` [C, V], ``g`` and ``beta`` [1, C],
    ``state_t`` [V, K] and, where an earlier pass kept it, ``inverse``
    [C, C] -> for each value head (``o`` [C, V], the transposed state
    leaving, ``inverse``), as three lists. The equations are ``_chunk``'s
    with ``exp(G_t - G_s)`` pulled out of the sums over the channels, and its
    order: what comes before the inverses for every head, the inverses
    together, the rest."""
    c = qs[0].shape[0]
    readers = len(vs) // len(qs)
    t = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = jnp.where(t == r, 1.0, 0.0)
    through = jnp.where(r <= t, 1.0, 0.0)          # [t, r]: g_r is in G_t
    after = 1.0 - through                          # [t, r]: g_r is in G_C - G_t
    before = through - eye                         # [t, r]: r in front of t
    runs = through.astype(jnp.bfloat16)
    pairs = []
    for j, (q, k) in enumerate(zip(qs, ks)):
        # every pair of tokens of the key head, once for all its readers
        qk, kk = _halves(
            _product("nt", _F32, jnp.concatenate([q, k], axis=0), k), 0)
        mine = slice(j * readers, (j + 1) * readers)
        for g, beta in zip(gs[mine], betas[mine]):
            column = jnp.sum(eye * g, axis=1, keepdims=True)      # [C, 1]
            # [t, s] = g_{s+1} + .. + g_t, a run sum of its own (0 on and
            # above the diagonal: the exponent is masked where it is made)
            exponent, = _run_sums(runs, column * before)
            pair = through * jnp.exp(exponent)                    # <= 1
            beta = jnp.sum(eye * beta, axis=1, keepdims=True)     # [C, 1]
            pairs.append((beta, beta * before * kk * pair, qk * pair))
    if inverses is None:
        inverses = _inverses(
            [jax.lax.stop_gradient(a) for _, a, _ in pairs], eye)
    outs, states = [], []
    for i, (v, g, state_t, (beta, a, b), inverse) in enumerate(zip(
            vs, gs, states_t, pairs, inverses)):
        q, k = qs[i // readers], ks[i // readers]
        decay = jnp.exp(jnp.sum(through * g, axis=1, keepdims=True))
        to_end = jnp.exp(jnp.sum(after * g, axis=1, keepdims=True))
        w_v, w_k = _halves(_solve(a, jnp.concatenate(
            [beta * v, beta * decay * k], axis=1), inverse), 1)
        read_k, read_q = _halves(_product(
            "nt", mxu, jnp.concatenate([w_k, q * decay], axis=0), state_t), 0)
        u = w_v - read_k
        outs.append(read_q + _product("nn", mxu, b, u))
        states.append(
            state_t * jnp.exp(jnp.sum(g, axis=1, keepdims=True))
            + _product("tn", mxu, u, k * to_end))
    return outs, states, inverses


def _key_heads(some, keys: int, readers: int, q_ref, k_ref, v_ref, g_ref,
               beta_ref):
    """Key heads ``some`` of a grid step's ``keys``: the value heads that
    read them, and their operands as ``_scalar_chunk`` takes them (a list a
    key head of q and of k, a list a value head of v, g and beta)."""
    mine = [i for j in some for i in range(j * readers, (j + 1) * readers)]
    return mine, (
        *([ref[_head(ref, j, keys)].astype(_F32) for j in some]
          for ref in (q_ref, k_ref)),
        [v_ref[_head(v_ref, i, keys * readers)].astype(_F32) for i in mine],
        [g_ref[i] for i in mine], [beta_ref[i] for i in mine])


def _scalar_forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                           keys, readers, mxu):
    """A grid step: one chunk of ``keys`` key heads, each with its
    ``readers`` value heads, all in one ``_scalar_chunk``; ``rest`` as
    ``_forward_kernel``'s."""
    *kept, carry = rest
    values = keys * readers

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        carry[...] = jnp.zeros_like(carry)

    mine, operands = _key_heads(range(keys), keys, readers, q_ref, k_ref,
                                v_ref, g_ref, beta_ref)
    if kept:
        for i in mine:
            kept[0][i] = carry[i]
    outs, states, inverses = _scalar_chunk(
        *operands, [carry[i] for i in mine], mxu=mxu)
    for i, out, state, inverse in zip(mine, outs, states, inverses):
        carry[i] = state
        if kept:
            kept[1][i] = inverse
        o_ref[_head(o_ref, i, values)] = out.astype(o_ref.dtype)


def _scalar_backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                            inverses_ref, do_ref, dq_ref, dk_ref, dv_ref,
                            dg_ref, dbeta_ref, carry, *, keys, readers, mxu):
    """A grid step of the backward pass, as ``_backward_kernel``'s, a key
    head at a time: its ``dq`` and ``dk`` are the sums over its readers
    (``jax.vjp`` of the chunk adds them here, in VMEM), ``dg`` a row a value
    head."""
    values = keys * readers

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        carry[...] = jnp.zeros_like(carry)

    for j in range(keys):
        mine, operands = _key_heads([j], keys, readers, q_ref, k_ref, v_ref,
                                    g_ref, beta_ref)
        inverses = [inverses_ref[i] for i in mine]
        _, transposed = jax.vjp(
            lambda *a: _scalar_chunk(*a, inverses, mxu=mxu)[:2],
            *operands, [states_ref[i] for i in mine])
        (dq,), (dk,), dvs, dgs, dbetas, dstates = transposed(
            ([do_ref[_head(do_ref, i, values)].astype(_F32) for i in mine],
             [carry[i] for i in mine]))
        dq_ref[_head(dq_ref, j, keys)] = dq.astype(dq_ref.dtype)
        dk_ref[_head(dk_ref, j, keys)] = dk.astype(dk_ref.dtype)
        for i, dv, dg, dbeta, dstate in zip(mine, dvs, dgs, dbetas, dstates):
            dv_ref[_head(dv_ref, i, values)] = dv.astype(dv_ref.dtype)
            dg_ref[i], dbeta_ref[i], carry[i] = dg, dbeta, dstate


def keys_a_step(keys: int, readers: int) -> int:
    """Key heads one grid step computes, each whole with its readers:
    ``heads_a_step``'s four value heads where the readers allow."""
    return next(n for n in (4, 2, 1)
                if keys % n == 0 and (n * readers <= 4 or n == 1))


def _scalar_specs(b, t, keys, readers, width, v_width, chunk, reverse: bool):
    """``_specs`` for ``keys`` key heads of ``readers`` value heads each: the
    grid (batch, groups of key heads, chunks), the key heads of a step, and
    the block specs of q and k [B, T, Hk * width], of v [B, T, H * v_width]
    and of the rows, the states and the inverses [B, H, N, ...], a step's
    value heads those of its key heads."""
    n = t // chunk
    per = keys_a_step(keys, readers)

    def at(i):
        return n - 1 - i if reverse else i

    def tokens(lanes):
        return pl.BlockSpec((None, chunk, lanes),
                            lambda b_, h_, i: (b_, at(i), h_),
                            memory_space=pltpu.VMEM)

    def a_head(*block):
        return pl.BlockSpec((None, per * readers, None) + block,
                            lambda b_, h_, i: (b_, h_, at(i), 0, 0),
                            memory_space=pltpu.VMEM)

    return ((b, keys // per, n), per, tokens(per * width),
            tokens(per * readers * v_width), a_head(1, chunk),
            a_head(v_width, width), a_head(chunk, chunk))


def scalar_forward(q, k, v, g, beta, *, chunk, mxu, interpret, keep: bool):
    """``forward`` at the scalar-decay rule's own shapes: ``q``, ``k``
    [B, T, Hk, K], ``v`` [B, T, H, V], ``g`` and ``beta`` [B, T, H], value
    head ``h`` reading key head ``h // (H / Hk)``. Returns what ``forward``
    does, in the same shapes."""
    b, t, keys, width = q.shape
    h, v_width = v.shape[2:]
    n, readers = t // chunk, h // keys
    grid, per, wide, v_wide, row, state, square = _scalar_specs(
        b, t, keys, readers, width, v_width, chunk, reverse=False)
    out = pl.pallas_call(
        functools.partial(_scalar_forward_kernel, keys=per, readers=readers,
                          mxu=mxu),
        grid=grid,
        in_specs=[wide, wide, v_wide, row, row],
        out_specs=[v_wide] + [state, square] * keep,
        out_shape=[jax.ShapeDtypeStruct((b, t, h * v_width), v.dtype)]
        + [jax.ShapeDtypeStruct((b, h, n, v_width, width), _F32),
           jax.ShapeDtypeStruct((b, h, n, chunk, chunk), _F32)] * keep,
        scratch_shapes=[pltpu.VMEM((per * readers, v_width, width), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
    )(q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1),
      _rows(g.astype(_F32), chunk), _rows(beta.astype(_F32), chunk))
    return out[0].reshape(b, t, h, v_width), tuple(out[1:])


def scalar_backward(q, k, v, g, beta, kept, do, *, chunk, mxu, interpret):
    """``backward`` at the scalar-decay rule's own shapes: ``dq`` and ``dk``
    at the key heads, ``dg`` and ``dbeta`` [B, T, H]."""
    b, t, keys, width = q.shape
    h, v_width = v.shape[2:]
    readers = h // keys
    grid, per, wide, v_wide, row, state, square = _scalar_specs(
        b, t, keys, readers, width, v_width, chunk, reverse=True)
    flat = [x.reshape(b, t, -1) for x in (q, k, v)]
    rows = [_rows(x.astype(_F32), chunk) for x in (g, beta)]
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_scalar_backward_kernel, keys=per, readers=readers,
                          mxu=mxu),
        grid=grid,
        in_specs=[wide, wide, v_wide, row, row, state, square, v_wide],
        out_specs=[wide, wide, v_wide, row, row],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in flat + rows],
        scratch_shapes=[pltpu.VMEM((per * readers, v_width, width), _F32)],
        compiler_params=_SEMANTICS, interpret=interpret,
    )(*flat, *rows, *kept, do.reshape(b, t, -1))
    dg, dbeta = (jnp.transpose(x.reshape(b, h, t), (0, 2, 1)).astype(a.dtype)
                 for x, a in ((dg, g), (dbeta, beta)))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg, dbeta)
