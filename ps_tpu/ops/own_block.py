"""Block diffusion's own-block term: a noised query's attention over the
noised keys of its own block, both directions, merged with what the strict
flash call gave the row of the earlier clean keys (``models/sdar.py``;
``q``, ``earlier`` [B, L, h, d], ``k``, ``v`` [B, L, h_kv, d], ``lse``
[B, L, h], the strict call's logsumexp, -1e30 where a row saw no key)::

    s       = q . k * d ** -0.5          over the ``block`` keys of q's block
    own_lse = logsumexp(s);   own = sum exp(s - own_lse) v
    total   = logaddexp(lse, own_lse)
    out     = exp(lse - total) * earlier + exp(own_lse - total) * own

Scores, both logsumexps, the weights, the weighted sum and the merge in f32;
the result in ``q``'s dtype. **Two realisations of these equations; ``path``
says from the shapes alone which runs.**

*The Mosaic calls* (``ops/own_block_mosaic.py``: one forward, one backward
under a ``jax.custom_vjp`` whose residuals are the five operands and nothing
else; what the SDAR cell runs). Heads of whole 128-lane tiles, a sequence of
whole 128-position tiles, a block that divides 128, q and k both bf16 or both
f32, one chip. A tile of 128 positions holds whole blocks, so the term is the
tile's queries over the tile's keys on the MXU under the block-diagonal mask,
and q, k, v, ``earlier`` and ``lse`` are read once and the output written
once: 219 MB a layer forward at the cell's shape (``[1, 8192, 32 on 4, 128]``
bf16) and about 375 MB backward, 0.27 and 0.46 ms at the HBM's 819 GB/s; the
calls take 0.43 and 0.75 ms (the module's table). The operands go in
head-major (``[B, h_kv, h / h_kv, L, d]``), which is how the rotation's and
the flash call's outputs lie on the chip: the transpositions here are
bitcasts in the compiled step, and XLA puts no copy of a ``[8192, 32, 128]``
array around the calls (``PERF.md`` section 6, PR 69, has the lists). Off the
chip the kernels' own bodies run interpreted (``ops/mosaic.py::interpret``).

*The XLA form* (``_xla``; every other shape, and the tests' oracle): products
and sums over the ``block`` keys as f32 broadcasts and lane reductions over
``[B, L / block, block, block, h_kv, h / h_kv, d]``, no matmul. Gradients are
JAX's own of it. At the cell's shape XLA makes twelve memory passes a layer
of it over ``[8192, 32, 128]`` f32 (7.1 ms a layer, forward, recomputation and
backward: ``PERF.md`` section 6, PR 50; alone 1.50 ms forward and 4.29 forward
and backward).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ps_tpu.ops import mosaic, own_block_mosaic


def path(q, k, block: int) -> str:
    """Which realisation ``q`` [B, L, h, d] on ``k`` [B, L, h_kv, d] takes at
    blocks of ``block``, read from the shapes, the dtypes and the mesh alone:
    ``"kernel"`` (``ops/own_block_mosaic.py``) where a head is whole 128-lane
    tiles, the sequence whole tiles of 128 positions, ``block`` divides 128,
    q and k are both bf16 or both f32 and the program is one chip's; else
    ``"xla"``."""
    from ps_tpu import api

    one_chip = not api.is_initialized() \
        or api.current_context().mesh.size == 1
    whole = q.shape[-1] % 128 == 0 and q.shape[1] % own_block_mosaic.TILE == 0 \
        and own_block_mosaic.TILE % block == 0
    return "kernel" if whole and one_chip and q.dtype == k.dtype and \
        q.dtype in (jnp.bfloat16, jnp.float32) else "xla"


def _head_major(q, k, v, earlier, lse):
    """The operands as the kernels take them: query-side [B, g, h / g, L, d],
    key-side [B, g, L, d], the logsumexp [B, g, h / g, L] in f32."""
    b, seq, h, d = q.shape
    g = k.shape[2]

    def wide(t):
        return jnp.transpose(t, (0, 2, 1, 3)).reshape(b, g, h // g, seq, d)

    def narrow(t):
        return jnp.transpose(t, (0, 2, 1, 3))

    return (wide(q), narrow(k), narrow(v), wide(earlier),
            jnp.transpose(lse.astype(jnp.float32), (0, 2, 1)).reshape(
                b, g, h // g, seq))


def _position_major(t):
    """A query-side result [B, g, h / g, L, d] back as [B, L, h, d]."""
    b, g, group, seq, d = t.shape
    return jnp.transpose(t.reshape(b, g * group, seq, d), (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel(q, k, v, earlier, lse, block, interpret):
    return own_block_mosaic.forward(q, k, v, earlier, lse, block=block,
                                    interpret=interpret)


def _kernel_fwd(q, k, v, earlier, lse, block, interpret):
    kept = (q, k, v, earlier, lse)
    return own_block_mosaic.forward(*kept, block=block,
                                    interpret=interpret), kept


def _kernel_bwd(block, interpret, kept, g):
    return tuple(own_block_mosaic.backward(*kept, g, block=block,
                                           interpret=interpret))


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def own_block(q, k, v, earlier, lse, block: int):
    """A noised query's attention over the noised keys of its own block,
    both directions, merged with what the kernel gave it of the earlier
    clean keys: ``q``, ``earlier`` [B, L, h, d], ``k``, ``v`` [B, L, h_kv,
    d], ``lse`` [B, L, h] (the kernel's logsumexp, -1e30 where a row saw no
    key). Scores, softmax and the merge in f32; the result in ``q``'s dtype;
    differentiable in all five. ``path`` says which realisation runs."""
    if path(q, k, block) != "kernel":
        return _xla(q, k, v, earlier, lse, block)
    return _position_major(_kernel(*_head_major(q, k, v, earlier, lse),
                                   block, mosaic.interpret()))


def _xla(q, k, v, earlier, lse, block: int):
    """The XLA form (module docstring): products and sums over the ``block``
    keys (no matmul of [block, d] x [d, block])."""
    b, seq, h, d = q.shape
    g = k.shape[2]
    n = seq // block
    f32 = jnp.float32
    # [B, n, query in block, key in block, K/V head, query head of it, d]
    qf = q.astype(f32).reshape(b, n, block, 1, g, h // g, d)
    kf = k.astype(f32).reshape(b, n, 1, block, g, 1, d)
    vf = v.astype(f32).reshape(b, n, 1, block, g, 1, d)
    s = jnp.sum(qf * kf, axis=-1) * (d ** -0.5)
    own_lse = jax.nn.logsumexp(s, axis=3, keepdims=True)
    own = jnp.sum(jnp.exp(s - own_lse)[..., None] * vf, axis=3)
    own, own_lse = own.reshape(b, seq, h, d), own_lse.reshape(b, seq, h)
    total = jnp.logaddexp(lse, own_lse)
    out = (jnp.exp(lse - total)[..., None] * earlier.astype(f32)
           + jnp.exp(own_lse - total)[..., None] * own)
    return out.astype(q.dtype)
