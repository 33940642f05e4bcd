"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context scaling on TPU (SURVEY.md §6 noted the natural slot: "a 'seq'
mesh axis with shard_map ring attention"). The PS data plane is untouched —
these are drop-in attention ops for models whose ACTIVATIONS are sharded
along a ``'seq'`` mesh axis, composing freely with the 'data' (batch) and
'model' (TP) axes:

- :func:`ring_attention` — bandwidth-optimal: K/V blocks rotate around the
  ring via ``lax.ppermute`` (one neighbor hop per step, riding ICI
  neighbor links), scores accumulate with a numerically-stable online
  softmax (flash-style running max/denominator). Works for any head count;
  causal masking skips nothing but masks exactly (global positions).
- :func:`ulysses_attention` — simplest: two ``lax.all_to_all`` calls swap
  the sharded dimension (sequence ↔ heads), each device computes FULL
  attention for its head slice. Needs ``num_heads %% seq_axis_size == 0``.

Both are pure functions of [B, T_local, H, D] blocks inside ``shard_map``;
the wrappers below take GLOBAL [B, T, H, D] arrays sharded with
``P(batch_axis, seq_axis, ...)`` and return the same sharding. Numerics are
asserted against single-device full attention in tests/test_ring_attention.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SEQ_AXIS = "seq"

_NEG = -1e30  # mask value: large-negative beats -inf (no NaN in exp paths)


def _block_scores(q, k, scale, causal, q_start, k_start):
    """[B,H,Tq,Tk] scores of one (q block, k block) pair, causally masked in
    GLOBAL positions when asked."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        qpos = q_start + jnp.arange(tq)[:, None]
        kpos = k_start + jnp.arange(tk)[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG)
    return s


def _ring_attention_block(q, k, v, *, axis: str, causal: bool, scale: float):
    """Per-shard ring attention (call inside shard_map; q/k/v local blocks
    [B, T_local, H, D])."""
    size = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    t_local = q.shape[1]
    b, h = q.shape[0], q.shape[2]
    perm = [(j, (j + 1) % size) for j in range(size)]

    del b, h
    # the carry must be device-varying over the SAME manual axes as the loop
    # outputs (shard_map tracks variance; a literal jnp.zeros((shape)) is
    # axis-invariant and fails the fori_loop carry type check). Anything
    # DERIVED from q inherits q's variance: zeros_like(q) for the
    # q-shaped numerator, a sliced-and-scaled q for the [B, H, T]-shaped
    # max/denominator accumulators (no q-shaped zeros_like fits those).
    zero_bht = q[..., 0].transpose(0, 2, 1) * 0             # [B, H, T_local]
    m0 = zero_bht + _NEG                                    # running max
    l0 = zero_bht                                           # denominator
    o0 = jnp.zeros_like(q)                                  # numerator

    def accumulate(i, m, l, o, k_cur, v_cur):
        # after i hops this device holds the K/V block of ring neighbor
        # (idx - i) — its global offset positions the causal mask
        src = (idx - i) % size
        s = _block_scores(q, k_cur, scale, causal,
                          idx * t_local, src * t_local)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)                      # rescale old sums
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_cur
        )
        return m_new, l, o

    def body(i, carry):
        m, l, o, k_cur, v_cur = carry
        m, l, o = accumulate(i, m, l, o, k_cur, v_cur)
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return m, l, o, k_nxt, v_nxt

    # size-1 hops inside the loop; the LAST block accumulates outside so no
    # K/V rotation is paid for a carry nobody reads (XLA can't DCE a
    # collective inside the loop body)
    m, l, o, k_last, v_last = jax.lax.fori_loop(
        0, size - 1, body, (m0, l0, o0, k, v)
    )
    m, l, o = accumulate(size - 1, m, l, o, k_last, v_last)
    # causal first tokens attend to >=1 key, so l > 0 always; guard anyway
    l = jnp.maximum(l, 1e-30)
    return o / l.transpose(0, 2, 1)[..., None]


def _ulysses_attention_block(q, k, v, *, axis: str, causal: bool,
                             scale: float):
    """Per-shard Ulysses attention: a2a swaps seq-sharded -> head-sharded,
    full attention on the local head slice, a2a back."""
    size = jax.lax.axis_size(axis)

    def seq_to_heads(x):  # [B, T/s, H, D] -> [B, T, H/s, D]
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):  # [B, T, H/s, D] -> [B, T/s, H, D]
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    s = _block_scores(qg, kg, scale, causal, 0, 0)
    p = jax.nn.softmax(s, axis=-1)
    og = jnp.einsum("bhqk,bkhd->bqhd", p, vg)
    del size
    return heads_to_seq(og)


def _wrap(block_fn, x_args, mesh, batch_axis, seq_axis):
    spec = P(batch_axis, seq_axis, None, None)
    fn = shard_map(block_fn, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(*x_args)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mesh: Optional[Mesh] = None, *, causal: bool = False,
                   seq_axis: str = SEQ_AXIS, batch_axis: Optional[str] = "data",
                   scale: Optional[float] = None) -> jax.Array:
    """Attention over GLOBAL [B, T, H, D] arrays sequence-sharded on
    ``seq_axis``. K/V blocks rotate the ring; per-device memory is
    O(T/seq · T/seq) per block pair instead of O(T²).

    Jit-friendly: call inside or outside jit; the output keeps the input's
    sharding (batch on ``batch_axis``, sequence on ``seq_axis``).
    """
    if mesh is None:
        from ps_tpu.api import current_context

        mesh = current_context().mesh
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block = functools.partial(_ring_attention_block, axis=seq_axis,
                              causal=causal, scale=scale)
    return _wrap(block, (q, k, v), mesh, batch_axis, seq_axis)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      mesh: Optional[Mesh] = None, *, causal: bool = False,
                      seq_axis: str = SEQ_AXIS,
                      batch_axis: Optional[str] = "data",
                      scale: Optional[float] = None) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism: swap the
    sharded dim from sequence to heads, run full per-head attention, swap
    back. Requires ``H %% mesh.shape[seq_axis] == 0``."""
    if mesh is None:
        from ps_tpu.api import current_context

        mesh = current_context().mesh
    size = mesh.shape[seq_axis]
    if q.shape[2] % size:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the "
            f"'{seq_axis}' axis ({size}); use ring_attention otherwise"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block = functools.partial(_ulysses_attention_block, axis=seq_axis,
                              causal=causal, scale=scale)
    return _wrap(block, (q, k, v), mesh, batch_axis, seq_axis)


def sequence_sharding(mesh: Mesh, seq_axis: str = SEQ_AXIS,
                      batch_axis: Optional[str] = "data") -> NamedSharding:
    """Placement for [B, T, ...] activations: batch over ``batch_axis``,
    sequence over ``seq_axis``."""
    return NamedSharding(mesh, P(batch_axis, seq_axis))
