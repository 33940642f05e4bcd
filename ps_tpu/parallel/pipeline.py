"""Pipeline parallelism: SPMD GPipe over a 'pipe' mesh axis.

The reference family scales parameters across servers and batch across
workers; pipeline parallelism is the third axis large models need. The
TPU-native shape (no per-stage processes, no point-to-point sends coded by
hand): every stage's parameters are STACKED along a leading stage dimension
and sharded ``P('pipe', ...)`` — each mesh slice holds exactly its stage —
and one ``shard_map`` program runs the classic GPipe schedule: at tick t a
stage applies itself to its current microbatch and hands the activation to
its ring neighbor via ``lax.ppermute``. ``M`` microbatches drain in
``M + S - 1`` ticks (the usual fill/drain bubble of S-1 ticks).

Everything is differentiable: ``jax.grad`` through the scan reverses the
permutes, giving the pipeline backward pass for free, so the fused PS step
(grad + psum + sharded apply) wraps a pipelined model exactly like any
other. Composes with the 'data' axis (microbatches are data-sharded) and
with ``partition_rules`` for the stage placement
(:func:`pipeline_partition_rules`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

PIPE_AXIS = "pipe"


def stack_stage_params(stage_params: Sequence[Any]) -> Any:
    """Stack S per-stage parameter trees (identical structure) along a new
    leading stage dimension — the tree the PS store registers and shards
    ``P('pipe', ...)``."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stage_params
    )


def pipeline_partition_rules(max_rank: int = 4, pattern: str = ".*"):
    """Rules placing every stacked-stage leaf's LEADING dim on 'pipe' (one
    rule per rank; rank-mismatched rules are skipped by the matcher)."""
    return [
        (pattern, ("pipe",) + (None,) * r) for r in range(max_rank)
    ]


def _gpipe_block(stage_params, x, *, stage_fn, axis: str, microbatches: int):
    """Per-shard GPipe schedule (inside shard_map).

    stage_params: THIS stage's params (leading stage dim already stripped
    by the P('pipe', ...) in_spec). x: [M, mb, ...] microbatches (every
    stage sees them; only stage 0 reads them — keeps the spec simple).
    Returns [M, mb, ...] final-stage outputs, replicated over the axis.
    """
    size = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(j, (j + 1) % size) for j in range(size)]
    mb_shape = x.shape[1:]
    # the P('pipe', ...) in_spec leaves a size-1 leading stage dim on the
    # local block; strip it so stage_fn sees one stage's params
    stage_params = jax.tree_util.tree_map(lambda l: l[0], stage_params)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 injects microbatch t (zeros once drained); others take
        # the neighbor's activation arriving in `state`
        mb_idx = jnp.minimum(t, microbatches - 1)
        inject = jnp.where(t < microbatches, x[mb_idx],
                           jnp.zeros(mb_shape, x.dtype))
        inp = jnp.where(idx == 0, inject, state)
        y = stage_fn(stage_params, inp)
        # the LAST stage emits microbatch t-(S-1) at tick t
        out_t = t - (size - 1)
        is_out = (idx == size - 1) & (out_t >= 0)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_out, y,
                      jax.lax.dynamic_index_in_dim(
                          outputs, jnp.maximum(out_t, 0), 0, keepdims=False)),
            jnp.maximum(out_t, 0), 0,
        )
        state = jax.lax.ppermute(y, axis, perm)
        return (state, outputs), None

    # the carry must share the loop outputs' device-variance (y varies with
    # this shard's stage params over 'pipe' AND with the data-sharded x over
    # the batch axis; literal zeros are invariant and fail the scan carry
    # type check) — mix in zeros DERIVED from both to inherit exactly that
    # variance
    vz = (jax.tree_util.tree_leaves(stage_params)[0].ravel()[0] * 0).astype(
        x.dtype
    ) + x.ravel()[0] * 0
    state0 = jnp.zeros(mb_shape, x.dtype) + vz
    out0 = jnp.zeros((microbatches,) + mb_shape, x.dtype) + vz
    (_, outputs), _ = jax.lax.scan(
        tick, (state0, out0), jnp.arange(microbatches + size - 1)
    )
    # replicate the last stage's outputs to every shard (out_spec P())
    return jax.lax.psum(
        jnp.where(idx == size - 1, outputs, jnp.zeros_like(outputs)), axis
    )


def make_pipeline_fn(stage_fn: Callable, mesh: Optional[Mesh] = None, *,
                     microbatches: int, axis: str = PIPE_AXIS,
                     batch_axis: Optional[str] = "data") -> Callable:
    """Build ``fn(stacked_params, x_microbatches) -> outputs``.

    Args:
      stage_fn: ``stage_fn(one_stage_params, activations) -> activations``
        — the repeated block (all stages share one structure; make layer-0
        embed / layer-N readout part of the loss instead, or branch inside
        on data you pack into the params).
      mesh: defaults to the live context mesh.
      microbatches: M; inputs are [M, mb, ...], outputs [M, mb, ...].
      axis: the stage axis name.
      batch_axis: mesh axis the per-microbatch dim (dim 1) shards over —
        each data slice pipelines only its batch rows, so widening 'data'
        really divides per-device work. ``None`` replicates the batch.

    The returned fn is jit-compatible and differentiable; stacked params
    must be sharded ``P('pipe', ...)`` (see :func:`pipeline_partition_rules`).
    """
    if mesh is None:
        from ps_tpu.api import current_context

        mesh = current_context().mesh
    if batch_axis is not None and mesh.shape.get(batch_axis, 1) <= 1:
        batch_axis = None
    block = functools.partial(_gpipe_block, stage_fn=stage_fn, axis=axis,
                              microbatches=microbatches)
    x_spec = P(None, batch_axis)  # [M, mb, ...]: mb rows over the data axis

    def fn(stacked_params, x):
        if x.shape[0] != microbatches:
            raise ValueError(
                f"x carries {x.shape[0]} microbatches but this pipeline was "
                f"built with microbatches={microbatches} — a clamped "
                f"schedule would silently duplicate data"
            )
        param_specs = jax.tree_util.tree_map(
            lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params
        )
        run = shard_map(
            block, mesh=mesh,
            in_specs=(param_specs, x_spec), out_specs=x_spec,
        )
        return run(stacked_params, x)

    return fn


def microbatch(batch: Any, microbatches: int) -> Any:
    """[B, ...] -> [M, B/M, ...] on every leaf."""

    def split(x):
        b = x.shape[0]
        if b % microbatches:
            raise ValueError(
                f"batch {b} not divisible by microbatches={microbatches}"
            )
        return x.reshape((microbatches, b // microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)
