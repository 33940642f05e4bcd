"""Flash attention as a Pallas TPU kernel.

Why this exists (BASELINE.md r5): at seq 512 the XLA-default attention
materializes the [B, h, S, S] probability tensor in HBM — at BERT-base
bench shapes that is ~200 MB of bf16 per layer per direction, which both
drops MFU (58.8% at seq 128 → 40.8% at seq 512) and OOMs batch 64. The
flash formulation (Dao et al.; online softmax over key blocks) keeps the
running (max, sum, accumulator) in VMEM and writes only the [S, d] output
and an [S] logsumexp per (batch, head) — O(S) memory, same math.

Design (TPU-first, per /opt/skills/guides/pallas_guide.md):

- FORWARD is the Pallas kernel: 3D grid (B*h, S/block_q, S/block_k) with
  the key-block axis INNERMOST, so the running (max, sum, accumulator)
  VMEM scratch persists across a query block's key steps while Mosaic
  stages the next key block's [block_k, d] K/V DMA. Dots run in the
  input dtype (bf16 on the MXU) with f32 accumulation. Causal masking
  skips the compute of key blocks fully past the diagonal via pl.when
  (their DMA still happens). Outputs: attention out and the logsumexp
  rows.
- BACKWARD is a custom VJP in blockwise JAX (Rabe & Staats style): exact
  probabilities are recomputed per key block from the saved logsumexp —
  never the full [S, S] — inside a lax.scan that accumulates dq and emits
  per-block dk/dv. XLA fuses each block's four matmuls; peak memory is
  O(S · block_k) per (b, h).

The padding mask is a [B, S] int/bool array (1 = attend), matching the
BERT convention; causal and mask compose. Numerics: parity with the
reference einsum attention is asserted to ~1e-5 f32 in
tests/test_flash_attention.py (CPU interpret mode runs the same kernel).

**On the current machine (TPU v5e, jax 0.9.0 / libtpu 0.0.34; PR 21's
chip_smoke.py, single runs)**: the kernel compiles through Mosaic at the
default 128-wide blocks and at 512-wide ones (the 128 ceiling BASELINE.md
r5 recorded belonged to an older compiler), matches the einsum attention
of models/bert.py at [32, 512, 12, 64] bf16 within 2 bf16 roundoffs of the
largest entry, forward and gradients, and carries BERT-base seq 512 through
``make_step`` on one chip and, inside ``shard_map``, on four. Its speed
against XLA's fused attention is not measured on the current machine
(ROADMAP.md S2 decides; BASELINE.md r5's 10.9 vs 17.6 ms/call predate it).
What flash delivers regardless is the O(S) attention memory, so the default
everywhere stays 'full'; switch to 'flash' when sequence length — not
arithmetic — is the binding constraint.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ps_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, block_k: int):
    """One (batch·head, q-block, kv-block) grid step. The kv dimension is
    the INNERMOST grid axis, so the (m, l, acc) VMEM scratch persists
    across a q-block's kv steps while Mosaic pipelines the next kv
    block's DMA behind this step's MXU work — the canonical flash
    structure. Dots run in the input dtype (bf16 on the MXU) with f32
    accumulation via preferred_element_type."""
    block_q = q_ref.shape[0]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: key blocks fully past this q block's diagonal contribute
    # nothing — skip their compute (their DMA still happens; acceptable)
    live = (j * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        # padding mask: column-broadcast of this block's key validity
        # (mask ref is [block_k, 1] — the trailing 1 satisfies TPU tiling)
        valid = mask_ref[:].astype(jnp.int32)
        s = jnp.where(valid.reshape(1, block_k) > 0, s, _NEG_INF)

        m = m_scr[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # gate, don't trust exp: on a fully-masked row m_new is _NEG_INF
        # itself, so exp(s - m_new) would be exp(0) = 1 for masked
        # entries — the gate keeps them at 0, which keeps l at 0 there
        # and makes the finalize zero-guard real (and consistent with the
        # backward's identical gate)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_scr[:, :1] = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, :1] = m_new

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_scr[:, :1]
        # fully-masked rows (all-pad keys) have l == 0: zeros, not NaN
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[:] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:, :1] + jnp.log(safe_l)  # [block_q, 1]


def _flash_fwd(q, k, v, mask, *, scale, causal, block_q, block_k,
               interpret):
    """q/k/v: [BH, S, d]; mask: [B, S] routed per program."""
    bh, seq, d = q.shape
    b = mask.shape[0]
    heads = bh // b
    grid = (bh, seq // block_q, seq // block_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_k=block_k
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh_, i, j: (bh_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_k, d), lambda bh_, i, j: (bh_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_k, d), lambda bh_, i, j: (bh_, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_k, 1),
                         lambda bh_, i, j: (bh_ // heads, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh_, i, j: (bh_, i, 0),
                         memory_space=pltpu.VMEM),
            # [BH, S, 1]: block (block_q, 1) satisfies the TPU tiling rule
            # (second-to-last divisible by 8, last equal to the array dim)
            pl.BlockSpec((None, block_q, 1), lambda bh_, i, j: (bh_, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v, mask[..., None])
    return out, lse[..., 0]


def _blockwise_bwd(q, k, v, mask, o, lse, do, *, scale, causal, block_k,
                   heads):
    """Exact flash backward, blockwise over keys — recomputes per-block
    probabilities from the saved logsumexp; never forms [S, S]."""
    bh, seq, d = q.shape
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    # D_i = sum_d dO_i * O_i  — the softmax-jacobian row term
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # [BH, S]
    qpos = jnp.arange(seq)[:, None]
    mask_bh = jnp.repeat(mask.astype(jnp.int32), heads, axis=0)  # [BH, S]

    num_blocks = seq // block_k

    def body(dq, j):
        sl = jax.lax.dynamic_slice_in_dim
        kj = sl(kf, j * block_k, block_k, axis=1)     # [BH, bk, d]
        vj = sl(vf, j * block_k, block_k, axis=1)
        mj = sl(mask_bh, j * block_k, block_k, axis=1)  # [BH, bk]
        s = jnp.einsum("bqd,bkd->bqk", qf, kj) * scale  # [BH, S, bk]
        kpos = j * block_k + jnp.arange(block_k)[None, :]
        if causal:
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        s = jnp.where(mj[:, None, :] > 0, s, _NEG_INF)
        # exact probs; the explicit gate keeps masked entries at 0 even on
        # fully-masked rows, where lse is itself _NEG_INF and the naive
        # exp(s - lse) would be exp(0) = 1
        p = jnp.where(s > _NEG_INF / 2,
                      jnp.exp(s - lse[..., None]), 0.0)
        dvj = jnp.einsum("bqk,bqd->bkd", p, dof)
        dp = jnp.einsum("bqd,bkd->bqk", dof, vj)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kj)
        dkj = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq, (dkj, dvj)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0, jnp.arange(num_blocks)
    )
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, seq, d)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, seq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, mask, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, mask, scale=scale, causal=causal,
                        block_q=block_q, block_k=block_k,
                        interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, mask, scale, causal, block_q, block_k,
                   interpret):
    out, lse = _flash_fwd(q, k, v, mask, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return out, (q, k, v, mask, out, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, mask, out, lse = res
    heads = q.shape[0] // mask.shape[0]
    dq, dk, dv = _blockwise_bwd(q, k, v, mask, out, lse, do, scale=scale,
                                causal=causal, block_k=block_k, heads=heads)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, mask: Optional[jax.Array] = None,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """Fused flash attention. ``q/k/v``: [B, S, h, d] (the model-side
    layout of ps_tpu/models/{bert,lm}.py); ``mask``: optional [B, S] with
    1 = attend (BERT padding convention); ``causal`` composes with it.
    Returns [B, S, h, d].

    ``interpret`` defaults to True off-TPU so tests exercise the same
    kernel logic on CPU. Sequence length must be divisible by the block
    sizes (pad to 128 — XLA-side attention pads the same way in practice).

    ``mesh`` defaults to the one ``ps_tpu.init`` built, if any. Under a
    mesh the kernel runs inside ``shard_map`` — batch over 'data', heads
    over 'model', wherever the axis exists and divides — because GSPMD
    cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned", first met on four chips in PR 21); a
    dimension its axis does not divide is computed replicated.
    """
    b, seq, h, d = q.shape
    if seq % block_q or seq % block_k:
        raise ValueError(
            f"seq len {seq} must be divisible by block_q={block_q} and "
            f"block_k={block_k} (pad the sequence)"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if mask is None:
        mask = jnp.ones((b, seq), jnp.int32)
    scale = d ** -0.5

    def local(q, k, v, mask):
        lb = q.shape[0]
        lh = q.shape[2]

        def pack(x):  # [B, S, h, d] -> [B*h, S, d]
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(lb * lh, seq, d)

        out = _flash(pack(q), pack(k), pack(v), mask, scale, causal,
                     block_q, block_k, interpret)
        return jnp.transpose(out.reshape(lb, lh, seq, d), (0, 2, 1, 3))

    if mesh is None:
        from ps_tpu import api

        mesh = api.current_context().mesh if api.is_initialized() else None
    if mesh is None:
        return local(q, k, v, mask)

    def axis(name, n):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and n % size == 0 else None

    spec = P(axis(DATA_AXIS, b), None, axis(MODEL_AXIS, h), None)
    # check_vma off: jax 0.9.0 types the kernel's VMEM scratch as unvarying
    # and refuses to mix it with the varying loads inside the kernel body
    # ("Primitive mul requires varying manual axes to match")
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec,
                                                 P(spec[0], None)),
                     out_specs=spec, check_vma=False)(q, k, v, mask)
