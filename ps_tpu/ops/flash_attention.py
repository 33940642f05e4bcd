"""Flash attention as a Pallas TPU kernel.

Why this exists (BASELINE.md r5): at seq 512 the XLA-default attention
materializes the [B, h, S, S] probability tensor in HBM — at BERT-base
bench shapes that is ~200 MB of bf16 per layer per direction, which both
drops MFU (58.8% at seq 128 → 40.8% at seq 512) and OOMs batch 64. The
flash formulation (Dao et al.; online softmax over key blocks) keeps the
running (max, sum, accumulator) in VMEM and writes only the [S, d] output
and an [S] logsumexp per (batch, head) — O(S) memory, same math.

Design (TPU-first, per /opt/skills/guides/pallas_guide.md):

- FORWARD is the Pallas kernel: 3D grid (B*h, S/block_q, S/block_k),
  ("parallel", "parallel", "arbitrary"), with the key-block axis INNERMOST,
  so the running (max, sum, accumulator) VMEM scratch persists across a
  query block's key steps while Mosaic stages the next key block's
  [block_k, d] K/V DMA. Dots run in the input dtype (bf16 on the MXU) with
  f32 accumulation. With one key block the step softmaxes whole rows and
  the kernel has no scratch and no rescaling. Causal masking skips the
  compute of key blocks fully past the diagonal via pl.when, and their
  K/V and mask index maps stop at the last live block (``_last_live``, the
  same expression as the skip), so a dead step copies nothing. Outputs:
  attention out and the logsumexp rows.
- The TILES are ``forward_tiles``' choice from (seq, head_dim, itemsize,
  causal): among the divisors of seq that are multiples of 128, the widest
  key block and then the widest query block whose grid step fits
  ``_VMEM_BUDGET`` (13 MiB by ``forward_vmem_bytes``' count, of Mosaic's
  16 MiB scoped default: the widest tile the rule can reach counts 12.9 MiB
  and compiles; one counting 21.6 MiB is refused), a causal call keeping
  block_k <= block_q. Key block first because a step's cost is mostly per
  query row (the carry's read-modify-write, the lane reductions), whatever
  the key width: at equal tile area (128, 512) takes 0.99 ms where
  (256, 256) takes 1.68 and (512, 128) 2.75 (BERT's shape, below). Causal
  block_k <= block_q because a key block wider than the query block
  computes scores the mask throws away: (1024, 1024) 1.48 ms, (512, 2048)
  1.80, (256, 4096) 2.43. ``block_q=`` / ``block_k=`` override the choice.
- BACKWARD is a custom VJP in blockwise JAX (Rabe & Staats style): exact
  probabilities are recomputed per key block from the saved logsumexp —
  never the full [S, S] — inside a lax.scan that accumulates dq and emits
  per-block dk/dv. XLA fuses each block's four matmuls; peak memory is
  O(S · 128) per (b, h): the scan's key block is ``_BWD_BLOCK_K``, its own
  constant, whatever tile the forward ran at.

- GROUPED-QUERY attention (fewer K/V heads than query heads, LFM2's 32 on
  8): ``k`` and ``v`` come in at their own head count and the forward's K/V
  index maps read head ``bh // group``; no repeated copy of K and V exists
  in HBM (at [2, 8192, 32, 64] bf16 the two copies would be 134 MB a layer,
  written and read again). The backward scan repeats the two in f32 as it
  casts them and sums dk and dv over each group. With equal head counts the
  program is the one it was.

The padding mask is a [B, S] int/bool array (1 = attend), matching the
BERT convention; causal and mask compose. Numerics: parity with the
reference einsum attention is asserted to ~1e-5 f32 in
tests/test_flash_attention.py (CPU interpret mode runs the same kernel),
at the chosen tiles and at forced ones.

**Measured (TPU v5e, jax 0.9.0 / libtpu 0.0.34; my chip runs, PR 29: the
forward call alone, bf16, median of 5 chains of 16 calls)**, against the
128 x 128 tiles every call ran at before:

| shape [B*h, S, d] | tiles, grid | ms a call | before | of its roofline |
|---|---|---|---|---|
| [384, 512, 64], BERT's padding mask | (512, 512), (384, 1, 1) | 0.539 | 4.672 | 24% (0.131 ms of MXU) |
| [32, 4096, 128], causal | (1024, 1024), (32, 4, 4), 10 of 16 steps live | 1.481 | 15.563 | 47% (0.70 ms) |

Both agree with an f32 einsum attention within 0.6 bf16 roundoffs of the
largest entry at every tile tried; inside the fused step the traces read
0.50 and 1.48 ms. A grid step costs about 0.4 us before it computes
anything, 0.15 ms of BERT's call: blocking several heads into one step
would return at most that and was not built. Measured and left for the
issue that writes the backward, whose kernel decides the residual's
layout: the logsumexp as a [BH, 1, S] row (one in-kernel transpose) in
place of the [BH, S, 1] column, which HBM pads to 128 lanes (100 MB a call
at BERT's shape) and XLA re-lays out afterwards: 0.29 ms a BERT layer,
values bit-identical (PERF.md section 7). The kernel compiles through
Mosaic inside ``shard_map`` on four chips as on one (PR 21). What flash
delivers besides is the O(S) attention memory; the default everywhere
stays 'full', and ROADMAP.md D4 holds the comparison at seq 512.
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

# Key block of the backward's lax.scan. Its own constant, not the forward's
# tile: at a sequence-wide block_k the scan would have one iteration and
# materialise the [BH, S, S] score, probability and gradient tensors in f32.
_BWD_BLOCK_K = 128

# What one grid step may hold in VMEM by forward_vmem_bytes' count, of the
# 16 MiB Mosaic scopes to a kernel on a v5e by default. The count leaves out
# Mosaic's own temporaries (the iota and select masks, the bf16 copy of the
# probabilities), hence the distance.
_VMEM_BUDGET = 13 * 2 ** 20


def forward_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                       itemsize: int) -> int:
    """VMEM one forward grid step keeps live, as the kernel below lays it
    out: q, k, v, mask, out and lse blocks double-buffered by the pipeline,
    the running (max, sum, accumulator) scratch, and the f32 score and
    probability tiles. The minor dimension is padded to the 128 lanes."""
    lanes = -(-head_dim // 128) * 128
    q_o = 2 * 2 * block_q * lanes * itemsize
    k_v = 2 * 2 * block_k * lanes * itemsize
    mask = 2 * 8 * block_k * 4          # [1, block_k] int32 on 8 sublanes
    lse = 2 * block_q * 128 * 4         # [block_q, 1] f32 on 128 lanes
    scratch = block_q * (2 * 128 + lanes) * 4
    tiles = 2 * block_q * block_k * 4
    return q_o + k_v + mask + lse + scratch + tiles


def forward_tiles(seq: int, head_dim: int, itemsize: int,
                  causal: bool) -> tuple[int, int]:
    """(block_q, block_k) of the forward kernel, from the operands' shapes
    alone: the widest key block, then the widest query block, among the
    divisors of ``seq`` that are multiples of 128 and keep
    ``forward_vmem_bytes`` within ``_VMEM_BUDGET``. A causal call keeps
    block_k <= block_q, so that a query block's diagonal tile, the one that
    computes masked scores, is no wider than the block itself."""
    if seq % 128:
        raise ValueError(
            f"seq len {seq} must be divisible by 128 (pad the sequence)")
    sizes = [b for b in range(seq, 0, -128) if seq % b == 0]
    for block_k in sizes:
        for block_q in sizes:
            if causal and block_k > block_q:
                continue
            if forward_vmem_bytes(block_q, block_k, head_dim,
                                  itemsize) <= _VMEM_BUDGET:
                return block_q, block_k
    raise ValueError(
        f"no forward tile fits {_VMEM_BUDGET} B of VMEM at head_dim "
        f"{head_dim}, itemsize {itemsize}")


def _last_live(qi, block_q: int, block_k: int):
    """The last key block a causal query block ``qi`` can see: the one
    that holds the key position of the block's last row."""
    return ((qi + 1) * block_q - 1) // block_k


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *scratch,
                scale: float, causal: bool):
    """One (batch·head, q-block, kv-block) grid step. The kv dimension is
    the INNERMOST grid axis, so the (m, l, acc) VMEM scratch persists
    across a q-block's kv steps while Mosaic pipelines the next kv
    block's DMA behind this step's MXU work — the canonical flash
    structure. Dots run in the input dtype (bf16 on the MXU) with f32
    accumulation via preferred_element_type. With one kv block there is
    no carry: ``_flash_fwd`` passes no scratch, and the step softmaxes its
    rows whole and writes them."""
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    qi = pl.program_id(1)
    j = pl.program_id(2)

    def fold(m, l, acc):
        """This key block folded into the running (max, sum, accumulator)."""
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32
        if causal:
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        # padding mask: this block's key validity as a [1, block_k] row,
        # broadcast over the query rows
        s = jnp.where(mask_ref[:] > 0, s, _NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # gate, don't trust exp: on a fully-masked row m_new is _NEG_INF
        # itself, so exp(s - m_new) would be exp(0) = 1 for masked
        # entries — the gate keeps them at 0, which keeps l at 0 there
        # and makes the finalize zero-guard real (and consistent with the
        # backward's identical gate)
        p = jnp.where(s > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    def write(m, l, acc):
        # fully-masked rows (all-pad keys) have l == 0: zeros, not NaN
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[:] = (acc / safe_l).astype(o_ref.dtype)
        lse_ref[:] = m + jnp.log(safe_l)  # [block_q, 1]

    if not scratch:
        write(*fold(_NEG_INF, 0.0, 0.0))
        return
    m_scr, l_scr, acc_scr = scratch

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: key blocks fully past this q block's diagonal contribute
    # nothing. Their compute is skipped here and their DMA in _flash_fwd,
    # whose index maps stop at the same _last_live block.
    live = (j <= _last_live(qi, block_q, block_k)) if causal else True

    @pl.when(live)
    def _step():
        m, l, acc = fold(m_scr[:, :1], l_scr[:, :1], acc_scr[:])
        m_scr[:, :1] = m
        l_scr[:, :1] = l
        acc_scr[:] = acc

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        write(m_scr[:, :1], l_scr[:, :1], acc_scr[:])


def _flash_fwd(q, k, v, mask, *, scale, causal, block_q, block_k,
               interpret):
    """q: [BH, S, d]; k/v: [BH / group, S, d]; mask: [B, S] routed per
    program."""
    bh, seq, d = q.shape
    b = mask.shape[0]
    heads = bh // b
    group = bh // k.shape[0]
    num_k = seq // block_k

    def kv_head(bh_):
        # query heads b * h + i of one group are consecutive, so their K/V
        # head b * (h / group) + i // group is bh_ // group
        return bh_ if group == 1 else bh_ // group

    def kv_block(i, j):
        if not causal:
            return j
        # a step past the diagonal names the block the step before it held,
        # and the pipeline copies nothing for an index that stays
        return jnp.minimum(j, _last_live(i, block_q, block_k))

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq // block_q, num_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh_, i, j: (bh_, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_k, d),
                         lambda bh_, i, j: (kv_head(bh_), kv_block(i, j), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_k, d),
                         lambda bh_, i, j: (kv_head(bh_), kv_block(i, j), 0),
                         memory_space=pltpu.VMEM),
            # [B, 1, S]: keys along the lanes, as the score tile has them
            pl.BlockSpec((None, 1, block_k),
                         lambda bh_, i, j: (bh_ // heads, 0, kv_block(i, j)),
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
        scratch_shapes=[] if num_k == 1 else [
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        # the scratch carries across the kv axis only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, mask.astype(jnp.int32)[:, None, :])
    return out, lse[..., 0]


def _blockwise_bwd(q, k, v, mask, o, lse, do, *, scale, causal, block_k,
                   heads):
    """Exact flash backward, blockwise over keys — recomputes per-block
    probabilities from the saved logsumexp; never forms [S, S]."""
    bh, seq, d = q.shape
    group = bh // k.shape[0]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if group > 1:
        # each K/V head once for every query head it serves
        kf, vf = (jnp.repeat(x, group, axis=0) for x in (kf, vf))
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
    if group > 1:
        dk, dv = (jnp.sum(x.reshape(bh // group, group, seq, d), axis=1)
                  for x in (dk, dv))
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
                                causal=causal, block_k=_BWD_BLOCK_K,
                                heads=heads)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, mask: Optional[jax.Array] = None,
                    causal: bool = False, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """Fused flash attention. ``q``: [B, S, h, d] (the model-side layout
    of ps_tpu/models/{bert,lm}.py); ``k/v``: [B, S, h_kv, d] with ``h_kv``
    dividing ``h`` (each K/V head serves ``h / h_kv`` consecutive query
    heads; equal for plain multi-head attention); ``mask``: optional
    [B, S] with 1 = attend (BERT padding convention); ``causal`` composes
    with it. Returns [B, S, h, d].

    ``block_q`` / ``block_k`` tile the forward kernel; left at None they
    are ``forward_tiles``' choice from the operands' shapes. ``interpret``
    defaults to True off-TPU so tests exercise the same kernel logic on
    CPU. Sequence length must be divisible by 128, the backward's key
    block, and by the forward's blocks (pad to 128 — XLA-side attention
    pads the same way in practice).

    ``mesh`` defaults to the one ``ps_tpu.init`` built, if any. Under a
    mesh the kernel runs inside ``shard_map`` — batch over 'data', heads
    over 'model', wherever the axis exists and divides — because GSPMD
    cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned", first met on four chips in PR 21); a
    dimension its axis does not divide is computed replicated.
    """
    b, seq, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv or v.shape != k.shape:
        raise ValueError(f"{h} query heads on K/V of shapes {k.shape}, "
                         f"{v.shape}: the K/V heads must divide them")
    if block_q is None or block_k is None:
        chosen = forward_tiles(seq, d, q.dtype.itemsize, causal)
        block_q, block_k = block_q or chosen[0], block_k or chosen[1]
    if seq % block_q or seq % block_k or seq % _BWD_BLOCK_K:
        raise ValueError(
            f"seq len {seq} must be divisible by block_q={block_q}, "
            f"block_k={block_k} and {_BWD_BLOCK_K} (pad the sequence)"
        )
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if mask is None:
        mask = jnp.ones((b, seq), jnp.int32)
    scale = d ** -0.5

    def local(q, k, v, mask):
        lb = q.shape[0]
        lh = q.shape[2]

        def pack(x):  # [B, S, h, d] -> [B*h, S, d], at x's own head count
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(
                lb * x.shape[2], seq, d)

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

    # what divides the K/V heads divides the query heads they serve
    spec = P(axis(DATA_AXIS, b), None, axis(MODEL_AXIS, h_kv), None)
    # check_vma off: jax 0.9.0 types the kernel's VMEM scratch as unvarying
    # and refuses to mix it with the varying loads inside the kernel body
    # ("Primitive mul requires varying manual axes to match")
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec,
                                                 P(spec[0], None)),
                     out_specs=spec, check_vma=False)(q, k, v, mask)
