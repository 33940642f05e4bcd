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
  attention out and the logsumexp, a [1, block_q] row a query block.
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
- BACKWARD is the custom VJP's two Pallas kernels, the forward's mathematics
  run the other way: exact probabilities recomputed a tile at a time from
  the saved logsumexp, never [S, S] in HBM, operands in their own dtype
  (bf16 on the MXU) with f32 accumulation, p and dS rounded to it before
  their second matmuls, exp, the gate, delta = rowsum(dO * O), the scale
  and the accumulators in f32. The dk / dv call has the grid
  (B*h_kv, S/block_k, group * S/block_q), its last axis innermost and
  "arbitrary": it walks the query heads a K/V head serves and their query
  blocks with dk and dv in f32 VMEM scratch, written once. Its tile is the
  transposed one (keys down the sublanes), so the logsumexp and delta come
  in as [1, block_q] rows, lane-dense in HBM, and the four matmuls contract
  as the MXU takes them; no tile is transposed. Causal: query blocks
  before ``_first_live`` (the mirror of ``_last_live``) are skipped by
  pl.when and the q / dO / row index maps start there, so a dead step
  copies nothing; live tiles wholly under the diagonal skip the position
  mask. The dq call has the forward's grid and index maps, dq in f32
  scratch, and turns the two rows into columns once a query block. Where
  one tile spans the sequence and the head counts are equal (BERT), one
  call gives all three gradients from the same dS, carries nothing and
  computes delta itself (sum_k p dP), so neither delta nor the forward's
  output is read: against the two calls 99.5 against 103.5 ms a step and
  610 MB less (delta from XLA instead: 97.6 ms, but the packed output
  stays live in every layer, 610 MB more). The gate is the forward's,
  said of the predicate: a masked entry is 0 whatever exp gives, so a
  fully masked row (logsumexp -1e30) has zero gradients.
- The backward's TILES are ``backward_tiles``', by the forward's rule
  under ``backward_vmem_bytes``' count (four f32 tiles where the forward
  has two): (512, 512) at BERT's shape, (1024, 512) at both causal ones.
  The tile matters little here, nothing is rescaled a step: at LFM2's
  shape (1024, 512) 15.7 + 13.0 ms, (512, 1024) 15.6 + 13.2, (512, 512)
  16.3 + 14.0, (1024, 256) 17.2 + 15.1, (2048, 512) 16.9 + 13.4;
  (1024, 1024) 14.9 + 12.3 compiles at 16 MiB's edge, over the count, and
  is not taken.

- GROUPED-QUERY attention (fewer K/V heads than query heads, LFM2's 32 on
  8): ``k`` and ``v`` come in at their own head count and the K/V index
  maps of the forward and of dq read head ``bh // group``; no repeated copy
  of K and V exists in HBM (at [2, 8192, 32, 64] bf16 the two copies would
  be 134 MB a layer, written and read again). The dk / dv call's grid is
  over the K/V heads and sums each group in its scratch. With equal head
  counts the program is the one it was.
- VALUES OF THEIR OWN WIDTH (latent attention: Kimi-Linear's keys are
  128 + 64 wide, its values 128): q, k, dq and dk blocks are ``d`` wide, v,
  the output, dO, dv and the output accumulator ``d_v``; the scale is
  ``d ** -0.5``. No zero-padded v of 192 exists (at [1, 8192, 32, .] bf16 it
  would be 34 MB more a tensor, four tensors, and a third more PV and dv
  work). ``forward_vmem_bytes`` / ``backward_vmem_bytes`` pad the lanes per
  operand (192 counts as 256), and ``forward_tiles`` / ``backward_tiles``
  take ``v_head_dim``: at 8,192 causal, 192 / 128, (1024, 512) and
  (512, 512), where 128 / 128 takes (1024, 1024) and (1024, 512). With
  ``d_v == d`` counts, tiles and lowered kernels are the ones they were.
  The 64 channels all heads share come in broadcast inside the 192-wide k
  (``models/kimi_linear.py``); reading them at one head through a grouped
  index map (a second score matmul a tile) was not built: ROADMAP R3.

- A WINDOW (``window=``, causal calls: Trinity's sliding layers see 2,048
  keys of 16,384, Mellum's 1,024 of 8,192): query ``i`` sees the keys ``j``
  with ``0 <= i - j < window``. A windowed call does not run on the tiled
  kernels above: its grid step is shaped like the band (THE BAND STEP,
  ``band``, the ``_band_*`` kernels and calls). *Forward and dq: grid
  (B*h, S/block), no key axis.* A step holds one query block and the slab of
  the ``ceil(window / block) + 1`` key and value blocks it can see, K, V and
  the mask passed to the call once a block of the slab as plain
  ``BlockSpec``s at block indices ``i - n + 1 .. i`` (a block before the
  sequence's start names block 0 and is dead by position); Mosaic pipelines
  them as it does any operand. Inside the step a static loop cuts the query
  block into sub-blocks of ``r`` rows; sub-block ``t`` takes its scores
  against **its own** slice of the slab, the ``window + r`` keys from the
  first key its first row sees to its last row's own, and, since every key a
  row sees is in the slice, softmaxes its rows **whole**: no running
  maximum, no ``alpha``, no rescaled accumulator, no scratch, nothing
  carried from one grid step to the next. Scores computed a row: ``window +
  r`` (2,304 at ``r`` 256) where the tiled kernel's three key blocks of
  1,024 computed 3,072. *Masks are added, and the position mask only where
  an edge is.* Relative to a sub-block the band's two edges are the same two
  triangles in every sub-block of every step (the lower edge in the slice's
  first ``r`` columns, the diagonal in its last ``r``): two f32 bias tiles
  of 0 / ``_NEG_INF`` made once a step, added to the pieces of the slice
  they cross; the columns between see no position mask. The padding mask is
  a [1, keys] bias row added to every piece. A row that sees no key (every
  score under ``_NEG_INF / 2``) gives zeros and a logsumexp of ``_NEG_INF``
  by a test a row, not a score; the backward reads such a row's logsumexp as
  ``-_NEG_INF``, so that its probabilities are 0. *dk / dv: the mirror*,
  grid (B*h_kv, S/block, group): a key block against the slab of query
  blocks that see it (q, dO and the logsumexp and delta rows of ``j .. j + n
  - 1``, a block past the sequence's end naming the last and dead by its
  logsumexp), ``r`` keys at a time against their own ``window + r``
  queries, the transposed tile, dk and dv summed over the slice in
  registers and over the group's query heads in the f32 scratch, the only
  carry left. A padded key's probabilities reach its own rows of dk and dv
  and nothing else, so the padding mask is not in this tile at all: those
  rows are written as zeros. The same work as the tiled kernels did: bf16
  operands, f32 accumulation and softmax, exact probabilities from the
  saved logsumexp, every pair of the band and no other; a whole-row softmax
  in place of the online one changes roundoff, not mathematics (on the chip
  the band's three results lie within 1 to 2 bf16 roundoffs of the tiled
  kernels', as far as those lie from each other at other tiles). Three
  Mosaic calls a layer as before, ``KEPT``, ``return_lse`` and the padding
  mask composing as before.
  ``block`` AND ``r`` are ``forward_band``'s and ``backward_band``'s, from
  (seq, window, head_dim, itemsize) alone (``_band_tiles``): ``r`` the
  tallest of 512, 256, 128 that is an eighth of the window at most (of a
  sub-block's ``window + r`` scores a row, ``r`` lie outside the band: a
  ninth at most), then the widest block whose step fits ``_VMEM_BUDGET`` by
  ``forward_band_vmem_bytes`` / ``backward_band_vmem_bytes`` (a wider block
  reads less of K and V twice and computes nothing more). Trinity's calls
  run at (1024, 256) forward and backward, Mellum's at (2048, 128) and
  (1024, 128). Under a window ``block_q=`` / ``block_k=`` force the
  forward's ``block`` and ``r``. A window so wide that no band fits VMEM
  (8,192 keys at head 128 in bf16: the slab alone is 8.4 MiB
  double-buffered) is refused by the rule; no tiled form is kept for it. A
  window that spans the sequence is the causal call; ``window=None`` is the
  program it was, to the jaxpr (``tests/test_flash_attention.py`` pins it).
  The table "Under a window" below has what the band replaced and what it
  reads.

- AN EDGE A BLOCK WIDE (``edge_block=``, causal calls without a window:
  block-diffusion training, ``models/sdar.py``, blocks of 4 in 8,192).
  Query ``i`` sees every key before the end of its own block of
  ``edge_block`` positions, ``j < (i // B + 1) * B``, or, with
  ``strict_edge``, before its start, ``j < (i // B) * B``. ``B`` is a power
  of two that divides 128 and so every tile: a query block's last row sees
  to the tile's own end at most, so ``_last_live`` / ``_first_live``, the
  index maps' clamps and the tiles stand as the causal call has them, and
  only the diagonal tiles' predicate changes (``_visible``: the first
  position of the query's block is ``i & -B``), in the forward, the dk / dv
  and the dq kernel alike; the backward's unmasked fast path takes, under the
  strict edge, the tiles that end before the query block starts. Under the
  strict edge a tile the bounds call live may hold no visible pair (the
  bounds are the causal call's, one block generous): it computes zeros.
  **Rows that see no key** (the strict edge's first block) come out as the
  padding mask's fully masked rows do: the gate keeps their probabilities at
  0, the output is 0, the logsumexp ``_NEG_INF`` itself, finite, and their
  gradients exactly zero. **The logsumexp as an output** (``return_lse=``):
  the second output of the ``custom_vjp``, with a cotangent of its own:
  ``d lse_i / d s_ij = p_ij``, so the backward runs on ``delta - dlse`` where
  ``delta`` stands and nothing else changes (two calls always: the one-call
  form computes delta inside). A caller merges two key sets by their
  logsumexps outside the kernel (``models/sdar.py::own_block``: the clean
  keys before a noised query's block through the strict call, its own block
  of ``B`` noised keys as a dense product). Without ``edge_block`` and
  ``return_lse`` the program is the one it was, to the jaxpr
  (``tests/test_flash_attention.py`` pins it at five cells' shapes).
  Inside the fused step (my chip runs, PR 50, [32, 8192, 128] on [4, 8192,
  128], blocks of 4, tiles (1024, 1024) / (1024, 512)): a layer's six calls
  take 36.6 ms (the two forwards about 5.0 each, the strict one with its
  logsumexp as an output; dk / dv 7.44 and 7.23; dq about 6 each), 68.6% of
  the roofline of ``L ** 2`` pairs a head over the two calls, where the
  causal call over the same keys reads 67% in the Mellum cell.

- THE RESIDUALS' NAMES (``KEPT``). The custom VJP keeps ``(q, k, v, mask,
  out, lse)``. Under a ``jax.checkpoint`` around the call none of the six
  lives from forward to backward, and the backward pass runs the forward
  kernel again only to get ``out`` and ``lse`` back: q, k and v are a
  projection away, these two are the whole call away. So ``_flash_vjp_fwd``
  passes the two through ``checkpoint_name`` before they enter the residual
  tuple, and a checkpoint whose policy is ``save_only_these_names(*KEPT)``
  keeps them (at [32, 16384, 128] bf16 134 MB and 2 MB a layer) and its
  recomputation holds no forward call: ``models/trinity.py`` and
  ``models/nemotron_h.py`` say so for their layers. What follows the call in
  such a layer is then recomputed from the forward pass's own output: in
  bf16 on the chip a second call reads re-rounded q, k, v and returns other
  bits (``PERF.md`` §6, PR 42: the gradient moves by up to 0.14 of a leaf's
  largest entry, towards the one no checkpoint gives). Under no checkpoint, or
  one that does not list them, the names are identity and the optimized
  program is the one it was (BERT, OLMoE, LFM2, Kimi-Linear).

The padding mask is a [B, S] int/bool array (1 = attend), matching the
BERT convention; causal, window and mask compose. Numerics: parity with the
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
would return at most that and was not built.

**The backward calls (my chip runs, PR 33: alone, median of 5 chains of 16
calls, and inside the fused step from the cells' traces)**, with the
plain-XLA scan over 128-key blocks they replace (f32 operands, every key
block against all queries, K and V repeated a group, dk / dv stacked by
dynamic-update-slice). FLOPs are the calls' own, the causal half where
causal: four matmuls in dk / dv, three in dq, five in the one call:

| shape [B*h, S, d] | call, tiles, grid | ms alone | ms in the step | the scan | of its roofline |
|---|---|---|---|---|---|
| [384, 512, 64], BERT's padding mask | one call, (512, 512), (384, 1, 1) | host-bound | 0.82 | 3.10 alone, 2.45 in the step | 40% (0.327 ms of MXU) |
| [32, 4096, 128], causal | dk / dv (1024, 512), (32, 8, 4), 20 of 32 steps live | 2.21 | 3.90 both | 18.25 alone, 17.6 | 63% (1.40 ms) |
| | dq, (32, 4, 8) | 1.86 | | | 56% (1.05 ms) |
| [64, 8192, 64] on [16, 8192, 64], causal | dk / dv (1024, 512), (16, 16, 32), 72 of 128 steps live a head | 15.67 | 15.07 | 153.7 alone, 154 | 37% (5.58 ms) |
| | dq, (64, 8, 16) | 12.97 | 12.30 | | 34% (4.19 ms) |

**Under a window (my chip runs, PR 41: alone, median of 5 chains of 8
calls; [32, 16384, 128] on [4, 16384, 128], causal, bf16).** Live steps are
the forward grid's, of those the causal call at the same tiles computes;
the band holds 23.4% of the causal pairs. FLOPs for the
roofline are the band's pairs', two matmuls forward, seven backward:

| window | tiles forward / backward | live steps a head | forward ms | backward ms (both calls) | forward, backward of its roofline |
|---|---|---|---|---|---|
| none (the full layer) | (1024, 1024) / (1024, 512) | 136 of 256 | 19.51 | 49.28 | 57% (11.2 ms), 79% (39.1 ms) |
| 2,048 | **(1024, 1024) / (1024, 512)**, the rule's | 45 of 136 | 8.30 | 21.55 (at forward (1024, 512): 13.74) | 32% (2.62 ms), 43% (9.16 ms) |
| 2,048 | (1024, 1024) / (1024, 1024) | 45 of 136 | 8.30 | 19.31 | compiles at 16 MiB's edge, over the count, not taken |
| 2,048 | (512, 512) / (512, 512) | 150 of 528 | 12.66 | 23.17 | 21%, 40% |
| 2,048 | (512, 256) / (512, 256) | 300 of 1,056 | 22.62 | 33.10 | |
| 2,048 | (256, 256) / (256, 256) | 540 of 2,080 | 30.00 | 50.08 | |
| 2,048 | (256, 128) / (256, 128) | 1,080 of 4,160 | 48.38 | 89.01 | |

A forward grid step takes 5.8 / 2.6 / 1.7 us at 1024^2 / 512^2 / 256^2
entries, so the tile that wastes a third of its scores (three key blocks of
1,024 for a row's 2,048 keys) beats the one that wastes a fifth (five of
512) by a third. Narrower windows at the same shape, forward / backward ms:
1,024: (1024, 1024) / (1024, 512) **6.46 / 17.19**, (512, 512) 9.76 / 18.34,
(256, 256) 23.90 / 43.11; 512: **6.47 / 14.47**, 8.25 / 15.83, 20.59 /
39.51; 256: **6.48 / 14.47**, 8.25 / 15.84, 18.97 / 37.64: the causal call's
tiles win at each, and under a window of 1,024 keys the forward stops
getting faster (two key blocks of 1,024 a query block whatever the window:
the floor of these tiles). Against the causal call on the same operands the
windowed forward at 2,048 takes 43% of the time for 33% of the live steps
and 23% of the pairs: the skip returns what the tiles let it. Inside the
fused step (the cell's traces, my chip runs, PR 42, seed 4200000101) a
windowed layer's calls take 29.0 ms (the forward 7.31, dk / dv 12.39, dq
9.33) and the full layer's 67.9 (19.19, 27.12, 21.58); a layer's
``jax.checkpoint`` that does not keep ``KEPT`` runs each forward twice.

**The band step (my chip runs, PR 53: alone, median of 5 chains of 8 calls,
seed 0, ``tools/window_table.py``, ``chiprun_out/pr53/window_table_1.json``;
the tiled calls above in the same call, at their (1024, 1024) / (1024, 512),
on the first and last rows).** The table above is history: PR 41's tiles are
the program a windowed call was until PR 53. Roofline: the band's pairs, 2.62
and 9.16 ms of MXU at Trinity's shape, 0.654 and 2.29 at Mellum's:

| shape, window | block x r | forward ms | dq ms | dk / dv ms | forward, backward of its roofline |
|---|---|---|---|---|---|
| [32, 16384, 128] on [4, 16384, 128], 2,048 | the tiled calls | 8.454 | 11.236 | 13.221 | 31%, 37% |
| | **1024 x 256**, the rules' | **4.056** | **5.112** | **6.639** | **65%, 78%** |
| | 1024 x 128 | 4.101 | 5.076 | 6.458 | 64%, 79% |
| | 1024 x 512 | 4.462 | 5.612 | 7.370 | 59%, 71% |
| | 2048 x 256 | refused (19.9 MiB) | 4.961 | 6.541 | -, 80% |
| | 2048 x 128 | refused (16.8 MiB) | 4.857 | 6.264 | -, 82% |
| | 512 x 256 | 4.435 | 5.630 | 6.921 | 59%, 73% |
| | 512 x 128 | 4.482 | 5.669 | 6.848 | 58%, 73% |
| | 256 x 256 | 5.950 | 7.300 | 8.469 | 44%, 58% |
| [32, 8192, 128] on [4, 8192, 128], 1,024 | the tiled calls | 2.766 | 3.713 | 4.439 | 24%, 28% |
| | **2048 x 128**, the forward rule's | **1.354** | 1.416 | 1.790 | 48%, 71% |
| | **1024 x 128**, the backward rule's | 1.351 | **1.506** | **1.875** | 48%, 68% |
| | 1024 x 256 | 1.274 | 1.528 | 1.960 | 51%, 66% |
| | 2048 x 256 | 1.220 | 1.470 | 1.927 | 54%, 67% |
| | 1024 x 512 | 1.455 | 1.772 | 2.334 | 45%, 56% |
| | 512 x 256 | 1.410 | 1.699 | 2.067 | 46%, 61% |
| | 256 x 256 | 1.821 | 2.198 | 2.340 | 36%, 50% |

Half the time at every band wider than 256: the rows whole (no rescale, no
scratch round trip), the masks added and on the edges' pieces only, 0.75 of
the scores. ``r`` hardly matters between 128 and 256 (a sixteenth of the
scores against the fixed cost of a sub-block) and 512 loses 10%; a block of
512 loses 9% to 1,024 (its slab is five blocks for a row's three), and 2,048
gains 2 to 5% in the backward where it fits. The rules take none of the
2,048s that Mosaic compiles over the backward's count. The band's results
lie within 0.002 (forward), 0.001 (dq) and 0.008 (dk / dv, entries of 4 to
5) of the tiled calls': one or two bf16 roundoffs, and as far as the bands
lie from each other. Inside the fused step (the Trinity cell's traces, my
chip runs, PR 53, seed 5300000101) a windowed layer's three calls take
15.21 ms where 29.01 (the forward 3.85 where 7.31, dk / dv 6.48 where
12.39, dq 4.88 where 9.31), the twelve 60.84 where 116.04, 77.4% of their
roofline where 40.6% and where the full layer's, unmoved, read 74.7%.

All three gradients agree with an f32 einsum attention on the same bf16
inputs within 1.0-1.3 roundoffs of their largest entry at every tile
tried, as the scan's did (0.8-1.2). Skipping the position mask under the
diagonal is 8% of the dk / dv call at LFM2's shape and 1% of dq's. The
logsumexp is a [BH, 1, S] row (the forward transposes its column once a
query block): the [BH, S, 1] column, which HBM pads to 128 lanes and XLA
re-lays out, costs BERT 3.1 ms a step (319.8 against 310.1 samples/s/chip
on one chip, 308.0 against 299.6 on four, and 1.2 GB more there; PERF.md
section 7). The kernels compile through Mosaic inside
``shard_map`` on four chips as on one (PR 21). What flash delivers besides
is the O(S) attention memory; the default everywhere stays 'full', and
ROADMAP.md D4 holds the comparison at seq 512.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ps_tpu.ops import mosaic
from ps_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

_NEG_INF = -1e30

# The names of the two residuals that only the forward call can produce, its
# output and logsumexp, for the policy of a ``jax.checkpoint`` around the call
# (the docstring's THE RESIDUALS' NAMES).
KEPT = ("flash_out", "flash_lse")

# A causal call's edge at a block's granularity (the docstring's AN EDGE A
# BLOCK WIDE): the block's length, a power of two that divides 128, and
# whether the query's own block is left out (strict).
Edge = Tuple[int, bool]

# What one grid step may hold in VMEM by forward_vmem_bytes' count, of the
# 16 MiB Mosaic scopes to a kernel on a v5e by default. The count leaves out
# Mosaic's own temporaries (the iota and select masks, the bf16 copy of the
# probabilities), hence the distance.
_VMEM_BUDGET = 13 * 2 ** 20


def _lanes(width: int) -> int:
    """``width`` as VMEM holds a minor dimension: padded to the 128 lanes."""
    return -(-width // 128) * 128


def forward_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                       itemsize: int, v_head_dim: Optional[int] = None) -> int:
    """VMEM one forward grid step keeps live, as the kernel below lays it
    out: q, k, v, mask, out and lse blocks double-buffered by the pipeline,
    the running (max, sum, accumulator) scratch, and the f32 score and
    probability tiles. The minor dimension is padded to the 128 lanes, each
    operand's own: q and k are ``head_dim`` wide, v, the output and its
    accumulator ``v_head_dim`` (``head_dim`` where None)."""
    qk, vo = _lanes(head_dim), _lanes(v_head_dim or head_dim)
    q_o = 2 * block_q * (qk + vo) * itemsize
    k_v = 2 * block_k * (qk + vo) * itemsize
    mask = 2 * 8 * block_k * 4          # [1, block_k] int32 on 8 sublanes
    lse = 2 * block_q * 128 * 4         # the [block_q, 128] its row is cut from
    scratch = block_q * (2 * 128 + vo) * 4
    tiles = 2 * block_q * block_k * 4
    return q_o + k_v + mask + lse + scratch + tiles


def _widest_tiles(vmem_bytes, what: str, seq: int, head_dim: int,
                  itemsize: int, causal: bool,
                  v_head_dim: Optional[int]) -> tuple[int, int]:
    """The widest key block, then the widest query block, among the
    divisors of ``seq`` that are multiples of 128 and keep ``vmem_bytes``
    within ``_VMEM_BUDGET``; a causal call keeps block_k <= block_q."""
    if seq % 128:
        raise ValueError(
            f"seq len {seq} must be divisible by 128 (pad the sequence)")
    sizes = [b for b in range(seq, 0, -128) if seq % b == 0]
    for block_k in sizes:
        for block_q in sizes:
            if causal and block_k > block_q:
                continue
            if vmem_bytes(block_q, block_k, head_dim, itemsize,
                          v_head_dim) <= _VMEM_BUDGET:
                return block_q, block_k
    raise ValueError(
        f"no {what} tile fits {_VMEM_BUDGET} B of VMEM at head_dim "
        f"{head_dim} (v {v_head_dim or head_dim}), itemsize {itemsize}")


def forward_tiles(seq: int, head_dim: int, itemsize: int, causal: bool,
                  v_head_dim: Optional[int] = None) -> tuple[int, int]:
    """(block_q, block_k) of the forward kernel, from the operands' shapes
    alone: ``_widest_tiles`` under ``forward_vmem_bytes``. A causal call
    keeps block_k <= block_q, so that a query block's diagonal tile, the
    one that computes masked scores, is no wider than the block itself. A
    windowed call does not run on these tiles: ``forward_band``."""
    return _widest_tiles(forward_vmem_bytes, "forward", seq, head_dim,
                         itemsize, causal, v_head_dim)


def _last_live(qi, block_q: int, block_k: int):
    """The last key block a causal query block ``qi`` can see: the one
    that holds the key position of the block's last row."""
    return ((qi + 1) * block_q - 1) // block_k


def _visible(qi, j, shape, q_axis: int, edge: Optional[Edge] = None):
    """Causal visibility of a score tile of query block ``qi`` and key
    block ``j``: the query position reaches the key position; under an
    ``edge`` a block wide, the key lies before the end of the query's
    block, or (strict) before its start. The queries run along ``q_axis``
    of ``shape``, the keys along the other."""
    qpos = qi * shape[q_axis] + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)
    kpos = j * shape[1 - q_axis] + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - q_axis)
    if edge is not None:
        block, strict = edge
        # the block is a power of two: ``& -block`` is its first position
        first = jnp.bitwise_and(qpos, -block)
        return kpos < (first if strict else first + block)
    return qpos >= kpos


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *scratch,
                scale: float, causal: bool, edge: Optional[Edge] = None):
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
            s = jnp.where(_visible(qi, j, s.shape, 0, edge), s, _NEG_INF)
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
        # the column as a [1, block_q] row, the layout the backward reads
        # and HBM does not pad
        lse_ref[:] = jnp.broadcast_to(m + jnp.log(safe_l),
                                      (block_q, 128)).T[:1]

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


def _vmem(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def _query_major_specs(bh: int, b: int, group: int, d: int, *, block_q: int,
                       block_k: int, causal: bool):
    """Block specs of the grid (B*h, S/block_q, S/block_k), keys innermost,
    that the forward and the dq call run on: a query-side [block_q, d]
    block, a [1, block_q] row of a [BH, 1, S] array (block (1, block_q)
    satisfies the TPU tiling rule: second-to-last equal to the array dim,
    last a multiple of 128), a K/V [block_k, d] block at head
    ``bh // group`` and the [1, block_k] row of the [B, 1, S] mask, keys
    along the lanes as the score tile has them."""
    heads = bh // b

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

    return (
        _vmem((None, block_q, d), lambda bh_, i, j: (bh_, i, 0)),
        _vmem((None, 1, block_q), lambda bh_, i, j: (bh_, 0, i)),
        _vmem((None, block_k, d),
              lambda bh_, i, j: (kv_head(bh_), kv_block(i, j), 0)),
        _vmem((None, 1, block_k),
              lambda bh_, i, j: (bh_ // heads, 0, kv_block(i, j))),
    )


def _qkv_specs(q, k, v, mask, **tiles):
    """``_query_major_specs`` of packed operands: the query block, the row,
    the key block and the mask row at q's width, then the query-side and
    key-side blocks at v's (the output or dO, and v)."""
    bh = q.shape[0]
    args = (bh, mask.shape[0], bh // k.shape[0])
    q_spec, row_spec, k_spec, mask_spec = _query_major_specs(
        *args, q.shape[-1], **tiles)
    o_spec, _, v_spec, _ = _query_major_specs(*args, v.shape[-1], **tiles)
    return q_spec, row_spec, k_spec, mask_spec, o_spec, v_spec


def _flash_fwd(q, k, v, mask, *, scale, causal, block_q, block_k,
               interpret, edge=None):
    """q: [BH, S, d]; k: [BH / group, S, d]; v: [BH / group, S, d_v];
    mask: [B, S] routed per program. Returns out [BH, S, d_v] and the
    logsumexp [BH, 1, S]."""
    bh, seq, _ = q.shape
    d_v = v.shape[-1]
    num_k = seq // block_k
    q_spec, row_spec, k_spec, mask_spec, o_spec, v_spec = _qkv_specs(
        q, k, v, mask, block_q=block_q, block_k=block_k, causal=causal)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          edge=edge),
        grid=(bh, seq // block_q, num_k),
        in_specs=[q_spec, k_spec, v_spec, mask_spec],
        out_specs=[o_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        scratch_shapes=[] if num_k == 1 else [
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
            pltpu.VMEM((block_q, d_v), jnp.float32),  # output accumulator
        ],
        # the scratch carries across the kv axis only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, mask.astype(jnp.int32)[:, None, :])


def backward_vmem_bytes(block_q: int, block_k: int, head_dim: int,
                        itemsize: int,
                        v_head_dim: Optional[int] = None) -> int:
    """VMEM one backward grid step keeps live, the larger of the two calls
    below: every block in or out double-buffered by the pipeline, the f32
    scratch, and the f32 s / p / dP / dS tiles. Mosaic overlays some of
    the four: (1024, 1024) at head 64, 21 MiB by this count, compiles
    under its 16, and (2048, 1024) does not. q, k, dq and dk are
    ``head_dim`` wide, dO, v and dv ``v_head_dim`` (``head_dim`` where
    None), each padded to the lanes on its own."""
    qk, vo = _lanes(head_dim), _lanes(v_head_dim or head_dim)
    q_side = 2 * block_q * itemsize           # a lane of a [block_q, .] block
    k_side = 2 * block_k * itemsize           # a lane of a [block_k, .] block
    rows = 2 * 2 * 8 * block_q * 4            # logsumexp, delta: [1, block_q]
    tiles = 4 * block_q * block_k * 4
    # dk / dv: q, dO in; k, v in, dk, dv out; the [block_k, 1] mask column
    # on 128 lanes; the dk and dv accumulators
    dkv = (q_side * (qk + vo) + 2 * k_side * (qk + vo) + rows
           + 2 * block_k * 128 * 4 + block_k * (qk + vo) * 4)
    # dq: q, dO in, dq out; k, v in; the [1, block_k] mask row; the dq
    # accumulator and the two columns made of the rows
    dq = (q_side * (2 * qk + vo) + k_side * (qk + vo) + rows
          + 2 * 8 * block_k * 4 + block_q * (qk + 2 * 128) * 4)
    return max(dkv, dq) + tiles


def backward_tiles(seq: int, head_dim: int, itemsize: int, causal: bool,
                   v_head_dim: Optional[int] = None) -> tuple[int, int]:
    """(block_q, block_k) of the two backward kernels, by the forward's
    rule: ``_widest_tiles`` under ``backward_vmem_bytes``."""
    return _widest_tiles(backward_vmem_bytes, "backward", seq, head_dim,
                         itemsize, causal, v_head_dim)


def _band_blocks(block: int, window: int) -> int:
    """Blocks of the slab one band step holds: those the window, rounded up
    to the lanes, reaches into, and the step's own."""
    return -(-_lanes(window) // block) + 1


def _edge_bias_bytes(r: int, window: int) -> int:
    """The two edges' f32 bias tiles: ``r`` columns each, and the lanes the
    window was rounded up by beside the lower one."""
    return r * (2 * r + (128 if window % 128 else 0)) * 4


def forward_band_vmem_bytes(block: int, r: int, window: int, head_dim: int,
                            itemsize: int,
                            v_head_dim: Optional[int] = None) -> int:
    """VMEM one grid step of the windowed forward keeps live
    (``_band_fwd_kernel``): the q, out and logsumexp blocks and the slab's K,
    V and mask blocks, double-buffered by the pipeline; a sub-block's f32
    scores against its whole slice, the f32 probabilities of one piece, the
    output accumulator and the two edges' bias tiles; and the probabilities
    rounded to the operands' type of every sub-block of the step, which
    Mosaic does not overlay. Within 5% of what Mosaic allocates at the nine
    bands read (window 2,048, head 128, bf16: (1024, 256) 11.59 MiB,
    (1024, 512) 14.95, (2048, 128) 16.79 and (2048, 256) 19.91, both
    refused; window 1,024: (1024, 256) 6.18, (2048, 256) 13.09)."""
    qk, vo = _lanes(head_dim), _lanes(v_head_dim or head_dim)
    slice_ = _lanes(window) + r
    a_block = 2 * block * (qk + vo) * itemsize + 2 * 8 * block * 4
    tiles = (r * slice_ * 4 + r * min(block, slice_) * 4
             + block * slice_ * itemsize + r * vo * 4)
    return ((1 + _band_blocks(block, window)) * a_block + tiles
            + _edge_bias_bytes(r, window))


def backward_band_vmem_bytes(block: int, r: int, window: int, head_dim: int,
                             itemsize: int,
                             v_head_dim: Optional[int] = None) -> int:
    """VMEM one grid step of the windowed backward keeps live, the larger
    of its two calls: every block in or out double-buffered, the f32 scratch
    and columns, the f32 s / p / dP / dS tiles of the widest piece (a block's
    width at most: nothing of a row outlives its piece) and the bias tiles.
    An over-count: at window 2,048, head 128, bf16, (1024, 256) counts
    12.1 MiB where Mosaic allocates 8.28 for dk / dv and 6.61 for dq."""
    qk, vo = _lanes(head_dim), _lanes(v_head_dim or head_dim)
    blocks = _band_blocks(block, window)
    a_block = 2 * block * itemsize            # a lane of a [block, .] block
    a_row = 2 * 8 * block * 4                 # a [1, block] f32 or int32 row
    # dk / dv: the slab's q, dO and two rows; k, v in, dk, dv out; the
    # [block, 1] mask column on 128 lanes; the dk and dv accumulators
    dkv = (blocks * (a_block * (qk + vo) + 2 * a_row)
           + 2 * a_block * (qk + vo) + 2 * block * 128 * 4
           + block * (qk + vo) * 4)
    # dq: q, dO in, dq out and the two rows; the slab's k, v and mask; the
    # two columns made of the rows
    dq = (a_block * (2 * qk + vo) + 2 * a_row
          + blocks * (a_block * (qk + vo) + a_row) + 2 * block * 128 * 4)
    tiles = (4 * r * min(block, _lanes(window) + r) * 4
             + r * (qk + vo) * 4)
    return max(dkv, dq) + tiles + _edge_bias_bytes(r, window)


def _band_tiles(vmem_bytes, what: str, seq: int, head_dim: int,
                itemsize: int, window: int,
                v_head_dim: Optional[int]) -> tuple[int, int]:
    """(block, r) of a band step. The sub-block first: the tallest of 512,
    256 and 128 rows that is an eighth of the window at most, 128 under a
    window of 2,048: a sub-block's slice is ``r`` keys longer than its rows'
    window, so at most a ninth of the scores it computes lie outside the
    band. Then the widest block, among the divisors of ``seq`` that are
    multiples of ``r``, whose step keeps ``vmem_bytes`` within
    ``_VMEM_BUDGET``: a wider block reads less of K and V twice and adds no
    scores."""
    if seq % 128:
        raise ValueError(
            f"seq len {seq} must be divisible by 128 (pad the sequence)")
    for r in (512, 256, 128):
        if r > 128 and 8 * r > window:
            continue
        for block in range(seq, 0, -r):
            if seq % block == 0 and vmem_bytes(
                    block, r, window, head_dim, itemsize,
                    v_head_dim) <= _VMEM_BUDGET:
                return block, r
    raise ValueError(
        f"no {what} band step fits {_VMEM_BUDGET} B of VMEM at window "
        f"{window}, head_dim {head_dim} (v {v_head_dim or head_dim}), "
        f"itemsize {itemsize}")


def forward_band(seq: int, head_dim: int, itemsize: int, window: int,
                 v_head_dim: Optional[int] = None) -> tuple[int, int]:
    """(block, r) of the windowed forward, from the operands' shapes and
    the window alone: ``_band_tiles`` under ``forward_band_vmem_bytes``."""
    return _band_tiles(forward_band_vmem_bytes, "forward", seq, head_dim,
                       itemsize, window, v_head_dim)


def backward_band(seq: int, head_dim: int, itemsize: int, window: int,
                  v_head_dim: Optional[int] = None) -> tuple[int, int]:
    """(block, r) of the two windowed backward calls, by the forward's
    rule under ``backward_band_vmem_bytes``."""
    return _band_tiles(backward_band_vmem_bytes, "backward", seq, head_dim,
                       itemsize, window, v_head_dim)


def _first_live(j, block_q: int, block_k: int):
    """The first query block that sees causal key block ``j``: the one
    that holds the row of the block's first key. The mirror of
    ``_last_live``: the dk / dv kernel's compute skip and the clamp of its
    query-side index maps."""
    return (j * block_k) // block_q


def _probabilities(s, lse, keep):
    """Exact probabilities of a score tile from the saved logsumexp. The
    gate is the forward's ``s > _NEG_INF / 2`` said of the predicate: a
    masked entry is 0 whatever exp gives, so a fully masked row, whose
    logsumexp is ``_NEG_INF`` itself, has zero gradients."""
    return jnp.where(keep, jnp.exp(s - lse), 0.0)


def _causal_steps(step, causal: bool, live, qi, j, block_q: int,
                  block_k: int, edge: Optional[Edge] = None):
    """Calls ``step(when, diagonal)`` for the tile of query block ``qi``
    and key block ``j``: once where nothing is causal; else once for the
    live tiles the diagonal crosses, which need the position mask, and once
    for those wholly under it, which do not (8% of the dk / dv call at
    LFM2's shape, 1% of the dq call). Dead tiles run neither."""
    if not causal:
        step(True, False)
        return
    under = (j + 1) * block_k - 1 <= qi * block_q
    if edge is not None and edge[1]:
        # a strict edge hides the first row's own block: the tile's last
        # key lies before that row
        under = (j + 1) * block_k <= qi * block_q
    step(jnp.logical_and(live, jnp.logical_not(under)), True)
    step(under, False)


def _dkv_kernel(q_ref, do_ref, lse_ref, k_ref, v_ref, mask_ref, *rest,
                scale: float, causal: bool, num_q: int, fused: bool,
                edge: Optional[Edge] = None):
    """One (batch x K/V head, key block, query head of the group x query
    block) grid step of dk and dv. The tile is the transposed one, keys
    down the sublanes and queries along the lanes, so that the logsumexp
    and delta are [1, block_q] rows and all four matmuls contract as the
    MXU takes them (k q^T and v dO^T over the head dimension, p^T dO and
    dS^T q over the queries): nothing is transposed in VMEM. ``rest`` is
    the delta row, the dk and dv blocks and their f32 scratch; or, where
    the one tile spans the sequence (``fused``), the dk, dv and dq blocks:
    nothing is carried, delta is computed here and the same dS gives dq
    as well."""
    if fused:
        dk_ref, dv_ref, dq_ref = rest
    else:
        delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    j = pl.program_id(1)
    qi = pl.program_id(2) % num_q

    def tile(diagonal):
        st = jax.lax.dot_general(
            k_ref[:], q_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_k, block_q] f32
        keep = mask_ref[:] > 0  # [block_k, 1]: this block's key validity
        if diagonal:
            keep = jnp.logical_and(keep, _visible(qi, j, st.shape, 1, edge))
        pt = _probabilities(st, lse_ref[:], keep)
        dpt = jax.lax.dot_general(
            v_ref[:], do_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if fused:
            # every key of a query is in this tile, so its delta is here
            # too: sum_k p dP, which equals rowsum(dO * O)
            delta = jnp.sum(pt * dpt, axis=0, keepdims=True)
        else:
            delta = delta_ref[:]
        dst = (pt * (dpt - delta)).astype(q_ref.dtype)
        dv = jax.lax.dot_general(
            pt.astype(do_ref.dtype), do_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk = jax.lax.dot_general(
            dst, q_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv, dst

    def write(dk, dv):
        # the softmax scale of dS, once a key row and not once a score
        dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv.astype(dv_ref.dtype)

    if fused:
        dk, dv, dst = tile(causal)
        write(dk, dv)
        # dS k with dS as its transpose: contracted over the keys, dim 0
        dq_ref[:] = (jax.lax.dot_general(
            dst, k_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale).astype(dq_ref.dtype)
        return

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def step(when, diagonal):
        @pl.when(when)
        def _step():
            dk, dv, _ = tile(diagonal)
            dk_scr[:] += dk
            dv_scr[:] += dv

    # causal: query blocks wholly before this key block see none of it.
    # Their compute is skipped here and their DMA in _flash_dkv, whose
    # index maps start at the same _first_live block.
    live = qi >= _first_live(j, block_q, block_k)
    _causal_steps(step, causal, live, qi, j, block_q, block_k, edge)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        write(dk_scr[:], dv_scr[:])


def _dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, mask_ref,
               dq_ref, dq_scr, lse_scr, delta_scr, *, scale: float,
               causal: bool, edge: Optional[Edge] = None):
    """One (batch x head, query block, key block) grid step of dq, the
    forward's grid and index maps. The tile has the queries down the
    sublanes, so the logsumexp and delta rows are turned into columns,
    once a query block."""
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    qi = pl.program_id(1)
    j = pl.program_id(2)

    def column(row_ref):  # [1, block_q] -> [block_q, 128], every lane alike
        return jnp.broadcast_to(row_ref[:], (128, block_q)).T

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        lse_scr[:] = column(lse_ref)
        delta_scr[:] = column(delta_ref)

    def tile(diagonal):
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32
        keep = mask_ref[:] > 0  # [1, block_k]
        if diagonal:
            keep = jnp.logical_and(keep, _visible(qi, j, s.shape, 0, edge))
        p = _probabilities(s, lse_scr[:, :1], keep)
        dp = jax.lax.dot_general(
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_scr[:, :1])
        return jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def step(when, diagonal):
        @pl.when(when)
        def _step():
            dq_scr[:] += tile(diagonal)

    live = j <= _last_live(qi, block_q, block_k)
    _causal_steps(step, causal, live, qi, j, block_q, block_k, edge)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[:] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_dkv(q, do, lse, delta, k, v, mask, *, scale, causal, block_q,
               block_k, interpret, edge=None):
    """dk, dv: grid (B * h_kv, S / block_k, group * S / block_q). The
    innermost axis walks the query heads a K/V head serves and, within
    each, the query blocks from the first live one. With ``delta`` None
    (``_flash_bwd`` says when) the call is the whole backward: one step a
    head that computes delta itself and returns dq as well, else None."""
    fused = delta is None
    bh, seq, d = q.shape
    bh_kv = k.shape[0]
    group = bh // bh_kv
    kv_heads = bh_kv // mask.shape[0]
    num_q = seq // block_q
    steps = group * num_q

    def q_head(g, t):
        return g * group + t // num_q

    def q_block(j, t):
        i = t % num_q
        if not causal:
            return i
        # a step before the diagonal names the first live block, which the
        # step that reaches it names again: one copy
        return jnp.maximum(i, _first_live(j, block_q, block_k))

    def q_side(width):
        return _vmem((None, block_q, width),
                     lambda g, j, t: (q_head(g, t), q_block(j, t), 0))

    def k_side(width):
        return _vmem((None, block_k, width), lambda g, j, t: (g, j, 0))

    d_v = v.shape[-1]
    q_spec, do_spec, k_spec, v_spec = (q_side(d), q_side(d_v), k_side(d),
                                       k_side(d_v))
    row_spec = _vmem((None, 1, block_q),
                     lambda g, j, t: (q_head(g, t), 0, q_block(j, t)))
    dk, dv, *dq = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          num_q=num_q, fused=fused, edge=edge),
        grid=(bh_kv, seq // block_k, steps),
        in_specs=[
            q_spec, do_spec, row_spec, k_spec, v_spec,
            # [B, S, 1]: keys down the sublanes, as the transposed tile
            # has them
            _vmem((None, block_k, 1), lambda g, j, t: (g // kv_heads, j, 0)),
        ] + [row_spec] * (not fused),
        out_specs=[k_spec, v_spec] + [q_spec] * fused,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)]
        + [jax.ShapeDtypeStruct(q.shape, q.dtype)] * fused,
        scratch_shapes=[] if fused else [
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, do, lse, k, v, mask[:, :, None], *[delta] * (not fused))
    return dk, dv, dq[0] if fused else None


def _flash_dq(q, do, lse, delta, k, v, mask, *, scale, causal, block_q,
              block_k, interpret, edge=None):
    """dq: the forward's grid, K/V head ``bh // group`` and the clamp at
    ``_last_live``."""
    bh, seq, d = q.shape
    q_spec, row_spec, k_spec, mask_spec, do_spec, v_spec = _qkv_specs(
        q, k, v, mask, block_q=block_q, block_k=block_k, causal=causal)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          edge=edge),
        grid=(bh, seq // block_q, seq // block_k),
        in_specs=[q_spec, do_spec, row_spec, row_spec, k_spec, v_spec,
                  mask_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # dq accumulator
            pltpu.VMEM((block_q, 128), jnp.float32),  # logsumexp column
            pltpu.VMEM((block_q, 128), jnp.float32),  # delta column
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, do, lse, delta, k, v, mask[:, None, :])


# -- the band step: a windowed call --------------------------------------------

class Band(NamedTuple):
    """What one grid step of a windowed call meets, from ``block``, ``r`` and
    the window alone (``band``): the step holds one block of its own side and
    ``blocks`` blocks of the other side end to end, the slab; sub-block ``t``
    of its own ``r`` rows meets the ``lanes(window) + r`` slab columns from
    ``first[t]`` on, its slice. Row ``a`` of a sub-block sees the slice's
    columns ``c`` with ``low <= c - a <= high``, whatever the sub-block and
    the step. ``pieces[t]`` cuts the slice where the slab changes block and
    where the two edges' chunks end: ``(block of the slab, its first row, its
    last row + 1, the slice column of the first, whether an edge crosses the
    piece)``. A piece no edge crosses holds only pairs the window keeps."""
    blocks: int
    low: int
    high: int
    first: tuple
    pieces: tuple


def band(block: int, r: int, window: int, mirror: bool = False) -> Band:
    """The geometry of the band step. Query side (the forward and dq): the
    slab is the key blocks ``i - blocks + 1 .. i`` of query block ``i``, so
    slab column ``p`` is key ``(i - blocks + 1) * block + p``, and the slice
    of sub-block ``t`` ends with that sub-block's own keys. ``mirror`` (dk /
    dv): the slab is the query blocks ``j .. j + blocks - 1`` of key block
    ``j``, and the slice starts with the sub-block's own queries. The window
    is rounded up to the 128 lanes for the slice, and the lanes it gains are
    masked with the lower edge."""
    wide = _lanes(window)
    blocks = _band_blocks(block, window)
    low, high = (0, window - 1) if mirror else (wide - window + 1, wide)
    # the chunks the edges cross: columns under low + r - 1 (some row's
    # lower bound lies past them) and columns past high
    cuts = {_lanes(low + r - 1), high + 1 - (high + 1) % 128}
    firsts, pieces = [], []
    for t in range(block // r):
        first = t * r if mirror else (blocks - 1) * block + t * r - wide
        at = sorted({0, wide + r}
                    | {c for c in cuts if 0 < c < wide + r}
                    | {c for c in range(block - first % block, wide + r,
                                        block)})
        firsts.append(first)
        pieces.append(tuple(
            ((first + c0) // block, (first + c0) % block,
             (first + c0) % block + c1 - c0, c0,
             c0 < low + r - 1 or c1 - 1 > high)
            for c0, c1 in zip(at, at[1:])))
    return Band(blocks, low, high, tuple(firsts), tuple(pieces))


def _edge_biases(geometry: Band, r: int):
    """``bias(c0, c1)``: the [r, c1 - c0] f32 tile that is 0 on the pairs of
    the slice's columns ``c0 .. c1 - 1`` the window keeps and ``_NEG_INF`` on
    the others, made once a kernel body: the band's two edges are the same
    two triangles in every sub-block of every step."""
    made = {}

    def bias(c0, c1):
        if (c0, c1) not in made:
            shape = (r, c1 - c0)
            ahead = (c0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
                     - jax.lax.broadcasted_iota(jnp.int32, shape, 0))
            made[c0, c1] = jnp.where(
                jnp.logical_and(ahead >= geometry.low,
                                ahead <= geometry.high), 0.0, _NEG_INF)
        return made[c0, c1]

    return bias


def _nt(a, b):
    """a b^T, f32 out: both contract their minor dimension."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _key_bias(mask_refs, qi, blocks: int):
    """``padding(m, lo, hi)``: the padding mask of keys ``lo .. hi - 1`` of
    the slab's block ``m`` as a [1, hi - lo] f32 row to add to the scores, 0
    or ``_NEG_INF``; a block before the sequence's start (its index map
    names block 0) is padding throughout. Read from the ref a piece at a
    time: Mosaic does not broadcast a lane slice of a row held as a value."""
    def padding(m, lo, hi):
        return jnp.where(
            jnp.logical_and(mask_refs[m][:, lo:hi] > 0,
                            qi + m >= blocks - 1), 0.0, _NEG_INF)

    return padding


def _column(row):
    """A [1, n] row as an [n, 1] column."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _band_fwd_kernel(q_ref, *refs, scale: float, geometry: Band, r: int):
    """One (batch x head, query block) grid step of a windowed forward:
    the query block against the slab of key blocks it can see, ``r`` rows at
    a time against their own slice. Every key a row sees is in its slice, so
    the rows are softmaxed whole: no running maximum, no rescaling and no
    scratch. All masks are added: the padding mask as a row, the two edges
    as tiles on the pieces they cross and nowhere else. A row that sees no
    key (every score under ``_NEG_INF / 2``) gives zeros and a logsumexp of
    ``_NEG_INF``, as the tiled kernel's gate has it."""
    n = geometry.blocks
    k_refs, v_refs, mask_refs = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    o_ref, lse_ref = refs[3 * n:]
    padding = _key_bias(mask_refs, pl.program_id(1), n)
    edge = _edge_biases(geometry, r)
    for t, pieces in enumerate(geometry.pieces):
        rows = slice(t * r, (t + 1) * r)
        q = q_ref[rows, :]
        scores = []
        for m, lo, hi, c0, crossed in pieces:
            s = _nt(q, k_refs[m][lo:hi, :]) * scale + padding(m, lo, hi)
            if crossed:
                s = s + edge(c0, c0 + hi - lo)
            scores.append(s)
        top = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=-1, keepdims=True) for s in scores])
        total, acc = 0.0, 0.0
        for s, (m, lo, hi, _, _) in zip(scores, pieces):
            p = jnp.exp(s - top)
            total += jnp.sum(p, axis=-1, keepdims=True)
            acc += _nn(p.astype(v_refs[m].dtype), v_refs[m][lo:hi, :])
        seen = top > _NEG_INF / 2
        o_ref[rows, :] = jnp.where(seen, acc / total, 0.0).astype(o_ref.dtype)
        lse = jnp.where(seen, top + jnp.log(total), _NEG_INF)
        lse_ref[:, rows] = jnp.broadcast_to(lse, (r, 128)).T[:1]


def _exponent_rows(lse, live=True):
    """The logsumexp as the backward subtracts it: a row that saw no key
    (``_NEG_INF``), or one of a block that is not ``live``, reads
    ``-_NEG_INF``, so that its probabilities are 0 whatever its scores."""
    return jnp.where(jnp.logical_and(lse > _NEG_INF / 2, live), lse,
                     -_NEG_INF)


def _band_dq_kernel(q_ref, do_ref, lse_ref, delta_ref, *refs, scale: float,
                    geometry: Band, r: int):
    """One (batch x head, query block) grid step of a windowed dq: the
    forward's slab and slices, exact probabilities from the saved logsumexp,
    dq of ``r`` rows summed over their slice's pieces in registers."""
    n = geometry.blocks
    k_refs, v_refs, mask_refs = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    dq_ref = refs[3 * n]
    padding = _key_bias(mask_refs, pl.program_id(1), n)
    edge = _edge_biases(geometry, r)
    lse, delta = _column(_exponent_rows(lse_ref[:])), _column(delta_ref[:])
    for t, pieces in enumerate(geometry.pieces):
        rows = slice(t * r, (t + 1) * r)
        q, do = q_ref[rows, :], do_ref[rows, :]
        dq = 0.0
        for m, lo, hi, c0, crossed in pieces:
            k = k_refs[m][lo:hi, :]
            s = _nt(q, k) * scale + padding(m, lo, hi)
            if crossed:
                s = s + edge(c0, c0 + hi - lo)
            p = jnp.exp(s - lse[rows])
            ds = p * (_nt(do, v_refs[m][lo:hi, :]) - delta[rows])
            dq += _nn(ds.astype(k.dtype), k)
        dq_ref[rows, :] = (dq * scale).astype(dq_ref.dtype)


def _band_dkv_kernel(*refs, scale: float, geometry: Band, r: int,
                     num_q: int):
    """One (batch x K/V head, key block, query head of the group) grid step
    of a windowed dk and dv, the mirror: the key block against the slab of
    query blocks that can see it (q, dO, and the logsumexp and delta rows),
    ``r`` keys at a time against their own slice, the transposed tile (keys
    down the sublanes). The only carry is over the group's query heads, in
    the f32 scratch. A query block past the sequence's end (its index map
    names the last) has no probability: its logsumexp reads ``-_NEG_INF``. A
    padded key's column of probabilities is its own rows of dk and dv and
    nothing else, so the padding mask is not in the tile: its rows are
    written as zeros."""
    n = geometry.blocks
    q_refs, do_refs, lse_refs, delta_refs = (
        refs[m * n:(m + 1) * n] for m in range(4))
    k_ref, v_ref, mask_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs[4 * n:]
    j, head = pl.program_id(1), pl.program_id(2)
    edge = _edge_biases(geometry, r)

    @pl.when(head == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    for t, pieces in enumerate(geometry.pieces):
        rows = slice(t * r, (t + 1) * r)
        k, v = k_ref[rows, :], v_ref[rows, :]
        dk, dv = 0.0, 0.0
        for m, lo, hi, c0, crossed in pieces:
            q, do = q_refs[m][lo:hi, :], do_refs[m][lo:hi, :]
            st = _nt(k, q) * scale
            if crossed:
                st = st + edge(c0, c0 + hi - lo)
            # a query block past the sequence's end has no probability
            pt = jnp.exp(st - _exponent_rows(lse_refs[m][:, lo:hi],
                                             j + m < num_q))
            dst = pt * (_nt(v, do) - delta_refs[m][:, lo:hi])
            dv += _nn(pt.astype(do.dtype), do)
            dk += _nn(dst.astype(q.dtype), q)
        dk_scr[rows, :] += dk
        dv_scr[rows, :] += dv

    @pl.when(head == pl.num_programs(2) - 1)
    def _finalize():
        valid = mask_ref[:] > 0  # [block, 1]
        dk_ref[:] = jnp.where(valid, dk_scr[:] * scale,
                              0.0).astype(dk_ref.dtype)
        dv_ref[:] = jnp.where(valid, dv_scr[:], 0.0).astype(dv_ref.dtype)


def _band_query_specs(bh: int, b: int, group: int, block: int, blocks: int):
    """Block specs of the grid (B*h, S/block) of a windowed forward and dq:
    ``q_side(width)`` a [block, width] block of the step's own queries,
    ``row`` their [1, block] row of a [BH, 1, S] array, ``k_side(width)``
    the slab's ``blocks`` [block, width] key blocks at head ``bh // group``,
    the last the queries' own and those before the sequence's start named
    block 0, and ``mask`` the same blocks' [1, block] rows of the [B, 1, S]
    mask."""
    heads = bh // b

    def slab(m):
        return lambda i: jnp.maximum(i + m - (blocks - 1), 0)

    def q_side(width):
        return _vmem((None, block, width), lambda g, i: (g, i, 0))

    def k_side(width):
        return [_vmem((None, block, width),
                      lambda g, i, at=slab(m): (g // group, at(i), 0))
                for m in range(blocks)]

    row = _vmem((None, 1, block), lambda g, i: (g, 0, i))
    mask = [_vmem((None, 1, block),
                  lambda g, i, at=slab(m): (g // heads, 0, at(i)))
            for m in range(blocks)]
    return q_side, row, k_side, mask


_BAND_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _band_fwd(q, k, v, mask, *, scale, window, block, r, interpret):
    """The windowed forward: out [BH, S, d_v] and the logsumexp [BH, 1, S]
    on the grid (B*h, S/block), K, V and the mask passed once a block of
    the slab."""
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    geometry = band(block, r, window)
    n = geometry.blocks
    q_side, row, k_side, mask_specs = _band_query_specs(
        bh, mask.shape[0], bh // k.shape[0], block, n)
    return pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale=scale, geometry=geometry,
                          r=r),
        grid=(bh, seq // block),
        in_specs=[q_side(d)] + k_side(d) + k_side(d_v) + mask_specs,
        out_specs=[q_side(d_v), row],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq), jnp.float32),
        ],
        compiler_params=_BAND_GRID,
        interpret=interpret,
    )(q, *[k] * n, *[v] * n, *[mask.astype(jnp.int32)[:, None, :]] * n)


def _band_dq(q, do, lse, delta, k, v, mask, *, scale, window, block, r,
             interpret):
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    geometry = band(block, r, window)
    n = geometry.blocks
    q_side, row, k_side, mask_specs = _band_query_specs(
        bh, mask.shape[0], bh // k.shape[0], block, n)
    return pl.pallas_call(
        functools.partial(_band_dq_kernel, scale=scale, geometry=geometry,
                          r=r),
        grid=(bh, seq // block),
        in_specs=[q_side(d), q_side(d_v), row, row] + k_side(d)
        + k_side(d_v) + mask_specs,
        out_specs=q_side(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_BAND_GRID,
        interpret=interpret,
    )(q, do, lse, delta, *[k] * n, *[v] * n, *[mask[:, None, :]] * n)


def _band_dkv(q, do, lse, delta, k, v, mask, *, scale, window, block, r,
              interpret):
    """The windowed dk, dv: grid (B * h_kv, S / block, group), the last
    axis the query heads a K/V head serves; q, dO and the two rows passed
    once a block of the slab, the blocks past the sequence's end named the
    last."""
    bh, seq, d = q.shape
    d_v = v.shape[-1]
    bh_kv = k.shape[0]
    group = bh // bh_kv
    kv_heads = bh_kv // mask.shape[0]
    num_q = seq // block
    geometry = band(block, r, window, mirror=True)
    n = geometry.blocks

    def slab(m):
        return lambda j: jnp.minimum(j + m, num_q - 1)

    def q_side(width):
        return [_vmem((None, block, width),
                      lambda g, j, t, at=slab(m): (g * group + t, at(j), 0))
                for m in range(n)]

    def k_side(width):
        return _vmem((None, block, width), lambda g, j, t: (g, j, 0))

    rows = [_vmem((None, 1, block),
                  lambda g, j, t, at=slab(m): (g * group + t, 0, at(j)))
            for m in range(n)]
    return pl.pallas_call(
        functools.partial(_band_dkv_kernel, scale=scale, geometry=geometry,
                          r=r, num_q=num_q),
        grid=(bh_kv, num_q, group),
        in_specs=q_side(d) + q_side(d_v) + rows + rows + [
            k_side(d), k_side(d_v),
            _vmem((None, block, 1), lambda g, j, t: (g // kv_heads, j, 0))],
        out_specs=[k_side(d), k_side(d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d_v), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*[q] * n, *[do] * n, *[lse] * n, *[delta] * n, k, v, mask[:, :, None])


def _flash_bwd(q, k, v, mask, out, lse, do, dlse=None, *, scale, causal,
               window, interpret, edge=None):
    """dq, dk, dv of ``_forward`` by two Mosaic calls (under a window the
    band step's), or by one where one tile spans the sequence and each K/V
    head serves one query head.
    q: [BH, S, d]; k: [BH / group, S, d]; v: [BH / group, S, d_v]; out,
    do: [BH, S, d_v]; mask: [B, S]; lse: [BH, 1, S]; ``dlse``: the
    logsumexp's own cotangent where it was an output, [BH, 1, S]. Exact
    probabilities are recomputed per tile from the logsumexp; no [S, S]
    tensor reaches HBM."""
    seq, d = q.shape[1:]
    shapes = (seq, d, q.dtype.itemsize)
    tiles = (backward_tiles(*shapes, causal, v.shape[-1]) if window is None
             else backward_band(*shapes, window, v.shape[-1]))
    delta = None
    if window is not None or dlse is not None or not (
            q.shape == k.shape and tiles == (seq, seq)):
        # D_i = sum_d dO_i * O_i, the softmax jacobian's row term, as a row
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]
    if dlse is not None:
        # d lse_i / d s_ij = p_ij: dS = p (dP - delta + dlse)
        delta = delta - dlse
    args = (q, do, lse, delta, k, v, mask.astype(jnp.int32))
    if window is not None:
        kwargs = dict(scale=scale, window=window, block=tiles[0], r=tiles[1],
                      interpret=interpret)
        dk, dv = _band_dkv(*args, **kwargs)
        return _band_dq(*args, **kwargs), dk, dv
    kwargs = dict(scale=scale, causal=causal, block_q=tiles[0],
                  block_k=tiles[1], interpret=interpret, edge=edge)
    dk, dv, dq = _flash_dkv(*args, **kwargs)
    if dq is None:
        dq = _flash_dq(*args, **kwargs)
    return dq, dk, dv


def _forward(q, k, v, mask, *, scale, causal, window, block_q, block_k,
             interpret, edge):
    """The forward call: under a window the band step, ``block_q`` its
    block and ``block_k`` its sub-block."""
    if window is not None:
        return _band_fwd(q, k, v, mask, scale=scale, window=window,
                         block=block_q, r=block_k, interpret=interpret)
    return _flash_fwd(q, k, v, mask, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret,
                      edge=edge)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(4, 12)))
def _flash(q, k, v, mask, scale, causal, window, edge, with_lse, block_q,
           block_k, interpret):
    """The output [BH, S, d_v] and, ``with_lse``, the logsumexp [BH, 1, S]
    beside it as a second output with a cotangent of its own."""
    out, lse = _forward(q, k, v, mask, scale=scale, causal=causal,
                        window=window, block_q=block_q, block_k=block_k,
                        interpret=interpret, edge=edge)
    return (out, lse) if with_lse else out


def _flash_vjp_fwd(q, k, v, mask, scale, causal, window, edge, with_lse,
                   block_q, block_k, interpret):
    out, lse = _forward(q, k, v, mask, scale=scale, causal=causal,
                        window=window, block_q=block_q, block_k=block_k,
                        interpret=interpret, edge=edge)
    # named on the variables the backward reads: a name on the call's
    # result, outside the custom_vjp, leaves these two unnamed
    out, lse = map(checkpoint_name, (out, lse), KEPT)
    return ((out, lse) if with_lse else out), (q, k, v, mask, out, lse)


def _flash_vjp_bwd(scale, causal, window, edge, with_lse, block_q, block_k,
                   interpret, res, g):
    do, dlse = g if with_lse else (g, None)
    dq, dk, dv = _flash_bwd(*res, do, dlse, scale=scale, causal=causal,
                            window=window, interpret=interpret, edge=edge)
    return dq, dk, dv, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, mask: Optional[jax.Array] = None,
                    causal: bool = False, window: Optional[int] = None,
                    edge_block: Optional[int] = None,
                    strict_edge: bool = False, return_lse: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    mesh: Optional[Mesh] = None):
    """Fused flash attention. ``q``: [B, S, h, d] (the model-side layout
    of ps_tpu/models/{bert,lm}.py); ``k``: [B, S, h_kv, d] and ``v``:
    [B, S, h_kv, d_v] with ``h_kv`` dividing ``h`` (each K/V head serves
    ``h / h_kv`` consecutive query heads; equal for plain multi-head
    attention) and ``d_v`` the values' own width (latent attention's keys
    are 192 wide and its values 128: no zero-padded v is made; the scale is
    ``d ** -0.5``, the keys'); ``mask``: optional [B, S] with 1 = attend
    (BERT padding convention); ``causal`` composes with it. ``window``
    (causal calls only): query ``i`` sees the keys ``j`` with
    ``0 <= i - j < window``, itself and the ``window - 1`` before it; the
    call runs the band step (the module docstring's A WINDOW), a block
    against the keys its window reaches and no other, so its work follows
    ``S * window`` and not ``S ** 2 / 2``. One that spans the sequence is the
    causal call. ``edge_block`` (causal calls
    without a window): the edge at the granularity of blocks of that many
    positions, a power of two that divides 128: query ``i`` sees every key
    before the end of its own block, or with ``strict_edge`` before its
    start; the first block's rows then see no key, and return zeros.
    Returns [B, S, h, d_v]; with ``return_lse`` the pair of it and the
    logsumexp of each row's visible scores [B, S, h] in f32 (about -1e30
    for a row that sees no key), an output with a gradient of its own.

    ``block_q`` / ``block_k`` tile the forward kernel; left at None they
    are ``forward_tiles``' choice from the operands' shapes. Under a window
    they are the band step's block and sub-block (``block_k`` a multiple of
    128 that divides ``block_q``), ``forward_band``'s choice. Off the chip
    the kernels run in interpret mode (``ops/mosaic.py::interpret``), so
    tests exercise the same kernel logic on CPU. Sequence length must be
    divisible by 128, the backward's key block, and by the forward's blocks
    (pad to 128 — XLA-side attention pads the same way in practice).

    ``mesh`` defaults to the one ``ps_tpu.init`` built, if any. Under a
    mesh the kernel runs inside ``shard_map`` — batch over 'data', heads
    over 'model', wherever the axis exists and divides — because GSPMD
    cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned", first met on four chips in PR 21); a
    dimension its axis does not divide is computed replicated.
    """
    b, seq, h, d = q.shape
    h_kv, d_v = k.shape[2], v.shape[3]
    if h % h_kv or v.shape[:3] != k.shape[:3] or k.shape[3] != d:
        raise ValueError(f"{h} query heads of width {d} on K/V of shapes "
                         f"{k.shape}, {v.shape}: the K/V heads must divide "
                         f"them, and k be as wide as q")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal=True and at "
                             f"least the query's own position")
        if window >= seq:
            window = None   # every earlier key is inside it
    edge = None
    if edge_block is not None:
        if not causal or window is not None or 128 % edge_block:
            raise ValueError(
                f"edge_block={edge_block} needs causal=True, no window and "
                f"a block length that divides 128")
        edge = (int(edge_block), bool(strict_edge))
    elif strict_edge:
        raise ValueError("strict_edge says which blocks an edge_block sees")
    if block_q is None or block_k is None:
        shapes = (seq, d, q.dtype.itemsize)
        chosen = (forward_tiles(*shapes, causal, d_v) if window is None
                  else forward_band(*shapes, window, d_v))
        block_q, block_k = block_q or chosen[0], block_k or chosen[1]
    if seq % block_q or seq % block_k or seq % 128:
        raise ValueError(
            f"seq len {seq} must be divisible by block_q={block_q}, "
            f"block_k={block_k} and 128 (pad the sequence)"
        )
    if window is not None and (block_q % block_k or block_k % 128):
        raise ValueError(
            f"under a window block_k={block_k} is the sub-block of "
            f"block_q={block_q}: a multiple of 128 that divides it")
    interpret = mosaic.interpret()
    if mask is None:
        mask = jnp.ones((b, seq), jnp.int32)
    scale = d ** -0.5

    def local(q, k, v, mask):
        lb = q.shape[0]
        lh = q.shape[2]

        def pack(x):  # [B, S, h, d] -> [B*h, S, d], at x's own head count
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(
                lb * x.shape[2], seq, x.shape[3])

        out = _flash(pack(q), pack(k), pack(v), mask, scale, causal,
                     window, edge, return_lse, block_q, block_k, interpret)
        if return_lse:
            out, lse = out
            lse = jnp.transpose(lse.reshape(lb, lh, seq), (0, 2, 1))
        out = jnp.transpose(out.reshape(lb, lh, seq, d_v), (0, 2, 1, 3))
        return (out, lse) if return_lse else out

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
    out_specs = (spec, P(*spec[:3])) if return_lse else spec
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec,
                                                 P(spec[0], None)),
                     out_specs=out_specs, check_vma=False)(q, k, v, mask)
