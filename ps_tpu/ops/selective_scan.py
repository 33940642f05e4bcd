"""Mamba-1's selective scan: a linear recurrence with a decay of its own for
every channel **and** state, which no matmul expresses (``ops/ssd.py`` is the
scalar-decay form, one decay a head; here ``A`` is [C, N])::

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t        h_{-1} = 0  [C, N]
    y_t = h_t C_t + D * x_t

Plain XLA, f32 inside, in two loops. The outer ``lax.scan`` walks chunks of
``CHUNK`` tokens and carries the state ``[B, N, C]`` (the channels on the
lanes: 5,120 is 40 whole tiles, where ``[C, N]`` would pad 16 states to 128
lanes); its body is under a ``jax.checkpoint``, so what lives between the
forward and the backward pass is **the state that entered each chunk**,
``[S / CHUNK, B, N, C]`` (84 MB a layer at the Phi-4-mini-flash cell's
``[1, 16384, 5120]`` on 16 states), and a chunk's decays and products are
made again inside its backward trip and dropped: no ``[S, C, N]`` array (5.4e9
B in f32 there) exists, forward or backward (``tests/test_chip_compile.py``
reads the compiled program). The inner ``lax.scan`` walks a chunk's tokens
with ``UNROLL`` of them written out a trip: XLA fuses a trip's tokens into a
few loop fusions, and no quotient of cumulated decays is formed anywhere, so
no decay is too strong or too weak for a chunk. A length the chunk does not
divide is padded with tokens of ``dt`` 0, which pass the state on unchanged.
Gradients are JAX's own of this program, all six.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 65, `.benchwork/
scan_table.py`, alone, median of 5 calls, ``x`` ``bf16[1, 16384, 5120]``,
``dt`` f32, 16 states)**, ms forward / forward and backward:

| chunk x unroll | forward | forward and backward |
|---|---|---|
| 256 x 16 | 20.4 | 100.4 |
| 128 x 32 | 22.0 | 100.3 |
| 128 x 16 | 20.5 | 81.4 |
| 128 x 8 | 12.7 | 83.9 |
| 64 x 16 | 19.8 | 65.7 |
| 64 x 8 | 17.1 | 61.5 |
| **64 x 4** | **11.0** | **57.7** |
| 32 x 16 | 20.2 | 67.0 |
| 32 x 8 | 17.6 | 62.8 |
| 32 x 4 | 14.6 | 59.1 |
| 16 x 8 | 18.1 | 65.8 |

The backward pass is most of it and shrinks with the chunk (what a trip
recomputes and keeps for its transposition) until the loops' trips cost more
than they save; inside the fused step 64 x 8 took 1,006.6 ms where 128 x 16
took 1,049.4 and 32 x 8 1,011.8. By its shapes the call moves 2.18e9 B a layer
forward and backward (``benchmark/families/phi4flash_step.py::scan_cost``),
2.7 ms at the HBM's 819 GB/s: the XLA form stands at about a twentieth of
that floor, and the Mosaic form (the state in VMEM over a block of channels,
as ``ops/ssd_mosaic.py`` keeps its own) is the next ``perf_opt``'s: ROADMAP
S22.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: tokens a chunk: one state a chunk is kept for the backward pass (the table
#: in the module docstring)
CHUNK = 64
#: tokens of a chunk written out in one loop body
UNROLL = 4


def selective_scan(x, dt, A, B, C, D, *, chunk: int = CHUNK,
                   unroll: int = UNROLL):
    """``x``, ``dt`` [B, S, C] (``dt`` > 0, after its softplus), ``A`` [C, N]
    (< 0), ``B``, ``C`` [B, S, N], ``D`` [C] -> ``y`` [B, S, C] in f32, every
    sequence from a zero state. Operands in any float dtype, read in f32 a
    chunk at a time; differentiable in all six."""
    b, s, c = x.shape
    f32 = jnp.float32
    chunk = min(chunk, s)
    pad = -s % chunk
    a_t = A.astype(f32).T                                   # [N, C]: C on the lanes

    def chunks(t):
        # a padded token has dt = 0: the state passes it unchanged
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(b, -1, chunk, t.shape[-1]), 1, 0)

    def token(h, args):                                     # h [B, N, C]
        x_t, dt_t, b_t, c_t = (t.astype(f32) for t in args)
        h = jnp.exp(dt_t[:, None, :] * a_t) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    @jax.checkpoint
    def body(h, args):
        h, y = jax.lax.scan(token, h, tuple(jnp.moveaxis(t, 1, 0)
                                            for t in args), unroll=unroll)
        return h, jnp.moveaxis(y, 0, 1)

    _, y = jax.lax.scan(body, jnp.zeros((b, a_t.shape[0], c), f32),
                        tuple(map(chunks, (x, dt, B, C))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, c)[:, :s]
    return y + D.astype(f32) * x.astype(f32)
