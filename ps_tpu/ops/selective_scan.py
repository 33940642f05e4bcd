"""Mamba-1's selective scan: a linear recurrence with a decay of its own for
every channel **and** state, which no matmul expresses (``ops/ssd.py`` is the
scalar-decay form, one decay a head; here ``A`` is [C, N])::

    h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) (x) B_t        h_{-1} = 0  [C, N]
    y_t = h_t C_t + D * x_t

**Two realisations of one recurrence; ``path`` says from the shapes alone
which runs.** Both walk the tokens one by one and form no quotient and no
cumulated product of decays, so no decay is too strong or too weak for a
chunk; both compute the decays, every exponential, the state and the sums in
f32 and read ``x``, ``B`` and ``C`` in the caller's dtype; both keep **the
state that entered each chunk** between the forward and the backward pass
and nothing with a token axis and a state axis at once: no ``[S, C, N]`` array
(5.4e9 B in f32 at the Phi-4-mini-flash cell's ``[1, 16384, 5120]`` on 16
states) exists, forward or backward (``tests/test_chip_compile.py`` reads the
compiled programs). A length the chunk does not divide is padded with tokens
of ``dt`` 0, which pass the state on unchanged.

*The Mosaic calls* (``ops/selective_scan_mosaic.py``: one forward, one
backward under a ``jax.custom_vjp``; what the cell runs). Channels that are
whole 128-lane tiles, states that are whole registers of eight sublanes
(Mamba-1's 16), ``x`` in bf16 or f32, one chip. The state ``[N, block]`` of a
block of channels lives in VMEM from a sequence's first token to its last;
the kernels' chunk is their tile of 256 tokens (21 MB of entering states a
layer in the cell). Off the chip the kernels' own bodies run interpreted
(``ops/mosaic.py::interpret``).

*The XLA form* (``_xla``; every other shape, and the tests' oracle beside
the token-by-token recurrence), f32 inside, in two loops. The outer
``lax.scan`` walks chunks of ``CHUNK`` tokens and carries the state ``[B, N,
C]`` (the channels on the lanes); its body is under a ``jax.checkpoint``, so a
chunk's decays and products are made again inside its backward trip and
dropped (84 MB of entering states a layer at the cell's shape). The inner
``lax.scan`` walks a chunk's tokens with ``UNROLL`` of them written out a
trip: XLA fuses a trip's tokens into a few loop fusions. Gradients are JAX's
own of this program, all six. ``chunk=`` and ``unroll=`` are this form's.

**Measured (TPU v5e, jax 0.9.0; my chip runs, PR 66, ``tools/scan_table.py``,
the call alone, four calls chained in one program, median of five chains;
``x`` ``bf16[1, 16384, 5120]``, ``dt`` f32, 16 states)**, ms:

| form | forward | backward | forward and backward |
|---|---|---|---|
| the XLA form, chunk 64 x unroll 4 (PR 65's table, a call dispatched alone: 11.0 / 57.7; 128 x 16 20.5 / 81.4, 64 x 8 17.1 / 61.5, 32 x 4 14.6 / 59.1) | 9.08 | - | 55.88 |
| **the Mosaic calls, tile 256 x block 512** | **2.81** | **7.12** | **9.93** |
| tile 128 x block 512 | 2.88 | 7.28 | 10.16 |

The tiles and blocks tried, on the same bodies but for how ``B`` and ``C``
are laid over the lanes (``_over_lanes``), forward / backward: in trips of
eight tokens, 256 x 512 3.08 / 7.43, 256 x 1024 2.90 / 7.57, 512 x 512 3.04 /
7.34; by one static slice a token, written out a tile, 128 x 512 2.81 / 7.19,
256 x 512 2.72 / 7.01, 128 x 256 3.51 / 7.78, 256 x 256 3.37 / 7.54, 128 x
1024 2.57 / 7.27.

By its shapes the call moves 2.18e9 B a layer forward and backward
(``benchmark/families/phi4flash_step.py::scan_cost``), 2.67 ms at the HBM's
819 GB/s: the kernels stand at 27% of that floor where the XLA form stood at
a twentieth, and bytes are no longer what bounds them: the vector ALU is
(``ops/selective_scan_mosaic.py``, "What bounds them"). Inside the cell's
traced step (``tools/scope_table.py``, seed 6600000211) the six calls take
2.59 ms forward (four: each layer's forward runs again in its checkpoint's
recomputation) and 6.88 backward (two), 24.12 of the 24.14 ms under
``ps.mamba/s6`` where the XLA form took 133.2: XLA puts nothing to speak of
around them. Blocks of 256 channels leave the ALU's slots emptier (two chains
a token where four fill them); blocks of 1,024 are faster forward and slower
backward, where sixteen registers of carried state and cotangent spill; a
tile of 256 tokens halves the entering states and the grid's steps. The
static slices are 0.09 ms a call faster than the trips of 32 tokens that
stayed, and take the interpreted kernels of the CPU tests twice as long to
compile; trips of eight wait for the XLU eight tokens at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ps_tpu.ops import mosaic, selective_scan_mosaic

#: tokens a chunk: one state a chunk is kept for the backward pass (the table
#: in the module docstring)
CHUNK = 64
#: tokens of a chunk written out in one loop body
UNROLL = 4


def path(x, A) -> str:
    """Which realisation ``x`` [B, S, C] on states ``A`` [C, N] takes, read
    from the shapes alone: ``"kernel"`` (``ops/selective_scan_mosaic.py``)
    where the channels are whole 128-lane tiles, the states whole registers
    of eight sublanes (Mamba-1's 16; 8 to 64 run the same body), ``x`` is
    bf16 or f32 and the program is one chip's; else ``"xla"``. Any length:
    what the kernels' tile does not divide is padded with tokens of ``dt``
    0."""
    from ps_tpu import api

    one_chip = not api.is_initialized() \
        or api.current_context().mesh.size == 1
    whole = selective_scan_mosaic.lanes(x.shape[-1]) \
        and A.shape[-1] in range(8, 65, 8)
    return "kernel" if whole and one_chip and x.dtype in (
        jnp.bfloat16, jnp.float32) else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kernel(x, dt, a, b, c, interpret):
    return selective_scan_mosaic.forward(x, dt, a, b, c, interpret=interpret,
                                         keep=False)[0]


def _kernel_fwd(x, dt, a, b, c, interpret):
    y, (states,) = selective_scan_mosaic.forward(
        x, dt, a, b, c, interpret=interpret, keep=True)
    return y, (x, dt, a, b, c, states)


def _kernel_bwd(interpret, kept, dy):
    return selective_scan_mosaic.backward(*kept, dy, interpret=interpret)


_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def selective_scan(x, dt, A, B, C, D, *, chunk: int = CHUNK,
                   unroll: int = UNROLL):
    """``x``, ``dt`` [B, S, C] (``dt`` > 0, after its softplus), ``A`` [C, N]
    (< 0), ``B``, ``C`` [B, S, N], ``D`` [C] -> ``y`` [B, S, C] in f32, every
    sequence from a zero state. Operands in any float dtype, read in f32;
    differentiable in all six. ``path`` says which realisation runs;
    ``chunk`` and ``unroll`` are the XLA form's."""
    if path(x, A) == "kernel":
        s = x.shape[1]

        def padded(t):  # a padded token has dt = 0: the state passes it
            return jnp.pad(t, ((0, 0), (
                0, -s % selective_scan_mosaic.TILE), (0, 0)))

        y = _kernel(padded(x), padded(dt), A, padded(B), padded(C),
                    mosaic.interpret())[:, :s]
    else:
        y = _xla(x, dt, A, B, C, chunk, unroll)
    return y + D.astype(jnp.float32) * x.astype(jnp.float32)


def _xla(x, dt, A, B, C, chunk: int, unroll: int):
    """The XLA form (module docstring): ``y`` without the ``D x`` skip."""
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
    return jnp.moveaxis(y, 0, 1).reshape(b, s + pad, c)[:, :s]
