"""The blocks more than one model computes alike. No model imports another:
a block two of them need stands here, a block one needs in that model's file.
The blocked readout (``blocked_head_nll``) came from ``models/mellum.py`` in
PR 63: Mellum's ``blocked_head_ce`` is the mean of its block sums, Ouro's four
readouts (``models/ouro.py``) take a position's loss from it and weight it.
``layer_norm``, ``diff_attention_block`` and ``gmu_block`` came with
Phi-4-mini-flash (``models/phi4flash.py``, PR 65), which is their one caller
so far: ISSUE 65 asked for them here, beside the closure and the norm they
are built on, where the next differential or memory-gated model finds them.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.obs import phases
from ps_tpu.ops import moe
from ps_tpu.ops import rope as rotary
from ps_tpu.ops.gated_conv import conv_silu_kernel
from ps_tpu.ops.ssd import ssd


def rms_norm(x, scale, eps):
    """Statistics in f32, result in ``x``'s dtype, as the published code."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (scale * xf).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    """LayerNorm over the last axis: mean and variance in f32, the scale and
    the bias applied there, result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (scale * xf + bias).astype(x.dtype)


def rope(x, theta, *, inv_freq=None, scale=None, interleaved=False):
    """Rotary positions on ``x`` [B, S, h, d], halves rotated against each
    other (``rotate_half``), angles in f32. The frequencies are
    ``theta ** (-2i / d)`` unless a table ``inv_freq`` [d / 2] is given (a
    layer type's own: blended and divided as YaRN's); ``scale`` multiplies
    cos and sin (YaRN's ``attention_factor``: a rotated q . k carries its
    square). ``interleaved``: channels ``2j`` and ``2j + 1`` are rotated
    against each other instead (``rope_interleave``), each pair by the
    ``j``-th angle, and come out where they went in: the partner is a lane's
    neighbour, fetched by two rolls and a select on the lane's parity, no
    strided slice. Without any of the three the trace is the one-table
    call's.

    **Where the halves' rotation runs on the chip.** At heads of one 128-lane
    tile on whole row blocks (``ops/rope.py::path``: Ouro, OLMoE, SDAR,
    Trinity's windowed layers, Mellum with its table and factor) it is
    ``ops/rope.py::rotate``, one Mosaic pass that reads ``x`` in its own
    dtype and writes the attention kernel's operand, the same f32 arithmetic
    in VMEM. The expression below, compiled for a v5e, makes XLA write
    ``x.astype(f32)``, ``-x2`` and ``x1`` as arrays of their own (half of each
    tile empty) before the fusion that sums them, and the same again for the
    cotangent; bytes a tensor a pass at ``bf16[1, 8192, 16, 128]``, as tiled
    (``ops/rope.py``'s table):

    | | written | read | passes a layer application |
    |---|---|---|---|
    | the expression | 67 + 134 + 34 MB | 67 + 67 + 134 MB | q and k: forward, recomputation, transposed |
    | ``rotate`` | 34 MB | 34 MB + 8 MB of tables | the same six |

    The [B, S, h, d] contract stands: ``rotate`` takes the head-major view,
    which is how the projection's product (or the head norm's output) lies on
    the chip and what ``flash_attention`` asks for, so the two transpositions
    here are bitcasts in the compiled step and no caller changes. Every other
    shape (heads of 64, a rotated slice, ``interleaved``, the narrow widths
    of tests and rehearsals) takes the expression, its trace untouched."""
    seq, dim = x.shape[1], x.shape[-1]
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    if rotary.path(x, interleaved) == "kernel":
        turned = rotary.rotate(jnp.transpose(x, (0, 2, 1, 3)),
                               *rotary.tables(angles, scale))
        return jnp.transpose(turned, (0, 2, 1, 3))
    if interleaved:
        cos, sin = (jnp.repeat(f(angles), 2, axis=-1)[None, :, None, :]
                    for f in (jnp.cos, jnp.sin))
        if scale is not None:
            cos, sin = cos * scale, sin * scale
        xf = x.astype(jnp.float32)
        even = jnp.arange(dim) % 2 == 0
        rotated = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                            jnp.roll(xf, 1, axis=-1))
        return (xf * cos + rotated * sin).astype(x.dtype)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)


def _full_attention(q, k, v, causal=True, window=None, **_):
    heads, kv_heads = q.shape[2], k.shape[2]
    if kv_heads != heads:
        k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        t = q.shape[1]
        seen = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            # query i sees keys i - window < j <= i
            seen = seen & ~jnp.tril(jnp.ones((t, t), bool), -window)
        s = jnp.where(seen[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def make_attn_fn(attn: str = "full", mesh=None, **kw) -> Callable:
    """'full' | 'flash' | 'ring' | 'ulysses'. 'flash' is the single-device
    Pallas kernel (O(S) attention memory; seq must be a multiple of 128).
    Both take ``k``, ``v`` [B, S, h_kv, d] at their own head count (the
    kernel reads head ``h // group``, 'full' repeats them) and ``window=``
    on a causal call (query i sees keys i - window < j <= i). 'ring' /
    'ulysses' need a 'seq' mesh axis, activations sharded P(batch, 'seq')
    and equal head counts."""
    if attn == "full":
        return _full_attention
    if attn == "flash":
        from ps_tpu.ops import flash_attention

        def flash_fn(q, k, v, causal=True, window=None):
            return flash_attention(q, k, v, causal=causal, window=window,
                                   **kw)

        return flash_fn
    from ps_tpu.parallel import ring_attention, ulysses_attention

    op = {"ring": ring_attention, "ulysses": ulysses_attention}[attn]

    def fn(q, k, v, causal=True):
        if k.shape[2] != q.shape[2]:
            raise ValueError(f"make_attn_fn({attn!r}): {k.shape[2]} K/V heads "
                             f"on {q.shape[2]} query heads, must be equal")
        return op(q, k, v, mesh, causal=causal, **kw)

    return fn


def mla_block(lp: Dict, x, config, attn_fn: Callable):
    """Multi-head latent attention of the normed activations ``x``
    [B, S, D]: K and V expanded from the normalised ``kv_lora_rank``-wide
    latent, the ``qk_rope_head_dim`` channels every head shares broadcast to
    the heads and concatenated behind each head's own ``qk_nope_head_dim``
    (one operand as wide as q: the kernel reads q and k at 192 and v at 128,
    ``ops/flash_attention.py``), scale ``(nope + rope) ** -0.5``. Two things
    are read from ``config`` and absent where it has no such field: a
    ``q_lora_rank``, and q goes through a latent of that width and its norm
    (``q_a``, ``q_norm``, ``q_b`` in ``lp``) where it was one matrix
    (``q``); a ``rope_theta``, and the shared channels and the matching last
    channels of every query head are rotated by their position, by
    interleaved pairs where ``rope_interleave``. With neither (Kimi-Linear:
    a plain q, no positions) the trace is the block's as that model had it.
    The shared channels' gradient is the sum over the heads."""
    c = config
    b, s, _ = x.shape
    heads, nope, pe = (c.num_attention_heads, c.qk_nope_head_dim,
                       c.qk_rope_head_dim)
    q_rank = getattr(c, "q_lora_rank", None)
    theta = getattr(c, "rope_theta", None)

    def proj(name, h):
        return h @ lp[name]["kernel"].astype(h.dtype)

    with jax.named_scope(phases.ATTN_LATENT):
        if q_rank is None:
            q = proj("q", x)
        else:
            q = proj("q_b", rms_norm(proj("q_a", x), lp["q_norm"]["scale"],
                                     c.rms_norm_eps))
        q = q.reshape(b, s, heads, nope + pe)
        latent = proj("kv_a", x)
        compressed, k_pe = jnp.split(latent, [c.kv_lora_rank], axis=-1)
        kv = proj("kv_b", rms_norm(compressed, lp["kv_norm"]["scale"],
                                   c.rms_norm_eps)).reshape(b, s, heads, -1)
    with jax.named_scope(phases.ATTN_ROPE):
        if theta is not None:
            pairs = getattr(c, "rope_interleave", False)
            k_pe = rope(k_pe[:, :, None, :], theta, interleaved=pairs)[:, :, 0]
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], theta,
                                     interleaved=pairs)], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, pe))],
            axis=-1)
    with jax.named_scope(phases.ATTN_FULL):
        a = attn_fn(q, k, kv[..., nope:], causal=True)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


def diff_attention_block(lp: Dict, q, k, v, attn_fn: Callable, *,
                         lambda_init: float, eps: float, core: str,
                         window: Optional[int] = None):
    """Differential attention (Ye et al., arXiv:2410.05258 section 2) of
    ``q`` [B, S, 2 h, d], ``k`` and ``v`` [B, S, 2 h_kv, d]: consecutive
    heads are a pair ``(q1, q2)``, ``(k1, k2)``, and a pair of value heads is
    ONE value head ``2 d`` wide, which both maps read::

        a_j = softmax(q_j k_j^T / sqrt(d)) v                  j = 1, 2
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        out = (1 - lambda_init) * rms_norm_{2d}(a_1 - lambda a_2) * w

    with four learned vectors of ``d`` and one norm scale of ``2 d`` in
    ``lp``, the norm a head. The two maps are ONE call of ``attn_fn``
    (opened under the scope ``core``), ``2 h`` query heads on ``2 h_kv`` K/V
    heads over the value heads twice: the first ``h`` query heads are the
    ``q1`` and read ``k1``, the last ``h`` the ``q2`` on ``k2`` (each K/V
    head serves ``h / h_kv`` consecutive query heads either way), keys ``d``
    wide against values ``2 d``: the kernel's ``d_v``. Two calls of ``h`` on
    ``h_kv`` heads are the same work in twice the grid launches and were not
    faster (``PERF.md`` section 6, PR 65); v's copy is 42 MB at the cell's
    shape and its two cotangents one sum. What follows the call runs in f32
    under ``ps.attn/diff``. Returns [B, S, h * 2 d]."""
    b, s, pairs, d = q.shape
    heads, kv_heads = pairs // 2, k.shape[2] // 2

    def firsts_then_seconds(t, n):
        return jnp.moveaxis(t.reshape(b, s, n, 2, d), 3, 2).reshape(
            b, s, 2 * n, d)

    v = v.reshape(b, s, kv_heads, 2 * d)
    with jax.named_scope(core):
        a = attn_fn(firsts_then_seconds(q, heads),
                    firsts_then_seconds(k, kv_heads),
                    jnp.concatenate([v, v], axis=2), causal=True,
                    window=window)
    with jax.named_scope(phases.ATTN_DIFF):
        a = a.astype(jnp.float32)
        lam = jnp.exp(jnp.vdot(lp["lambda_q1"], lp["lambda_k1"])) \
            - jnp.exp(jnp.vdot(lp["lambda_q2"], lp["lambda_k2"])) + lambda_init
        a = rms_norm(a[:, :, :heads] - lam * a[:, :, heads:],
                     lp["head_norm"]["scale"], eps) * (1.0 - lambda_init)
    return a.reshape(b, s, -1).astype(q.dtype)


def gmu_block(lp: Dict, x, memory):
    """The Gated Memory Unit (SambaY, arXiv:2507.06607) of the normed
    activations ``x`` [B, S, D] over ``memory`` [B, S, d_inner], another
    layer's scan output: ``(memory * silu(x W_in)) W_out``; no scan, no
    taps, no state of its own."""
    gate = jax.nn.silu(x @ lp["in_proj"]["kernel"].astype(x.dtype))
    return (memory.astype(x.dtype) * gate) \
        @ lp["out_proj"]["kernel"].astype(x.dtype)


def mamba_block(lp: Dict, x, *, heads: int, head_dim: int, groups: int,
                state: int, chunk: int, eps: float):
    """The Mamba-2 mixer of the normed activations ``x`` [B, S, D], for
    ``heads`` heads of ``head_dim`` on ``groups`` groups of B and C over a
    state of ``state``: ``[z | xBC | dt] = x W_in`` (which bears the name
    'mamba_in'); the x, B and C channels through the causal taps, the bias
    and the SiLU of ``ops/gated_conv.py::conv_silu_kernel``; ``dt =
    softplus(dt + dt_bias)`` in f32; the scan
    (``ops/ssd.py`` in chunks of ``chunk``: two Mosaic calls at the cells'
    shapes, whose forward runs again in a layer's recomputation for the
    states its backward reads, so the output bears no name for a policy to
    keep; the XLA form at shapes the kernels do not take); then, in f32, the
    skip ``D x``, the gate ``silu(z)`` first and an RMSNorm over each group's
    channels after it (a share that holds whole groups has the uncut mixer's
    norm over them, exactly; at one group the norm is over all ``heads *
    head_dim`` channels); the out projection.
    Nemotron-H's (a share of the heads: what comes out is that share's part
    of a sum) and Granite-4.0-H's (whole).

    **The element-wise stages take the layout the scan's Mosaic calls state.**
    The taps are ``conv_silu_kernel``: at the cells' shapes two Mosaic calls,
    which write ``x``, ``B`` and ``C`` row-major as the scan's calls read them
    (``ops/ssd_mosaic.py``), and at a shape ``gated_conv.path`` does not take
    (the tests' small ones) the XLA form, as for Kimi-Linear's and
    Qwen3-Next's mixers: the shapes choose, and no argument, field or
    environment variable. (While the scan was XLA's at Granite's shape its
    einsums read ``x`` in three layouts and the Mosaic taps cost that cell
    471.91 ms a step where 443.03, in copies, PR 57; no cell's scan is XLA's
    since PR 59.) Behind the
    scan the skip is ``repeat(D, head_dim)`` times the taps' ``x`` as ``[B,
    S, heads * head_dim]``, the layout the scan's call writes ``y`` in: as
    4-D math over ``[.., heads, head_dim]`` XLA chose a layout of its own for
    it and paid for the difference in four copies of ``[1, 8192, 4096]`` in
    a layer's backward (``PERF.md`` section 6, PR 72). The same products
    forward, to the bit; ``D``'s gradient sums the same terms in another
    order."""
    b, s, _ = x.shape
    inner = heads * head_dim
    conv_dim = inner + 2 * groups * state
    projected = checkpoint_name(
        x @ lp["in_proj"]["kernel"].astype(x.dtype), "mamba_in")
    z, xbc, dt = jnp.split(projected, [inner, inner + conv_dim], axis=-1)
    with jax.named_scope(phases.MAMBA_CONV):
        xbc = conv_silu_kernel(xbc, lp["conv"]["kernel"], lp["conv"]["bias"])
    xs, b_in, c_in = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    with jax.named_scope(phases.MAMBA_SSD):
        y = ssd(xs.reshape(b, s, heads, head_dim), dt, -jnp.exp(lp["A_log"]),
                b_in.reshape(b, s, groups, -1), c_in.reshape(b, s, groups, -1),
                chunk=min(chunk, s))
    with jax.named_scope(phases.MAMBA_GATE):
        # the skip over the flat channels, as the scan's calls lay them out
        y = y.reshape(b, s, inner).astype(jnp.float32) \
            + jnp.repeat(lp["D"], head_dim) * xs.astype(jnp.float32)
        # the gate first, then the norm over each group's channels
        y = y * jax.nn.silu(z.astype(jnp.float32))
        y = rms_norm(y.reshape(b, s, groups, -1),
                     lp["out_norm"]["scale"].reshape(groups, -1), eps)
    return y.reshape(b, s, inner).astype(x.dtype) \
        @ lp["out_proj"]["kernel"].astype(x.dtype)


def dense_ffn(lp: Dict, x):
    """``W2(silu(W1 x) * W3 x)``."""
    def w(name):
        return lp[name]["kernel"].astype(x.dtype)

    return (jax.nn.silu(x @ w("w1")) * (x @ w("w3"))) @ w("w2")


def window_of(routing: moe.Routing, tokens, gate, up, down, *,
              whole_window: bool):
    """Dispatch, the held experts and combine over one window of
    ``routing``'s sorted pairs: ``ops/moe.py::over_windows``' ``layer``.
    ``up`` None: ungated ``relu(x W1)**2 W2`` experts, else SwiGLU.
    ``whole_window``: the grouped matmuls do the whole window's work,
    whatever is live."""
    with jax.named_scope(phases.MOE_DISPATCH):
        rows = moe.dispatch(tokens, routing)
    with jax.named_scope(phases.MOE_EXPERT):
        rows = moe.expert_ffn(
            rows, gate, up, down, routing.group_sizes,
            activation="relu2" if up is None else "swiglu",
            expected_rows=rows.shape[0] if whole_window else None)
    with jax.named_scope(phases.MOE_COMBINE):
        return moe.combine(rows, routing)


#: ``over_windows``' ``layer`` must be the same object from call to call
LIVE_ROWS = functools.partial(window_of, whole_window=False)
WHOLE_WINDOW = functools.partial(window_of, whole_window=True)


@jax.checkpoint
def experts_of(tokens, gate, up, down, routing: moe.Routing):
    """Dispatch, the held SwiGLU experts and combine over the windows of the
    held pairs, recomputed in the backward pass: between two layers only
    ``tokens`` and ``routing`` live on. The stacks are cast to the tokens'
    precision in here, so their copies are made again for the backward and
    not kept from the forward (twelve of 38 MB in a Kimi step's peak)."""
    return moe.over_windows(
        LIVE_ROWS, routing, tokens,
        *(stack.astype(tokens.dtype) for stack in (gate, up, down)))


def init_expert_bias(config):
    """The selection bias at step 0: zeros, one row an expert layer. The
    share models re-export it: the benchmark imports it from each."""
    return jnp.zeros((config.num_expert_layers, config.router_width),
                     jnp.float32)


def token_ce(logits, targets):
    """Mean next-token CE in logsumexp form — no [B, T, V] f32
    log-probability tensor is materialized (see bert.mlm_loss)."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), -1)
    tok = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - tok.astype(jnp.float32))


def blocked_head_nll(hidden, head, targets, block, *, summed=False):
    """The next-token negative log-likelihood of ``hidden @ head`` for normed
    hidden states ``hidden`` [B, S, D], a head [D, V] and ``targets`` [B, S],
    in ``token_ce``'s logsumexp form and f32, the logits formed ``block``
    positions of every sequence at a time, each block under a
    ``jax.checkpoint``: [B, block, V] logits and their cotangent live at
    once, never [B, S, V]. A position's loss [B, S], for a caller that
    weights it (Ouro's exit distribution); with ``summed`` each block's sum
    [S / block] and no array a position, which is the program
    ``mellum.blocked_head_ce`` has traced to since PR 46. The head's
    gradient is the sum over the blocks, and over every call that reads the
    same ``head``."""
    b, s, d = hidden.shape
    if s % block:
        raise ValueError(f"blocked_head_ce: blocks of {block} do not tile {s}")

    @jax.checkpoint
    def block_nll(args):
        h, t = args                                  # [B, block, D], [B, block]
        z = h @ head.astype(h.dtype)
        lse = jax.nn.logsumexp(z.astype(jnp.float32), -1)
        tok = jnp.take_along_axis(z, t[..., None], -1)[..., 0]
        nll = lse - tok.astype(jnp.float32)
        return jnp.sum(nll) if summed else nll

    blocks = (jnp.moveaxis(hidden.reshape(b, s // block, block, d), 1, 0),
              jnp.moveaxis(targets.reshape(b, s // block, block), 1, 0))
    out = jax.lax.map(block_nll, blocks)
    return out if summed else jnp.moveaxis(out, 0, 1).reshape(b, s)
