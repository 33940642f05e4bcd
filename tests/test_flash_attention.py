"""Pallas flash attention ≡ the reference einsum attention.

The kernels, forward and backward, run in interpret mode on CPU — the same
online-softmax loop, block structure, and masking logic as on the chip —
and must match the models' `_full_attention` (ps_tpu/models/blocks.py) in both
the forward output and every input gradient, causal and padded, including
the numerically delicate cases (fully-masked rows, block-boundary
diagonals).
"""

import importlib

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import primitives
from ps_tpu.models.blocks import _full_attention
from ps_tpu.ops import flash_attention
from ps_tpu.ops.flash_attention import (_VMEM_BUDGET, _first_live,
                                        _last_live, backward_band,
                                        backward_tiles, backward_vmem_bytes,
                                        band, forward_band, forward_tiles,
                                        forward_vmem_bytes)

# the module itself: ``ps_tpu.ops.flash_attention`` names the function
fa = importlib.import_module("ps_tpu.ops.flash_attention")

B, S, H, D = 2, 256, 4, 64


def _qkv(seed, s=S, b=B, h=H, d=D):
    rng = np.random.default_rng(seed)
    shape = (b, s, h, d)
    return tuple(
        jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
        for _ in range(3)
    )


def _ref(q, k, v, mask=None, causal=False, window=None):
    """The models' einsum attention, with the BERT-style [B, S] mask; under
    ``window`` a plain band mask: query i sees keys i - window < j <= i."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        t = q.shape[1]
        seen = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            seen = seen & ~jnp.tril(jnp.ones((t, t), bool), -window)
        s = jnp.where(seen[None, None], s, -1e30)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv(0)
    got = flash_attention(q, k, v, causal=causal)
    want = _ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_with_padding_mask():
    q, k, v = _qkv(1)
    rng = np.random.default_rng(2)
    mask = jnp.asarray((rng.random((B, S)) < 0.7).astype(np.int32))
    got = flash_attention(q, k, v, mask=mask)
    want = _ref(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _padding(seed, b, s):
    """A [b, s] mask that really pads, key 0 kept valid: a causal row whose
    every visible key is masked is DEGENERATE — the einsum reference
    softmaxes all -1e30 to uniform garbage while flash emits zeros (the
    convention asserted by test_fully_masked_rows_emit_zeros_fwd_and_bwd);
    reference parity is only defined on non-degenerate rows."""
    mask = np.asarray(np.random.default_rng(seed).random((b, s)) < 0.8,
                      np.int32)
    mask[:, 0] = 1
    return mask


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = _qkv(3)
    mask = jnp.asarray(_padding(4, B, S))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, mask=mask, causal=causal) ** 2)

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


# (seq, batch, heads, head_dim, causal, block_q, block_k); None = the
# chooser's tile. Forced tiles with block_q != block_k put the causal
# diagonal mid-way through a block, in both orders, with key blocks past
# the diagonal whose fetch is clamped. Under a window the call is the band
# step, block_q its block and block_k its sub-block ``r``.
TILE_CASES = [
    pytest.param(256, 2, 4, 64, False, None, None, None, id="chosen-s256"),
    pytest.param(512, 2, 2, 64, False, None, None, None, id="chosen-s512-one-block"),
    pytest.param(512, 2, 2, 64, True, None, None, None, id="chosen-s512-causal"),
    pytest.param(1024, 1, 2, 128, True, None, None, None, id="chosen-s1024-d128-causal"),
    pytest.param(512, 2, 2, 64, True, 256, 128, None, id="causal-q256-k128"),
    pytest.param(512, 2, 2, 64, True, 128, 256, None, id="causal-q128-k256"),
    pytest.param(512, 1, 2, 64, True, 512, 128, None, id="causal-q512-k128"),
    pytest.param(512, 1, 2, 64, True, 128, 512, None, id="causal-q128-k512"),
    pytest.param(512, 2, 2, 64, False, 128, 256, None, id="padded-q128-k256"),
    pytest.param(256, 2, 4, 64, True, 128, 128, None, id="causal-q128-k128"),
    # a window: smaller than a sub-block, a sub-block, wider and off the
    # lanes, one sub-block a block and several, the rule's band under it,
    # and wider than the sequence (the causal call)
    pytest.param(512, 2, 2, 64, True, 128, 128, 64, id="window64-q128-k128"),
    pytest.param(512, 2, 2, 64, True, 128, 128, 128, id="window128-q128-k128"),
    pytest.param(512, 1, 2, 64, True, 256, 128, 200, id="window200-q256-k128"),
    pytest.param(512, 1, 2, 64, True, 512, 256, 200, id="window200-q512-k256"),
    pytest.param(512, 1, 2, 64, True, 512, 512, 100, id="window100-one-block"),
    pytest.param(1024, 1, 2, 128, True, None, None, 512, id="window512-chosen-s1024-d128"),
    pytest.param(512, 1, 2, 64, True, 128, 128, 1024, id="window1024-past-the-sequence"),
]


@pytest.mark.parametrize("seq,b,h,d,causal,block_q,block_k,window",
                         TILE_CASES)
def test_tiles_match_reference_forward_and_gradients(seq, b, h, d, causal,
                                                     block_q, block_k,
                                                     window):
    """Whatever tiles the forward runs at, chosen or forced: the output and
    the gradients of q, k and v are the einsum attention's."""
    q, k, v = _qkv(11, s=seq, b=b, h=h, d=d)
    mask = jnp.asarray(_padding(12, b, seq))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k)

    def ref(q, k, v):
        return _ref(q, k, v, mask=mask, causal=causal, window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_one_sequence_wide_block_with_a_fully_masked_row():
    """BERT's tile: the whole sequence in one key step, so no carry and no
    scratch. Batch row 1 is all padding: exactly zero out and gradients
    there, the reference's values on row 0."""
    seq = 512
    assert forward_tiles(seq, D, 4, False) == (seq, seq)
    q, k, v = _qkv(13, s=seq, h=2)
    mask = np.stack([_padding(14, 1, seq)[0], np.zeros(seq, np.int32)])
    mask = jnp.asarray(mask)

    def value_and_grads(attn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attn(q, k, v, mask=mask) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    out = np.asarray(flash_attention(q, k, v, mask=mask))
    np.testing.assert_array_equal(out[1], 0.0)
    np.testing.assert_allclose(out[0], np.asarray(_ref(q, k, v, mask=mask))[0],
                               rtol=2e-5, atol=2e-5)
    (_, got), (_, want) = value_and_grads(flash_attention), value_and_grads(_ref)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_array_equal(np.asarray(g)[1], 0.0, err_msg=name)
        np.testing.assert_allclose(np.asarray(g)[0], np.asarray(w)[0],
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("seq,head_dim,itemsize,causal,want", [
    (512, 64, 2, False, (512, 512)),     # bert-base.s512.flash and .x4
    (4096, 128, 2, True, (1024, 1024)),  # olmoe-1b-7b.s4096.zipf
    (256, 64, 4, False, (256, 256)),     # this file's S
])
def test_forward_tiles_are_pinned_and_fit_the_budget(seq, head_dim, itemsize,
                                                     causal, want):
    got = forward_tiles(seq, head_dim, itemsize, causal)
    assert got == want
    assert forward_vmem_bytes(*got, head_dim, itemsize) <= _VMEM_BUDGET
    assert _VMEM_BUDGET < 16 * 2 ** 20  # Mosaic's scoped default, v5e


@pytest.mark.parametrize("block_q,block_k", [
    (128, 128), (256, 128), (128, 256), (512, 128), (128, 512), (1024, 512),
    (512, 1024)])
def test_causal_clamp_and_live_test_agree(block_q, block_k):
    """_last_live is both the kernel's compute skip and the index maps'
    clamp: block j is live exactly when its first key is visible to the
    query block's last row, and a dead step names a live block."""
    seq = 2048
    for qi in range(seq // block_q):
        last_row = (qi + 1) * block_q - 1
        last = _last_live(qi, block_q, block_k)
        for j in range(seq // block_k):
            assert (j <= last) == (j * block_k <= last_row)
        assert 0 <= last and last * block_k <= last_row


def _grads(attn, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(attn(q, k, v) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


def _ref_grouped(q, k, v, mask=None, causal=False, window=None):
    """The einsum attention on K/V repeated for the query heads they
    serve: its k / v gradients sum over each group."""
    group = q.shape[2] // k.shape[2]
    return _ref(q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
                mask=mask, causal=causal, window=window)


# (seq, batch, heads, K/V heads, head_dim, causal, backward block_q,
# block_k); None = backward_tiles' choice. The forward runs at its own
# chosen tile throughout.
BACKWARD_CASES = [
    pytest.param(512, 2, 2, 2, 64, True, 256, 128, None, id="causal-q256-k128"),
    pytest.param(512, 2, 2, 2, 64, True, 128, 256, None, id="causal-q128-k256"),
    pytest.param(512, 1, 2, 2, 64, True, 512, 128, None, id="causal-q512-k128"),
    pytest.param(512, 1, 2, 2, 64, True, 128, 512, None, id="causal-q128-k512"),
    pytest.param(512, 1, 4, 1, 64, True, None, None, None, id="grouped-4to1-chosen"),
    pytest.param(512, 2, 8, 2, 64, True, 256, 128, None, id="grouped-4to1-q256-k128"),
    pytest.param(512, 1, 4, 1, 64, True, 128, 256, None, id="grouped-4to1-q128-k256"),
    pytest.param(256, 2, 4, 2, 64, False, 128, 128, None, id="grouped-2to1-padded"),
    pytest.param(1024, 1, 2, 2, 128, True, None, None, None, id="d128-s1024-chosen"),
    pytest.param(1024, 1, 2, 2, 128, True, 512, 256, None, id="d128-s1024-q512-k256"),
    pytest.param(512, 2, 2, 2, 64, False, 256, 128, None, id="padded-q256-k128"),
    pytest.param(512, 2, 2, 2, 64, False, 128, 512, None, id="padded-q128-k512"),
    # a window, the backward's band forced (block, r): the dq call's slab
    # of keys and the dk / dv call's of queries, grouped 8 to 1 as the 32
    # query heads on 4 K/V heads that bring it
    pytest.param(512, 1, 2, 2, 64, True, 128, 128, 64, id="window64-q128-k128"),
    pytest.param(512, 1, 2, 2, 64, True, 128, 128, 128, id="window128-q128-k128"),
    pytest.param(512, 1, 2, 2, 64, True, 256, 128, 200, id="window200-q256-k128"),
    pytest.param(512, 1, 2, 2, 64, True, 512, 256, 200, id="window200-q512-k256"),
    pytest.param(512, 1, 8, 1, 64, True, 128, 128, 200, id="window200-grouped-8to1"),
    pytest.param(512, 1, 32, 4, 128, True, None, None, 256, id="window256-32on4-d128-chosen"),
    pytest.param(512, 1, 2, 2, 64, True, 512, 512, 100, id="window100-one-call"),
    pytest.param(512, 1, 2, 2, 64, True, 128, 128, 512, id="window512-the-sequence"),
]


@pytest.mark.parametrize("seq,b,h,h_kv,d,causal,block_q,block_k,window",
                         BACKWARD_CASES)
def test_backward_tiles_match_reference_gradients(monkeypatch, seq, b, h,
                                                  h_kv, d, causal, block_q,
                                                  block_k, window):
    """Whatever tiles the two backward kernels run at, chosen or forced
    (under a window the band step's block and sub-block): dq, dk and dv are
    the einsum attention's, dk and dv summed over the query heads a K/V head
    serves."""
    if block_q is not None:
        for rule in ("backward_tiles", "backward_band"):
            monkeypatch.setattr(fa, rule, lambda *shape: (block_q, block_k))
    q, _, _ = _qkv(21, s=seq, b=b, h=h, d=d)
    _, k, v = _qkv(22, s=seq, b=b, h=h_kv, d=d)
    mask = jnp.asarray(_padding(23, b, seq))
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, mask=mask, causal=causal, window=window), q, k, v)
    want = _grads(lambda q, k, v: _ref_grouped(
        q, k, v, mask=mask, causal=causal, window=window), q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("block_q,block_k", [(512, 512), (256, 512),
                                             (512, 128)])
def test_backward_with_a_fully_masked_row(monkeypatch, block_q, block_k):
    """Batch row 1 is all padding, whose logsumexp is -1e30 itself: exactly
    zero gradients there at a sequence-wide backward tile (no scratch in
    either call) and at narrower ones, the reference's on row 0."""
    monkeypatch.setattr(fa, "backward_tiles",
                        lambda *shape: (block_q, block_k))
    seq = 512
    q, k, v = _qkv(24, s=seq, h=2)
    mask = jnp.asarray(np.stack([_padding(25, 1, seq)[0],
                                 np.zeros(seq, np.int32)]))
    got = _grads(lambda q, k, v: flash_attention(q, k, v, mask=mask), q, k, v)
    want = _grads(lambda q, k, v: _ref(q, k, v, mask=mask), q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_array_equal(np.asarray(g)[1], 0.0, err_msg=name)
        np.testing.assert_allclose(np.asarray(g)[0], np.asarray(w)[0],
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("h_kv,causal", [(4, False), (1, True)])
def test_bf16_gradients_within_roundoffs_of_the_f32_reference(h_kv, causal):
    """bf16 operands as every cell feeds them: p and dS are rounded to
    bf16 before their second matmuls and the gradients once more when
    written, so each agrees with the f32 einsum attention on the same
    (bf16-valued) inputs to 2 roundoffs (2**-8) of its largest entry; a
    wrong block or mask is off by O(1) of it."""
    q, _, _ = _qkv(26, s=512, b=1)
    _, k, v = _qkv(27, s=512, b=1, h=h_kv)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal=causal).astype(jnp.float32), q, k, v)
    want = _grads(lambda q, k, v: _ref_grouped(q, k, v, causal=causal),
                  *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, w, name in zip(got, want, "qkv"):
        assert g.dtype == jnp.bfloat16
        err = np.abs(np.asarray(g, np.float32) - np.asarray(w)).max()
        assert err <= 2 * 2.0 ** -8 * np.abs(np.asarray(w)).max(), name


@pytest.mark.parametrize("seq,head_dim,itemsize,causal,want", [
    (512, 64, 2, False, (512, 512)),     # bert-base.s512.flash and .x4
    (4096, 128, 2, True, (1024, 512)),   # olmoe-1b-7b.s4096.zipf
    (8192, 64, 2, True, (1024, 512)),    # lfm2-24b-a2b.s8192.zipf
    (256, 64, 4, False, (256, 256)),     # this file's S
])
def test_backward_tiles_are_pinned_and_fit_the_budget(seq, head_dim,
                                                      itemsize, causal, want):
    got = backward_tiles(seq, head_dim, itemsize, causal)
    assert got == want
    assert backward_vmem_bytes(*got, head_dim, itemsize) <= _VMEM_BUDGET
    assert not causal or got[1] <= got[0]


def _live_steps(seq, block_q, block_k, window=None):
    """Grid steps of one head's causal call that compute, by the
    definition: the tile's last query row sees its first key and, under a
    window, its first row's window reaches its last key."""
    return sum((i + 1) * block_q - 1 >= j * block_k
               and (window is None
                    or i * block_q - (window - 1) <= (j + 1) * block_k - 1)
               for i in range(seq // block_q) for j in range(seq // block_k))


@pytest.mark.parametrize("seq,head_dim,live,steps", [
    (4096, 128, 20, 32),    # olmoe-1b-7b.s4096.zipf
    (8192, 64, 72, 128),    # lfm2-24b-a2b.s8192.zipf
])
def test_live_share_of_the_causal_backward_grid_is_pinned(seq, head_dim,
                                                          live, steps):
    """The mechanism's engagement is a pure function of the tiles: the
    share of a head's grid steps that the causal skip leaves, the same
    count from the dk / dv call's side (_first_live) and from the dq
    call's (_last_live)."""
    block_q, block_k = backward_tiles(seq, head_dim, 2, True)
    num_q, num_k = seq // block_q, seq // block_k
    assert num_q * num_k == steps
    assert _live_steps(seq, block_q, block_k) == live
    assert sum(num_q - _first_live(j, block_q, block_k)
               for j in range(num_k)) == live
    assert sum(_last_live(i, block_q, block_k) + 1
               for i in range(num_q)) == live


@pytest.mark.parametrize("block_q,block_k", [
    (128, 128), (256, 128), (128, 256), (512, 128), (128, 512), (1024, 512),
    (512, 1024)])
def test_first_live_is_the_skip_and_the_clamp(block_q, block_k):
    """_first_live is both the dk / dv kernel's compute skip and its
    query-side index maps' clamp: query block i sees key block j exactly
    when its last row reaches the block's first key, the same tiles
    _last_live keeps, and a dead step names the first live block."""
    seq = 2048
    for j in range(seq // block_k):
        first = _first_live(j, block_q, block_k)
        for i in range(seq // block_q):
            live = (i + 1) * block_q - 1 >= j * block_k
            assert (i >= first) == live
            assert (j <= _last_live(i, block_q, block_k)) == live
            # the index map: max(i, first) is i on a live step and the
            # block the first live step copies on a dead one
            assert max(i, first) == (i if live else first)
        assert first < seq // block_q


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq,calls", [(512, 2), (2048, 3)])
@pytest.mark.parametrize("block_q,block_k", [(None, None), (128, 128),
                                             (256, 512)])
def test_vjp_is_pallas_calls_and_no_scan(block_q, block_k, seq, calls,
                                         causal):
    """Whatever tile the forward ran at, the gradient is the forward call
    and the backward's own: dk / dv and dq, or the one call that gives all
    three where one backward tile spans the sequence. No scan, no while."""
    assert (backward_tiles(seq, D, 4, causal) == (seq, seq)) == (calls == 2)
    q, k, v = _qkv(15, s=seq, b=1, h=2)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)),
        argnums=(0, 1, 2)))(q, k, v)
    names = primitives(jaxpr.jaxpr)
    assert names.count("pallas_call") == calls
    assert not {"scan", "while"} & set(names)


@pytest.mark.parametrize("window", [None, 256])
@pytest.mark.parametrize("seq,calls", [(512, 2), (2048, 3)])
def test_a_checkpoint_that_keeps_the_named_residuals_drops_the_forward_call(
        seq, calls, window):
    """Under a ``jax.checkpoint`` the backward pass runs the forward call
    again only for its output and logsumexp; one whose policy keeps the two
    by the names the kernel gives them (``KEPT``) holds one call fewer, and
    its gradients are the same bits."""
    assert (backward_tiles(seq, D, 4, True) == (seq, seq)) == (calls == 2)
    q, k, v = _qkv(16, s=seq, b=1, h=2)

    def grad(**checkpoint):
        return jax.grad(jax.checkpoint(
            lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=True, window=window))), **checkpoint),
            argnums=(0, 1, 2))

    plain, keeps = grad(), grad(
        policy=jax.checkpoint_policies.save_only_these_names(*fa.KEPT))
    if window is not None:
        calls = 3  # the band step's backward is two calls at any length
    for fn, want in ((plain, calls + 1), (keeps, calls)):
        names = primitives(jax.make_jaxpr(fn)(q, k, v).jaxpr)
        assert names.count("pallas_call") == want
    for got, want, name in zip(jax.jit(keeps)(q, k, v),
                               jax.jit(plain)(q, k, v), "qkv"):
        assert float(jnp.max(jnp.abs(want))) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


def test_matches_lm_full_attention_op():
    """The drop-in contract with the LM's attention interface."""
    q, k, v = _qkv(5, s=128)
    got = flash_attention(q, k, v, causal=True)
    want = _full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_emit_zeros_fwd_and_bwd():
    """The documented degenerate-row convention, actually asserted: a row
    whose every (visible) key is masked produces EXACTLY zero output and
    zero gradients — forward and backward consistent — where the einsum
    reference would softmax all -1e30 into uniform garbage."""
    q, k, v = _qkv(7, s=128)
    mask = jnp.zeros((B, 128), jnp.int32)  # everything padded

    out = flash_attention(q, k, v, mask=mask)
    np.testing.assert_array_equal(np.asarray(out), 0.0)

    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, mask=mask) ** 2), argnums=(0, 1, 2))(q, k, v)
    for got, name in zip(g, "qkv"):
        np.testing.assert_array_equal(np.asarray(got), 0.0, err_msg=name)

    # causal corner: key 0 masked -> row 0 sees nothing -> zeros; later
    # rows see key 1+ and are finite and normal
    mask2 = np.ones((B, 128), np.int32)
    mask2[:, 0] = 0
    out2 = np.asarray(flash_attention(q, k, v, mask=jnp.asarray(mask2),
                                      causal=True))
    np.testing.assert_array_equal(out2[:, 0], 0.0)
    assert np.isfinite(out2).all() and np.abs(out2[:, 1:]).max() > 0


def test_block_divisibility_validated():
    q, k, v = _qkv(6, s=96)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("h_kv,window", [(4, None), (2, None), (2, 40)],
                         ids=["h_kv4", "h_kv2", "h_kv2-window40"])
def test_under_a_mesh_the_kernel_runs_sharded_and_agrees(h_kv, window):
    """GSPMD cannot partition a Mosaic kernel, so under ps.init's mesh the
    call goes through shard_map, the backward's calls with it: batch over
    'data' and the K/V heads over 'model' where they divide, replicated
    where they do not — same values and gradients as the plain call either
    way, on as many K/V heads as query heads or on half, and under a
    window."""
    import ps_tpu as ps

    q, _, _ = _qkv(8, s=128)  # B=2, H=4
    _, k, v = _qkv(9, s=128, h=h_kv)
    mask = np.ones((B, 128), np.int32)
    # under the window no row may lose every key it sees to the padding (a
    # degenerate row: _padding's docstring)
    mask[1, (70 if window is None else 100):] = 0
    mask = jnp.asarray(mask)

    def value_and_grads():
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, mask=mask, causal=window is not None,
                            window=window) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    want = value_and_grads()
    if window is not None:
        ref = jax.value_and_grad(lambda q, k, v: jnp.sum(_ref_grouped(
            q, k, v, mask=mask, causal=True, window=window) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=5e-4,
                                                    atol=5e-4), want, ref)
    for mesh_shape in ({"data": 2, "model": 4}, {"data": 8}):
        ps.init(backend="tpu", mesh_shape=mesh_shape)  # 8 cannot divide B
        got = jax.jit(value_and_grads)()
        ps.shutdown()
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-5), got, want)


def test_bert_flash_matches_full():
    """Model-level contract: BertMLM(attn='flash') ≡ attn='full' logits,
    including a real padding mask."""
    import ps_tpu as ps
    from ps_tpu.models.bert import BertConfig, BertMLM

    ps.init(backend="tpu")
    ids = jnp.asarray(np.random.default_rng(0).integers(
        5, 500, size=(2, 128)).astype(np.int32))
    mask = np.ones((2, 128), np.int32)
    mask[:, 100:] = 0  # trailing padding, the BERT convention
    mask = jnp.asarray(mask)
    logits = {}
    for attn in ("full", "flash"):
        cfg = BertConfig.tiny(max_len=128, attn=attn)
        m = BertMLM(cfg)
        params = m.init(jax.random.key(0), ids, mask)["params"]
        logits[attn] = m.apply({"params": params}, ids, mask)
    np.testing.assert_allclose(
        np.asarray(logits["flash"])[:, :100], np.asarray(logits["full"])[:, :100],
        rtol=2e-4, atol=2e-4,
    )
    ps.shutdown()

# -- attention that sees a window ---------------------------------------------

def _slices(seq, block, r, window, mirror):
    """For every step and sub-block of a band call: the positions of its own
    ``r`` rows, the positions its slice's columns hold, the pairs the kernel
    keeps (the two edges where a piece is crossed, every pair where it is
    not; nothing of a block outside the sequence) and those the pieces call
    uncrossed."""
    g = band(block, r, window, mirror=mirror)
    width = -(-window // 128) * 128 + r
    ahead = np.arange(width)[None, :] - np.arange(r)[:, None]
    edges = (ahead >= g.low) & (ahead <= g.high)
    for step in range(seq // block):
        base = step * block if mirror else (step - g.blocks + 1) * block
        for t, pieces in enumerate(g.pieces):
            own = step * block + t * r + np.arange(r)
            held = base + g.first[t] + np.arange(width)
            free = np.zeros(width, bool)
            at = 0
            for m, lo, hi, c0, crossed in pieces:
                # the pieces tile the slice, each inside one block of the
                # slab, on the lanes
                assert c0 == at and 0 <= lo < hi <= block
                assert lo % 128 == 0 and hi % 128 == 0
                assert m * block + lo == g.first[t] + c0
                assert 0 <= m < g.blocks
                free[c0:c0 + hi - lo] = not crossed
                at += hi - lo
            assert at == width
            inside = (held >= 0) & (held < seq)
            kept = np.where(free[None, :], True, edges) & inside[None, :]
            yield own, held, kept, edges, free


@pytest.mark.parametrize("block,r", [
    (128, 128), (256, 128), (256, 256), (512, 128), (512, 512), (1024, 256),
    (1024, 512)])
@pytest.mark.parametrize("window", [1, 64, 128, 200, 512, 2047])
def test_a_sub_blocks_slice_holds_exactly_the_pairs_its_rows_see(block, r,
                                                                 window):
    """The band step's geometry, from both sides: the slice of a sub-block
    of queries (forward, dq) holds every key its rows see, the first at the
    sequence's start included and the last their own, and keeps exactly
    those; the slice of a sub-block of keys (dk / dv) every query that sees
    them, up to the sequence's end. A piece the edges do not cross holds
    only pairs inside the band, so it needs no mask."""
    seq = 2048
    for mirror in (False, True):
        for own, held, kept, edges, free in _slices(seq, block, r, window,
                                                    mirror):
            ahead = (held[None, :] - own[:, None]) * (1 if mirror else -1)
            seen = ((ahead >= 0) & (ahead < window)
                    & (held >= 0)[None, :] & (held < seq)[None, :])
            np.testing.assert_array_equal(kept, seen)
            # none missing: the slice reaches the first and the last
            # position any of its rows' windows holds
            if mirror:
                assert held[0] <= own[0]
                assert held[-1] >= min(own[-1] + window - 1, seq - 1)
            else:
                assert held[0] <= max(own[0] - window + 1, 0)
                assert held[-1] >= own[-1]
            # and an uncrossed piece is unmasked by right, not by luck
            assert edges[:, free].all()
            assert seen.sum(axis=1).min() >= 1


# [B, S, h, d] of a cell's windowed call, its K/V heads and window, and the
# band the rules give it: (block, r) forward and backward, blocks a slab
BANDS = {
    "trinity-mini.s16384.b1.zipf":
        ((1, 16384, 32, 128), 4, 2048, (1024, 256), (1024, 256), 3, 3),
    "mellum2-12b-a2.5b.s8192.b1.zipf.x4":
        ((1, 8192, 32, 128), 4, 1024, (2048, 128), (1024, 128), 2, 2),
    "a-window-of-512-in-16384":
        ((1, 16384, 32, 128), 4, 512, (2048, 128), (1024, 128), 2, 2),
}


@pytest.mark.parametrize("cell", sorted(BANDS))
def test_the_cells_windowed_calls_take_the_band_step(cell):
    """From the shapes alone: a windowed call is three ``pallas_call``s with
    no key axis in the forward's and dq's grids and the group for dk / dv's
    last, at the block and sub-block the rules choose under their VMEM
    counts, K and V passed once a block of the slab."""
    shape, h_kv, window, forward, backward, fwd_blocks, bwd_blocks = (
        BANDS[cell])
    b, seq, h, d = shape
    assert forward_band(seq, d, 2, window) == forward
    assert backward_band(seq, d, 2, window) == backward
    assert fa.forward_band_vmem_bytes(*forward, window, d, 2) <= _VMEM_BUDGET
    assert fa.backward_band_vmem_bytes(*backward, window, d,
                                       2) <= _VMEM_BUDGET
    for tiles in (forward, backward):
        assert tiles[0] % tiles[1] == 0 and seq % tiles[0] == 0
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, seq, h_kv, d), jnp.bfloat16)
    with jax.default_device("tpu"):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True,
                window=window).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, kv, kv)
    calls = dict(_pallas_calls(jaxpr.jaxpr))
    assert sorted(calls) == ["_band_dkv_kernel", "_band_dq_kernel",
                             "_band_fwd_kernel"]
    group = h // h_kv
    want = {"_band_fwd_kernel": ((b * h, seq // forward[0]),
                                 1 + 3 * fwd_blocks),
            "_band_dq_kernel": ((b * h, seq // backward[0]),
                                4 + 3 * bwd_blocks),
            "_band_dkv_kernel": ((b * h_kv, seq // backward[0], group),
                                 4 * bwd_blocks + 3)}
    for name, (grid, operands) in want.items():
        assert calls[name].params["grid_mapping"].grid == grid
        assert len(calls[name].invars) == operands


def _pallas_calls(jaxpr):
    """(kernel name, equation) of every ``pallas_call`` under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["jaxpr"].debug_info.func_name, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_window_no_band_fits_is_refused_and_r_divides_the_block():
    """The slab of a window of 8,192 keys at head 128 does not fit VMEM
    beside a sub-block's scores: the rule says so, and does not fall back
    to another program. A forced band whose sub-block does not divide its
    block is refused."""
    with pytest.raises(ValueError, match="band step fits"):
        forward_band(16384, 128, 2, 8192)
    assert forward_band(16384, 128, 2, 4096) == (256, 256)
    q, k, v = _qkv(35, s=512, b=1, h=2)
    with pytest.raises(ValueError, match="sub-block"):
        flash_attention(q, k, v, causal=True, window=64, block_q=128,
                        block_k=256)


def test_windowed_rows_that_lose_every_key_give_zeros_fwd_and_bwd():
    """Under a window a row can lose all it sees to the padding mask (here
    the keys 100..299 are padding and the window is 64: rows 163..299 see
    nothing): exactly zero output and zero dq there, finite everywhere, the
    reference's values on the rows that see a key, and zero dk / dv for the
    padded keys."""
    seq, window = 512, 64
    q, k, v = _qkv(36, s=seq, b=1, h=2)
    mask = np.ones((1, seq), np.int32)
    mask[:, 100:300] = 0
    dead = np.arange(seq)
    dead = (dead >= 100 + window - 1) & (dead < 300)
    mask = jnp.asarray(mask)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=True,
                               window=window, block_q=256, block_k=128)

    def ref(q, k, v):
        return _ref(q, k, v, mask=mask, causal=True, window=window)

    out, want = np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v))
    np.testing.assert_array_equal(out[:, dead], 0.0)
    np.testing.assert_allclose(out[:, ~dead], want[:, ~dead], rtol=2e-5,
                               atol=2e-5)

    def loss(attn):
        # the reference softmaxes a dead row to uniform garbage: keep it
        # out of both losses
        return lambda q, k, v: jnp.sum(
            jnp.where(jnp.asarray(dead)[None, :, None, None], 0.0,
                      attn(q, k, v)) ** 2)

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    wanted = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, wanted, "qkv"):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4,
                                   atol=5e-4, err_msg=name)
    np.testing.assert_array_equal(np.asarray(got[0])[:, dead], 0.0)
    for g in got[1:]:
        np.testing.assert_array_equal(np.asarray(g)[:, 100:300], 0.0)


def test_window_with_values_of_their_own_width():
    """Keys 96 wide, values 64, 4 query heads on 2 K/V heads, a window off
    every block's edge: the output and the three gradients."""
    seq, window = 512, 200
    rng = np.random.default_rng(31)
    q = jnp.asarray(rng.normal(0, 1, (1, seq, 4, 96)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (1, seq, 2, 96)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (1, seq, 2, 64)).astype(np.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=128, block_k=128)

    def ref(q, k, v):
        return _ref_grouped(q, k, v, causal=True, window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)), rtol=2e-5, atol=2e-5)
    for g, w, name in zip(_grads(flash, q, k, v), _grads(ref, q, k, v),
                          "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=5e-4,
                                   atol=5e-4, err_msg=name)


def test_window_past_the_sequence_is_the_causal_program():
    q, k, v = _qkv(33, s=512, b=1, h=2)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, **kw)), argnums=(0, 1, 2)))(
                q, k, v))

    assert jaxpr(window=512) == jaxpr(window=4096) == jaxpr()
    assert jaxpr(window=511) != jaxpr()


def test_window_is_refused_without_causal_or_below_one():
    q, k, v = _qkv(34)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)


# sha256 (first 16 hex digits) of the text of value_and_grad's jaxpr at
# window=None, addresses struck out, as the tree before the window gave it
# (commit 79ddf58, jax 0.9.0): [B, S, h, d] bf16, K/V heads, v's width, causal
PROGRAMS_BEFORE_THE_WINDOW = {
    "bert": ((2, 512, 12, 64), 12, 64, False, "c2b5c6db6099ca06"),
    "olmoe": ((1, 4096, 16, 128), 16, 128, True, "6cf1771998371f68"),
    "kimi": ((1, 1024, 4, 192), 4, 128, True, "4f8113987517734f"),
    "lfm2": ((1, 1024, 8, 64), 2, 64, True, "f8e821b91e8d6443"),
}


def _without_names(jaxpr):
    """``jaxpr`` with its ``name`` equations taken out, each result read as
    its operand, and the names they gave."""
    alias, eqns, names = {}, [], []

    def see(v):
        if isinstance(v, jax.extend.core.Literal):
            return v
        return alias.get(v, v)

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            alias[eqn.outvars[0]] = see(eqn.invars[0])
            names.append(eqn.params["name"])
        else:
            eqns.append(eqn.replace(invars=[see(v) for v in eqn.invars]))
    return jaxpr.replace(eqns=eqns,
                         outvars=[see(v) for v in jaxpr.outvars]), names


@pytest.mark.parametrize("cell", sorted(PROGRAMS_BEFORE_THE_WINDOW))
def test_without_a_window_the_program_is_the_one_it_was(monkeypatch, cell):
    """``window=None`` traces, forward and backward, to the jaxpr the
    kernels gave before they knew a window, once the two residuals' names
    (``KEPT``: identity outside a checkpoint that lists them) are left out:
    the hash is of the trace with ``checkpoint_name`` made identity, and the
    trace as it is differs from that one by two ``name`` equations a call
    and nothing else."""
    import hashlib
    import re

    shape, h_kv, d_v, causal, want = PROGRAMS_BEFORE_THE_WINDOW[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(shape[:2] + (h_kv, shape[3]), jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:2] + (h_kv, d_v), jnp.bfloat16)

    def trace():
        with jax.default_device("tpu"):
            return jax.make_jaxpr(jax.value_and_grad(
                lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, causal=causal).astype(jnp.float32)),
                argnums=(0, 1, 2)))(q, k, v)

    def text(jaxpr):
        return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))

    named = trace()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    plain = trace()
    assert hashlib.sha256(text(plain).encode()).hexdigest()[:16] == want
    stripped, names = _without_names(named.jaxpr)
    assert tuple(names) == fa.KEPT
    assert text(stripped) == text(plain.jaxpr)
    assert text(named.jaxpr) != text(plain.jaxpr)


# -- an edge a block wide, rows that see no key, the logsumexp as an output ----

def _ref_edge(q, k, v, block, strict):
    """Dense attention under an edge at a block's granularity: query ``i``
    sees the keys before the end of its block, or (strict) before its
    start. K/V repeated for the query heads they serve. Returns the output
    and the logsumexp [B, S, h]; a row that sees no key gives zeros and
    -1e30."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    of = jnp.arange(q.shape[1]) // block
    seen = of[None, :] < of[:, None] if strict else of[None, :] <= of[:, None]
    some = jnp.any(seen, axis=-1)[None, None, :, None]
    m = jnp.max(jnp.where(seen, s, -jnp.inf), axis=-1, keepdims=True)
    m = jnp.where(some, m, 0.0)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.where(some, total, 1.0), v)
    lse = jnp.where(some, m + jnp.log(jnp.where(some, total, 1.0)), -1e30)
    return out, jnp.transpose(lse[..., 0], (0, 2, 1))


# (seq, heads, K/V heads, head_dim, edge block, strict, forward tiles,
# backward tiles); None = the rule's choice
EDGE_CASES = [
    pytest.param(512, 2, 2, 64, 4, False, (128, 128), (128, 128), id="b4-inclusive-q128-k128"),
    pytest.param(512, 2, 2, 64, 4, True, (128, 128), (128, 128), id="b4-strict-q128-k128"),
    pytest.param(512, 4, 1, 64, 4, False, (256, 128), (256, 128), id="b4-inclusive-4to1-q256-k128"),
    pytest.param(512, 4, 1, 64, 4, True, (256, 128), (256, 128), id="b4-strict-4to1-q256-k128"),
    pytest.param(512, 2, 2, 64, 32, True, (128, 128), (256, 128), id="b32-strict-q128-k128"),
    pytest.param(512, 2, 2, 64, 128, False, (128, 128), (128, 128), id="b128-inclusive-a-tile"),
    pytest.param(512, 2, 2, 64, 128, True, (128, 128), (128, 128), id="b128-strict-a-tile"),
    pytest.param(512, 2, 2, 64, 1, False, (128, 128), (128, 128), id="b1-is-causal"),
    pytest.param(512, 2, 2, 64, 16, True, (512, 512), (512, 512), id="b16-strict-one-block"),
    pytest.param(1024, 8, 1, 128, 4, True, None, None, id="b4-strict-8to1-d128-chosen"),
]


@pytest.mark.parametrize("seq,h,h_kv,d,block,strict,forward,backward",
                         EDGE_CASES)
def test_block_edge_matches_dense_forward_and_gradients(
        monkeypatch, seq, h, h_kv, d, block, strict, forward, backward):
    """The edge a block wide, inclusive and strict, in the forward, the
    dk / dv and the dq kernel: output, logsumexp and the gradients of q, k
    and v through both outputs are the dense form's, at forced and chosen
    tiles. The first block's rows under the strict edge see no key."""
    if backward is not None:
        monkeypatch.setattr(fa, "backward_tiles", lambda *shape: backward)
    block_q, block_k = forward or (None, None)
    q, _, _ = _qkv(41, s=seq, b=1, h=h, d=d)
    _, k, v = _qkv(42, s=seq, b=1, h=h_kv, d=d)
    rng = np.random.default_rng(43)
    w_out = jnp.asarray(rng.normal(0, 1, (1, seq, h, d)).astype(np.float32))
    w_lse = jnp.asarray(rng.normal(0, 1, (1, seq, h)).astype(np.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, edge_block=block,
                               strict_edge=strict, return_lse=True,
                               block_q=block_q, block_k=block_k)

    def ref(q, k, v):
        return _ref_edge(q, k, v, block, strict)

    def loss(attn):
        def of(q, k, v):
            out, lse = attn(q, k, v)
            # a row that sees no key has no logsumexp to weigh
            return jnp.sum(out * w_out) + jnp.sum(
                jnp.where(lse > -1e29, lse * w_lse, 0.0))
        return of

    for got, want, name in zip(flash(q, k, v), ref(q, k, v),
                               ("out", "lse")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    g_got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


@pytest.mark.parametrize("return_lse", [False, True])
def test_rows_that_see_no_key_give_zeros_and_no_nan(return_lse):
    """Under the strict edge the first block's queries see nothing: output
    0, a logsumexp of -1e30's size, and gradients that are finite
    everywhere and exactly zero for those queries."""
    block = 8
    q, k, v = _qkv(44, s=256, b=1, h=2, d=64)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, edge_block=block,
                               strict_edge=True, return_lse=return_lse)

    got = flash(q, k, v)
    out, lse = got if return_lse else (got, None)
    np.testing.assert_array_equal(np.asarray(out)[:, :block], 0.0)
    assert np.isfinite(np.asarray(out)).all()
    if return_lse:
        assert (np.asarray(lse)[:, :block] < -1e29).all()
        assert np.isfinite(np.asarray(lse)).all()
        assert (np.abs(np.asarray(lse)[:, block:]) < 100).all()

    def loss(q, k, v):
        got = flash(q, k, v)
        if not return_lse:
            return jnp.sum(got ** 2)
        # the merge that reads the logsumexp: weights of two key sets
        out, lse = got
        return jnp.sum(out ** 2) + jnp.sum(jnp.logaddexp(lse, 0.0))

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_array_equal(np.asarray(grads[0])[:, :block], 0.0)
    # the last block's keys are seen by no query at all
    for g in grads[1:]:
        np.testing.assert_array_equal(np.asarray(g)[:, -block:], 0.0)


def test_the_logsumexp_carries_a_cotangent_of_its_own():
    """A loss of the logsumexp alone: dS = p * dlse, through both backward
    kernels, on a plain causal call too."""
    q, k, v = _qkv(45, s=256, b=2, h=4, d=64)
    for kw, (block, strict) in (({}, (1, False)),
                                ({"edge_block": 4}, (4, False))):
        def flash(q, k, v):
            return jnp.sum(jnp.sin(flash_attention(
                q, k, v, causal=True, return_lse=True, **kw)[1]))

        def ref(q, k, v):
            return jnp.sum(jnp.sin(_ref_edge(q, k, v, block, strict)[1]))

        for got, want, name in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                                   jax.grad(ref, (0, 1, 2))(q, k, v), "qkv"):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=5e-4, atol=5e-4, err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(jax.grad(flash, 2)(q, k, v)), 0.0)


def test_an_edge_is_refused_where_it_has_no_meaning():
    q, k, v = _qkv(46)
    with pytest.raises(ValueError, match="edge_block"):
        flash_attention(q, k, v, edge_block=4)
    with pytest.raises(ValueError, match="edge_block"):
        flash_attention(q, k, v, causal=True, window=64, edge_block=4)
    with pytest.raises(ValueError, match="edge_block"):
        flash_attention(q, k, v, causal=True, edge_block=48)
    with pytest.raises(ValueError, match="strict_edge"):
        flash_attention(q, k, v, causal=True, strict_edge=True)


def test_under_a_mesh_the_edge_and_the_logsumexp_run_sharded():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    q, _, _ = _qkv(47, s=256, b=2, h=4, d=64)
    _, k, v = _qkv(48, s=256, b=2, h=2, d=64)
    got = flash_attention(q, k, v, causal=True, edge_block=4,
                          strict_edge=True, return_lse=True, mesh=mesh)
    for g, w in zip(got, _ref_edge(q, k, v, 4, True)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=2e-5)


# sha256 (first 16 hex digits) of the text of value_and_grad's jaxpr, names
# and all, addresses struck out, as the tree before the edge gave it (commit
# fdcedf2, jax 0.9.0): [B, S, h, d] bf16, K/V heads, v's width, causal, window
PROGRAMS_BEFORE_THE_EDGE = {
    "bert": ((2, 512, 12, 64), 12, 64, False, None, "6b89a5fd508abea2"),
    "olmoe": ((1, 4096, 16, 128), 16, 128, True, None, "b41ce700ff96f1b1"),
    "trinity-full": ((1, 16384, 32, 128), 4, 128, True, None,
                     "76f8390badbc8d4d"),
    # the windowed calls of these two cells were pinned here until they
    # became the band step (PR 53); their full layers and Nemotron-H's share
    # (commit 0fa2f40) stand in
    "mellum-full": ((1, 8192, 32, 128), 4, 128, True, None,
                    "e275f450b9524c6e"),
    "nemotron": ((1, 8192, 4, 128), 1, 128, True, None, "909fe9c3ab518763"),
}


@pytest.mark.parametrize("cell", sorted(PROGRAMS_BEFORE_THE_EDGE))
def test_without_an_edge_the_program_is_the_one_it_was(cell):
    """No ``edge_block`` and no ``return_lse``: forward and backward trace
    to the jaxpr the kernels gave before they knew either, kernel bodies
    included, at the shapes of the cells that share this file."""
    import hashlib
    import re

    shape, h_kv, d_v, causal, window, want = PROGRAMS_BEFORE_THE_EDGE[cell]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(shape[:2] + (h_kv, shape[3]), jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:2] + (h_kv, d_v), jnp.bfloat16)
    with jax.default_device("tpu"):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=causal,
                window=window).astype(jnp.float32)), argnums=(0, 1, 2)))(
                    q, k, v)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("strict,want", [(False, "628f70edb28031ce"),
                                         (True, "db12945bdc71633f")],
                         ids=["clean-queries", "noised-queries"])
def test_the_edged_calls_are_the_program_they_were(strict, want):
    """SDAR's two calls a layer ([1, 8192, 32 on 4, 128], blocks of 4; the
    strict one with its logsumexp as an output) trace to the jaxpr they gave
    before a windowed call became the band step (commit 0fa2f40): the tiled
    kernels lost their window arms and nothing else."""
    import hashlib
    import re

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, edge_block=4,
                              strict_edge=strict, return_lse=strict)
        if not strict:
            return jnp.sum(out.astype(jnp.float32))
        out, lse = out
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(
            jnp.logaddexp(lse, 0.0))

    with jax.default_device("tpu"):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
            q, kv, kv)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


@pytest.mark.parametrize("seq,head_dim,causal,forward,backward", [
    (512, 64, False, (512, 512), (512, 512)),        # bert-base.s512.flash
    (4096, 128, True, (1024, 1024), (1024, 512)),    # olmoe-1b-7b.s4096.zipf
    (16384, 128, True, (1024, 1024), (1024, 512)),   # trinity-mini.s16384
    (8192, 128, True, (1024, 1024), (1024, 512)),    # mellum2 and sdar, 8,192
])
def test_the_cells_tiles_are_the_ones_they_were(seq, head_dim, causal,
                                                forward, backward):
    """The tiles take no edge: a call with one runs at the causal call's,
    and the old calls at the ones they had."""
    assert forward_tiles(seq, head_dim, 2, causal) == forward
    assert backward_tiles(seq, head_dim, 2, causal) == backward
    assert all(128 % b == 0 and t % b == 0
               for b in (1, 2, 4, 8, 16, 32, 64, 128)
               for t in forward + backward)
