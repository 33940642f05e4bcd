"""Pallas flash attention ≡ the reference einsum attention.

The kernel runs in interpret mode on CPU — the same online-softmax loop,
block structure, and masking logic as on the chip — and must match the
models' `_full_attention` (ps_tpu/models/lm.py) in both the forward
output and every input gradient, causal and padded, including the
numerically delicate cases (fully-masked rows, block-boundary diagonals).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_tpu.models.lm import _full_attention
from ps_tpu.ops import flash_attention
from ps_tpu.ops.flash_attention import (_VMEM_BUDGET, _last_live,
                                        forward_tiles, forward_vmem_bytes)

B, S, H, D = 2, 256, 4, 64


def _qkv(seed, s=S, b=B, h=H, d=D):
    rng = np.random.default_rng(seed)
    shape = (b, s, h, d)
    return tuple(
        jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
        for _ in range(3)
    )


def _ref(q, k, v, mask=None, causal=False):
    """The models' einsum attention, with the BERT-style [B, S] mask."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -1e30)
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
# the diagonal whose fetch is clamped.
TILE_CASES = [
    pytest.param(256, 2, 4, 64, False, None, None, id="chosen-s256"),
    pytest.param(512, 2, 2, 64, False, None, None, id="chosen-s512-one-block"),
    pytest.param(512, 2, 2, 64, True, None, None, id="chosen-s512-causal"),
    pytest.param(1024, 1, 2, 128, True, None, None, id="chosen-s1024-d128-causal"),
    pytest.param(512, 2, 2, 64, True, 256, 128, id="causal-q256-k128"),
    pytest.param(512, 2, 2, 64, True, 128, 256, id="causal-q128-k256"),
    pytest.param(512, 1, 2, 64, True, 512, 128, id="causal-q512-k128"),
    pytest.param(512, 1, 2, 64, True, 128, 512, id="causal-q128-k512"),
    pytest.param(512, 2, 2, 64, False, 128, 256, id="padded-q128-k256"),
    pytest.param(256, 2, 4, 64, True, 128, 128, id="causal-q128-k128"),
]


@pytest.mark.parametrize("seq,b,h,d,causal,block_q,block_k", TILE_CASES)
def test_tiles_match_reference_forward_and_gradients(seq, b, h, d, causal,
                                                     block_q, block_k):
    """Whatever tiles the forward runs at, chosen or forced: the output and
    the gradients of q, k and v are the einsum attention's."""
    q, k, v = _qkv(11, s=seq, b=b, h=h, d=d)
    mask = jnp.asarray(_padding(12, b, seq))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               block_q=block_q, block_k=block_k)

    def ref(q, k, v):
        return _ref(q, k, v, mask=mask, causal=causal)

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


def _scan_lengths(jaxpr):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scan_lengths(sub)
    return found


@pytest.mark.parametrize("block_q,block_k", [(None, None), (128, 128),
                                             (256, 512)])
def test_backward_scan_keeps_its_own_key_block(block_q, block_k):
    """The VJP scans the keys 128 at a time whatever tile the forward ran
    at: a sequence-wide forward block must not turn the scan into one
    iteration over [BH, S, S] tensors."""
    seq = 512
    q, k, v = _qkv(15, s=seq, b=1, h=2)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_q=block_q, block_k=block_k)), argnums=(0, 1, 2)))(
            q, k, v)
    assert _scan_lengths(jaxpr.jaxpr) == [seq // 128]


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


def test_under_a_mesh_the_kernel_runs_sharded_and_agrees():
    """GSPMD cannot partition a Mosaic kernel, so under ps.init's mesh the
    call goes through shard_map: batch over 'data' and heads over 'model'
    where they divide, replicated where they do not — same values and
    gradients as the plain call either way."""
    import ps_tpu as ps

    q, k, v = _qkv(8, s=128)  # B=2, H=4
    mask = np.ones((B, 128), np.int32)
    mask[1, 70:] = 0
    mask = jnp.asarray(mask)

    def value_and_grads():
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, mask=mask) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    want = value_and_grads()
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