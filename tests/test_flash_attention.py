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

B, S, H, D = 2, 256, 4, 64


def _qkv(seed, s=S):
    rng = np.random.default_rng(seed)
    shape = (B, s, H, D)
    return tuple(
        jnp.asarray(rng.normal(0, 1, shape).astype(np.float32))
        for _ in range(3)
    )


def _ref(q, k, v, mask=None, causal=False):
    """The models' einsum attention, with the BERT-style [B, S] mask."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
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


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    q, k, v = _qkv(3)
    rng = np.random.default_rng(4)
    mask = np.asarray(rng.random((B, S)) < 0.8, np.int32)
    # keep key 0 valid: a causal row whose every visible key is masked is
    # DEGENERATE — the einsum reference softmaxes all -1e30 to uniform
    # garbage while flash emits zeros (the convention asserted by
    # test_fully_masked_rows_emit_zeros_fwd_and_bwd); reference parity is
    # only defined on non-degenerate rows
    mask[:, 0] = 1
    mask = jnp.asarray(mask)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref(q, k, v, mask=mask, causal=causal) ** 2)

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


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