"""Qwen3-Next (``ps_tpu/models/qwen3_next.py``; the gated delta rule at one
decay a head and fewer key heads than value heads through
``ps_tpu/ops/kda.py``; attention at keys and values of 256 with a gate the q
projection carries; softmax top-k over a wide router beside a gated shared
expert) against its plain reference
(``benchmark/families/qwen3_next_reference.py``: the rule token by token,
whole rows of attention, a masked loop over the held experts), at small sizes
on the CPU with seeded weights; then the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to a few of their
largest entry (5e-5; seen: 2.6e-5, the b | a projection), but the two per-head vectors of the decay (``A_log``,
``dt_bias``), whose gradient is a sum over every token and channel of a head
of terms that cancel (entries of 1e-4 beside the matrices' 0.1, off by 3e-6):
2e-2 of their own largest entry (seen: 6e-3; the rule's own ``dg`` is held to
2e-5 by the op's test below, where a wrong sum over the channels would show). The weights are scaled up from the
cell's 0.02 and the zero-centred norms' ``w`` moved off 0, so that every
mixer, every expert and every ``1 + w`` moves the loss by far more than that.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from benchmark.families import flash
from benchmark.families import qwen3_next_reference as reference
from benchmark.families import qwen3_next_step
from jaxpr_tools import checkpoint_names, equations, flash_calls
from ps_tpu.models import blocks, qwen3_next
from ps_tpu.models.blocks import make_attn_fn
from ps_tpu.ops import kda as kda_ops
from ps_tpu.ops import moe
from ps_tpu.ops.gated_conv import path as taps_path
from ps_tpu.ops.flash_attention import (_VMEM_BUDGET, backward_tiles,
                                        backward_vmem_bytes, forward_tiles,
                                        forward_vmem_bytes)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 5e-5
DECAY_TOL = 2e-2
CELL = "qwen3-next-80b-a3b.s8192.b1.zipf"
#: the cell's four-layer period, a quarter of 16 experts held, two value
#: heads a key head, a quarter of a head's channels rotated
SIZES = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=1e7, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, router_width=16, num_experts=4,
    expert_start=4, num_experts_per_tok=3, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_scaling=None, use_sliding_window=False,
    mlp_only_layers=[], decoder_sparse_step=1, tie_word_embeddings=False,
    hidden_act="silu", dtype="float32")


def _setup(seed=0, batch=2, seq=128, **changes):
    sizes = {**SIZES, **changes}
    cfg = qwen3_next.Qwen3NextConfig.from_dict(sizes)
    params = jax.jit(lambda k: qwen3_next.init_params(k, cfg))(
        jax.random.key(seed))
    # away from the cell's 0.02 and from w = 0: every layer and every
    # norm's scale then matters to the loss
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        5 * x if x.ndim > 1 else x + 0.1 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"],
                       size=(batch, seq + 1)).astype(np.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


def _system(cfg, params, batch, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            qwen3_next.make_loss_fn(cfg, attn=attn), has_aux=True))(
                params, batch)


def _plain(sizes, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, sizes), has_aux=True))(
                params)


@functools.lru_cache(maxsize=None)
def _base():
    sizes, cfg, params, batch = _setup()
    return sizes, cfg, params, batch, _plain(sizes, params, batch)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _assert_grads_close(grads, ref_grads):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        tol = DECAY_TOL if name.endswith(("['A_log']", "['dt_bias']")) \
            else F32_TOL
        assert _rel(g, r) <= tol, (name, _rel(g, r))


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, logits, counts and every gradient, for the four-layer period
    with four of sixteen experts held."""
    sizes, cfg, params, batch, ((ref_loss, ref_aux), ref_grads) = _base()
    (loss, aux), grads = _system(cfg, params, batch, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    for name in ("expert_tokens", "held_tokens"):
        np.testing.assert_array_equal(np.asarray(aux[name]),
                                      np.asarray(ref_aux[name]))
    assert aux["expert_tokens"].shape == (4, 16)
    assert aux["held_tokens"].shape == (4, 4)
    assert np.all(np.asarray(aux["expert_tokens"]).sum(-1) == 2 * 128 * 3)
    # every tensor has a gradient that is not nothing
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))
    _assert_grads_close(grads, ref_grads)
    with jax.default_matmul_precision("highest"):
        hidden, *_ = qwen3_next.apply(params, batch["inputs"], cfg,
                                      make_attn_fn(attn))
        logits = qwen3_next.logits_of(params, hidden, cfg)
        want = reference.logits_fn(params, batch["inputs"], sizes)
    assert logits.shape == (2, 128, 256)
    assert _rel(logits, want) <= F32_TOL


def test_the_picks_are_used_as_they_are_where_the_config_says_so():
    """``norm_topk_prob`` false is built, not refused: the model and the
    reference both leave the picks' probabilities unrenormalised."""
    sizes, cfg, params, batch = _setup(seq=64, norm_topk_prob=False)
    (loss, _), _ = _system(cfg, params, batch)
    (ref_loss, _), _ = _plain(sizes, params, batch)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    (normed, _), _ = _plain({**sizes, "norm_topk_prob": True}, params, batch)
    assert abs(float(normed) - float(ref_loss)) > 1e-3


# -- the rule at one decay a head and two value heads a key head ----------------

WIDTH = {"plain": 32, "kernel": 128}
PATHS = sorted(WIDTH)


def _rule_inputs(path, seq, batch=2, seed=0):
    """Unit q and k of two key heads for four value heads, one decay a value
    head and a token: as strong as the configuration's strongest head gives
    (``exp(A_log)`` 16 times a softplus about 1.3: 21 nats a token), a
    middling, a weak and a nearly absent one."""
    width = WIDTH[path]
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(batch, seq, 2, width)) for _ in range(2))
    v = rng.normal(size=(batch, seq, 4, width))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -np.array([16.0, 4.0, 0.5, 1e-3]) * 1.3 * rng.uniform(
        0.5, 1.5, size=(batch, seq, 4))
    beta = rng.uniform(0.1, 0.95, size=(batch, seq, 4))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule on key heads repeated as the
    reference repeats them."""
    r = v.shape[2] // q.shape[2]
    return jax.vmap(lambda q, k, v, g, beta: reference.delta_rule(
        reference.to_value_heads(q, r), reference.to_value_heads(k, r), v, g,
        beta))(q, k, v, g, beta)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("seq", [64, 192], ids=["one_chunk", "three_chunks"])
def test_the_scalar_decay_rule_equals_the_token_by_token_recurrence(seq,
                                                                    path):
    """``kda`` at ``g`` [B, T, H] and q, k of H / 2 heads, both realisations
    (the kernels interpreted): forward and all five gradients, each at its
    operand's own shape."""
    args = _rule_inputs(path, seq)
    wide = jax.ShapeDtypeStruct(args[2].shape, jnp.float32)
    assert kda_ops.path(wide, wide, wide, 64) == path
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, want = kda_ops.kda(*args), _recurrence(*args)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _rel(got, want) <= F32_TOL
        grads, ref_grads = (
            jax.grad(lambda *a: jnp.sum(f(*a) * weights),
                     argnums=(0, 1, 2, 3, 4))(*args)
            for f in (kda_ops.kda, _recurrence))
    for name, a, g, r in zip("q k v g beta".split(), args, grads, ref_grads):
        assert g.shape == a.shape, name
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, r) <= F32_TOL, (name, _rel(g, r))


def test_the_special_cases_are_the_general_rule_on_broadcast_operands():
    """One decay a head is that decay in every channel, a key head read
    twice is that head repeated: to the bit, and Kimi-Linear's call (``g``
    [B, T, H, K], equal head counts) traces to the same equations as
    before the special cases existed (no broadcast, no repeat)."""
    q, k, v, g, beta = _rule_inputs("plain", 64)
    wide = (jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2), v,
            jnp.broadcast_to(g[..., None], v.shape), beta)
    np.testing.assert_array_equal(np.asarray(kda_ops.kda(q, k, v, g, beta)),
                                  np.asarray(kda_ops.kda(*wide)))
    names = [e.primitive.name for e in equations(
        jax.make_jaxpr(kda_ops.kda)(*wide).jaxpr)]
    general = [e.primitive.name for e in equations(jax.make_jaxpr(
        functools.partial(kda_ops._kda, chunk=64))(*wide).jaxpr)]
    assert names == general


@pytest.mark.parametrize("heads", [(3, 4), (4, 2)], ids=["3-for-4", "4-for-2"])
def test_kda_refuses_key_heads_that_do_not_divide_the_value_heads(heads):
    keys, values = heads
    q = k = jnp.zeros((1, 64, keys, 32))
    v = jnp.zeros((1, 64, values, 32))
    with pytest.raises(ValueError, match="divisor of the value heads"):
        kda_ops.kda(q, k, v, jnp.zeros((1, 64, values)),
                    jnp.zeros((1, 64, values)))



# -- the kernels of the special case (``ops/kda_mosaic.py::_scalar_chunk``) -----------

def _reader_inputs(readers, seq, batch=2, seed=0):
    """``_rule_inputs`` at the kernels' width with ``4 / readers`` key heads
    for the four value heads."""
    q, k, v, g, beta = _rule_inputs("kernel", seq, batch, seed)
    rng = np.random.default_rng(seed + 1)
    q, k = (rng.normal(size=(batch, seq, 4 // readers, WIDTH["kernel"]))
            for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return [jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32), v, g,
            beta]


def _value_and_grads(f, args, weights):
    return f(*args), jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weights),
        argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("readers", [1, 2, 4])
def test_a_key_head_is_read_once_by_each_of_its_value_heads(readers):
    """The scalar-decay kernels at one, two and four value heads a key head
    (four, two and one key head a grid step) over two chunks: the
    token-by-token recurrence, forward and all five gradients, ``dq`` and
    ``dk`` summed over the readers inside the kernel."""
    args = _reader_inputs(readers, 128)
    assert kda_ops.path(*args[:3], 64, args[3]) == "scalar_kernel"
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape), jnp.float32)
    with jax.default_matmul_precision("highest"):
        (got, grads), (want, ref_grads) = (
            _value_and_grads(f, args, weights)
            for f in (kda_ops.kda, _recurrence))
    assert _rel(got, want) <= F32_TOL
    for name, a, g, r in zip("q k v g beta".split(), args, grads, ref_grads):
        assert g.shape == a.shape, name
        assert _rel(g, r) <= F32_TOL, (name, _rel(g, r))


def _broadcast(q, k, v, g, beta):
    """The operands as the per-channel kernels take them."""
    r = v.shape[2] // q.shape[2]
    return (jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
            jnp.broadcast_to(g[..., None], v.shape[:3] + q.shape[3:]), beta)


def test_the_scalar_body_and_the_per_channel_kernels_agree_at_bf16_operands():
    """The cell's dtypes (q, k, v in bf16, decays and strengths in f32) and
    its one-pass products (no ``highest``): the scalar body on the operands'
    own shapes and the per-channel kernels on broadcast ones are both the
    recurrence to within the rounding of a bf16 output, which the
    per-channel kernels' own distance reads, and so is their distance to
    each other; the gradients likewise (``tests/test_kimi_linear.py::
    test_kernel_and_plain_form_agree_at_bf16_operands``'s pattern)."""
    args = _rule_inputs("kernel", 192)
    args = [x.astype(jnp.bfloat16) for x in args[:3]] + args[3:]
    exact = [x.astype(jnp.float32) for x in args]
    assert kda_ops.path(*args[:3], 64, args[3]) == "scalar_kernel"
    assert kda_ops.path(*_broadcast(*args)[:3], 64,
                        _broadcast(*args)[3]) == "kernel"
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=args[2].shape), jnp.float32)

    def per_channel(*a):
        return kda_ops.kda(*_broadcast(*a))

    (want, want_grads), (ours, our_grads), (theirs, their_grads) = (
        _value_and_grads(f, a, weights) for f, a in (
            (_recurrence, exact), (kda_ops.kda, args), (per_channel, args)))
    ours, theirs = (x.astype(jnp.float32) for x in (ours, theirs))
    bound = 4 * _rel(theirs, want)
    assert 1e-3 < bound < 4e-2
    assert _rel(ours, want) <= bound and _rel(ours, theirs) <= bound
    for name, g, p, r in zip("q k v g beta".split(), our_grads, their_grads,
                             want_grads):
        g, p = g.astype(jnp.float32), p.astype(jnp.float32)
        assert _rel(g, r) <= max(4 * _rel(p, r), bound), name


def _shapes(keys, values, width, g_width):
    def array(*shape):
        return jax.ShapeDtypeStruct((1, 128) + shape, jnp.float32)

    return (array(keys, width), array(keys, width), array(values, width),
            array(values, *g_width), array(values))


@pytest.mark.parametrize("keys,width,g_width,chunk,want", [
    (2, 128, (), 64, "scalar_kernel"),
    (4, 128, (), 64, "scalar_kernel"),
    (4, 128, (128,), 64, "kernel"),
    (2, 128, (128,), 64, "kernel"),
    (2, 32, (), 64, "plain"),
    (4, 32, (32,), 64, "plain"),
    (2, 128, (), 32, "plain"),
], ids=["scalar", "scalar-equal-heads", "per-channel",
        "per-channel-fewer-keys", "scalar-narrow", "per-channel-narrow",
        "scalar-chunk-32"])
def test_the_realisation_is_read_from_the_operands_shapes(keys, width,
                                                          g_width, chunk,
                                                          want):
    """``kda.path`` of the operands as the caller hands them, and what
    ``kda`` then traces: the scalar kernels take q and k at the key heads
    and ``g`` a head (no repeat, no broadcast to the channels in front of
    the call), the per-channel kernels and the plain form broadcast
    operands; nothing but the shapes is asked."""
    q, k, v, g, beta = _shapes(keys, 4, width, g_width)
    assert kda_ops.path(q, k, v, chunk, g) == want
    jaxpr = jax.make_jaxpr(functools.partial(kda_ops.kda, chunk=chunk))(
        q, k, v, g, beta).jaxpr
    calls = [e for e in equations(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == (0 if want == "plain" else 1)
    for call in calls:
        read = [tuple(x.aval.shape) for x in call.invars]
        heads = keys if want == "scalar_kernel" else 4
        assert read[:2] == [(1, 128, heads * width)] * 2
        assert ((1, 128, 4 * width) in read[3:]) == (want == "kernel")


def test_the_cells_rule_takes_the_scalar_kernels():
    """At the benchmark cell's shapes (16 key heads of 128 read by 32 value
    heads, one decay a head, chunk 64) the rule runs on the scalar-decay
    kernels, two key heads with their four value heads a grid step."""
    from ps_tpu.ops import kda_mosaic

    keys = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16)
    values = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32)
    assert kda_ops.path(keys, keys, values, 64, g) == "scalar_kernel"
    assert kda_mosaic.keys_a_step(16, 2) == 2
    assert [kda_mosaic.keys_a_step(16, r) for r in (1, 4, 8)] == [4, 1, 1]


@pytest.mark.parametrize("mesh_shape", [{"data": 2, "model": 2}, {"data": 8},
                                        {"data": 2, "model": 4}],
                         ids=["data2_model2", "data8", "data2_model4"])
def test_under_a_mesh_the_scalar_kernels_run_sharded_and_agree(mesh_shape):
    """Under ``ps.init``'s mesh the scalar kernels go through ``shard_map``
    with the key heads split over 'model' where they divide (a key head
    stays with its readers; four cannot divide two key heads, eight cannot
    divide the batch: replicated there): the same values and gradients as
    without a mesh (``tests/test_kimi_linear.py::
    test_under_a_mesh_the_kernels_run_sharded_and_agree``'s pattern)."""
    args = _rule_inputs("kernel", 128)     # B = 2, two key heads for four

    def value_and_grads():
        return jax.value_and_grad(lambda *a: jnp.sum(kda_ops.kda(*a) ** 2),
                                  argnums=(0, 1, 2, 3, 4))(*args)

    want = value_and_grads()
    ps.init(backend="tpu", mesh_shape=mesh_shape)
    try:
        jaxpr = jax.make_jaxpr(value_and_grads)().jaxpr
        got = jax.jit(value_and_grads)()
    finally:
        ps.shutdown()
    assert "shard_map" in {e.primitive.name for e in jaxpr.eqns}
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5),
        got, want)


# -- the share ---------------------------------------------------------------------

def _layer(seed=3, tokens=96):
    """One expert layer's weights over all 32 experts with its shared expert
    and that expert's gate, and tokens."""
    sizes = {**SIZES, "router_width": 32, "num_experts": 32,
             "expert_start": 0, "num_experts_per_tok": 5}
    rng = np.random.default_rng(seed)
    d, f, e = 64, 32, 32

    def w(*shape, scale=0.2):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    lp = {"router": {"kernel": w(d, e, scale=0.3)},
          "gate": w(e, d, f), "up": w(e, d, f), "down": w(e, f, d),
          "shared": {"w1": {"kernel": w(d, f)}, "w3": {"kernel": w(d, f)},
                     "w2": {"kernel": w(f, d)}},
          "shared_gate": {"kernel": w(d, 1)}}
    x = jnp.asarray(rng.normal(size=(1, tokens, d)), jnp.float32)
    return sizes, lp, x


def test_the_sixteen_shares_add_up_with_the_gated_shared_expert_counted_once():
    """The expert layer run sixteen times, each holding two of the 32
    experts: the routed parts and ONE gated shared expert sum to the uncut
    reference layer; each share equals the reference's share."""
    sizes, lp, x = _layer()
    with jax.default_matmul_precision("highest"):
        whole, mask = reference.experts(lp, x[0], sizes)
        shared = jax.nn.sigmoid(x[0] @ lp["shared_gate"]["kernel"]) \
            * reference.swiglu(lp["shared"], x[0])
    routed = jnp.zeros_like(whole)
    for start in range(0, 32, 2):
        share = {**sizes, "num_experts": 2, "expert_start": start}
        held = {**lp, **{n: lp[n][start:start + 2]
                         for n in ("gate", "up", "down")}}
        with jax.default_matmul_precision("highest"):
            out, routing = qwen3_next.moe_block(
                held, x, qwen3_next.Qwen3NextConfig.from_dict(share))
            want, _ = reference.experts(held, x[0], share)
        np.testing.assert_allclose(out[0], want, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(routing.counts), np.asarray(mask.sum(0), np.int32))
        routed = routed + (out[0] - shared)
    assert float(jnp.max(jnp.abs(shared))) > 0.05
    np.testing.assert_allclose(routed + shared, whole, atol=5e-5)
    # every share's output summed counts the shared expert sixteen times
    assert float(jnp.max(jnp.abs(routed + 16 * shared - whole))) > 0.5


def test_routing_is_softmax_top_k_renormalised_over_all_the_picks():
    sizes, lp, x = _layer()
    cfg = qwen3_next.Qwen3NextConfig.from_dict(
        {**sizes, "num_experts": 2, "expert_start": 6})
    with jax.default_matmul_precision("highest"):
        _, routing = qwen3_next.moe_block(
            {**lp, **{n: lp[n][6:8] for n in ("gate", "up", "down")}}, x, cfg)
        probs = jax.nn.softmax(x[0] @ lp["router"]["kernel"], -1)
    top, picks = jax.lax.top_k(probs, 5)
    # two held of five picked: the routing carries two picks a token, each
    # weighted by its probability over the sum of ALL five
    held = np.asarray((picks >= 6) & (picks < 8))
    weights = np.asarray(top / top.sum(-1, keepdims=True))
    live = np.asarray(routing.live)
    assert live.sum() == held.sum()
    np.testing.assert_allclose(
        np.sort(np.asarray(routing.weights)[live]),
        np.sort(weights[held]), rtol=1e-5)


# -- the attention's pieces ----------------------------------------------------------

def test_the_rotation_turns_the_first_quarter_and_passes_the_rest():
    """The model's ``blocks.rope`` over the first 64 of 256 channels is the
    reference's explicit pairs (channel j against j + 32), and the other
    192 channels come out as they went in."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 96, 3, 256)),
                    jnp.float32)
    got = jnp.concatenate([blocks.rope(x[..., :64], 1e7), x[..., 64:]], -1)
    want = jax.vmap(lambda s: reference.rotate(s, 1e7, 64))(x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    assert float(jnp.max(jnp.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]))) > .1


def test_the_gate_is_the_second_half_of_each_heads_q_columns():
    """``[q | gate] = x W_q`` a head at a time: zeroing the gate's columns
    halves the attention's output (sigmoid(0)), zeroing q's leaves the
    softmax uniform; the family's ``gate_half`` reads the same columns."""
    sizes, cfg, params, batch = _setup(seq=64)
    lp = params["layer3"]["attn"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 64, 64)),
                    jnp.float32)
    kernel = np.array(lp["q"]["kernel"])
    gate_columns = np.zeros_like(kernel)
    qwen3_next_step.gate_half(gate_columns, sizes)[...] = 1
    assert gate_columns.sum() == kernel.size / 2
    assert gate_columns.reshape(64, 4, 32)[:, :, 16:].all()
    no_gate = {**lp, "q": {"kernel": jnp.asarray(kernel
                                                 * (1 - gate_columns))}}
    with jax.default_matmul_precision("highest"):
        def core(p):   # the gated output in front of the out projection
            return qwen3_next.attention_block(
                {**p, "out": {"kernel": jnp.eye(64)}}, x, cfg,
                make_attn_fn("full"))
        gated, halved = core(lp), core(no_gate)
        gate = jax.nn.sigmoid((x @ lp["q"]["kernel"]).reshape(
            1, 64, 4, 32)[..., 16:]).reshape(1, 64, 64)
    np.testing.assert_allclose(gated, 2 * halved * gate, atol=1e-5)


# -- what is kept and what is recomputed ----------------------------------------------

def test_a_layers_checkpoint_keeps_the_kernels_residuals_by_name():
    """Under the layer's checkpoint the gradient holds one forward kernel
    call of the rule a delta-rule layer and its backward, and three flash
    calls for the attention layer (a policy-less checkpoint holds a second
    forward of each: fifteen where these are nine), and the names its policy
    lists are all given."""
    sizes, cfg, params, batch = _setup(
        seq=128, linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=1, linear_num_value_heads=2,
        hidden_size=128, head_dim=128)
    assert taps_path(jax.ShapeDtypeStruct((2, 128, 512), jnp.float32),
                     jax.ShapeDtypeStruct((512, 4), jnp.float32)) == "plain"

    def kernel_calls(layer):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(qwen3_next, "_layer", layer)
            jaxpr = jax.make_jaxpr(jax.grad(lambda p: qwen3_next.make_loss_fn(
                cfg, attn="flash")(p, batch)[0]))(params).jaxpr
        # the rule's and the attention's Mosaic calls bear no name (the
        # grouped matmul's do)
        return flash_calls(jaxpr), checkpoint_names(jaxpr)

    calls, given = kernel_calls(qwen3_next._layer)
    assert calls == 3 + 3 * 2
    assert set(kda_ops.KEPT) | set(moe.ROUTE_KEPT) \
        | set(qwen3_next.PRODUCTS_KEPT) | {"flash_out", "flash_lse"} <= given
    plain, _ = kernel_calls(jax.checkpoint(qwen3_next._layer.__wrapped__,
                                           static_argnums=(2, 3, 4)))
    assert plain == 4 + 3 * 3


# -- the published widths ---------------------------------------------------------------

def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_the_published_widths_take_the_kernels_and_their_tiles_fit():
    """At the cell's shapes the rule's call takes the Mosaic kernels
    (``kda.path`` of the broadcast operands) and the flash kernel's tiles at
    keys and values of 256 fit its VMEM budget, forward and backward; the
    whole loss lowers to a jaxpr whose unnamed Mosaic calls are the rule's,
    the taps' and the attention's."""
    config = _json("benchmark/configs/qwen3-next-80b-a3b.json")
    cfg = qwen3_next.Qwen3NextConfig.from_dict(config)
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    assert kda_ops.path(wide, wide, wide, 64) == "kernel"
    block_q, block_k = forward_tiles(8192, 256, 2, True, 256)
    assert forward_vmem_bytes(block_q, block_k, 256, 2, 256) <= _VMEM_BUDGET
    back_q, back_k = backward_tiles(8192, 256, 2, True, 256)
    assert backward_vmem_bytes(back_q, back_k, 256, 2, 256) <= _VMEM_BUDGET
    assert min(block_q, block_k, back_q, back_k) >= 256
    params = jax.eval_shape(lambda k: qwen3_next.init_params(k, cfg),
                            jax.random.key(0))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    loss = qwen3_next.make_loss_fn(cfg, attn="flash")
    with jax.default_device("tpu"):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(
            params, {"inputs": ids, "targets": ids})
    assert "interpret=True" not in str(jaxpr)
    # the Mosaic calls that bear no name: the attention's three, and a
    # delta-rule layer's rule forward and backward and its taps forward,
    # forward again under the layer's checkpoint and backward
    assert taps_path(jax.ShapeDtypeStruct((1, 8192, 8192), jnp.bfloat16),
                     jax.ShapeDtypeStruct((8192, 4), jnp.float32)) == "kernel"
    assert flash_calls(jaxpr.jaxpr) == 3 + 3 * (2 + 3)


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` as published; the cuts and only
    the cuts differ; 625,667,136 parameters."""
    config = _json("benchmark/configs/qwen3-next-80b-a3b.json")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["router_width"], config["expert_start"],
            config["vocab_size"]) == (4, 32, 512, 0, 18992)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert len(config["reduced"]) == 4 and len(config["assumed"]) >= 8
    cfg = qwen3_next.Qwen3NextConfig.from_dict(config)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (cfg.held, cfg.rotary_dim) == ((0, 32), 64)
    shapes = jax.eval_shape(lambda k: qwen3_next.init_params(k, cfg),
                            jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == config["parameters"] == 625_667_136
    assert {kind: count(shapes[layer][kind]) for layer, kind in (
        ("layer0", "gdn"), ("layer3", "attn"), ("layer1", "moe"))} == {
            "gdn": 33_718_464, "attn": 27_263_488, "moe": 104_859_648}
    # the operations from shapes, at the cell's sizes
    tokens = 8192
    core_flops, core_bytes = qwen3_next_step.gdn_core_cost(
        1, 8192, 16, 32, 128, 128, 64, 3)
    a_key_head = 2 * 64 * 64 * 128
    a_value_head = 64 * 64 * 256 + 6 * 64 * 128 * 128 + 64 * 64 * 128
    assert core_flops == 3 * (16 * a_key_head + 32 * a_value_head) * 3 * 128
    assert core_bytes == 3 * 8192 * (
        3 * ((2 * 16 + 32) * 128 * 2 + 32 * 8) + 2 * 32 * 128 * 2)
    # less than the general rule's count at the same value heads, in both
    from benchmark.families.kimi_step import kda_core_cost
    general = kda_core_cost(1, 8192, 32, 128, 128, 64, 3)
    assert core_flops < general[0] and core_bytes < 0.5 * general[1]
    pairs = flash.seen_pairs(8192)
    kernel_flops, kernel_bytes = qwen3_next_step.flash_cost(config, 1, 8192,
                                                            1)
    assert kernel_flops == 16 * 2 * pairs * (5 * 256 + 4 * 256)
    live = 4 * tokens * 10 / 16
    flops = qwen3_next_step.step_flops(config, tokens, 8192, live)
    dense = 6.0 * tokens * (625_667_136 - 4 * 32 * 3_145_728
                            - 18992 * 2048)
    # the dense matmuls' 6 N T, the pairs, the attention's quadratic term and
    # the rule's own: within a few percent of the sum of its parts
    assert flops == pytest.approx(
        dense + live * 18 * 2048 * 512
        + 3 * 16 * 8192 * 512 * tokens + core_flops, rel=0.01)


def test_the_cell_is_what_issue_60_named(listed_for):
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "s8192.b1.zipf", 1)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size"}
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    listed = {m["name"] for m in listed_for(CELL)}
    assert {"step.mfu", "kernel.flash_roofline"} <= listed
    assert {"throughput", "setup_s"} <= {m["moves"]
                                         for m in listed_for(CELL)}
    traffic = _json("benchmark/traffic/s8192.b1.zipf.json")
    assert traffic["loss_step"] in qwen3_next_step.LOSS_STEPS
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("change", [
    {"rope_scaling": {"type": "yarn", "factor": 4.0}},
    {"use_sliding_window": True}, {"mlp_only_layers": [0]},
    {"decoder_sparse_step": 2}, {"tie_word_embeddings": True},
    {"hidden_act": "gelu"}, {"attention_bias": True},
    {"num_nextn_predict_layers": 1},
    {"layer_types": ["full_attention"] * 4},
    {"linear_num_value_heads": 3}, {"partial_rotary_factor": 0.2}],
    ids=lambda c: next(iter(c)))
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        qwen3_next.Qwen3NextConfig.from_dict({**SIZES, **change})


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json("benchmark/configs/qwen3-next-80b-a3b.json")
    traffic = _json("benchmark/traffic/s8192.b1.zipf.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        qwen3_next_step.build(config, {**traffic, "pool": 16}, 1, 0)


# -- the reference's own pieces and the family's checks -------------------------------

def test_reference_in_blocks_as_in_one(monkeypatch):
    """The blocks of query rows and of tokens are how the reference fits the
    chip, not what it computes."""
    sizes, _, params, batch, ((ref_loss, _), _) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    with jax.default_matmul_precision("highest"):
        loss, _ = reference.loss_fn(params, batch, sizes)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)


def test_witness_grads_are_the_reference_gradients_of_those_leaves():
    sizes, _, params, batch, ((ref_loss, _), ref_grads) = _base()
    names = ("layer1/gdn/A_log", "layer3/attn/q/kernel", "layer2/moe/gate")
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(lambda p, b: reference.witness_grads(
            p, b, sizes, names))(params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    assert set(grads) == set(names)
    for name in names:
        want = functools.reduce(lambda t, part: t[part], name.split("/"),
                                ref_grads)
        assert _rel(grads[name], want) <= 1e-5, name


def _step0_inputs(fault=None):
    """What ``qwen3_next_step.step0_checks`` reads, made by hand: two layers
    of 512 experts, witnesses whose gradient is the reference's, AdamW
    applied by the rule; ``fault`` spoils one thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    config = {"head_dim": 4}
    pairs = 81920
    counts = rng.multinomial(pairs, np.ones(512) / 512, size=2)
    ref_counts = counts.copy()
    if fault == "routed_elsewhere":
        counts[0, 0] += 2000         # over FLIP_SHARE of the pairs
        counts[0, 1:501] -= 4
    if fault == "dropped":
        counts[1, 5] -= 1
        ref_counts[1, 5] -= 1
    got = {"expert_tokens": counts, "held_tokens": counts[:, :32],
           "expert_windows": np.ones(2, np.int32)}
    want = {"expert_tokens": ref_counts, "held_tokens": ref_counts[:, :32]}
    witnesses = {}
    scale = 0.5                      # the clip halved the gradient
    for name in qwen3_next_step.GRAD_COSINE:
        before = rng.normal(size=(16, 16)) * 0.02
        ref_grad = rng.normal(size=(16, 16))
        grad = ref_grad * scale
        if fault == "direction" and name.endswith("A_log"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "gate_half" and name.endswith("attn/q/kernel"):
            # the gate's half alone, a twentieth as long as q's: the whole
            # matrix's cosine stays above its limit (0.997)
            half = np.zeros_like(grad)
            qwen3_next_step.gate_half(half, config)[...] = 1
            ref_grad = ref_grad * (1 - half) + 0.05 * half * ref_grad
            grad = scale * (ref_grad * (1 - half) + np.abs(ref_grad) * half)
        if fault == "length" and name.endswith("router/kernel"):
            grad = grad * 1.2
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = qwen3_next_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("in_qkvz/kernel"):
            # the first moment applied without its bias correction
            after = qwen3_next_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    clipped = 1.3 if fault == "clip" else 1.0
    return got, want, witnesses, clipped, rule, pairs, config


STEP0_FAULTS = {None: None,
                "routed_elsewhere": "expert_counts_match_reference",
                "dropped": "no_dropped_tokens",
                "direction": "gradient_matches_reference",
                "gate_half": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    result = qwen3_next_step.step0_checks(*_step0_inputs(fault))
    failed = {name for name, ok in result["checks"].items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if fault else set())
    if fault == "gate_half":   # the whole matrix alone would have passed
        name = qwen3_next_step.GATE_HALF[0]
        assert result["detail"][f"grad_cosine.{name}"] \
            >= qwen3_next_step.GRAD_COSINE[name]


def test_step0_checks_read_a_gradient_alone():
    """A witness without ``after`` (the grad-check tool's cases) is read for
    its direction and length and for no apply."""
    got, want, witnesses, *rest = _step0_inputs()
    alone = {k: {"mu": w["mu"], "reference_grad": w["reference_grad"]}
             for k, w in witnesses.items()}
    result = qwen3_next_step.step0_checks(got, want, alone, *rest)
    assert all(result["checks"].values())
    assert not any(k.startswith("apply_error") for k in result["detail"])
