"""Kimi-Linear (``ps_tpu/models/kimi_linear.py``; the chunked gated delta
rule of ``ps_tpu/ops/kda.py``; keys wider than values in
``ps_tpu/ops/flash_attention.py``; the held experts, sigmoid routing and
selection bias of ``ps_tpu/ops/moe.py`` beside a shared expert) against its
plain reference (``benchmark/families/kimi_reference.py``: the delta rule
token by token, whole rows of attention over the concatenated keys, a masked
loop over the held experts), at small sizes on the CPU with seeded weights;
then the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to 1e-5 of their
largest entry (seen: under 6e-6). The weights are scaled up from the cell's
0.02 so that every mixer and every expert moves the loss by far more than
that.
"""

import functools
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from benchmark.families import flash
from benchmark.families import kimi_reference as reference
from benchmark.families import kimi_step
from jaxpr_tools import checkpoint_names, primitives
from ps_tpu.models import kimi_linear
from ps_tpu.models.blocks import _full_attention, make_attn_fn
from ps_tpu.ops import flash_attention, kda as kda_ops, kda_mosaic, moe
from ps_tpu.ops.flash_attention import (backward_tiles, backward_vmem_bytes,
                                        forward_tiles, forward_vmem_bytes)
from ps_tpu.ops.gated_conv import causal_taps

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "kimi-linear-48b-a3b.s8192.b1.zipf"
#: the cell's five-layer pattern, an eighth of 16 experts held
SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            head_dim=16, num_heads=4,
                            short_conv_kernel_size=4),
    gate_low_rank=16, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    first_k_dense_replace=1, router_width=16, num_experts=2, expert_start=4,
    num_experts_per_token=4, num_shared_experts=1, moe_renormalize=True,
    routed_scaling_factor=2.446, bias_update_rate=1e-3, rms_norm_eps=1e-5,
    mla_use_nope=True, q_lora_rank=None, num_expert_group=1, topk_group=1,
    moe_router_activation_func="sigmoid", tie_word_embeddings=False,
    dtype="float32")


def _setup(seed=0, batch=2, seq=128, **changes):
    sizes = {**SIZES, **changes}
    cfg = kimi_linear.KimiLinearConfig.from_dict(sizes)
    params = jax.jit(lambda k: kimi_linear.init_params(k, cfg))(
        jax.random.key(seed))
    # away from the cell's 0.02: every layer then matters to the loss
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"],
                       size=(batch, seq + 1)).astype(np.int32)
    bias = jnp.asarray(0.1 * rng.normal(size=(
        cfg.num_expert_layers, cfg.router_width)), jnp.float32)
    return sizes, cfg, params, {"inputs": ids[:, :-1],
                                "targets": ids[:, 1:]}, bias


def _system(cfg, params, batch, bias, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            kimi_linear.make_loss_fn(cfg, attn=attn), has_aux=True))(
                params, batch, bias)


def _plain(sizes, params, batch, bias):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, bias, sizes), has_aux=True))(
                params)


@functools.lru_cache(maxsize=None)
def _base():
    sizes, cfg, params, batch, bias = _setup()
    return sizes, cfg, params, batch, bias, _plain(sizes, params, batch, bias)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _assert_grads_close(grads, ref_grads, tol=F32_TOL):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert _rel(g, r) <= tol, (jax.tree_util.keystr(path), _rel(g, r))


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, logits, counts, the next bias and every gradient, for the
    five-layer pattern with two of sixteen experts held."""
    sizes, cfg, params, batch, bias, ((ref_loss, ref_aux), ref_grads) = _base()
    (loss, aux), grads = _system(cfg, params, batch, bias, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    for name in ("expert_tokens", "held_tokens", "expert_bias"):
        np.testing.assert_array_equal(np.asarray(aux[name]),
                                      np.asarray(ref_aux[name]))
    assert aux["expert_tokens"].shape == (4, 16)
    assert aux["held_tokens"].shape == (4, 2)
    assert np.all(np.asarray(aux["expert_tokens"]).sum(-1) == 2 * 128 * 4)
    # every tensor has a gradient that is not nothing
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))
    _assert_grads_close(grads, ref_grads)
    with jax.default_matmul_precision("highest"):
        hidden, _ = kimi_linear.apply(params, batch["inputs"], cfg, bias,
                                      make_attn_fn(attn))
        logits = kimi_linear.logits_of(params, hidden, cfg)
        want = reference.logits_fn(params, batch["inputs"], bias, sizes)
    assert logits.shape == (2, 128, 256)
    assert _rel(logits, want) <= F32_TOL


def test_fused_step_matches_reference():
    """Through ``KVStore.make_step(has_aux=True)`` with the bias as the
    step's extra argument: the loss, the aux and, read from AdamW's first
    moment behind a clip that does not bite, every gradient; then AdamW's
    rule on the parameters. A batch of eight: the test mesh has eight
    devices along ``data``."""
    sizes, cfg, params, batch, bias = _setup(seed=1, batch=8, seq=64)
    (ref_loss, ref_aux), ref_grads = _plain(sizes, params, batch, bias)
    rule = dict(learning_rate=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    ps.init(backend="tpu")
    try:
        store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1e9,
                           placement="replicated", **rule)
        store.init(params)
        step = store.make_step(kimi_linear.make_loss_fn(cfg), has_aux=True)
        with jax.default_matmul_precision("highest"):
            loss, _, aux = step(store.shard_batch(batch), bias)
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
        np.testing.assert_array_equal(np.asarray(aux["expert_bias"]),
                                      np.asarray(ref_aux["expert_bias"]))
        import optax

        flat = jax.tree_util.tree_leaves_with_path(ref_grads)
        assert len(flat) == len(store.keys())
        for path, r in flat:
            key = "/".join(p.key for p in path)
            state = store.optimizer_state(key)
            mu = optax.tree_utils.tree_get(state, "mu")
            assert _rel(mu / 0.1, r) <= F32_TOL, key
            before = functools.reduce(lambda t, p: t[p.key], path, params)
            want = kimi_step.adamw_first_step(
                before, mu, optax.tree_utils.tree_get(state, "nu"), **rule)
            np.testing.assert_allclose(store.pull(key), want, atol=1e-6)
    finally:
        ps.shutdown()


# -- the chunked rule against the recurrence ----------------------------------

#: the width at which each realisation of the rule runs (``kda.path``): the
#: XLA form below 128 lanes, the Mosaic kernels (interpret mode here) at them
WIDTH = {"plain": 32, "kernel": 128}
PATHS = sorted(WIDTH)


def _rule_inputs(seq, heads=3, width=32, batch=2, seed=0):
    """Unit q and k, decays as strong as the configuration's strongest head
    gives (``exp(A_log)`` 16, a softplus about 0.1: 1.6 nats a token, 102 a
    chunk) beside a middling and a weak head."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(batch, seq, heads, width)) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    a = np.array([16.0, 4.0, 1.0])[:heads]
    g = -a[None, None, :, None] * 0.1 * rng.uniform(
        0.5, 1.5, size=(batch, seq, heads, width))
    beta = rng.uniform(0.1, 0.95, size=(batch, seq, heads))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _path_inputs(path, seq):
    """``_rule_inputs`` at the width that takes ``path``; the kernels with
    two heads, one grid step's worth (``kda_mosaic.heads_a_step``)."""
    args = _rule_inputs(seq, width=WIDTH[path],
                        **(dict(heads=2) if path == "kernel" else {}))
    assert seq % 64 or kda_ops.path(*args[:3], 64) == path
    return args


def _recurrence(q, k, v, g, beta):
    return jax.vmap(reference.delta_rule)(q, k, v, g, beta)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("seq", [64, 192, 512],
                         ids=["one_chunk", "three_chunks", "eight_chunks"])
def test_chunked_kda_equals_the_token_by_token_recurrence(seq, path):
    """Forward and all five gradients, at decays that overflow a chunk
    whose cumulated decay is divided out (``exp(-G)`` is not an f32)."""
    args = _path_inputs(path, seq)
    lost = -np.cumsum(np.asarray(args[3])[:, :64], axis=1)
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(lost.astype(np.float32))).any()
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
    for name, g, r in zip("q k v g beta".split(), grads, ref_grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, r) <= F32_TOL, (name, _rel(g, r))


@pytest.mark.parametrize("path", PATHS)
def test_kda_refuses_a_sequence_its_chunks_do_not_divide(path):
    args = _path_inputs(path, 100)
    with pytest.raises(ValueError, match="must divide"):
        kda_ops.kda(*args)
    with pytest.raises(ValueError, match="must divide"):
        kda_ops.kda(*_rule_inputs(96, width=WIDTH[path]), chunk=24)
    # the documented pad: tokens of beta 0 and g 0 behind the sequence
    # change nothing before them
    padded = [jnp.pad(x, ((0, 0), (0, 28)) + ((0, 0),) * (x.ndim - 2))
              for x in args]
    assert kda_ops.path(*padded[:3], 64) == path
    with jax.default_matmul_precision("highest"):
        got = kda_ops.kda(*padded)[:, :100]
        assert _rel(got, _recurrence(*args)) <= F32_TOL


@pytest.mark.parametrize("path", PATHS)
def test_kda_is_causal_and_keeps_bf16_in_bf16_out(path):
    args = _path_inputs(path, 128)
    out = kda_ops.kda(*args)
    t = 70
    moved = kda_ops.kda(args[0], args[1], args[2].at[:, t].add(1.0),
                        *args[3:])
    np.testing.assert_array_equal(np.asarray(out[:, :t]),
                                  np.asarray(moved[:, :t]))
    assert np.all(np.any(np.asarray(out[:, t]) != np.asarray(moved[:, t]),
                         axis=-1))
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    assert kda_ops.kda(q, k, v, *args[3:]).dtype == jnp.bfloat16


def test_under_a_mesh_the_kernels_run_sharded_and_agree():
    """GSPMD cannot partition a Mosaic call, so under ``ps.init``'s mesh
    the kernels go through ``shard_map``, batch over 'data' and heads over
    'model' where they divide, replicated where they do not: the same
    values and gradients as without a mesh either way."""
    args = _path_inputs("kernel", 128)     # B = 2, H = 2

    def value_and_grads():
        return jax.value_and_grad(lambda *a: jnp.sum(kda_ops.kda(*a) ** 2),
                                  argnums=(0, 1, 2, 3, 4))(*args)

    want = value_and_grads()
    for mesh_shape in ({"data": 2, "model": 2}, {"data": 8}):
        ps.init(backend="tpu", mesh_shape=mesh_shape)  # 8 cannot divide B
        try:
            got = jax.jit(value_and_grads)()
        finally:
            ps.shutdown()
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-5), got, want)


@pytest.mark.parametrize("mesh_shape", [None, {"data": 2, "model": 2}],
                         ids=["no_mesh", "data2_model2"])
def test_a_checkpoint_that_keeps_the_named_residuals_drops_the_forward_call(
        mesh_shape):
    """Under a caller's ``jax.checkpoint`` the backward pass runs the
    forward kernel again only for its output, states and inverses; one whose
    policy keeps the three by the names the kernel gives them (``KEPT``)
    holds one call fewer, inside ``shard_map`` as outside, and all five
    gradients are the same bits."""
    args = _path_inputs("kernel", 128)     # B = 2, H = 2

    def grad(**checkpoint):
        return jax.grad(jax.checkpoint(
            lambda *a: jnp.sum(jnp.sin(kda_ops.kda(*a))),
            **checkpoint), argnums=(0, 1, 2, 3, 4))

    plain, keeps = grad(), grad(
        policy=jax.checkpoint_policies.save_only_these_names(*kda_ops.KEPT))
    if mesh_shape:
        ps.init(backend="tpu", mesh_shape=mesh_shape)
    for fn, calls in ((plain, 3), (keeps, 2)):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        names = primitives(jaxpr)
        assert names.count("pallas_call") == calls
        assert ("shard_map" in names) == bool(mesh_shape)
        assert checkpoint_names(jaxpr) == set(kda_ops.KEPT)
    for got, want, name in zip(jax.jit(keeps)(*args), jax.jit(plain)(*args),
                               "q k v g beta".split()):
        assert float(jnp.max(jnp.abs(want))) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


def test_the_mixers_checkpoint_keeps_its_inputs_and_the_named_three(capsys):
    """``models/kimi_linear.py::_mixer`` at a kernel-path width: its
    gradient holds the forward call once and the backward call, and what
    lives from the forward pass to the backward pass is its inputs and the
    kernel's three named residuals (``KEPT``), nothing of the taps, the
    normalisation, the decays, the gates or the gated norm."""
    heads, width, batch, seq, rank = 2, 128, 2, 128, 16
    rng = np.random.default_rng(0)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    wide = (batch, seq, heads * width)
    projected = (normal(*wide), normal(*wide), normal(*wide),
                 normal(batch, seq, rank), normal(batch, seq, rank),
                 normal(batch, seq, heads))
    weights = {**{f"{n}_conv": normal(heads * width, 4) for n in "qkv"},
               "f_b": {"kernel": normal(rank, heads * width)},
               "g_b": {"kernel": normal(rank, heads * width)},
               "dt_bias": normal(heads * width),
               "A_log": jnp.log(jnp.asarray([16.0, 1.0])),
               "out_norm": {"scale": 1 + 0.1 * normal(width)}}

    def loss(*args):  # linear in the mixer's output: it keeps nothing itself
        return jnp.sum(kimi_linear._mixer(*args, heads, 1e-5))

    args = (projected, weights)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(*args).jaxpr
    assert primitives(jaxpr).count("pallas_call") == 2
    assert checkpoint_names(jaxpr) == set(kda_ops.KEPT)
    jax.ad_checkpoint.print_saved_residuals(loss, *args)
    kept = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    chunks = seq // 64
    # the output is read by the gated norm too, so jax rounds what it keeps
    # of it (a ``reduce_precision`` that hides its name in this listing)
    for line, (shape, what) in zip(kept, (
            ((batch, seq, heads, width), "reduce_precision"),
            ((batch, heads, chunks, width, width), "named 'kda_states'"),
            ((batch, heads, chunks, 64, 64), "named 'kda_inverses'"))):
        assert line.startswith(f"f32[{','.join(map(str, shape))}]"), line
        assert what in line, line
    assert len(kept) == 3, kept
    grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(g))) and float(jnp.max(jnp.abs(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_the_shapes_alone_say_which_realisation_runs():
    """``kda.path``: the kernels at the cell's shape (32 heads of 128, 8,192
    tokens in chunks of 64), the XLA form at every width of this file's
    models and wherever a head does not fill whole lane tiles."""
    def shapes(t, h, width, v_width=None):
        x = jax.ShapeDtypeStruct((1, t, h, width), jnp.bfloat16)
        return x, x, jax.ShapeDtypeStruct((1, t, h, v_width or width),
                                          jnp.bfloat16)

    assert kda_ops.path(*shapes(8192, 32, 128), 64) == "kernel"
    assert kda_ops.path(*shapes(128, 2, 256, 128), 64) == "kernel"
    for width in (SIZES["linear_attn_config"]["head_dim"], 32, 64, 192):
        assert kda_ops.path(*shapes(128, 4, width), 64) == "plain"
    assert kda_ops.path(*shapes(128, 4, 128, 64), 64) == "plain"
    assert kda_ops.path(*shapes(128, 4, 128), 32) == "plain"


def test_kernel_and_plain_form_agree_at_bf16_operands():
    """The cell's dtypes (q, k, v in bf16, decays and strengths in f32) and
    its one-pass products (no ``highest`` here: the kernels round the
    operands of their default-class products to bf16, as the chip does to
    the plain form's): both realisations are the recurrence to within the
    rounding of a bf16 output, which the plain form's own distance reads,
    and so is their distance to each other; the gradients likewise."""
    args = _rule_inputs(192, heads=2, width=128)
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    args = [q, k, v, *args[3:]]
    exact = [x.astype(jnp.float32) for x in args]
    weights = jnp.asarray(np.random.default_rng(9).normal(
        size=v.shape), jnp.float32)

    def both(f, *a):
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weights)
        return f(*a).astype(jnp.float32), jax.grad(
            loss, argnums=(0, 1, 2, 3, 4))(*a)

    plain = functools.partial(kda_ops._kda, chunk=64)
    (want, want_grads), (ours, our_grads), (theirs, their_grads) = (
        both(_recurrence, *exact), both(kda_ops.kda, *args),
        both(plain, *args))
    assert kda_ops.path(q, k, v, 64) == "kernel"
    bound = 4 * _rel(theirs, want)
    assert 1e-3 < bound < 4e-2
    assert _rel(ours, want) <= bound and _rel(ours, theirs) <= bound
    for name, g, p, r in zip("q k v g beta".split(), our_grads, their_grads,
                             want_grads):
        g, p = g.astype(jnp.float32), p.astype(jnp.float32)
        assert _rel(g, r) <= max(4 * _rel(p, r), bound), name


def _chunk_matrix(c, decay, seed):
    """``A = beta * strict(K K^T * D)`` of one chunk of ``c`` tokens as the
    scalar body makes it: unit keys, ``D[t, s] = exp(g_{s+1} + .. + g_t)``
    for log-decays of ``decay`` nats a token, strengths up to 0.95."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(c, 16))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    cum = np.cumsum(-decay * rng.uniform(0.3, 2.3, size=c))
    beta = rng.uniform(0.05, 0.95, size=(c, 1))
    pair = np.exp(np.tril(cum[:, None] - cum[None, :]))     # no exp of a gain
    return np.tril(beta * (k @ k.T) * pair, -1)


@pytest.mark.parametrize("decay", [1e-6, 0.1, 16.0],
                         ids=["no_decay", "a_tenth", "sixteen"])
@pytest.mark.parametrize("c", [16, 32, 64])
def test_the_kernels_inverse_is_numpys(c, decay):
    """``kda_mosaic._inverses`` (pure ``jnp``: no kernel around it here)
    against ``numpy.linalg.inv(I + a)`` in float64, at the kernels' chunk
    and at the two sizes below it, from a chunk that forgets nothing (the
    inverse's entries largest) to one that forgets a key in a token."""
    mats = [_chunk_matrix(c, decay, seed) for seed in (0, 1)]
    got = kda_mosaic._inverses([jnp.asarray(a, jnp.float32) for a in mats],
                               jnp.eye(c, dtype=jnp.float32))
    for a, x in zip(mats, got):
        want = np.linalg.inv(np.eye(c) + a)
        assert x.shape == (c, c) and x.dtype == jnp.float32
        assert np.abs(np.asarray(x, np.float64) - want).max() \
            <= 2e-6 * np.abs(want).max()


def test_the_inverses_of_a_step_are_taken_level_by_level():
    """The shape of ``_inverses`` at the kernels' chunk: a chain is six
    products (one a level, ``P @ [P | X]``, [64, 64] x [64, 128]), every one
    of f32 operands at ``Precision.HIGHEST``, and two chains alternate
    product by product (a chain's time on the chip is its depth: one's
    product is in flight while the other's operands are made), each
    computing what it computes alone, to the bit."""
    highest = jax.lax.Precision.HIGHEST
    mats = [jnp.asarray(_chunk_matrix(64, 0.1, seed), jnp.float32)
            for seed in (0, 1)]
    eye = jnp.eye(64, dtype=jnp.float32)
    both = jax.make_jaxpr(kda_mosaic._inverses)(mats, eye).jaxpr
    products = [e for e in both.eqns if e.primitive.name == "dot_general"]
    assert len(products) == 12
    assert primitives(
        jax.make_jaxpr(kda_mosaic._inverses)(mats[:1], eye).jaxpr).count(
            "dot_general") == 6
    for eqn in products:
        left, right = (v.aval for v in eqn.invars)
        assert (left.shape, right.shape) == ((64, 64), (64, 128))
        assert left.dtype == right.dtype == jnp.float32
        assert eqn.outvars[0].aval.shape == (64, 128)
        assert eqn.params["precision"] == (highest, highest)
    # which of the two matrices each product descends from
    chain = {v: {i} for i, v in enumerate(both.invars[:2])}
    for eqn in both.eqns:
        reads = set().union(*(chain[v] for v in eqn.invars
                              if not hasattr(v, "val") and v in chain))
        chain.update((v, reads) for v in eqn.outvars)
    assert [chain[e.outvars[0]] for e in products] == [{0}, {1}] * 6
    together = kda_mosaic._inverses(mats, eye)
    for a, x in zip(mats, together):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(kda_mosaic._inverses([a], eye)[0]))


def test_causal_taps_are_the_convolution_of_both_mixers():
    """``ops/gated_conv.py::causal_taps`` at four taps against the
    reference's padded ``nn.Conv1d``, and the transpose (sign -1) against
    autodiff."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 40, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    want = jnp.stack([reference.conv_silu(s, w) for s in x])
    np.testing.assert_allclose(jax.nn.silu(causal_taps(x, w)), want,
                               atol=1e-6)
    ct = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    back = jax.grad(lambda x: jnp.sum(causal_taps(x, w) * ct))(x)
    np.testing.assert_allclose(back, causal_taps(ct, w, -1), atol=1e-6)


# -- the shares ---------------------------------------------------------------

def _layer(seed=3, tokens=96):
    """One expert layer's weights over all 16 experts with its shared
    expert, and tokens."""
    sizes = {**SIZES, "num_experts": 16, "expert_start": 0}
    rng = np.random.default_rng(seed)
    d, f, e = 64, 32, 16

    def w(*shape, scale=0.2):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    lp = {"router": {"kernel": w(d, e, scale=0.3)},
          "gate": w(e, d, f), "up": w(e, d, f), "down": w(e, f, d),
          "shared": {"w1": {"kernel": w(d, f)}, "w3": {"kernel": w(d, f)},
                     "w2": {"kernel": w(f, d)}}}
    x = jnp.asarray(rng.normal(size=(1, tokens, d)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.1, jnp.float32)
    return sizes, lp, x, bias


def _share(sizes, lp, x, bias, start, count):
    cfg = kimi_linear.KimiLinearConfig.from_dict(
        {**sizes, "num_experts": count, "expert_start": start})
    held = {**lp, **{n: lp[n][start:start + count]
                     for n in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        return kimi_linear.moe_block(held, x, cfg, bias)


def test_the_shares_add_up_with_the_shared_expert_counted_once():
    """The expert layer run four times, each holding four of the sixteen
    experts: the routed parts and ONE shared expert sum to the uncut
    reference layer; each share equals the reference's share."""
    sizes, lp, x, bias = _layer()
    with jax.default_matmul_precision("highest"):
        whole, mask = reference.experts(lp, x[0], bias, sizes)
        shared = reference.swiglu(lp["shared"], x[0])
    routed = jnp.zeros_like(whole)
    for start in range(0, 16, 4):
        out, routing = _share(sizes, lp, x, bias, start, 4)
        with jax.default_matmul_precision("highest"):
            want, _ = reference.experts(
                {**lp, **{n: lp[n][start:start + 4]
                          for n in ("gate", "up", "down")}},
                x[0], bias, {**sizes, "num_experts": 4,
                             "expert_start": start})
        np.testing.assert_allclose(out[0], want, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(routing.counts), np.asarray(mask.sum(0), np.int32))
        # a token none of whose picks is held gets the shared expert alone
        nothing = ~np.asarray(routing.live).any(axis=-1)
        np.testing.assert_allclose(np.asarray(out[0])[nothing],
                                   np.asarray(shared)[nothing], atol=1e-6)
        routed = routed + (out[0] - shared)
    assert float(jnp.max(jnp.abs(shared))) > 0.1
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)
    # every share's output summed counts the shared expert four times
    assert float(jnp.max(jnp.abs(routed + 4 * shared - whole))) > 0.1


#: enough tokens for two windows where four of sixteen experts are held at
#: four picks: three times an even load in whole tiles is 1,536 rows of the
#: 2,048 pairs the row buffers used to hold
WINDOW_TOKENS = 512


@pytest.mark.parametrize("boost,windows", [(0.0, 1), (8.0, 2), (-8.0, 1)],
                         ids=["even_load", "overflow", "no_live_row"])
def test_a_share_in_windows_is_the_references_values_and_gradients(boost,
                                                                   windows):
    """The held experts over a window of ``R`` rows, beside the shared
    expert, against the plain masked loop, values and every gradient: at a
    load the first window holds; with every token sent to the held experts,
    so that a second window runs and nothing is dropped; and with no live
    row at all (the shared expert alone)."""
    sizes, lp, x, bias = _layer(tokens=WINDOW_TOKENS)
    start, count = 4, 4
    bias = bias.at[start:start + count].add(boost)
    share = {**sizes, "num_experts": count, "expert_start": start}
    cfg = kimi_linear.KimiLinearConfig.from_dict(share)

    def held(lp):
        return {**lp, **{n: lp[n][start:start + count]
                         for n in ("gate", "up", "down")}}

    def system(lp, x):
        return kimi_linear.moe_block(held(lp), x, cfg, bias)

    def plain(lp, x):
        return reference.experts(held(lp), x[0], bias, share)[0][None]

    def grads(f):
        return jax.jit(jax.grad(lambda lp, x: jnp.sum(jnp.sin(f(lp, x))),
                                argnums=(0, 1)))(lp, x)

    with jax.default_matmul_precision("highest"):
        out, routing = jax.jit(system)(lp, x)
        assert routing.window.shape == (1536,)
        assert moe.num_windows(routing) == 2
        assert int(moe.live_windows(routing)) == windows
        live = int(routing.group_sizes.sum())
        assert live == int(routing.live.sum())
        if boost:
            assert live == (4 * WINDOW_TOKENS if boost > 0 else 0)
        else:
            assert 0 < live <= 1536
        assert _rel(out, plain(lp, x)) <= F32_TOL
        _assert_grads_close(grads(lambda lp, x: system(lp, x)[0]),
                            grads(plain))


def test_every_expert_held_is_one_window_of_the_whole_buffer():
    """With all sixteen experts held the window is the ``T x k`` pairs: no
    loop, and the routed part is, to the bit, the whole-buffer gather written
    out here."""
    sizes, lp, x, bias = _layer(tokens=WINDOW_TOKENS)
    with jax.default_matmul_precision("highest"):
        out, routing = _share(sizes, lp, x, bias, 0, 16)
        assert routing.live is None and routing.window is None
        assert moe.num_windows(routing) == 1
        rows = jnp.take(x[0], routing.order // 4, axis=0)
        rows = moe.expert_ffn(rows, lp["gate"], lp["up"], lp["down"],
                              routing.group_sizes)
        back = jnp.take(rows, routing.inverse, axis=0).reshape(
            WINDOW_TOKENS, 4, -1)
        want = jnp.einsum("tkd,tk->td", back, routing.weights,
                          preferred_element_type=jnp.float32)
        want = want + kimi_linear.dense_ffn(lp["shared"], x[0])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want))


def test_aux_counts_the_windows_each_expert_layer_ran():
    sizes, cfg, params, batch, bias, _ = _base()
    (_, aux), _ = _system(cfg, params, batch, bias)
    np.testing.assert_array_equal(np.asarray(aux["expert_windows"]),
                                  np.ones(cfg.num_expert_layers, np.int32))
    assert aux["expert_windows"].dtype == jnp.int32
    # the cell's shapes: 8,192 tokens, eight picks, 8 of 256 held
    assert moe.window_rows(8192, 8, 8, 256) == 6144


def test_routing_is_sigmoid_top8_renormalised_and_scaled():
    sizes, lp, x, bias = _layer()
    _, routing = _share(sizes, lp, x, bias, 4, 4)
    scores = jax.nn.sigmoid(jnp.dot(x[0], lp["router"]["kernel"],
                                    precision="highest"))
    _, picks = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(np.asarray(routing.experts),
                                  np.asarray(picks))
    picked = jnp.take_along_axis(scores, routing.experts, axis=-1)
    np.testing.assert_allclose(
        routing.weights, 2.446 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-6)
    # the bias carries no gradient, in the model's loss either
    sizes, cfg, params, batch, bias, _ = _base()
    g = jax.grad(lambda b: kimi_linear.make_loss_fn(cfg)(
        params, batch, b)[0])(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


# -- keys wider than values in the flash kernels ------------------------------

@pytest.mark.parametrize("tiles", [(128, 128), (256, 256)],
                         ids=["two_calls", "one_call"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_keys_wider_than_values_equals_einsum(causal, tiles,
                                                         monkeypatch):
    """q and k 48 wide, v 32 (the cell's 192 and 128 in small), forward and
    three gradients, by the two backward calls and by the one call whose
    tile spans the sequence."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 48)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 4, 48)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 4, 32)), jnp.float32)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=tiles[0],
                               block_k=tiles[1])

    # the backward's own tiles would be (256, 256), one call: forced to
    # (128, 128) for the two calls
    monkeypatch.setattr(
        importlib.import_module("ps_tpu.ops.flash_attention"),
        "backward_tiles", lambda *a: tiles)
    with jax.default_matmul_precision("highest"):
        out = kernel(q, k, v)
        assert out.shape == (2, 256, 4, 32)
        np.testing.assert_allclose(
            out, _full_attention(q, k, v, causal=causal), atol=2e-5)
        weights = jnp.asarray(rng.normal(size=out.shape), jnp.float32)
        got = jax.grad(lambda *a: jnp.sum(kernel(*a) * weights),
                       argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(
            _full_attention(*a, causal=causal) * weights),
            argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)
    with pytest.raises(ValueError, match="as wide as q"):
        flash_attention(q, k[..., :32], v)


def _vmem_before_pr34(block_q, block_k, head_dim, itemsize):
    """``forward_vmem_bytes`` and ``backward_vmem_bytes`` as they stood
    before a value width of its own."""
    lanes = -(-head_dim // 128) * 128
    forward = (2 * 2 * block_q * lanes * itemsize
               + 2 * 2 * block_k * lanes * itemsize + 2 * 8 * block_k * 4
               + 2 * block_q * 128 * 4 + block_q * (2 * 128 + lanes) * 4
               + 2 * block_q * block_k * 4)
    q_side, k_side = (2 * block_q * lanes * itemsize,
                      2 * block_k * lanes * itemsize)
    rows = 2 * 2 * 8 * block_q * 4
    dkv = (2 * q_side + 4 * k_side + rows + 2 * block_k * 128 * 4
           + 2 * block_k * lanes * 4)
    dq = (3 * q_side + 2 * k_side + rows + 2 * 8 * block_k * 4
          + block_q * (lanes + 2 * 128) * 4)
    return forward, max(dkv, dq) + 4 * block_q * block_k * 4


@pytest.mark.parametrize("shape", [(8192, 64, True), (4096, 128, True),
                                   (512, 64, False)],
                         ids=["lfm2", "olmoe", "bert"])
def test_tiles_and_vmem_counts_of_the_cells_stay(shape):
    """Where the values are as wide as the keys, the counts and the tiles
    are what they were: the four flash cells' kernels are today's."""
    seq, d, causal = shape
    want = {(8192, 64): ((1024, 1024), (1024, 512)),
            (4096, 128): ((1024, 1024), (1024, 512)),
            (512, 64): ((512, 512), (512, 512))}[(seq, d)]
    assert (forward_tiles(seq, d, 2, causal),
            backward_tiles(seq, d, 2, causal)) == want
    assert (forward_tiles(seq, d, 2, causal, d),
            backward_tiles(seq, d, 2, causal, d)) == want
    for block_q, block_k in ((128, 128), (512, 256), (1024, 1024)):
        old = _vmem_before_pr34(block_q, block_k, d, 2)
        for v_dim in (None, d):
            assert (forward_vmem_bytes(block_q, block_k, d, 2, v_dim),
                    backward_vmem_bytes(block_q, block_k, d, 2,
                                        v_dim)) == old
    # the lanes are padded per operand: 192 counts as 256, 128 as 128
    assert forward_vmem_bytes(512, 512, 192, 2, 128) \
        == forward_vmem_bytes(512, 512, 256, 2, 128) \
        < forward_vmem_bytes(512, 512, 256, 2)
    assert forward_tiles(8192, 192, 2, True, 128) == (1024, 512)
    assert backward_tiles(8192, 192, 2, True, 128) == (512, 512)


# -- the reference's own pieces -----------------------------------------------

def test_reference_in_blocks_as_in_one(monkeypatch):
    """The reference's attention in blocks of query rows and its recurrence
    in blocks of tokens (what lets 8,192 positions fit on the chip) are the
    attention and the recurrence in one block."""
    sizes, _, params, batch, bias, ((ref_loss, _), ref_grads) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    (loss, _), grads = _plain(sizes, params, batch, bias)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    _assert_grads_close(grads, ref_grads)


def test_witness_grads_are_the_reference_gradients_of_those_leaves():
    sizes, _, params, batch, bias, ((ref_loss, _), ref_grads) = _base()
    assert set(kimi_step.GRAD_COSINE) == {
        "layer0/kda/k/kernel", "layer0/kda/f_b/kernel",
        "layer3/attn/kv_b/kernel", "layer1/moe/shared/w1/kernel",
        "layer3/moe/router/kernel", "layer2/moe/gate"}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(lambda p: reference.witness_grads(
            p, batch, bias, sizes, kimi_step.GRAD_COSINE))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for name, g in grads.items():
        want = functools.reduce(lambda t, part: t[part], name.split("/"),
                                ref_grads)
        np.testing.assert_allclose(g, want, atol=1e-6)


# -- the family's pieces ------------------------------------------------------

def _step0_inputs(fault=None):
    """What ``kimi_step.step0_checks`` reads, made by hand: two layers of
    256 experts, witnesses whose gradient is the reference's, AdamW applied
    by the rule; ``fault`` spoils one thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    pairs, rate = 65536, 1e-3
    counts = rng.multinomial(pairs, np.ones(256) / 256, size=2)
    ref_counts = counts.copy()
    if fault == "routed_elsewhere":
        counts[0, 0] += 800          # over FLIP_SHARE of the pairs
        counts[0, 1:201] -= 4
    if fault == "dropped":
        counts[1, 5] -= 1
        ref_counts[1, 5] -= 1
    bias = kimi_step.bias_by_sign_rule(counts, rate)
    if fault == "bias":
        bias[1, 7] += np.float32(1e-3)
    got = {"expert_tokens": counts, "held_tokens": counts[:, :8],
           "expert_bias": bias}
    want = {"expert_tokens": ref_counts, "held_tokens": ref_counts[:, :8]}
    witnesses = {}
    scale = 0.5                      # the clip halved the gradient
    for name in kimi_step.GRAD_COSINE:
        before = rng.normal(size=(16, 8)) * 0.02
        ref_grad = rng.normal(size=(16, 8))
        grad = ref_grad * scale
        if fault == "direction" and name.endswith("f_b/kernel"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "length" and name.endswith("router/kernel"):
            grad = grad * 1.2
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = kimi_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("kv_b/kernel"):
            # the first moment applied without its bias correction
            after = kimi_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    clipped = 1.3 if fault == "clip" else 1.0
    return got, want, witnesses, clipped, rule, pairs, rate


STEP0_FAULTS = {None: None,
                "routed_elsewhere": "expert_counts_match_reference",
                "dropped": "no_dropped_tokens",
                "bias": "expert_bias_follows_sign_rule",
                "direction": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    checks = kimi_step.step0_checks(*_step0_inputs(fault))["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if fault else set())


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_cells_are_what_issue_34_named(listed_for):
    traffic = _json("benchmark/traffic/s8192.b1.zipf.json")
    assert "pool" not in traffic.pop("rehearse")
    assert traffic.pop("loss_step") in kimi_step.LOSS_STEPS == (32, 48, 64)
    for prose in ("pool_why", "loss_step_why"):
        traffic.pop(prose)
    assert traffic == {
        "per_chip_batch": 1, "seq_len": 8192, "attn": "flash",
        "ids": {"kind": "zipf", "s": 1.0}, "input": "direct",
        "pool": "fresh", "block_steps": 2, "warmup_steps": 4,
        "trace_blocks": 2}
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b", "s8192.b1.zipf", 1)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    # depth, the two layer lists (and the group that holds them), the
    # experts held and the vocabulary
    assert set(entry["reduced"]) == {
        "num_hidden_layers", "linear_attn_config", "kda_layers",
        "full_attn_layers", "num_experts", "vocab_size"}
    assert {"throughput", "loss_at_n"} <= {
        m["moves"] for m in listed_for(CELL)}
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    # the control: BERT at 512 with attn left at its default
    flash, full = (_json(f"benchmark/traffic/s512.{a}.json")
                   for a in ("flash", "full"))
    assert full == {**flash, "attn": "full"}
    control = next(w for w in manifest["workloads"]
                   if w["name"] == "bert-base.s512.full")
    assert (control["config"], control["traffic"], control["chips"]) == (
        "bert-base", "s512.full", 1)


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` as published; the cuts and only
    the cuts differ; 602,434,432 parameters."""
    config = _json("benchmark/configs/kimi-linear-48b-a3b.json")
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: config[k] for k in published} == published
    assert config["linear_attn_config"] == {
        "full_attn_layers": [4], "head_dim": 128, "kda_layers": [1, 2, 3, 5],
        "num_heads": 32, "short_conv_kernel_size": 4}
    was = config["published"]
    assert (was["num_hidden_layers"], was["num_experts"],
            was["vocab_size"]) == (27, 256, 163840)
    assert len(was["linear_attn_config"]["kda_layers"]) == 20
    assert was["linear_attn_config"]["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["router_width"], config["expert_start"],
            config["vocab_size"], config["gate_low_rank"]) == (
                5, 8, 256, 0, 20480, 128)
    assert len(config["reduced"]) == 4 and len(config["assumed"]) >= 8
    cfg = kimi_linear.KimiLinearConfig.from_dict(config)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.held,
            cfg.num_expert_layers) == (32, 128, (0, 8), 4)
    shapes = jax.eval_shape(lambda k: kimi_linear.init_params(k, cfg),
                            jax.random.key(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    bias = int(np.prod(kimi_linear.init_expert_bias(cfg).shape))
    assert (count, bias, count + bias) == (602_433_408, 1024, 602_434_432)
    mixer = {kind: sum(int(np.prod(x.shape)) for x in
                       jax.tree_util.tree_leaves(shapes[layer][kind]))
             for layer, kind in (("layer0", "kda"), ("layer3", "attn"))}
    assert mixer == {"kda": 39_514_272, "attn": 29_114_880}
    # the operations from shapes, at the cell's sizes
    tokens = 8192
    live = 4 * tokens * 8 / 32
    flops = kimi_step.step_flops(config, tokens, 8192, live)
    assert flops == pytest.approx(19.3e12, rel=0.02)
    rule_flops, rule_bytes = kimi_step.kda_core_cost(1, 8192, 32, 128, 128,
                                                     64, 4)
    per_chunk_head = 2 * 64 * 64 * 128 + 64 * 64 * 256 \
        + 6 * 64 * 128 * 128 + 64 * 64 * 128
    assert rule_flops == 3 * per_chunk_head * 4 * 32 * 128
    assert rule_bytes == 4 * 8192 * 32 * (3 * (3 * 128 * 2 + 4 * 128 + 4)
                                          + 2 * 128 * 2)
    # the latent attention's kernel: every query head with K and V of its
    # own, keys 192 wide and values 128, over the triangle with its diagonal
    pairs = flash.seen_pairs(8192)
    kernel_flops, kernel_bytes = flash.cost(1, 32, 32, 8192, 192, 128, 1,
                                            pairs)
    assert kernel_flops == 32 * 2 * pairs * (5 * 192 + 4 * 128)
    assert 2 * pairs == 8192 * 8193
    assert kernel_bytes == 32 * 8192 * (
        (2 * 192 * 2 + 2 * 128 * 2 + 4)
        + (2 * 192 * 2 + 2 * 128 * 2 + 8 + (192 + 128) * 2)
        + (2 * 192 * 2 + 2 * 128 * 2 + 8 + 192 * 2))


@pytest.mark.parametrize("change", [
    {"mla_use_nope": False}, {"q_lora_rank": 1536}, {"num_expert_group": 8},
    {"topk_group": 4}, {"moe_router_activation_func": "softmax"},
    {"tie_word_embeddings": True}, {"num_key_value_heads": 1},
    {"linear_attn_config": {**SIZES["linear_attn_config"],
                            "kda_layers": [1, 2, 3]}},
    {"linear_attn_config": {**SIZES["linear_attn_config"],
                            "full_attn_layers": [4, 5]}}],
    ids=lambda c: next(iter(c)))
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        kimi_linear.KimiLinearConfig.from_dict({**SIZES, **change})


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json("benchmark/configs/kimi-linear-48b-a3b.json")
    traffic = _json("benchmark/traffic/s8192.b1.zipf.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        kimi_step.build(config, {**traffic, "pool": 16}, 1, 0)


def test_the_cells_program_is_the_one_before_the_latent_block_moved():
    """The loss ``KVStore.make_step`` differentiates, value and gradient, at
    the cell's shapes (the configuration's file, [1, 8192] tokens, the flash
    kernel as the chip compiles it) traces to the jaxpr it gave at commit
    7b3f956, when ``mla_block`` stood in ``models/kimi_linear.py``: the block's
    two options and the three scopes around its parts leave Kimi-Linear's
    program as it was (a scope's name is in no equation).

    Re-pinned by PR 57 (the commit after c7e065a), on purpose: the twelve
    ``conv_silu`` bodies of the four KDA layers (q, k and v: forward, the
    forward again under the mixer's checkpoint, backward) are Mosaic calls
    (``ops/gated_conv.py``). Shown first on that tree with the taps
    alone put back (``gated_conv.path`` answering "plain" and
    ``gated_conv._conv_silu`` given c7e065a's two rules, letter for letter):
    the hash was 7b3f956's, ``c949d15bfbd55590``, so nothing else of the
    program moved.

    Re-pinned by PR 58 to the program the chip traces, under
    ``jax.default_device("tpu")``: until then the hash (``7a8428adbec9c2e9``)
    was of a program no machine runs, flash and the taps through Mosaic and
    the grouped matmuls and the KDA rule interpreted, the CPU's answer.
    Shown first that it is d251cd7's: on that tree with ``jax.devices``
    answering a TPU the same trace gave this hash (``CHANGES.md``, PR 58).

    Re-pinned by PR 62, on purpose: the four forward KDA kernels take their
    step's four inverses together, six [64, 64] x [64, 128] products a chain
    (``kda_mosaic._inverses``; 706 ``dot_general`` in the text where 722
    were). Shown first on that tree with the forward kernel calling
    ``_chunk`` a head at a time and ``_inverses`` given fc665e2's
    ``_inverse`` body for each matrix: the hash was fc665e2's,
    ``ed78106ae5358e69``, so nothing else of the program moved (the
    backward kernels call ``_chunk`` a head at a time as they did)."""
    import hashlib
    import re

    config = _json("benchmark/configs/kimi-linear-48b-a3b.json")
    cfg = kimi_linear.KimiLinearConfig.from_dict(config)
    params = jax.eval_shape(lambda k: kimi_linear.init_params(k, cfg),
                            jax.random.key(0))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    bias = jax.eval_shape(lambda: kimi_linear.init_expert_bias(cfg))
    loss = kimi_linear.make_loss_fn(cfg, attn="flash")
    with jax.default_device("tpu"):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(
            params, {"inputs": ids, "targets": ids}, bias)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert "interpret=True" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "03ef5e42e82a1c48"
