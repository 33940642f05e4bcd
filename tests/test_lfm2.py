"""LFM2-MoE (``ps_tpu/models/lfm2.py``, ``ps_tpu/ops/gated_conv.py``, the
held experts, sigmoid routing and selection bias of ``ps_tpu/ops/moe.py``,
grouped-query heads in ``ps_tpu/ops/flash_attention.py``) against its plain
reference (``benchmark/families/lfm2_reference.py``: a masked loop over the
held experts, whole rows of attention against repeated K/V, three shifted
products for the convolution), at small sizes on the CPU with seeded weights;
then the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to 1e-5 of their
largest entry (seen: under 2e-6). The weights are scaled up from the cell's
0.02 so that every mixer and every expert moves the loss by far more than
that.
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
from benchmark.families import lfm2_reference as reference
from benchmark.families import lfm2_step
from ps_tpu.models import lfm2
from ps_tpu.models.blocks import _full_attention
from ps_tpu.ops import flash_attention, moe
from ps_tpu.ops.gated_conv import gated_short_conv

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
#: the cell's five-layer pattern, an eighth of 16 experts held
SIZES = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=5,
             layer_types=["conv", "full_attention", "conv", "conv", "conv"],
             num_dense_layers=1, num_attention_heads=4,
             num_key_value_heads=2, conv_L_cache=3, router_width=16,
             num_experts=2, expert_start=4, num_experts_per_tok=4,
             norm_topk_prob=True, routed_scaling_factor=1.0,
             use_expert_bias=True, bias_update_rate=1e-3, norm_eps=1e-5,
             rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
             dtype="float32")


def _setup(seed=0, batch=2, seq=128, **changes):
    sizes = {**SIZES, **changes}
    cfg = lfm2.Lfm2Config.from_dict(sizes)
    params = jax.jit(lambda k: lfm2.init_params(k, cfg))(jax.random.key(seed))
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
            lfm2.make_loss_fn(cfg, attn=attn), has_aux=True))(
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


def _assert_grads_close(grads, ref_grads, tol=F32_TOL):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        err = float(jnp.max(jnp.abs(g - r)) / (jnp.max(jnp.abs(r)) + 1e-30))
        assert err <= tol, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, counts, the next bias and every gradient, for the five-layer
    pattern with two of sixteen experts held."""
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


def test_fused_step_matches_reference():
    """Through ``KVStore.make_step(has_aux=True)`` with the bias as the
    step's extra argument: the loss, the aux and, read from AdamW's first
    moment behind a clip that does not bite, every gradient. A batch of
    eight: the test mesh has eight devices along ``data``."""
    sizes, cfg, params, batch, bias = _setup(seed=1, batch=8, seq=64)
    (ref_loss, ref_aux), ref_grads = _plain(sizes, params, batch, bias)
    ps.init(backend="tpu")
    try:
        store = ps.KVStore(optimizer="adamw", learning_rate=1e-3, b1=0.9,
                           b2=0.95, clip_by_global_norm=1e9,
                           placement="replicated")
        store.init(params)
        step = store.make_step(lfm2.make_loss_fn(cfg), has_aux=True)
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
            mu = optax.tree_utils.tree_get(store.optimizer_state(key), "mu")
            err = float(jnp.max(jnp.abs(mu / 0.1 - r))
                        / jnp.max(jnp.abs(r)))
            assert err <= F32_TOL, (key, err)
    finally:
        ps.shutdown()


def _layer(seed=3, tokens=96):
    """One expert layer's weights over all 16 experts, and tokens."""
    sizes = {**SIZES, "num_experts": 16, "expert_start": 0}
    rng = np.random.default_rng(seed)
    d, f, e = 64, 32, 16
    lp = {"router": {"kernel": jnp.asarray(rng.normal(size=(d, e)) * 0.3,
                                           jnp.float32)},
          "gate": jnp.asarray(rng.normal(size=(e, d, f)) * 0.2, jnp.float32),
          "up": jnp.asarray(rng.normal(size=(e, d, f)) * 0.2, jnp.float32),
          "down": jnp.asarray(rng.normal(size=(e, f, d)) * 0.2, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(1, tokens, d)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.1, jnp.float32)
    return sizes, lp, x, bias


def _share(sizes, lp, x, bias, start, count):
    cfg = lfm2.Lfm2Config.from_dict(
        {**sizes, "num_experts": count, "expert_start": start})
    held = {"router": lp["router"],
            **{n: lp[n][start:start + count] for n in ("gate", "up", "down")}}
    with jax.default_matmul_precision("highest"):
        return lfm2.moe_block(held, x, cfg, bias)


def test_the_shares_add_up():
    """The expert layer run eight times, each holding two of the sixteen
    experts, sums to the uncut reference layer; each share equals the
    reference's share; and a token none of whose picks is held gets exactly
    zero from that share."""
    sizes, lp, x, bias = _layer()
    with jax.default_matmul_precision("highest"):
        whole, mask = reference.experts(lp, x[0], bias, sizes)
    total = jnp.zeros_like(whole)
    some_token_got_nothing = False
    for start in range(0, 16, 2):
        out, routing = _share(sizes, lp, x, bias, start, 2)
        with jax.default_matmul_precision("highest"):
            want, _ = reference.experts(
                {"router": lp["router"],
                 **{n: lp[n][start:start + 2]
                    for n in ("gate", "up", "down")}},
                x[0], bias, {**sizes, "num_experts": 2,
                             "expert_start": start})
        np.testing.assert_allclose(out[0], want, atol=2e-6)
        np.testing.assert_array_equal(
            np.asarray(routing.counts), np.asarray(mask.sum(0), np.int32))
        np.testing.assert_array_equal(
            np.asarray(routing.group_sizes),
            np.asarray(mask.sum(0), np.int32)[start:start + 2])
        nothing = ~np.asarray(routing.live).any(axis=-1)
        some_token_got_nothing |= bool(nothing.any())
        assert np.all(np.asarray(out[0])[nothing] == 0.0)
        assert np.all(np.abs(np.asarray(out[0])[~nothing]).max(-1) > 0)
        total = total + out[0]
    assert some_token_got_nothing
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # the whole layer in one piece is the same program path with no mask
    out, routing = _share(sizes, lp, x, bias, 0, 16)
    assert routing.live is None
    np.testing.assert_allclose(out[0], whole, atol=1e-5)


#: enough tokens for two windows where two of sixteen experts are held at
#: four picks: three times an even load in whole tiles is 1,536 rows of the
#: 2,048 pairs the row buffers used to hold
WINDOW_TOKENS = 1024


@pytest.mark.parametrize("boost,windows", [(0.0, 1), (8.0, 2), (-8.0, 1)],
                         ids=["even_load", "overflow", "no_live_row"])
def test_a_share_in_windows_is_the_references_values_and_gradients(boost,
                                                                   windows):
    """The held experts over a window of ``R`` rows against the plain
    masked loop, values and every gradient: at a load the first window holds;
    with every token sent to the held experts, so that a second window runs
    and nothing is dropped; and with no live row at all."""
    sizes, lp, x, bias = _layer(tokens=WINDOW_TOKENS)
    start, count = 4, 2
    bias = bias.at[start:start + count].add(boost)
    share = {**sizes, "num_experts": count, "expert_start": start}
    cfg = lfm2.Lfm2Config.from_dict(share)

    def held(lp):
        return {"router": lp["router"], **{
            n: lp[n][start:start + count] for n in ("gate", "up", "down")}}

    def system(lp, x):
        return lfm2.moe_block(held(lp), x, cfg, bias)

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
            assert live == (2 * WINDOW_TOKENS if boost > 0 else 0)
        else:
            assert 0 < live <= 1536
        np.testing.assert_allclose(out, plain(lp, x), atol=2e-6)
        _assert_grads_close(grads(lambda lp, x: system(lp, x)[0]),
                            grads(plain))


def test_every_expert_held_is_one_window_of_the_whole_buffer():
    """With all sixteen experts held the window is the ``T x k`` pairs: no
    loop, and the output is, to the bit, the whole-buffer gather written out
    here."""
    sizes, lp, x, bias = _layer(tokens=WINDOW_TOKENS)
    with jax.default_matmul_precision("highest"):
        out, routing = _share(sizes, lp, x, bias, 0, 16)
        assert routing.live is None and routing.window is None
        assert moe.num_windows(routing) == 1
        assert moe.window_rows(WINDOW_TOKENS, 4, 16, 16) == 4 * WINDOW_TOKENS
        rows = jnp.take(x[0], routing.order // 4, axis=0)
        rows = moe.expert_ffn(rows, lp["gate"], lp["up"], lp["down"],
                              routing.group_sizes)
        back = jnp.take(rows, routing.inverse, axis=0).reshape(
            WINDOW_TOKENS, 4, -1)
        want = jnp.einsum("tkd,tk->td", back, routing.weights,
                          preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want))


def test_aux_counts_the_windows_each_expert_layer_ran():
    sizes, cfg, params, batch, bias, _ = _base()
    (_, aux), _ = _system(cfg, params, batch, bias)
    np.testing.assert_array_equal(np.asarray(aux["expert_windows"]),
                                  np.ones(cfg.num_expert_layers, np.int32))
    assert aux["expert_windows"].dtype == jnp.int32
    # the cell's shapes: 16,384 tokens, four picks, 8 of 64 held
    assert moe.window_rows(16384, 4, 8, 64) == 24576


def test_renormalisation_runs_over_all_four_picks():
    sizes, lp, x, bias = _layer()
    # four held of four picks: a routing that keeps all of a token's picks
    # (fewer held than picks keeps the held ones, ops/moe.py)
    _, routing = _share(sizes, lp, x, bias, 6, 4)
    scores = jax.nn.sigmoid(jnp.dot(x[0], lp["router"]["kernel"],
                                    precision="highest"))
    picked = jnp.take_along_axis(scores, routing.experts, axis=-1)
    np.testing.assert_allclose(
        routing.weights, picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # held or not, a token's four weights sum to one (less the 1e-6)
    np.testing.assert_allclose(routing.weights.sum(-1), 1.0, atol=1e-5)
    held = np.asarray(routing.live)
    assert 0 < held.sum() < held.size


def test_bias_moves_selection_never_weights_or_gradient():
    sizes, lp, x, _ = _layer()
    tokens = x[0]
    free = moe.route(tokens, lp["router"]["kernel"], 4, renormalize=True,
                     scoring="sigmoid", renorm_eps=1e-6)
    push = jnp.zeros((16,)).at[11].set(10.0)    # expert 11 wins every token
    forced = moe.route(tokens, lp["router"]["kernel"], 4, renormalize=True,
                       scoring="sigmoid", bias=push, renorm_eps=1e-6)
    assert np.all(np.asarray(forced.experts)[:, 0] == 11)
    assert int(forced.counts[11]) == tokens.shape[0] > int(free.counts[11])
    # the weights are the scores' alone: no 10.0 in them
    picked = jnp.take_along_axis(forced.probs, forced.experts, axis=-1)
    np.testing.assert_allclose(
        forced.weights, picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # a bias that changes no pick changes nothing at all
    same = moe.route(tokens, lp["router"]["kernel"], 4, renormalize=True,
                     scoring="sigmoid", bias=jnp.full((16,), 0.25),
                     renorm_eps=1e-6)
    for a, b in zip(free, same):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it carries no gradient, in the model's loss either
    sizes, cfg, params, batch, bias, _ = _base()
    g = jax.grad(lambda b: lfm2.make_loss_fn(cfg)(params, batch, b)[0])(bias)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def test_bias_update_is_the_sign_rule():
    sizes, cfg, params, batch, bias, _ = _base()
    (_, aux), _ = _system(cfg, params, batch, bias)
    counts = np.asarray(aux["expert_tokens"], np.float64)
    want = np.asarray(bias) + np.float32(1e-3) * np.sign(
        counts.mean(-1, keepdims=True) - counts).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(aux["expert_bias"]), want)
    assert {-1.0, 1.0} <= set(np.sign(counts.mean(-1, keepdims=True)
                                      - counts).ravel())
    # from zeros, as the benchmark's check applies it
    np.testing.assert_array_equal(
        np.asarray(moe.balance_bias(jnp.zeros_like(bias),
                                    aux["expert_tokens"], 1e-3)),
        lfm2_step.bias_by_sign_rule(counts, 1e-3))
    # switched off, the bias neither selects nor moves
    off = lfm2.Lfm2Config.from_dict({**sizes, "use_expert_bias": False})
    (_, aux_off), _ = _system(off, params, batch, bias)
    np.testing.assert_array_equal(np.asarray(aux_off["expert_bias"]),
                                  np.asarray(bias))
    (_, aux_zero), _ = _system(cfg, params, batch, jnp.zeros_like(bias))
    np.testing.assert_array_equal(np.asarray(aux_off["expert_tokens"]),
                                  np.asarray(aux_zero["expert_tokens"]))


@pytest.mark.parametrize("causal", [True, False])
def test_grouped_query_flash_equals_attention_on_repeated_kv(causal):
    """Four query heads on two K/V heads through the kernel's index map,
    forward and backward, against plain attention on K and V repeated."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 256, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)

    def plain(q, k, v):
        k, v = (jnp.repeat(t, 2, axis=2) for t in (k, v))
        return _full_attention(q, k, v, causal=causal)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                   atol=2e-5)
        weights = jnp.asarray(rng.normal(size=(2, 256, 4, 32)), jnp.float32)
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * weights),
                              argnums=(0, 1, 2))(q, k, v)
                     for f in (kernel, plain))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)
    with pytest.raises(ValueError, match="must divide"):
        flash_attention(q[:, :, :3], k, v)


def test_conv_mixer_is_causal_and_its_gradient_is_the_formulas():
    rng = np.random.default_rng(1)
    bcx = jnp.asarray(rng.normal(size=(2, 64, 3 * 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    y = gated_short_conv(bcx, w)
    # a change at position t moves no output before t, and moves t
    t = 23
    moved = gated_short_conv(bcx.at[:, t].add(1.0), w)
    assert np.array_equal(np.asarray(y[:, :t]), np.asarray(moved[:, :t]))
    assert np.all(np.any(np.asarray(y[:, t]) != np.asarray(moved[:, t]), -1))
    # three taps: position t + 3 and later do not see t
    assert np.array_equal(np.asarray(y[:, t + 3:]),
                          np.asarray(moved[:, t + 3:]))

    def written_out(bcx, w):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        u = jnp.pad(b * x, ((0, 0), (2, 0), (0, 0)))
        return c * sum(w[:, j] * u[:, j:j + 64] for j in range(3))

    np.testing.assert_allclose(y, written_out(bcx, w), atol=1e-6)
    ct = jnp.asarray(rng.normal(size=y.shape), jnp.float32)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1))(
        bcx, w) for f in (gated_short_conv, written_out))
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=2e-5)
    # bf16 in, bf16 out, the arithmetic in f32
    assert gated_short_conv(bcx.astype(jnp.bfloat16), w).dtype == jnp.bfloat16


def _route_before_pr32(x, router, top_k, renormalize=False):
    """``ops/moe.py::route`` as it stood before held experts, sigmoid scores
    and the bias: what OLMoE's cell must keep running."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    num_experts = probs.shape[-1]
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    picked = jax.nn.one_hot(experts, num_experts, dtype=probs.dtype)
    weights = jnp.einsum("te,tke->tk", probs, picked)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    group_sizes = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
    return (logits, probs, weights, experts.astype(jnp.int32), group_sizes,
            order, inverse)


@pytest.mark.parametrize("renormalize", [False, True])
@pytest.mark.parametrize("held", [None, (0, 16)])
def test_route_with_softmax_and_every_expert_is_bitwise_todays(
        renormalize, held, monkeypatch):
    _, lp, x, _ = _layer()
    got = moe.route(x[0], lp["router"]["kernel"], 4, renormalize, held=held)
    want = _route_before_pr32(x[0], lp["router"]["kernel"], 4, renormalize)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert got.live is None and got.counts is got.group_sizes
    # and the traced program is the same, op for op, but for the four names
    # ``route`` gives since PR 51 (``ROUTE_KEPT``: the identity under no
    # checkpoint)
    named = jax.make_jaxpr(lambda x, r: moe.route(x, r, 4, renormalize,
                                                  held=held)[:7])(
        x[0], lp["router"]["kernel"])
    assert [e.params["name"] for e in named.jaxpr.eqns
            if e.primitive.name == "name"] == list(moe.ROUTE_KEPT)
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    a = jax.make_jaxpr(lambda x, r: moe.route(x, r, 4, renormalize,
                                              held=held)[:7])(
        x[0], lp["router"]["kernel"])
    b = jax.make_jaxpr(lambda x, r: _route_before_pr32(x, r, 4, renormalize))(
        x[0], lp["router"]["kernel"])
    assert str(a) == str(b)


def test_reference_attends_in_query_blocks_as_in_one(monkeypatch):
    """The reference's attention in blocks of query rows (what lets 8,192
    positions fit on the chip) is the attention in one block."""
    sizes, _, params, batch, bias, ((ref_loss, _), ref_grads) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    (loss, _), grads = _plain(sizes, params, batch, bias)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    _assert_grads_close(grads, ref_grads)


def test_witness_grads_are_the_reference_gradients_of_those_leaves():
    sizes, _, params, batch, bias, ((ref_loss, _), ref_grads) = _base()
    assert set(lfm2_step.GRAD_COSINE) == {
        "layer0/conv/in_proj/kernel", "layer2/moe/gate",
        "layer1/moe/router/kernel", "layer1/attn/q/kernel"}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.jit(lambda p: reference.witness_grads(
            p, batch, bias, sizes, lfm2_step.GRAD_COSINE))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for name, g in grads.items():
        want = ref_grads
        for part in name.split("/"):
            want = want[part]
        np.testing.assert_allclose(g, want, atol=1e-6)


def _step0_inputs(fault=None):
    """What ``lfm2_step.step0_checks`` reads, made by hand: two layers of 64
    experts, a witness whose gradient is the reference's, AdamW applied by
    the rule; ``fault`` spoils one thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    pairs, rate = 4096, 1e-3
    counts = rng.multinomial(pairs, np.ones(64) / 64, size=2)
    ref_counts = counts.copy()
    if fault == "routed_elsewhere":
        counts[0, 0] += 60
        counts[0, 1:61] -= 1
    if fault == "dropped":
        counts[1, 5] -= 1
        ref_counts[1, 5] -= 1
    bias = lfm2_step.bias_by_sign_rule(counts, rate)
    if fault == "bias":
        bias[1, 7] += np.float32(1e-3)
    got = {"expert_tokens": counts, "held_tokens": counts[:, :8],
           "expert_bias": bias}
    want = {"expert_tokens": ref_counts, "held_tokens": ref_counts[:, :8]}
    witnesses = {}
    scale = 0.5                      # the clip halved the gradient
    for name in lfm2_step.GRAD_COSINE:
        before = rng.normal(size=(16, 8)) * 0.02
        ref_grad = rng.normal(size=(16, 8))
        grad = ref_grad * scale
        if fault == "direction" and name.endswith("gate"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "length" and name.endswith("router/kernel"):
            grad = grad * 1.05
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = lfm2_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("in_proj/kernel"):
            # the first moment applied without its bias correction
            after = lfm2_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    clipped = 1.3 if fault == "clip" else 1.0
    return got, want, witnesses, clipped, rule, pairs, rate


STEP0_FAULTS = {None: None, "routed_elsewhere": "expert_counts_match_reference",
                "dropped": "no_dropped_tokens",
                "bias": "expert_bias_follows_sign_rule",
                "direction": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    checks = lfm2_step.step0_checks(*_step0_inputs(fault))["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if fault else set())


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_cell_is_what_issue_32_named(listed_for):
    traffic = _json("benchmark/traffic/s8192.zipf.json")
    assert "pool" not in traffic.pop("rehearse")
    assert traffic.pop("loss_step") in lfm2_step.LOSS_STEPS
    for prose in ("pool_why", "loss_step_why"):
        traffic.pop(prose)
    assert traffic == {
        "per_chip_batch": 2, "seq_len": 8192, "attn": "flash",
        "ids": {"kind": "zipf", "s": 1.0}, "input": "direct",
        "pool": "fresh", "block_steps": 4, "warmup_steps": 4,
        "trace_blocks": 2}
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "lfm2-24b-a2b.s8192.zipf")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b", "s8192.zipf", 1)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert set(entry["reduced"]) == {"num_hidden_layers", "layer_types",
                                     "num_dense_layers", "num_experts",
                                     "vocab_size"}
    assert {"throughput", "loss_at_n"} <= {
        m["moves"] for m in listed_for(cell["name"])}
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_configuration_holds_the_published_widths():
    """Every width as the catalog's ``config`` has it; the cuts and only the
    cuts differ; 469M parameters."""
    config = _json("benchmark/configs/lfm2-24b-a2b.json")
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: config[k] for k in published} == published
    assert config["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536, "layer_types": config["published"]["layer_types"]}
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["router_width"],
            config["expert_start"], config["vocab_size"]) == (
                5, 1, 8, 64, 0, 8192)
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert len(config["reduced"]) == 5 and len(config["assumed"]) >= 6
    cfg = lfm2.Lfm2Config.from_dict(config)
    assert (cfg.head_dim, cfg.held, cfg.rope_theta) == (64, (0, 8), 1e6)
    shapes = jax.eval_shape(lambda k: lfm2.init_params(k, cfg),
                            jax.random.key(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == 469_284_992
    # the operations from shapes, at the cell's sizes: ISSUE 32's reckoning
    tokens = 2 * 8192
    flops = lfm2_step.step_flops(config, tokens, 8192, 4 * tokens * 4 / 8)
    assert flops == pytest.approx(20.0e12, rel=0.01)
    assert lfm2_step.conv_gate_bytes(config, tokens) == 4 * 11 * tokens * 4096
    # the attention layer's kernel: two sequences, 32 heads on 8, 64 wide,
    # over the triangle; the forward's two products and its bytes, then the
    # nine of the three calls (what the cell's roofline counts since PR 33)
    pairs = flash.seen_pairs(8192)
    assert pairs == 8192 * 8193 // 2
    kernel_flops, kernel_bytes = flash.cost(2, 32, 8, 8192, 64, 64, 1, pairs,
                                            backward=None)
    assert kernel_flops == 2 * 2 * 32 * 2 * pairs * 64
    assert kernel_bytes == 2 * 2 * 40 * 8192 * 64 * 2 + 4 * 2 * 32 * 8192
    assert flash.cost(2, 32, 8, 8192, 64, 64, 1, pairs)[0] \
        == 9 * kernel_flops / 2


def test_config_refuses_what_the_model_does_not_compute():
    for change in ({"conv_bias": True}, {"tie_word_embeddings": False},
                   {"layer_types": ["conv"] * 4},
                   {"layer_types": ["conv"] * 4 + ["sliding_attention"]},
                   {"rope_parameters": {"rope_type": "yarn",
                                        "rope_theta": 1e6}}):
        with pytest.raises(ValueError):
            lfm2.Lfm2Config.from_dict({**SIZES, **change})
    with pytest.raises(ValueError, match="held experts"):
        moe.route(jnp.zeros((4, 8)), jnp.zeros((8, 16)), 2, held=(12, 8))
    with pytest.raises(ValueError, match="scoring"):
        moe.route(jnp.zeros((4, 8)), jnp.zeros((8, 16)), 2, scoring="tanh")


def test_warm_up_is_linear_from_the_first_step():
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9}
    rate, at_step0 = lfm2_step.learning_rate(dict(rule), 2000)
    assert [float(rate(k)) for k in (0, 1, 999, 1999, 2000, 10**6)] == \
        pytest.approx([2e-7, 4e-7, 2e-4, 4e-4, 4e-4, 4e-4])
    assert at_step0 == {**rule, "learning_rate": pytest.approx(2e-7)}
    assert lfm2_step.learning_rate(dict(rule), 0) == (4e-4, rule)


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json("benchmark/configs/lfm2-24b-a2b.json")
    traffic = _json("benchmark/traffic/s8192.zipf.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        lfm2_step.build(config, {**traffic, "pool": 16}, 1, 0)


def test_forward_tiles_of_the_cells_stay():
    """Keyed on shapes: LFM2's call at 8,192 and head 64 takes the tile
    OLMoE's takes at 4,096 and head 128, BERT's keeps its own."""
    from ps_tpu.ops.flash_attention import forward_tiles

    assert forward_tiles(8192, 64, 2, True) == (1024, 1024)
    assert forward_tiles(4096, 128, 2, True) == (1024, 1024)
    assert forward_tiles(512, 64, 2, False) == (512, 512)
