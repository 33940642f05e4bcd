"""JoyAI-LLM-Flash (``ps_tpu/models/joyai.py``: latent attention with rotated
keys of their own and a compressed q on every layer, sigmoid routing beside a
shared expert, a prediction module for the token after next) against its plain
reference (``benchmark/families/joyai_reference.py``: whole rows under an
explicit causal mask, the rotation as a complex product of the pairs), at
small sizes on the CPU, and the pieces of its benchmark family
(``benchmark/families/joyai_step.py``): the limits of the step-0 checks with
the faults planted that they are there for, the operations from shapes, the
configuration and the cell (its rehearsal and the one decoder reader on a
hand-made result of its scopes are cases of ``tests/test_phases.py``).
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import flash_calls, primitives
from benchmark.families import flash
from benchmark.families import joyai_reference as reference
from benchmark.families import joyai_step
from benchmark.families.moe_step import fresh_batches
from ps_tpu.models import blocks, joyai

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "joyai-llm-flash.s8192.b1.zipf"
CONFIG = "benchmark/configs/joyai-llm-flash.json"
TRAFFIC = "benchmark/traffic/s8192.b1.zipf.n96.json"
#: the cell's stack in small: the dense layer, one expert layer and the
#: module (three blocks: what the CPU compiles in seconds), 4 heads of 16 + 8 from a latent of 32, q from a latent of 48, 4 of
#: 16 experts held (experts 4-7), 4 picks
SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=2,
    num_nextn_predict_layers=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=32e6, rope_interleave=True, rope_scaling=None,
    first_k_dense_replace=1, router_width=16, n_routed_experts=4,
    expert_start=4, num_experts_per_tok=4, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    n_group=1, topk_group=1, moe_layer_freq=1, attention_bias=False,
    hidden_act="silu", tie_word_embeddings=False, rms_norm_eps=1e-6,
    bias_update_rate=1e-3, mtp_loss_weight=0.3, dtype="float32")


def _setup(seed=0, batch=2, seq=128, **changes):
    sizes = {**SIZES, **changes}
    cfg = joyai.JoyaiConfig.from_dict(sizes)
    params = jax.jit(lambda k: joyai.init_params(k, cfg))(
        jax.random.key(seed))
    # away from the cell's 0.02: every layer then matters to the loss
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    ids = next(fresh_batches(batch, seq, sizes["vocab_size"], 1.0, seed))
    # a bias that moves some picks, and differs a layer
    bias = 0.02 * jax.random.normal(
        jax.random.key(seed + 1),
        (cfg.num_expert_layers, sizes["router_width"]), jnp.float32)
    return sizes, cfg, params, ids, bias


def _system(cfg, params, batch, bias, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            joyai.make_loss_fn(cfg, attn=attn), has_aux=True))(
                params, batch, bias)


@functools.lru_cache(maxsize=None)
def _plain_of(sizes):
    config = dict(sizes)
    return jax.jit(jax.value_and_grad(
        lambda p, batch, bias: reference.loss_fn(p, batch, bias, config),
        has_aux=True))


def _plain(sizes, params, batch, bias):
    with jax.default_matmul_precision("highest"):
        return _plain_of(tuple(sorted(sizes.items())))(params, batch, bias)


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


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, its two terms, every layer's counts (the module's the last row)
    and every gradient of the loss ``make_step`` differentiates, the
    embedding's and the head's (each read twice) among them; with 'flash' the
    kernel at keys of 24 and values of 16, forward and backward."""
    sizes, cfg, params, batch, bias, ((ref_loss, ref_aux), ref_grads) = _base()
    (loss, aux), grads = _system(cfg, params, batch, bias, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    for name in ("loss", "ce", "mtp_ce"):
        assert abs(float(aux[name]) - float(ref_aux[name])) \
            <= F32_TOL * float(ref_aux[name]), name
    assert float(aux["loss"]) == pytest.approx(
        float(aux["ce"]) + 0.3 * float(aux["mtp_ce"]), rel=1e-6)
    for name in ("expert_tokens", "held_tokens"):
        np.testing.assert_array_equal(np.asarray(aux[name]),
                                      np.asarray(ref_aux[name]))
    # the main stack's expert layer, then the module's
    assert aux["expert_tokens"].shape == (2, 16)
    assert aux["held_tokens"].shape == (2, 4)
    assert aux["expert_bias"].shape == bias.shape
    assert np.all(np.asarray(aux["expert_tokens"]).sum(-1) == 2 * 128 * 4)
    assert int(aux["dropped_tokens"]) == 0
    assert int(aux["mtp_positions"]) == 2 * 127
    assert int(aux["live_pairs_per_step"]) == int(
        np.asarray(aux["held_tokens"]).sum())
    assert float(aux["held_pair_share"]) == pytest.approx(
        np.asarray(aux["held_tokens"]).sum()
        / np.asarray(aux["expert_tokens"]).sum())
    # every tensor has a gradient that is not nothing
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))
    _assert_grads_close(grads, ref_grads)


def test_fused_step_matches_reference_for_three_steps():
    """Through ``KVStore.make_step(has_aux=True)`` with the bias handed from
    step to step: step 0's loss, aux and, read from AdamW's first moment
    behind a clip that does not bite, every gradient, then AdamW's rule on the
    parameters; steps 1 and 2 against the reference on the store's own
    parameters and the bias the step before gave. A batch of eight: the test
    mesh has eight devices along ``data``."""
    import optax

    import ps_tpu as ps

    sizes, cfg, params, _, _ = _setup(seed=1, batch=8, seq=64)
    stream = fresh_batches(8, 64, sizes["vocab_size"], 1.0, 1)
    rule = dict(learning_rate=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    ps.init(backend="tpu")
    try:
        store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1e9,
                           placement="replicated", **rule)
        store.init(params)
        fused = store.make_step(joyai.make_loss_fn(cfg), has_aux=True)
        bias = joyai.init_expert_bias(cfg)
        for n in range(3):
            batch = next(stream)
            (ref_loss, ref_aux), ref_grads = _plain(
                sizes, jax.device_get(store.params()), batch, bias)
            with jax.default_matmul_precision("highest"):
                loss, _, aux = fused(store.shard_batch(batch), bias)
            assert abs(float(loss) - float(ref_loss)) \
                <= F32_TOL * float(ref_loss), n
            for name in ("ce", "mtp_ce"):
                assert float(aux[name]) == pytest.approx(
                    float(ref_aux[name]), rel=F32_TOL), (n, name)
            counts = np.asarray(aux["expert_tokens"])
            np.testing.assert_array_equal(counts,
                                          np.asarray(ref_aux["expert_tokens"]))
            # the rule of its own, on the step's counts, the module's row too
            want = np.asarray(bias) + np.float32(1e-3) * np.sign(
                counts.mean(-1, keepdims=True) - counts).astype(np.float32)
            np.testing.assert_array_equal(np.asarray(aux["expert_bias"]),
                                          want)
            bias = aux["expert_bias"]
            if n:
                continue
            flat = jax.tree_util.tree_leaves_with_path(ref_grads)
            assert len(flat) == len(store.keys())
            for path, r in flat:
                key = "/".join(p.key for p in path)
                state = store.optimizer_state(key)
                mu = optax.tree_utils.tree_get(state, "mu")
                assert _rel(mu / 0.1, r) <= F32_TOL, key
                before = functools.reduce(lambda t, p: t[p.key], path, params)
                after = joyai_step.adamw_first_step(
                    before, mu, optax.tree_utils.tree_get(state, "nu"),
                    **rule)
                np.testing.assert_allclose(store.pull(key), after, atol=1e-6)
    finally:
        ps.shutdown()


def test_a_layers_checkpoint_keeps_the_flash_residuals_and_the_routing(
        monkeypatch, attn="flash"):
    """With 'flash' the loss's gradient holds two kernel calls a layer, the
    module's among the three (the forward and, one tile spanning these 128
    positions, one backward call), where a ``jax.checkpoint`` without a policy
    holds three, the forward run again for its output and logsumexp, and
    routes every expert layer twice; loss and every gradient are the same
    bits."""
    _, cfg, params, batch, bias = _setup()

    def trace_and_run():
        fn = jax.value_and_grad(joyai.make_loss_fn(cfg, attn=attn),
                                has_aux=True)
        jaxpr = jax.make_jaxpr(fn)(params, batch, bias).jaxpr
        return (flash_calls(jaxpr), primitives(jaxpr).count("top_k"),
                jax.jit(fn)(params, batch, bias))

    calls, picks, ((loss, _), grads) = trace_and_run()
    monkeypatch.setattr(joyai, "_layer", jax.checkpoint(
        joyai._layer.__wrapped__, static_argnums=(3, 4)))
    plain_calls, plain_picks, ((plain_loss, _), plain_grads) = trace_and_run()
    assert calls == 3 * 2 and plain_calls == 3 * 3
    # the policy lists ``ops/moe.py::ROUTE_KEPT`` too: the two expert layers
    # (the main stack's and the module's) route once each, not twice
    assert picks == 2 and plain_picks == 2 * 2
    assert float(loss) == float(plain_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- the rotation --------------------------------------------------------------

def _halves(x, theta):
    """``rotate_half`` written here: the rotation a pair rotation is not."""
    seq, _, dim = x.shape
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float32) / dim)
    angle = np.arange(seq, dtype=np.float32)[:, None] * freq[None]
    cos, sin = (np.concatenate([f(angle)] * 2, -1)[:, None, :]
                for f in (np.cos, np.sin))
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return x * cos + np.concatenate([-x2, x1], -1) * sin


def test_the_rotation_on_pairs_is_the_references_complex_product():
    """``blocks.rope(..., interleaved=True)`` rotates channels ``2j`` and
    ``2j + 1`` against each other by the ``j``-th angle and leaves them where
    they were: the reference's complex product, the complex product written
    with numpy's complex numbers, and not the rotation of halves; without the
    option the call is the rotation of halves it was."""
    x = np.asarray(jax.random.normal(jax.random.key(0), (2, 96, 3, 8)))
    theta = 32e6
    got = np.asarray(blocks.rope(jnp.asarray(x), theta, interleaved=True))
    for b in range(2):
        want = np.asarray(reference.rotate_pairs(jnp.asarray(x[b]), theta))
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)
        z = x[b, ..., 0::2] + 1j * x[b, ..., 1::2]
        freq = theta ** (-np.arange(0, 8, 2) / 8)
        z = z * np.exp(1j * np.arange(96)[:, None, None] * freq)
        np.testing.assert_allclose(got[b, ..., 0::2], z.real, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(got[b, ..., 1::2], z.imag, rtol=1e-4,
                                   atol=1e-5)
        halves = _halves(x[b], theta)
        assert np.max(np.abs(got[b] - halves)) > 0.5
        np.testing.assert_allclose(
            np.asarray(blocks.rope(jnp.asarray(x), theta))[b], halves,
            rtol=1e-5, atol=1e-6)
    # position 0 is not turned, and a rotation keeps a pair's length
    np.testing.assert_array_equal(got[:, 0], x[:, 0])
    np.testing.assert_allclose(
        got[..., 0::2] ** 2 + got[..., 1::2] ** 2,
        x[..., 0::2] ** 2 + x[..., 1::2] ** 2, rtol=1e-4, atol=1e-6)


def test_a_rotation_left_out_or_on_halves_is_another_layer():
    """``mla_block`` against the reference's attention: equal as configured;
    with ``rope_theta`` absent or ``rope_interleave`` false it is another
    function."""
    sizes, cfg, params, batch, _ = _setup(seed=2, batch=1)
    lp = params["layer0"]["attn"]
    x = jax.random.normal(jax.random.key(5), (1, 128, 64), jnp.float32)
    attn_fn = blocks.make_attn_fn("full")
    with jax.default_matmul_precision("highest"):
        want = reference.attention(lp, x[0], sizes)
        got = blocks.mla_block(lp, x, cfg, attn_fn)[0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        for change in ({"rope_theta": None}, {"rope_interleave": False}):
            other = blocks.mla_block(lp, x, dataclasses.replace(cfg, **change),
                                     attn_fn)[0]
            assert _rel(other, want) > 1e-2, change


def test_the_shared_key_channels_are_one_heads_worth_summed_over_heads():
    """``kv_a``'s last ``qk_rope_head_dim`` columns make one head's worth of
    rotated channels that every head reads: the attention call sees the same
    64 (here 8) behind each head's own, and their gradient is the sum of what
    each head's copy would get."""
    _, cfg, params, _, _ = _setup(seed=3, batch=1)
    lp = params["layer1"]["attn"]
    x = jax.random.normal(jax.random.key(7), (1, 128, 64), jnp.float32)
    seen = {}

    def spy(q, k, v, causal=True):
        seen["k"] = k
        return blocks.make_attn_fn("full")(q, k, v, causal=causal)

    with jax.default_matmul_precision("highest"):
        blocks.mla_block(lp, x, cfg, spy)
        k = np.asarray(seen["k"])
        assert k.shape == (1, 128, 4, 24)
        for h in range(1, 4):
            np.testing.assert_array_equal(k[:, :, h, 16:], k[:, :, 0, 16:])
        assert np.max(np.abs(k[:, :, 1, :16] - k[:, :, 0, :16])) > 0.1

        # a kv_a whose shared columns are widened to a copy a head, each
        # head reading its own: the shared columns' gradient is their sum
        def loss(pe_columns):        # [D, heads, pe]
            latent = x @ lp["kv_a"]["kernel"][:, :32]
            kv = (blocks.rms_norm(latent, lp["kv_norm"]["scale"], 1e-6)
                  @ lp["kv_b"]["kernel"]).reshape(1, 128, 4, 32)
            k_pe = blocks.rope(jnp.einsum("bsd,dhp->bshp", x, pe_columns),
                               cfg.rope_theta, interleaved=True)
            q = (blocks.rms_norm(x @ lp["q_a"]["kernel"],
                                 lp["q_norm"]["scale"], 1e-6)
                 @ lp["q_b"]["kernel"]).reshape(1, 128, 4, 24)
            q = jnp.concatenate([q[..., :16], blocks.rope(
                q[..., 16:], cfg.rope_theta, interleaved=True)], -1)
            k = jnp.concatenate([kv[..., :16], k_pe], -1)
            a = blocks.make_attn_fn("full")(q, k, kv[..., 16:])
            return jnp.sum(jnp.sin(a.reshape(1, 128, -1)
                                   @ lp["out"]["kernel"]))

        a_head = jnp.repeat(lp["kv_a"]["kernel"][:, None, 32:], 4, axis=1)
        each = jax.grad(loss)(a_head)                      # [D, heads, pe]
        whole = jax.grad(lambda p: jnp.sum(jnp.sin(blocks.mla_block(
            p, x, cfg, blocks.make_attn_fn("full")))))(lp)
    np.testing.assert_allclose(whole["kv_a"]["kernel"][:, 32:],
                               each.sum(axis=1), rtol=1e-4, atol=1e-6)
    assert float(jnp.max(jnp.abs(each[:, 0] - each[:, 1]))) > 1e-4


# -- the module's shift ----------------------------------------------------------

def test_the_module_reads_the_next_token_and_is_scored_on_the_one_after():
    """Position ``i`` embeds token ``i + 1`` (``targets[i]``) and is scored
    against token ``i + 2`` (``targets[i + 1]``); the last position has no
    token after next: it weighs 0 and, by causality, what it reads moves no
    counted position."""
    sizes, cfg, params, batch, bias = _setup(seed=4, batch=1)
    loss_fn = jax.jit(joyai.make_loss_fn(cfg))
    targets = jnp.asarray(batch["targets"])
    with jax.default_matmul_precision("highest"):
        _, aux = loss_fn(params, batch, bias)
        # by hand: the module's logits at position i against targets[i + 1]
        hidden, _ = joyai.apply(params, batch["inputs"], cfg, bias)
        out, _ = joyai.mtp_block(params, hidden, targets, cfg, bias[-1])
        logp = jax.nn.log_softmax(out @ params["head"]["kernel"], -1)
        nll = -jnp.take_along_axis(logp[:, :-1], targets[:, 1:, None], -1)
        assert float(jnp.mean(nll)) == pytest.approx(float(aux["mtp_ce"]),
                                                     rel=1e-5)
        assert int(aux["mtp_positions"]) == 127
        # the token the last position reads: every other position's output
        # stands, and the loss does not read the last position's
        other = targets.at[:, -1].add(7) % 256
        out_other, _ = joyai.mtp_block(params, hidden, other, cfg, bias[-1])
        np.testing.assert_allclose(out_other[:, :-1], out[:, :-1], rtol=0,
                                   atol=1e-6)
        assert float(jnp.max(jnp.abs(out_other[:, -1] - out[:, -1]))) > 1e-3
        counted = jnp.ones((1, 128)).at[:, -1].set(0.0)
        after_next = jnp.concatenate([targets[:, 1:], targets[:, :1] * 0], 1)
        assert float(joyai.head_ce(params["head"], out_other, after_next,
                                   counted)) == pytest.approx(
            float(aux["mtp_ce"]), rel=1e-6)
        # a token a counted position reads (token 41, at position 40) moves
        # the module's loss and not the main head's logits; as a label it
        # moves both losses
        moved = {**batch, "targets": np.asarray(targets.at[:, 40].add(7)
                                                % 256)}
        _, aux_moved = loss_fn(params, moved, bias)
    assert abs(float(aux_moved["mtp_ce"]) - float(aux["mtp_ce"])) > 1e-4
    assert float(aux_moved["ce"]) != float(aux["ce"])


# -- the share -----------------------------------------------------------------

@pytest.mark.parametrize("which", ["layer1", "mtp"])
def test_the_sixteen_shares_add_up_with_the_rest_counted_once(which):
    """One expert layer (of the main stack, and the module's) run sixteen
    times, each share holding one of the sixteen experts under four picks a
    token: attention, the shared expert and the router (whole on every chip,
    counted once) plus the sixteen shares' routed parts equal the uncut
    reference layer; each share equals the reference's share."""
    sizes, _, params, _, bias = _setup(seed=3, batch=1, n_routed_experts=16,
                                       expert_start=0)
    lp = params["mtp"]["layer"] if which == "mtp" else params[which]
    row = bias[-1] if which == "mtp" else bias[0]
    eps = sizes["rms_norm_eps"]
    x = jax.random.normal(jax.random.key(11), (1, 128, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        cfg = joyai.JoyaiConfig.from_dict(sizes)
        h = x + blocks.mla_block(
            lp["attn"], blocks.rms_norm(x, lp["input_norm"]["scale"], eps),
            cfg, blocks.make_attn_fn("full"))
        u = blocks.rms_norm(h, lp["post_attn_norm"]["scale"], eps)
        shared = blocks.dense_ffn(lp["moe"]["shared"], u)
        routed = jnp.zeros_like(h)
        for start in range(16):
            cut = {**sizes, "n_routed_experts": 1, "expert_start": start}
            held = {**lp["moe"], **{n: lp["moe"][n][start:start + 1]
                                    for n in ("gate", "up", "down")}}
            out, routing = joyai.moe_block(
                held, u, joyai.JoyaiConfig.from_dict(cut), row)
            want, mask = reference.experts(held, u[0], cut, row)
            np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=2e-5)
            assert int(routing.group_sizes.sum()) == int(mask[:, start].sum())
            routed = routed + (out - shared)
        whole, _ = reference.layer(lp, x, sizes, row)
    assert float(jnp.max(jnp.abs(routed))) > 0.05
    np.testing.assert_allclose(h + shared + routed, whole, rtol=1e-5,
                               atol=5e-5)


def test_a_layer_two_of_whose_dominant_experts_are_held_runs_one_window():
    """The window is 4.25 even loads of the held experts
    (``HELD_ROWS_OVER_EVEN``): at the cell's sizes 17,408 rows, which hold
    two experts that every token picks (16,384 pairs) where ``ops/moe.py``'s
    3 (12,288) would open a second window."""
    from ps_tpu.ops import moe

    cfg = joyai.JoyaiConfig.from_dict({**_json(CONFIG)})
    assert joyai.window_rows(cfg, 8192) == 17408
    assert moe.window_rows(8192, 8, 16, 256) == 12288
    assert joyai.window_rows(cfg, 8192) % moe.GROUPED_MATMUL_ROWS == 0
    # never more than the pairs there are
    small = joyai.JoyaiConfig.from_dict(SIZES)
    assert joyai.window_rows(small, 8) == 8 * 4
    # a router that sends every token to experts 4 and 5 among its four
    sizes, cfg, params, batch, _ = _setup(seed=5, batch=1)
    lp = params["layer1"]["moe"]
    router = np.zeros((64, 16), np.float32)
    router[:, 4:8] = 0.0
    bias = np.full((16,), -1.0, np.float32)
    bias[[0, 1, 4, 5]] = 1.0
    u = jax.random.normal(jax.random.key(2), (1, 128, 64), jnp.float32)
    out, routing = joyai.moe_block(
        {**lp, "router": {"kernel": jnp.asarray(router)}}, u, cfg,
        jnp.asarray(bias))
    assert int(routing.group_sizes.sum()) == 2 * 128
    assert routing.window.shape == (min(128 * 4, 512 * -(-4.25 * 128 // 512)),)
    assert int(moe.live_windows(routing)) == 1


# -- the limits, with the faults planted that they are there for ---------------

def _readings(**changes):
    read = {"loss_rel_diff": 1e-5, "ce_rel_diff": 1e-5,
            "mtp_ce_rel_diff": 1e-5,
            "pairs_on_another_expert_than_reference": [300, 500],
            **{f"grad_cosine.{k}": 0.9995 for k in joyai_step.GRAD_COSINE},
            "lengths_apart": 0.03}
    return {**read, **changes}


STEP0_FAULTS = [
    ({}, []),
    ({"loss_rel_diff": 5e-3}, ["loss"]),
    ({"ce_rel_diff": 5e-3}, ["ce"]),
    ({"mtp_ce_rel_diff": 5e-3}, ["mtp_ce"]),
    ({"mtp_ce_rel_diff": float("nan")}, ["mtp_ce"]),
    ({"pairs_on_another_expert_than_reference": [300, 6000]}, ["counts"]),
    ({"grad_cosine.embed/tokens": 0.9}, ["cosine.embed/tokens"]),
    ({"grad_cosine.head/kernel": float("nan")}, ["cosine.head/kernel"]),
    ({"grad_cosine.layer1/attn/kv_a/kernel#pe": 0.8},
     ["cosine.layer1/attn/kv_a/kernel#pe"]),
    ({"lengths_apart": 0.6}, ["lengths"]),
]


@pytest.mark.parametrize("change,missed", STEP0_FAULTS,
                         ids=lambda c: str(c)[:48])
def test_the_limits_name_what_misses_them(change, missed):
    assert joyai_step.fails(_readings(**change), pairs=65536) == missed


def _leaf(tree, name):
    for part in name.partition("#")[0].split("/"):
        tree = tree[part]
    return tree


def _against(sizes, value, aux, grads, ref):
    (ref_value, ref_aux), ref_grads = ref
    pick = {k: joyai_step.of_witness(
        k, lambda key: _leaf(grads, key), sizes)
        for k in joyai_step.GRAD_COSINE}
    want = {k: joyai_step.of_witness(
        k, lambda key: _leaf(ref_grads, key), sizes)
        for k in joyai_step.GRAD_COSINE}
    read = joyai_step.readings(value, aux, pick, ref_value, ref_aux, want)
    return joyai_step.fails(read, pairs=2 * 128 * 4)


def _fed_this_token(block):
    """Position ``i`` reads token ``i`` where token ``i + 1`` is meant."""
    return lambda p, h, t, *a, **kw: block(p, h, jnp.roll(t, 1, axis=1), *a,
                                           **kw)


def _without_shared_head_norm(block):
    """``shared_head.norm`` the identity: the one norm whose scale is
    ``params['mtp']['norm']``'s own (traced) array."""
    norm = joyai.rms_norm

    def without(params, hidden, next_tokens, *a, **kw):
        scale = params["mtp"]["norm"]["scale"]
        joyai.rms_norm = lambda x, s, eps: (x if s is scale
                                            else norm(x, s, eps))
        try:
            return block(params, hidden, next_tokens, *a, **kw)
        finally:
            joyai.rms_norm = norm

    return without


#: a fault: fields of the configuration replaced, or ``mtp_block`` wrapped
PLANTED = {
    "rotation_left_out": {"rope_theta": None},
    "rotation_on_halves": {"rope_interleave": False},
    "second_weight_dropped": {"mtp_loss_weight": 1.0},
    "module_fed_this_token": _fed_this_token,
    "no_shared_head_norm": _without_shared_head_norm,
}


@functools.lru_cache(maxsize=None)
def _four_layers():
    sizes, cfg, params, batch, bias = _setup(seed=6, num_hidden_layers=3)
    return sizes, cfg, params, batch, bias, _plain(sizes, params, batch, bias)


@pytest.mark.parametrize("fault", [None] + sorted(PLANTED))
def test_a_planted_fault_misses_one_of_the_cells_limits(monkeypatch, fault):
    """The system through ``joyai_step.readings`` and ``fails`` against the
    reference, as ``tools/joyai_grad_check.py`` does on the chip: as it is it
    misses nothing; with the rotation left out or on halves, the module fed
    token ``i``, the second term's weight dropped or ``shared_head.norm``
    left out it misses at least one limit. A stack with a third layer: the
    witnesses name ``layer2``."""
    sizes, cfg, params, batch, bias, ref = _four_layers()
    plant = PLANTED.get(fault)
    if isinstance(plant, dict):
        cfg = dataclasses.replace(cfg, **plant)
    elif plant is not None:
        monkeypatch.setattr(joyai, "mtp_block", plant(joyai.mtp_block))
    (value, aux), grads = _system(cfg, params, batch, bias)
    missed = _against(sizes, value, aux, grads, ref)
    assert bool(missed) == (fault is not None), missed


def test_step0_checks_on_the_reference_itself_and_on_a_fault():
    """``step0_checks`` on hand-made moments that are the reference's own
    gradient, clipped: every check holds; with the embedding's gradient
    turned, a pair dropped, the module's term off, a bias row left at zero, or
    the applied parameters rounded to bfloat16, the check that is there for
    it fails."""
    sizes, cfg, params, batch, bias = _setup(seed=6, num_hidden_layers=3)
    zero = jnp.zeros_like(bias)
    (_, ref_aux), ref_grads = _plain(sizes, params, batch, zero)
    flat = {"/".join(p.key for p in path): np.asarray(g) for path, g in
            jax.tree_util.tree_leaves_with_path(ref_grads)}
    before = {"/".join(p.key for p in path): np.asarray(g) for path, g in
              jax.tree_util.tree_leaves_with_path(params)}
    rule = dict(learning_rate=1e-6, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1, clip_by_global_norm=1.0)
    norm = float(np.sqrt(sum(np.vdot(g, g) for g in flat.values())))
    scale = min(1.0, 1.0 / norm)
    counts = np.asarray(ref_aux["expert_tokens"])
    aux = {**jax.device_get(ref_aux), "dropped_tokens": 0,
           "mtp_positions": 2 * 127, "expert_windows": np.ones(3, np.int32),
           "expert_bias": joyai_step.bias_by_sign_rule(counts, 1e-3)}

    def witnesses(turned=None, kept_in=np.float32):
        out = {}
        for name in joyai_step.GRAD_COSINE:
            grad = scale * joyai_step.of_witness(name, flat.get, sizes)
            if name == turned:
                grad = np.roll(grad, 1, axis=0)
            w = {"before": joyai_step.of_witness(name, before.get, sizes),
                 "mu": (1 - rule["b1"]) * grad,
                 "nu": (1 - rule["b2"]) * grad * grad,
                 "reference_grad": joyai_step.of_witness(name, flat.get,
                                                         sizes)}
            after = joyai_step.adamw_first_step(
                w["before"], w["mu"], w["nu"], **rule).astype(np.float32)
            w["after"] = np.asarray(jnp.asarray(after).astype(kept_in).astype(
                jnp.float32))
            out[name] = w
        return out

    pairs = 2 * 128 * 4

    def failed(got=aux, **kw):
        checks = joyai_step.step0_checks(got, aux, witnesses(**kw),
                                         norm * scale, rule, pairs,
                                         1e-3)["checks"]
        return [k for k, ok in checks.items() if not ok]

    assert failed() == []
    assert failed(turned="embed/tokens") == ["gradient_matches_reference"]
    assert failed(kept_in=jnp.bfloat16) == ["adamw_apply_matches_rule"]
    assert failed({**aux, "dropped_tokens": 1}) == ["no_dropped_tokens"]
    assert failed({**aux, "mtp_ce": 1.01 * aux["mtp_ce"]}) \
        == ["loss_and_its_terms_match_reference"]
    still = aux["expert_bias"].copy()
    still[-1] = 0.0      # the module's row not updated
    assert failed({**aux, "expert_bias": still}) \
        == ["expert_bias_follows_sign_rule"]


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_a_layers_calls_are_what_kimis_own_count_gives():
    """``kimi_step.flash_cost`` for its one latent layer is a sixth of
    ``joyai_step.flash_cost`` for six. No cell's facts call Kimi's since
    PR 67 (``flash.cost`` is the one count): the test goes with the
    function."""
    from benchmark.families import kimi_step

    if not hasattr(kimi_step, "flash_cost"):
        pytest.skip("benchmark/families/kimi_step.py has no flash_cost "
                    "any more")
    got = joyai_step.flash_cost(1, 32, 8192, 192, 128, 6)
    one = kimi_step.flash_cost(1, 32, 8192, 192, 128, 1)
    assert got[0] == pytest.approx(6 * one[0]) and got[1] == 6 * one[1]


def test_the_kernels_are_counted_as_kimis_six_calls_a_step():
    """``flash_cost``: ``flash.cost`` at 32 heads with K and V of their own,
    keys 192, values 128, six layers (the module's the sixth), half the
    square."""
    got = joyai_step.flash_cost(1, 32, 8192, 192, 128, 6)
    assert got == flash.cost(1, 32, 32, 8192, 192, 128, 6, 8192 * 8192 / 2)
    # a layer's three calls: five products over the 192-wide keys, four over
    # the 128-wide values, half the square of pairs a head
    assert got[0] / 6 == 32 * 2 * (8192 * 8192 / 2) * (5 * 192 + 4 * 128)
    assert got[0] / 6 == pytest.approx(3.16e12, rel=2e-3)
    assert joyai_step.blocks_of(_json(CONFIG)) == (6, 5)


def test_dense_flops_count_both_head_passes_and_the_latents():
    config = _json(CONFIG)
    seq, d, v = 8192, 2048, 16160
    mixer = 6 * (d * 1536 + 1536 * 32 * 192 + d * 576 + 512 * 32 * 256
                 + 32 * 128 * d) + 3 * 32 * seq * (192 + 128)
    per_token = (6 * mixer + 6 * 3 * d * 7168
                 + 5 * 6 * (d * 256 + 3 * d * 768)
                 + 6 * 2 * d * d + 2 * 6 * d * v)
    assert joyai_step.dense_flops(config, seq, seq) == pytest.approx(
        seq * per_token, rel=1e-12)
    assert joyai_step.pair_flops(config) == 3 * 6 * 2048 * 768
    # one head pass fewer, without the module
    without = joyai_step.dense_flops(
        {**config, "num_nextn_predict_layers": 0}, seq, seq)
    assert joyai_step.dense_flops(config, seq, seq) - without \
        == pytest.approx(seq * (mixer + 6 * (d * 256 + 3 * d * 768)
                                + 6 * 2 * d * d + 6 * d * v), rel=1e-9)
    # about 27 TFLOP of the model's own with a 16th of the pairs held
    whole = joyai_step.step_flops(config, seq, seq, 5 * 4096)
    assert 25e12 < whole < 29e12


# -- the configuration and the cell -------------------------------------------

def test_the_parameter_count_is_the_files():
    """680,439,808 from ``init_params``' shapes at the configuration's sizes,
    and by the parts the file states."""
    config = _json(CONFIG)
    cfg = joyai.JoyaiConfig.from_dict(config)
    shapes = jax.eval_shape(lambda k: joyai.init_params(k, cfg),
                            jax.random.key(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    parts = joyai_step.param_count(config)
    assert count == parts["total"] == 680_439_808
    assert parts["a_latent_attention_mixer"] == 26_347_520
    assert parts["the_dense_layer"] == 70_391_808
    assert parts["an_expert_layer"] == 107_091_968
    assert parts["the_prediction_module"] == 115_486_720
    assert parts["embedding_head_final_norm"] == 2 * 33_095_680 + 2048
    reduced = " ".join(config["reduced"])
    for number in ("680,439,808", "26,347,520", "70,391,808", "107,091,968",
                   "115,486,720", "33,095,680"):
        assert number in reduced, number
    moe = shapes["layer4"]["moe"]
    assert moe["gate"].shape == (16, 2048, 768)
    assert moe["router"]["kernel"].shape == (2048, 256)
    assert shapes["layer0"]["ffn"]["w1"]["kernel"].shape == (2048, 7168)
    assert shapes["mtp"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert shapes["mtp"]["layer"]["attn"]["q_b"]["kernel"].shape \
        == (1536, 32 * 192)
    assert "layer5" not in shapes and "embed" not in shapes["mtp"] \
        and "head" not in shapes["mtp"]
    assert joyai.init_expert_bias(cfg).shape == (5, 256)


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` under its name but the three
    the manifest lists as reduced; what the file assumes is named under
    ``assumed``; the manifest's entries are the ones ISSUE 54 names, at the
    end of their lists, and what of ``per_layer`` lists the cell is one
    of the readers that answer for any decoder."""
    config, manifest = _json(CONFIG), _json("BENCHMARK.json")
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "joyai-llm-flash")
    assert entry["file"] == CONFIG
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert config["source"].startswith(entry["source"])
    for word in ("2405.04434", "2412.19437"):
        assert word in config["source"], word
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert (config["router_width"], config["expert_start"],
            config["mtp_loss_weight"]) == (256, 0, 0.3)
    assumed = " ".join(config["assumed"])
    for word in ("AFTER its final norm", "embedding's half first",
                 "mtp_loss_weight 0.3", "b_e += 1e-3", "AdamW", "8,000 steps",
                 "normal(0, 0.02)", "noaux_tc", "ep_size", "pre-shifted"):
        assert word in assumed, word
    assert "sixteen chips share each layer" in config["deployment"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "joyai-llm-flash",
                    "traffic": "s8192.b1.zipf.n96", "chips": 1,
                    "why": cell["why"]}
    # which lists name the cell is the manifest's to say: a name listed for
    # it is one its rehearsal gives (tests/test_phases.py holds that), and
    # one of a reader that answers for any decoder, never another model's
    assert {m["name"].split(".")[0] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])} <= {
        "decoder", "kernel", "step", "host"}
    # the traffic Kimi-Linear's cell runs, read at step 96 where Kimi's is
    # read at 48 (ISSUE 54: only if n = 48 misses the spread, and it does):
    # the file Nemotron's cell runs, as it stands
    kimi = next(w for w in manifest["workloads"]
                if w["config"] == "kimi-linear-48b-a3b")
    theirs = _json(f"benchmark/traffic/{kimi['traffic']}.json")
    ours = _json(TRAFFIC)
    assert {k for k in ours if ours[k] != theirs.get(k)} \
        == {"loss_step", "loss_step_why"}
    traffic = _json(TRAFFIC)
    assert (traffic["seq_len"], traffic["per_chip_batch"], traffic["attn"],
            traffic["pool"]) == (8192, 1, "flash", "fresh")
    assert traffic["loss_step"] in joyai_step.LOSS_STEPS


@pytest.mark.parametrize("change", [
    {"n_group": 8}, {"topk_group": 4},
    {"rope_scaling": {"type": "yarn", "factor": 40}},
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"scoring_func": "softmax"}, {"num_nextn_predict_layers": 2},
    {"hidden_act": "gelu"}, {"q_lora_rank": None},
    {"num_key_value_heads": 8}, {"moe_layer_freq": 2},
    {"first_k_dense_replace": 3}],
    ids=lambda c: "{}={}".format(*next(iter(c.items())))[:40])
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        joyai.JoyaiConfig.from_dict({**SIZES, **change})


def test_ep_size_is_read_by_nothing():
    assert joyai.JoyaiConfig.from_dict({**SIZES, "ep_size": 16}) \
        == joyai.JoyaiConfig.from_dict({**SIZES, "ep_size": 1})


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config, traffic = _json(CONFIG), _json(TRAFFIC)
    with pytest.raises(ValueError, match="re-uses no batch"):
        joyai_step.build(config, {**traffic, "pool": 16}, 1, 0)
    with pytest.raises(ValueError, match="knows no model"):
        joyai_step.build({**config, "model": "kimi_linear"}, traffic, 1, 0)


def test_the_two_losses_go_to_their_gauges():
    from ps_tpu.obs import default_registry

    assert joyai.observe_losses(jnp.float32(9.5), jnp.float32(9.75)) \
        == (9.5, 9.75)
    text = default_registry().render_prometheus()
    assert "ps_joyai_ce 9.5" in text and "ps_joyai_mtp_ce 9.75" in text
