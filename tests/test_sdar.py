"""SDAR (``ps_tpu/models/sdar.py``: a Qwen3-MoE layer trained by block
diffusion, a clean and a noised copy of every sequence through one stack
under an attention edge a block wide) against its plain reference
(``benchmark/families/sdar_reference.py``, the doubled sequence under one
explicit ``[2 L, 2 L]`` mask), at small sizes on the CPU, and the pieces of
its benchmark family (``benchmark/families/sdar_step.py``): the noising, the
limits of the step-0 checks, the operations from shapes, the configuration
and the cell (its rehearsal and the one decoder reader on a hand-made result
of its scopes are cases of ``tests/test_phases.py``).
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import (flash_calls, recomputed, traced_and_run,
                         weight_products)
from benchmark.families import flash
from benchmark.families import sdar_reference as reference
from benchmark.families import sdar_step
from ps_tpu.models import sdar
from ps_tpu.ops import own_block

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "sdar-30b-a3b.s8192.b1.zipf.bd4"
CONFIG = "benchmark/configs/sdar-30b-a3b.json"
TRAFFIC = "benchmark/traffic/s8192.b1.zipf.bd4.n160.json"
#: the cell's stack in small: three layers, blocks of 4 in 128 positions, 4
#: query heads on 2 K/V heads, 4 of 16 experts held (experts 4-7), 4 picks
SIZES = dict(
    vocab_size=256, hidden_size=64, moe_intermediate_size=32,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, router_width=16, num_experts=4, expert_start=4,
    num_experts_per_tok=4, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=1e6, block_length=4, mask_token_id=255,
    router_aux_loss_coef=1e-3, use_sliding_window=False, sliding_window=None,
    rope_scaling=None, mlp_only_layers=[], decoder_sparse_step=1,
    attention_bias=False, hidden_act="silu", tie_word_embeddings=False,
    noise={"kind": "uniform_per_block", "t_min": 1e-3, "t_max": 1.0},
    dtype="float32")


def _setup(seed=0, batch=2, seq=128, **changes):
    sizes = {**SIZES, **changes}
    cfg = sdar.SdarConfig.from_dict(sizes)
    params = jax.jit(lambda k: sdar.init_params(k, cfg))(jax.random.key(seed))
    # away from the cell's 0.02: every layer then matters to the loss
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    noised = next(sdar_step.noised_batches(batch, seq, sizes,
                                           {"kind": "zipf", "s": 1.0}, seed))
    return sizes, cfg, params, noised


def _system(cfg, params, batch, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            sdar.make_loss_fn(cfg, attn=attn), has_aux=True))(params, batch)


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


def _assert_grads_close(grads, ref_grads, tol=F32_TOL):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert _rel(g, r) <= tol, (jax.tree_util.keystr(path), _rel(g, r))


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, its two terms, counts and every gradient of the loss
    ``make_step`` differentiates, on one noised batch, for the three-layer
    stack with four of sixteen experts held; with 'flash' the edged kernels
    forward and backward, K and V at their own head count, and the merge."""
    sizes, cfg, params, batch, ((ref_loss, ref_aux), ref_grads) = _base()
    (loss, aux), grads = _system(cfg, params, batch, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    for name in ("loss", "ce", "load_balance", "masked_ce"):
        assert abs(float(aux[name]) - float(ref_aux[name])) \
            <= F32_TOL * float(ref_aux[name]), name
    for name in ("expert_tokens", "held_tokens"):
        np.testing.assert_array_equal(np.asarray(aux[name]),
                                      np.asarray(ref_aux[name]))
    assert aux["expert_tokens"].shape == (3, 16)
    assert aux["held_tokens"].shape == (3, 4)
    # both copies of both sequences are routed: 2 x 2 x 128 tokens, 4 picks
    assert np.all(np.asarray(aux["expert_tokens"]).sum(-1) == 2 * 2 * 128 * 4)
    assert int(aux["dropped_tokens"]) == 0
    assert int(aux["masked_positions"]) == int(
        np.count_nonzero(batch["weights"]))
    assert float(aux["held_pair_share"]) == pytest.approx(
        np.asarray(aux["held_tokens"]).sum()
        / np.asarray(aux["expert_tokens"]).sum())
    # every tensor has a gradient that is not nothing
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(ref_grads))
    _assert_grads_close(grads, ref_grads)
    with jax.default_matmul_precision("highest"):
        hidden, *_ = sdar.apply(params, batch["ids"], batch["noised_ids"],
                                cfg, sdar.make_edge_attn(attn))
        logits = sdar.logits_of(params, hidden, cfg)
        want = reference.logits_fn(params, batch["ids"], batch["noised_ids"],
                                   sizes)
    assert logits.shape == (2, 128, 256)
    assert _rel(logits, want) <= F32_TOL


def test_fused_step_matches_reference():
    """Through ``KVStore.make_step(has_aux=True)``: the loss, the aux and,
    read from AdamW's first moment behind a clip that does not bite, every
    gradient; then AdamW's rule on the parameters. A batch of eight: the
    test mesh has eight devices along ``data``."""
    import optax

    import ps_tpu as ps

    sizes, cfg, params, batch = _setup(seed=1, batch=8, seq=64)
    (ref_loss, ref_aux), ref_grads = _plain(sizes, params, batch)
    rule = dict(learning_rate=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    ps.init(backend="tpu")
    try:
        store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1e9,
                           placement="replicated", **rule)
        store.init(params)
        fused = store.make_step(sdar.make_loss_fn(cfg), has_aux=True)
        with jax.default_matmul_precision("highest"):
            loss, _, aux = fused(store.shard_batch(batch))
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
        np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]),
                                      np.asarray(ref_aux["expert_tokens"]))
        flat = jax.tree_util.tree_leaves_with_path(ref_grads)
        assert len(flat) == len(store.keys())
        for path, r in flat:
            key = "/".join(p.key for p in path)
            state = store.optimizer_state(key)
            mu = optax.tree_utils.tree_get(state, "mu")
            assert _rel(mu / 0.1, r) <= F32_TOL, key
            before = functools.reduce(lambda t, p: t[p.key], path, params)
            want = sdar_step.adamw_first_step(
                before, mu, optax.tree_utils.tree_get(state, "nu"), **rule)
            np.testing.assert_allclose(store.pull(key), want, atol=1e-6)
    finally:
        ps.shutdown()


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_a_layers_checkpoint_keeps_the_flash_residuals_and_nothing_else(
        monkeypatch, attn):
    """What a layer's policy lists by name (``flash_attention.KEPT``,
    ``ops/moe.py::ROUTE_KEPT``, ``sdar.PRODUCTS_KEPT``; the test keeps the
    name it had when the first was the whole list) is not made again. With
    'flash' the loss's gradient holds six kernel calls a layer, the two
    forwards and each one's dk / dv and dq, where a ``jax.checkpoint``
    without a policy holds eight. A layer's recomputation holds none of the
    q, k, v and out projections' products nor the router's, no ``top_k`` and
    neither ``argsort`` of the pairs, where the policy-less one holds each
    once (the sort it keeps is ``ops/moe.py::_window_index``'s over a
    window's rows, which bears no name, once for the dispatch and once for
    ``combine``). Loss and every gradient are the same bits, as
    ``tests/test_trinity.py``'s counterpart asks: in f32 on the CPU a kept
    value is the value its recomputation makes, and ``traced_and_run``
    compiles both programs without the fusions that would round them
    apart. (On the chip a kept value is the forward pass's own bf16 array;
    the benchmark's step-0 checks hold that.) Two layers: they are alike,
    and a third buys seconds of compiling and nothing else."""
    _, cfg, params, batch = _setup(num_hidden_layers=2)
    lp = params["layer0"]
    projections = [lp["attn"][n]["kernel"].shape for n in "qkv"] + [
        lp["attn"]["out"]["kernel"].shape, lp["moe"]["router"]["kernel"].shape]

    def trace_and_run():
        jaxpr, out = traced_and_run(jax.value_and_grad(
            sdar.make_loss_fn(cfg, attn=attn), has_aux=True), params, batch)
        again = list(recomputed(jaxpr))
        return (flash_calls(jaxpr), weight_products(again),
                [e.primitive.name for e in again], out)

    calls, products, again, ((loss, _), grads) = trace_and_run()
    monkeypatch.setattr(sdar, "_layer", jax.checkpoint(
        sdar._layer.__wrapped__, static_argnums=(2, 3)))
    plain_calls, plain_products, plain_again, ((plain_loss, _), plain_grads) \
        = trace_and_run()
    layers, flash_on = cfg.num_hidden_layers, attn == "flash"
    assert calls == layers * 6 * flash_on
    assert plain_calls == layers * 8 * flash_on
    assert products == []
    assert sorted(plain_products) == sorted(layers * projections)
    assert (again.count("top_k"), plain_again.count("top_k")) == (0, layers)
    assert (again.count("sort"), plain_again.count("sort")) \
        == (2 * layers, 4 * layers)
    assert float(loss) == float(plain_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


# -- the attention form: two kernel calls and the own-block merge --------------

def _dense_under_the_mask(q, k, v, block):
    """Dense attention of the doubled sequence ``[x~ ; x]`` under the
    reference's explicit ``[2 L, 2 L]`` mask. ``q``, ``k``, ``v`` [2, L, h,
    d], row 0 the clean copy and row 1 the noised one (the model's order):
    returns the same."""
    seq, heads = q.shape[1], q.shape[2]
    group = heads // k.shape[2]
    doubled = [jnp.concatenate([t[1], t[0]], axis=0) for t in (q, k, v)]
    qd, kd, vd = doubled[0], *(jnp.repeat(t, group, axis=1)
                               for t in doubled[1:])
    mask = reference.mask_rows(0, 2 * seq, seq, block)
    assert mask.shape == (2 * seq, 2 * seq) and mask.dtype == bool
    s = jnp.einsum("qhd,khd->hqk", qd, kd) * (q.shape[-1] ** -0.5)
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
    out = jnp.einsum("hqk,khd->qhd", p, vd)
    return jnp.stack([out[seq:], out[:seq]])


def _two_calls_and_the_merge(q, k, v, block, attn):
    fn = sdar.make_edge_attn(attn)
    clean = fn(q[:1], k[:1], v[:1], block, False, False)
    earlier, lse = fn(q[1:], k[:1], v[:1], block, True, True)
    noised = sdar.own_block(q[1:], k[1:], v[1:], earlier, lse, block)
    return jnp.concatenate([clean, noised], axis=0)


@pytest.mark.parametrize("attn", ["full", "flash"])
@pytest.mark.parametrize("block", [4, 32])
def test_two_calls_and_the_merge_match_dense_attention_under_the_mask(
        block, attn):
    """The clean queries' call, the noised queries' strict call with its
    logsumexp and the own-block term merged by it, against one softmax over
    the doubled sequence under the explicit ``[2 L, 2 L]`` mask: the output
    and the gradients of q, k and v of both copies, at blocks of 4 and at a
    block that spans four of a tile's rows of eight. The first block's
    noised queries see no clean key: the merge gives them their own block's
    softmax alone."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(0, 1, (2, 256, heads, 32)), jnp.float32)
               for heads in (4, 2, 2))
    w = jnp.asarray(rng.normal(0, 1, q.shape), jnp.float32)
    # heads of 32 channels: the XLA form (tests/test_own_block.py, the kernels)
    assert own_block.path(q[1:], k[1:], block) == "xla"
    got = _two_calls_and_the_merge(q, k, v, block, attn)
    want = _dense_under_the_mask(q, k, v, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    g_got = jax.grad(lambda *a: jnp.sum(w * _two_calls_and_the_merge(
        *a, block, attn)), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(w * _dense_under_the_mask(
        *a, block)), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=5e-4,
                                   atol=5e-4, err_msg=name)
    # the clean keys' gradient is the sum over both copies' queries, the
    # noised keys' their own block's alone: neither is nothing
    assert all(float(jnp.max(jnp.abs(g_got[1][row]))) > 1e-3
               for row in (0, 1))


def test_the_references_mask_is_its_three_terms():
    """``mask_rows``: block-diagonal on the noised half, offset block-causal
    from noised to clean, block-causal on the clean half, nothing from clean
    to noised; whole or a block of rows at a time."""
    seq, block = 16, 4
    mask = np.asarray(reference.mask_rows(0, 2 * seq, seq, block))
    of = np.arange(seq) // block
    np.testing.assert_array_equal(mask[:seq, :seq],
                                  of[:, None] == of[None, :])
    np.testing.assert_array_equal(mask[:seq, seq:], of[:, None] > of[None, :])
    np.testing.assert_array_equal(mask[seq:, seq:],
                                  of[:, None] >= of[None, :])
    assert not mask[seq:, :seq].any()
    assert mask.any(axis=-1).all()          # every row sees a key
    np.testing.assert_array_equal(
        np.asarray(reference.mask_rows(8, 8, seq, block)), mask[8:16])
    # a clean row sees L (L + B) / 2 pairs in all, a noised one L (L - B) / 2
    # of the clean keys: what the kernels are counted at
    assert mask[seq:].sum() == seq * (seq + block) // 2
    assert mask[:seq, seq:].sum() == seq * (seq - block) // 2
    assert mask[seq:].sum() + mask[:seq, seq:].sum() \
        == sdar_step.seen_pairs(seq, block) == seq * seq


def test_reference_in_blocks_as_in_one(monkeypatch):
    """The reference's query blocks and logit blocks change nothing."""
    sizes, cfg, params, batch, ((want, _), want_grads) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)
    monkeypatch.setattr(reference, "LOGIT_BLOCK", 32)
    (got, _), grads = _plain(sizes, params, batch)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    _assert_grads_close(grads, want_grads, 2e-5)


def test_witness_grads_are_the_reference_gradients_of_those_leaves():
    sizes, cfg, params, batch, ((want, _), want_grads) = _base()
    names = ["layer1/attn/k/kernel", "embed/tokens"]
    with jax.default_matmul_precision("highest"):
        (got, _), grads = jax.jit(lambda p, b: reference.witness_grads(
            p, b, sizes, names))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert sorted(grads) == sorted(names)
    np.testing.assert_allclose(grads["layer1/attn/k/kernel"],
                               want_grads["layer1"]["attn"]["k"]["kernel"],
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(grads["embed/tokens"],
                               want_grads["embed"]["tokens"], rtol=1e-5,
                               atol=1e-7)


# -- the share -----------------------------------------------------------------

def test_the_eight_shares_add_up_with_attention_counted_once():
    """One layer run eight times, each share holding two of the sixteen
    experts under four picks a token: attention (whole on every chip,
    counted once) plus the eight shares' expert parts equal the uncut
    reference layer on the doubled sequence; each share equals the
    reference's share."""
    sizes, _, params, batch = _setup(seed=3, batch=1, router_width=16,
                                     num_experts=16, expert_start=0,
                                     num_hidden_layers=1)
    lp = params["layer0"]
    eps, seq = sizes["rms_norm_eps"], batch["ids"].shape[1]
    both = jnp.concatenate([batch["ids"], batch["noised_ids"]], axis=0)
    x = jnp.take(params["embed"]["tokens"], both, axis=0)    # [2, L, D]
    with jax.default_matmul_precision("highest"):
        cfg = sdar.SdarConfig.from_dict(sizes)
        h = x + sdar.attention_block(
            lp["attn"], sdar.rms_norm(x, lp["input_norm"]["scale"], eps), cfg,
            sdar.make_edge_attn("full"))
        u = sdar.rms_norm(h, lp["post_attn_norm"]["scale"], eps)
        routed = jnp.zeros_like(h)
        for start in range(0, 16, 2):
            cut = {**sizes, "num_experts": 2, "expert_start": start}
            held = {**lp["moe"], **{n: lp["moe"][n][start:start + 2]
                                    for n in ("gate", "up", "down")}}
            out, routing = sdar.moe_block(held, u,
                                          sdar.SdarConfig.from_dict(cut))
            want, mask, _ = reference.experts(held, u.reshape(2 * seq, -1),
                                              cut)
            np.testing.assert_allclose(out.reshape(2 * seq, -1), want,
                                       rtol=1e-5, atol=2e-5)
            assert int(routing.group_sizes.sum()) == int(
                mask[:, start:start + 2].sum())
            routed = routed + out
        # the uncut reference layer, on [x~ ; x]
        doubled = jnp.concatenate([x[1], x[0]], axis=0)
        r = doubled + reference.attention(
            lp["attn"], reference.rms_norm(doubled,
                                           lp["input_norm"]["scale"], eps),
            sizes)
        whole, _, _ = reference.experts(
            lp["moe"], reference.rms_norm(r, lp["post_attn_norm"]["scale"],
                                          eps), sizes)
        want = r + whole
    got = h + routed
    assert float(jnp.max(jnp.abs(routed))) > 0.05
    np.testing.assert_allclose(jnp.concatenate([got[1], got[0]], axis=0),
                               want, rtol=1e-5, atol=5e-5)


def test_routing_is_softmax_top_k_renormalised_over_all_picks():
    """A token's weights are its picks' softmax probabilities over their
    sum, over all picks whether held or not: a share's weights sum to less
    than one where a pick is absent."""
    sizes, cfg, params, batch = _setup(seed=4, batch=1, num_hidden_layers=1)
    u = jnp.asarray(np.random.default_rng(4).normal(
        0, 1, (2, 128, 64)), jnp.float32)
    _, routing = sdar.moe_block(params["layer0"]["moe"], u, cfg)
    probs = jax.nn.softmax(
        u.reshape(256, 64) @ params["layer0"]["moe"]["router"]["kernel"], -1)
    top, _ = jax.lax.top_k(probs, 4)
    held = np.asarray(routing.live)
    sums = np.asarray(jnp.sum(jnp.where(routing.live, routing.weights, 0.0),
                              axis=-1))
    assert np.all(sums <= 1 + 1e-6) and np.any(sums < 0.99)
    np.testing.assert_allclose(
        np.sort(np.asarray(routing.weights), axis=-1),
        np.sort(np.asarray(top / top.sum(-1, keepdims=True)), axis=-1),
        rtol=1e-5)
    assert held.any(axis=-1).sum() < 256      # some tokens hold nothing here


def test_a_layer_three_of_whose_dominant_experts_are_held_runs_one_window():
    """Every token picks the same four experts and three of them are among
    the four held of sixteen: three even loads, which fill ``ops/moe.py``'s
    window of three to the row so that any straggler opens a second one;
    SDAR's window (``HELD_ROWS_OVER_EVEN`` 4.25) holds them in one, and the
    layer still equals the reference's. At the cell's shapes the window is
    69,632 rows where ``ops/moe.py``'s is 49,152."""
    from ps_tpu.ops import moe

    sizes, cfg, params, _ = _setup(seed=5, batch=1, num_hidden_layers=1)
    lp = params["layer0"]["moe"]
    rng = np.random.default_rng(5)
    # a common direction that experts 4, 5, 6 (held) and 1 (absent) like
    common = jnp.asarray(rng.normal(0, 1, 64), jnp.float32)
    liked = jnp.zeros(16).at[jnp.array([1, 4, 5, 6])].set(1.0)
    lp = {**lp, "router": {"kernel": lp["router"]["kernel"]
                           + jnp.outer(common, liked) / 8}}
    u = common + 0.3 * jnp.asarray(rng.normal(0, 1, (2, 512, 64)),
                                   jnp.float32)
    tokens = 1024
    with jax.default_matmul_precision("highest"):
        out, routing = sdar.moe_block(lp, u, cfg)
        want, mask, _ = reference.experts(lp, u.reshape(tokens, 64), sizes)
    even = tokens * 4 * 4 // 16
    held = int(routing.group_sizes.sum())
    assert 3 * even - 16 <= held <= 4 * even
    assert moe.window_rows(tokens, 4, 4, 16) == 3 * even
    assert routing.window.shape[0] == sdar.window_rows(cfg, tokens) \
        == 4 * even                 # 4.25 even loads, capped at the pairs
    assert int(moe.live_windows(routing)) == 1
    np.testing.assert_allclose(out.reshape(tokens, 64), want, rtol=1e-5,
                               atol=2e-5)
    cell = sdar.SdarConfig.from_dict(_json(CONFIG))
    assert sdar.window_rows(cell, 2 * 8192) == 69632 == 4.25 * 16384
    assert moe.window_rows(2 * 8192, 8, 16, 128) == 49152


# -- the noising ---------------------------------------------------------------

def _draws(seed, n=16, batch=1, seq=2048, **changes):
    stream = sdar_step.noised_batches(batch, seq, {**SIZES, **changes},
                                      {"kind": "zipf", "s": 1.0}, seed)
    return [next(stream) for _ in range(n)]


def test_the_noising_is_a_function_of_the_seed():
    a, b, other = _draws(11, n=3), _draws(11, n=3), _draws(12, n=3)
    for x, y, z in zip(a, b, other):
        for name in ("ids", "noised_ids", "weights"):
            np.testing.assert_array_equal(x[name], y[name])
        assert not np.array_equal(x["ids"], z["ids"])
        assert not np.array_equal(x["weights"], z["weights"])
    # no batch and no noise draw twice in a run
    assert not np.array_equal(a[0]["ids"], a[1]["ids"])
    assert not np.array_equal(a[0]["weights"], a[1]["weights"])
    # a large seed, as the driver's
    big = _draws(2 ** 31 + 12345, n=1)[0]
    assert big["ids"].dtype == np.int32 and big["weights"].dtype == np.float32


@pytest.mark.parametrize("block", [4, 16])
def test_the_noised_copy_carries_the_mask_id_where_the_weight_is(block):
    """The mask id is never drawn as a token; the noised ids are the ids but
    at the masked positions; a weight is 0 off them and ``1 / t_b`` on them,
    one level a block; about half the positions are masked; the mean weight
    over all positions is near 1 (a position is masked with probability
    ``t_b`` and then weighs ``1 / t_b``)."""
    draws = _draws(13, block_length=block)
    ids, noised, weights = (np.concatenate([d[k] for d in draws])
                            for k in ("ids", "noised_ids", "weights"))
    mask_id = SIZES["mask_token_id"]
    assert ids.min() >= 0 and ids.max() < mask_id
    masked = noised == mask_id
    np.testing.assert_array_equal(noised[~masked], ids[~masked])
    np.testing.assert_array_equal(weights > 0, masked)
    by_block = weights.reshape(weights.shape[0], -1, block)
    level = by_block.max(axis=-1, keepdims=True)
    assert np.all((by_block == 0) | (by_block == level))
    assert level.max() <= 1 / SIZES["noise"]["t_min"] * (1 + 1e-6)
    assert level[level > 0].min() >= 1 - 1e-6
    assert 0.45 < masked.mean() < 0.55
    assert abs(weights.mean() - 1) < 0.1
    # the program's gauge beside aux's masked_positions: the last batch's
    from ps_tpu.obs import default_registry
    assert default_registry().snapshot()["ps_sdar_masked_share"] \
        == pytest.approx(np.count_nonzero(draws[-1]["weights"])
                         / draws[-1]["weights"].size)


def test_an_unknown_schedule_and_a_ragged_sequence_are_refused():
    with pytest.raises(ValueError, match="noise schedule"):
        next(sdar_step.noised_batches(
            1, 128, {**SIZES, "noise": {"kind": "cosine"}},
            {"kind": "zipf", "s": 1.0}, 0))
    _, cfg, params, batch = _setup(seq=128)
    with pytest.raises(ValueError, match="whole number of blocks"):
        sdar.apply(params, batch["ids"][:, :126], batch["noised_ids"][:, :126],
                   cfg)


# -- the limits, the counts from shapes ---------------------------------------

def _readings(**changes):
    read = {"masked_ce_rel_diff": 1e-5, "loss_rel_diff": 1e-5,
            "ce_rel_diff": 1e-5,
            "load_balance_rel_diff": 1e-4,
            "pairs_on_another_expert_than_reference": [300, 500],
            **{f"grad_cosine.{k}": 0.999 for k in sdar_step.GRAD_COSINE},
            "lengths_apart": 0.03}
    return {**read, **changes}


STEP0_FAULTS = [
    ({}, []),
    ({"masked_ce_rel_diff": 5e-4}, ["masked_ce"]),
    ({"loss_rel_diff": 5e-4}, ["loss"]),
    ({"ce_rel_diff": 5e-4}, ["ce"]),
    ({"load_balance_rel_diff": 1e-2}, ["load_balance"]),
    ({"pairs_on_another_expert_than_reference": [300, 6000]}, ["counts"]),
    ({"grad_cosine.layer1/attn/k/kernel": 0.98},
     ["cosine.layer1/attn/k/kernel"]),
    ({"grad_cosine.embed/tokens#mask": float("nan")},
     ["cosine.embed/tokens#mask"]),
    ({"grad_cosine.layer2/moe/router/kernel": 0.85},
     ["cosine.layer2/moe/router/kernel"]),
    ({"lengths_apart": 0.3}, ["lengths"]),
]


@pytest.mark.parametrize("change,missed", STEP0_FAULTS,
                         ids=lambda c: str(c)[:48])
def test_the_limits_name_what_misses_them(change, missed):
    assert sdar_step.fails(_readings(**change), pairs=131072) == missed


def test_step0_checks_on_the_reference_itself_and_on_a_fault():
    """``step0_checks`` on hand-made moments that are the reference's own
    gradient, clipped: every check holds; with the k projection's gradient
    turned, or a pair dropped, the check that is there for it fails."""
    sizes, cfg, params, batch, ((_, ref_aux), ref_grads) = _base()
    rows = sdar_step.witness_rows(batch, sizes)
    flat = {"/".join(p.key for p in path): np.asarray(g) for path, g in
            jax.tree_util.tree_leaves_with_path(ref_grads)}
    before = {"/".join(p.key for p in path): np.asarray(g) for path, g in
              jax.tree_util.tree_leaves_with_path(params)}
    rule = dict(learning_rate=1e-6, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1, clip_by_global_norm=1.0)
    norm = float(np.sqrt(sum(np.vdot(g, g) for g in flat.values())))
    scale = min(1.0, 1.0 / norm)
    aux = {**jax.device_get(ref_aux), "dropped_tokens": 0,
           "masked_positions": int(np.count_nonzero(batch["weights"]))}

    def witnesses(turned=None):
        out = {}
        for name in sdar_step.GRAD_COSINE:
            grad = scale * sdar_step.of_witness(name, flat.get, rows)
            if name == turned:
                grad = np.roll(grad, 1, axis=0)
            w = {"before": sdar_step.of_witness(name, before.get, rows),
                 "mu": (1 - rule["b1"]) * grad,
                 "nu": (1 - rule["b2"]) * grad * grad,
                 "reference_grad": sdar_step.of_witness(name, flat.get, rows)}
            w["after"] = sdar_step.adamw_first_step(
                w["before"], w["mu"], w["nu"], **rule).astype(np.float32)
            out[name] = w
        return out

    pairs = 2 * 2 * 128 * 4
    good = sdar_step.step0_checks(aux, aux, witnesses(), norm * scale, rule,
                                  pairs)
    assert all(good["checks"].values()), good["checks"]
    assert good["detail"]["clip_scale"] == pytest.approx(scale)
    turned = sdar_step.step0_checks(
        aux, aux, witnesses("layer1/attn/k/kernel"), norm * scale, rule,
        pairs)
    assert [k for k, ok in turned["checks"].items() if not ok] \
        == ["gradient_matches_reference"]
    dropped = {**aux, "dropped_tokens": 1}
    assert not sdar_step.step0_checks(
        dropped, aux, witnesses(), norm * scale, rule,
        pairs)["checks"]["no_dropped_tokens"]


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_the_kernels_are_counted_at_l_squared_pairs_a_head():
    """``flash_cost``: ISSUE 50's ``flash.cost(batch=2, heads=32, kv_heads=4,
    seq=8192, 128, 128, layers, pairs=8192 * 8192 // 2)``; a layer's
    kernels 4.95 TFLOP; the causal call over the doubled sequence would
    count twice that."""
    assert sdar_step.seen_pairs(8192, 4) == 8192 * 8192
    got = sdar_step.flash_cost(1, 32, 4, 8192, 128, 6, 4)
    assert got == flash.cost(2, 32, 4, 8192, 128, 128, 6, 8192 * 8192 // 2)
    assert got[0] == 6 * 32 * 2 * 8192 * 8192 * 9 * 128
    assert got[0] / 6 == pytest.approx(4.95e12, rel=2e-3)
    doubled, _ = flash.cost(1, 32, 4, 16384, 128, 128, 6,
                            flash.seen_pairs(16384))
    assert doubled / got[0] == pytest.approx(2.0, rel=1e-3)


def test_dense_flops_count_the_step_as_the_program_runs_it():
    config = _json(CONFIG)
    layers, seq = 6, 8192
    per_token = 6 * 2048 * (4096 * 2 + 512 * 2 + 128)
    cores = 3 * 4 * 32 * 128 * (seq * seq + seq * 4)
    head = 6 * 2048 * 18992 * seq
    want = layers * (2 * seq * per_token + cores) + head
    assert sdar_step.dense_flops(config, 1, seq) == pytest.approx(want,
                                                                  rel=1e-12)
    assert sdar_step.pair_flops(config) == 3 * 6 * 2048 * 768
    # about 36 TFLOP of the model's own with an eighth of the pairs held
    whole = sdar_step.step_flops(config, 1, seq, 6 * 16384)
    assert 33e12 < whole < 38e12


# -- the configuration and the cell -------------------------------------------

def test_the_parameter_count_is_the_files():
    """645,623,296 from ``init_params``' shapes at the configuration's
    sizes, and by the parts the file states."""
    config = _json(CONFIG)
    cfg = sdar.SdarConfig.from_dict(config)
    shapes = jax.eval_shape(lambda k: sdar.init_params(k, cfg),
                            jax.random.key(0))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    parts = sdar_step.param_count(config)
    assert count == parts["total"] == 645_623_296
    assert parts["a_layer_beside_its_experts"] == 19_140_864
    assert parts["a_layers_held_experts"] == 75_497_472
    assert parts["embedding_head_final_norm"] == 77_793_280
    assert "645,623,296" in " ".join(config["reduced"])
    moe = shapes["layer5"]["moe"]
    assert moe["gate"].shape == (16, 2048, 768)
    assert moe["router"]["kernel"].shape == (2048, 128)
    assert "layer6" not in shapes


def test_configuration_holds_the_published_widths():
    """Every number of the catalog's ``config`` under its key but the three
    the manifest lists as reduced; what the file assumes is named under
    ``assumed``; the manifest's entries are the ones ISSUE 50 names."""
    config, manifest = _json(CONFIG), _json("BENCHMARK.json")
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    entry = next(c for c in manifest["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["file"] == CONFIG
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] in config["source"] and len(config["source"]) < 200
    assert "Block Diffusion" in config["source"]
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["mask_token_id"] == config["vocab_size"] - 1
    assert (config["block_length"], config["router_width"],
            config["expert_start"]) == (4, 128, 0)
    assumed = " ".join(config["assumed"])
    for word in ("block_length 4", "t_b", "mask_token_id 18991", "no shift",
                 "two-copy", "router_aux_loss_coef 0.001", "AdamW"):
        assert word in assumed, word
    assert "eight chips share each layer" in config["deployment"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "sdar-30b-a3b",
                    "traffic": "s8192.b1.zipf.bd4.n160", "chips": 1,
                    "why": cell["why"]}
    traffic = _json(TRAFFIC)
    assert (traffic["seq_len"], traffic["per_chip_batch"],
            traffic["block_length"], traffic["pool"]) == (8192, 1, 4, "fresh")
    assert traffic["loss_step"] == int(cell["traffic"].rpartition(".n")[2])
    assert traffic["loss_step"] in sdar_step.LOSS_STEPS


@pytest.mark.parametrize("change", [
    {"use_sliding_window": True}, {"sliding_window": 4096},
    {"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
    {"mlp_only_layers": [0]}, {"decoder_sparse_step": 2},
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"hidden_act": "gelu"}, {"block_length": 48}, {"mask_token_id": 256}],
    ids=lambda c: "{}={}".format(*next(iter(c.items())))[:40])
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        sdar.SdarConfig.from_dict({**SIZES, **change})


def test_family_refuses_a_pool_it_would_have_to_cycle_and_another_block():
    config, traffic = _json(CONFIG), _json(TRAFFIC)
    with pytest.raises(ValueError, match="re-uses no batch"):
        sdar_step.build(config, {**traffic, "pool": 16}, 1, 0)
    with pytest.raises(ValueError, match="blocks of 8"):
        sdar_step.build(config, {**traffic, "block_length": 8}, 1, 0)
