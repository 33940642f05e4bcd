"""OLMoE (``ps_tpu/models/olmoe.py``, ``ps_tpu/ops/moe.py``) against its
plain reference (``benchmark/families/olmoe_reference.py``: every expert on
every token under a 0/1 mask, full attention, no sort, no ``ragged_dot``), at
small sizes on the CPU with seeded weights; then the family's pieces: the
checks that the benchmark's ``correct`` holds after step 0, and the stream
that never repeats a batch.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums (sorted groups against a masked loop over experts, a blockwise
softmax against a whole one): losses agree to a few f32 roundoffs, gradients
to 1e-5 of their norm (seen: 2e-7 to 6e-7). ``F32_TOL`` leaves ten times
that and is four orders under what any missing piece moves (the pieces are
knocked out one by one below). In bf16 the loss, a mean over 128 tokens and
512 logits each, differs by 1.6e-6 to 3.9e-6 over three seeds (``BF16_TOL``
2**-14 leaves 15 times that), the gradients by 0.4% to 1.5% of their norm, up
to 10% on the router's where one top-2 pick flips between bf16 and f32
activations (seen: 0 or 1 of 512 pairs).
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from benchmark.families import moe_step, olmoe_reference as reference
from ps_tpu.models import olmoe
from ps_tpu.ops import moe

F32_TOL = 1e-5
BF16_TOL = 2.0 ** -14
SIZES = dict(vocab_size=512, hidden_size=64, intermediate_size=32,
             num_hidden_layers=2, num_attention_heads=4, num_experts=8,
             num_experts_per_tok=2, norm_topk_prob=False, rms_norm_eps=1e-5,
             rope_theta=10000.0, load_balance_coef=0.01, z_loss_coef=0.001,
             dtype="float32")


def _setup(seed=0, batch=2, seq=64, **changes):
    sizes = {**SIZES, **changes}
    cfg = olmoe.OlmoeConfig.from_dict(sizes)
    params = jax.jit(lambda k: olmoe.init_params(k, cfg))(
        jax.random.key(seed))
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], size=(batch, seq + 1)).astype(np.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


def _system(cfg, params, batch, attn="full"):
    return jax.jit(jax.value_and_grad(
        olmoe.make_loss_fn(cfg, attn=attn), has_aux=True))(params, batch)


def _plain(sizes, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p, b: reference.loss_fn(p, b, sizes), has_aux=True))(
            params, batch)


@functools.lru_cache(maxsize=None)
def _base():
    """The default sizes' setup with both sides' results, computed once;
    nothing here may be changed in place."""
    sizes, cfg, params, batch = _setup()
    return (sizes, cfg, params, batch, _system(cfg, params, batch),
            _plain(sizes, params, batch))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _worst(grads, ref_grads):
    return max(_rel(g, r) for g, r in zip(
        jax.tree_util.tree_leaves(grads),
        jax.tree_util.tree_leaves(ref_grads)))


def _assert_same(got, want, tol=F32_TOL):
    (loss, aux), grads = got
    (ref_loss, ref_aux), ref_grads = want
    assert abs(float(loss) - float(ref_loss)) <= tol * abs(float(ref_loss))
    for term in ("ce", "load_balance", "z_loss"):
        assert float(aux[term]) == pytest.approx(float(ref_aux[term]),
                                                 rel=tol), term
    np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]),
                                  np.asarray(ref_aux["expert_tokens"]))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), ref_g in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert _rel(g, ref_g) <= tol, jax.tree_util.keystr(path)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """Loss, each of its three terms, the per-expert token counts and every
    gradient; 'flash' needs a sequence that its 128-wide blocks divide."""
    if attn == "full":
        sizes, cfg, params, batch, got, want = _base()
    else:
        sizes, cfg, params, batch = _setup(seq=128)
        got = _system(cfg, params, batch, attn)
        want = _plain(sizes, params, batch)
    _assert_same(got, want)
    counts = np.asarray(got[0][1]["expert_tokens"])
    # both layers' pairs, none dropped, spread over all eight experts
    assert counts.sum() == 2 * batch["inputs"].size * 2
    assert (counts > 0).all()


CASES = {
    # a router of zeros ties every probability and top_k takes the first
    # index: all tokens on expert 0, seven groups of size 0
    "one_expert": dict(num_experts_per_tok=1),
    "renormalised": dict(norm_topk_prob=True),
    "bf16": dict(dtype="bfloat16"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_case(case):
    sizes, cfg, params, batch = _setup(**CASES[case])
    if case == "one_expert":
        for i in range(sizes["num_hidden_layers"]):
            router = params[f"layer{i}"]["moe"]["router"]
            router["kernel"] = jnp.zeros_like(router["kernel"])
    got = _system(cfg, params, batch)
    want = _plain({**sizes, "dtype": "float32"}, params, batch)
    if case == "bf16":
        assert abs(float(got[0][0]) - float(want[0][0])) \
            <= BF16_TOL * float(want[0][0])
        rels = [_rel(g, r) for g, r in zip(
            jax.tree_util.tree_leaves(got[1]),
            jax.tree_util.tree_leaves(want[1]))]
        # bf16 is not f32: the f32 tolerance would have caught it
        assert 100 * F32_TOL < min(rels) and max(rels) < 0.2
        return
    _assert_same(got, want)
    counts = np.asarray(got[0][1]["expert_tokens"])
    if case == "one_expert":
        pairs = sizes["num_hidden_layers"] * batch["inputs"].size
        assert counts.tolist() == [pairs] + [0] * 7
        assert float(got[0][1]["load_balance"]) == pytest.approx(
            sizes["num_hidden_layers"] * 1.0)
    if case == "renormalised":
        # the published model does NOT renormalise: the two must differ
        assert _worst(got[1], _base()[4][1]) > 1000 * F32_TOL


def _no_rope(monkeypatch):
    monkeypatch.setattr(olmoe, "rope", lambda x, theta: x)


KNOCK_OUTS = {
    "rope": _no_rope,
    "load_balance": lambda mp: dict(load_balance_coef=0.0),
    "z_loss": lambda mp: dict(z_loss_coef=0.0),
}


@pytest.mark.parametrize("piece", sorted(KNOCK_OUTS))
def test_system_without_a_piece_fails_the_reference(piece, monkeypatch):
    """Each piece of the published block moves some gradient by a thousand
    times the tolerance of ``test_system_matches_reference`` (the loss of
    random weights is a blunt witness: it moves by 1e-4 to 3e-3), so that
    test fails if the piece is removed from the system."""
    sizes, _, params, batch, _, ((ref_loss, _), ref_grads) = _base()
    changes = KNOCK_OUTS[piece](monkeypatch) or {}
    cfg = olmoe.OlmoeConfig.from_dict({**sizes, **changes})
    (loss, _), grads = _system(cfg, params, batch)
    assert abs(float(loss) - float(ref_loss)) \
        > 5 * F32_TOL * abs(float(ref_loss)), piece
    assert _worst(grads, ref_grads) > 1000 * F32_TOL, piece


def test_every_expert_held_is_one_window_of_the_whole_buffer():
    """OLMoE holds every expert: its routing has no window to run in turns,
    and the layer's output is, to the bit, the whole-buffer gather written
    out here."""
    _, cfg, params, _, _, _ = _base()
    lp = params["layer0"]["moe"]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 64, 64)),
                    jnp.float32)
    out, routing = olmoe.moe_block(lp, x, cfg)
    assert routing.live is None and routing.window is None
    assert moe.num_windows(routing) == 1
    assert int(moe.live_windows(routing)) == 1
    tokens = x.reshape(128, 64)
    rows = jnp.take(tokens, routing.order // 2, axis=0)
    rows = moe.expert_ffn(rows, lp["gate"], lp["up"], lp["down"],
                          routing.group_sizes)
    back = jnp.take(rows, routing.inverse, axis=0).reshape(128, 2, 64)
    want = jnp.einsum("tkd,tk->td", back, routing.weights,
                      preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out).reshape(128, 64),
                                  np.asarray(want))


@pytest.mark.parametrize("cell,shapes,rows", [
    ("olmoe-1b-7b.s4096.zipf", (8192, 8, 64, 64), 65536),
    ("lfm2-24b-a2b.s8192.zipf", (16384, 4, 8, 64), 24576),
    ("kimi-linear-48b-a3b.s8192.b1.zipf", (8192, 8, 8, 256), 6144),
    ("nemotron-3-super-120b-a12b.s8192.b1.zipf", (8192, 22, 8, 512), 8704)])
def test_window_rows_at_the_cells_shapes(cell, shapes, rows):
    """``R`` from (tokens, picks, held, router width): three times an even
    load in whole tiles of 512 rows; with every expert held, every pair."""
    assert moe.window_rows(*shapes) == rows
    tokens, top_k, held, _ = shapes
    assert rows % moe.GROUPED_MATMUL_ROWS == 0
    assert rows <= tokens * min(top_k, held)


def test_qk_norm_makes_the_scale_of_q_immaterial():
    """RMSNorm over the whole q projection follows it, so a q kernel five
    times as large gives the same loss (but for eps); without the QK-norm
    the attention scores would be five times as large."""
    loss = _base()[4][0][0]
    sizes, cfg, params, batch = _setup()
    for i in range(sizes["num_hidden_layers"]):
        q = params[f"layer{i}"]["attn"]["q"]
        q["kernel"] = 5.0 * q["kernel"]
    (scaled, _), _ = _system(cfg, params, batch)
    assert float(scaled) == pytest.approx(float(loss), rel=1e-4)
    (ref_scaled, _), _ = _plain(sizes, params, batch)
    assert float(ref_scaled) == pytest.approx(float(loss), rel=1e-4)


def _adamw_by_hand(params, grads, m, v, t, *, learning_rate, b1, b2, eps,
                   weight_decay, clip_by_global_norm):
    """One AdamW step behind a global-norm clip, written from the rule."""
    leaves = jax.tree_util.tree_leaves(grads)
    norm = np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                       for g in leaves))
    scale = min(1.0, clip_by_global_norm / norm)
    out_p, out_m, out_v = {}, {}, {}
    for k in params:
        g = np.asarray(grads[k], np.float64) * scale
        out_m[k] = b1 * m[k] + (1 - b1) * g
        out_v[k] = b2 * v[k] + (1 - b2) * g * g
        m_hat = out_m[k] / (1 - b1 ** t)
        v_hat = out_v[k] / (1 - b2 ** t)
        out_p[k] = params[k] - learning_rate * (
            m_hat / (np.sqrt(v_hat) + eps) + weight_decay * params[k])
    return out_p, out_m, out_v


def test_fused_step_with_aux_matches_hand_applied_adamw():
    """``ps.init`` -> ``KVStore(adamw)`` -> ``make_step(has_aux=True)`` ->
    ``shard_batch`` for three steps, against three steps of the plain
    reference's gradients applied by hand. The mesh has 8 devices."""
    rule = dict(learning_rate=1e-2, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1, clip_by_global_norm=1.0)
    sizes, cfg, params, _ = _setup(num_hidden_layers=1)
    ids = np.random.default_rng(1).integers(
        0, sizes["vocab_size"], size=(3, 8, 33)).astype(np.int32)
    batches = [{"inputs": b[:, :-1], "targets": b[:, 1:]} for b in ids]

    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", placement="replicated", **rule)
    store.init(params)
    step = store.make_step(olmoe.make_loss_fn(cfg), has_aux=True)

    flat = {jax.tree_util.keystr(p): np.asarray(x, np.float64) for p, x in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    treedef = jax.tree_util.tree_structure(params)
    m = {k: np.zeros_like(x) for k, x in flat.items()}
    v = {k: np.zeros_like(x) for k, x in flat.items()}
    for t, batch in enumerate(batches, start=1):
        loss, new_params, aux = step(store.shard_batch(batch))
        tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(x, jnp.float32) for x in flat.values()])
        (ref_loss, ref_aux), grads = _plain(sizes, tree, batch)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        assert aux["expert_tokens"].shape == (sizes["num_experts"],)
        assert int(aux["expert_tokens"].sum()) == 2 * batch["inputs"].size
        np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]),
                                      np.asarray(ref_aux["expert_tokens"]))
        gflat = {jax.tree_util.keystr(p): g for p, g in
                 jax.tree_util.tree_flatten_with_path(grads)[0]}
        flat, m, v = _adamw_by_hand(flat, gflat, m, v, t, **rule)
        for (path, x) in jax.tree_util.tree_flatten_with_path(new_params)[0]:
            want = flat[jax.tree_util.keystr(path)]
            # Adam's first steps are lr * sign(g): a gradient entry of
            # either sign within f32 noise of zero moves 2 * lr
            close = np.isclose(np.asarray(x), want, rtol=1e-4, atol=1e-5)
            assert close.mean() > 0.999, jax.tree_util.keystr(path)


def test_witness_grads_are_the_reference_gradients_of_those_leaves():
    sizes, _, params, batch, _, ((ref_loss, _), ref_grads) = _base()
    names = ("layer0/attn/q/kernel", "layer1/moe/router/kernel")
    (loss, aux), grads = jax.jit(lambda p, b: reference.witness_grads(
        p, b, sizes, names))(params, batch)
    assert float(loss) == float(ref_loss)
    assert sorted(grads) == sorted(names)
    np.testing.assert_allclose(
        grads[names[0]], ref_grads["layer0"]["attn"]["q"]["kernel"],
        rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(
        grads[names[1]], ref_grads["layer1"]["moe"]["router"]["kernel"],
        rtol=1e-6, atol=1e-9)


RULE = dict(learning_rate=4e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
            clip_by_global_norm=1.0)


def _step0_inputs(fault=None):
    """What ``moe_step.step0_checks`` reads, made by numpy from the rule: a
    gradient of global norm 5 (its two witnesses and a rest), clipped to 1,
    one AdamW step; ``fault`` breaks one piece as a broken step would."""
    rng = np.random.default_rng(0)
    names = list(moe_step.GRAD_COSINE)
    grads = {n: rng.normal(size=(32, 16)) for n in names + ["rest"]}
    norm = np.sqrt(sum(np.sum(g * g) for g in grads.values()))
    grads = {n: g * 5.0 / norm for n, g in grads.items()}
    aux = {"ce": 10.8, "load_balance": 20.0, "z_loss": 17.0,
           "expert_tokens": np.full(64, 1024)}
    got = dict(aux)
    system = dict(grads)               # what the step differentiated to
    if fault == "other_gradient":      # 8% of another direction: cosine 0.9968
        other = rng.normal(size=(32, 16))
        system[names[0]] = grads[names[0]] + 0.08 * other * (
            np.linalg.norm(grads[names[0]]) / np.linalg.norm(other))
    if fault == "one_gradient_twice_as_long":
        system[names[0]] = 2 * grads[names[0]]
    scale = 1.0 / np.sqrt(sum(np.sum(g * g) for g in system.values()))
    if fault == "no_clip":
        scale = 1.0
    if fault == "clip_per_tensor":
        scale = None
    witnesses, total = {}, 0.0
    for n, g in system.items():
        s = 1.0 / np.linalg.norm(g) if scale is None else scale
        mu = (1 - RULE["b1"]) * s * g
        total += np.sum(mu * mu)
        if n == "rest":
            continue
        before = rng.normal(size=g.shape) * 0.02
        nu = (1 - RULE["b2"]) * (s * g) ** 2
        rule = dict(RULE, weight_decay=0.0) if fault == "no_decay" else RULE
        witnesses[n] = {
            "before": before, "mu": mu, "nu": nu, "reference_grad": grads[n],
            "after": moe_step.adamw_first_step(before, mu, nu, **rule)}
    if fault == "z_loss_off":
        got["z_loss"] = 17.0 * (1 + 2e-3)
    if fault == "dropped":
        got["expert_tokens"] = np.full(64, 1024) - np.eye(64, dtype=int)[0]
    return got, aux, witnesses, float(np.sqrt(total)) / (1 - RULE["b1"])


STEP0_FAULTS = {
    None: None,
    "no_clip": "gradient_clipped_to_global_norm",
    "clip_per_tensor": "gradient_clipped_to_global_norm",
    "no_decay": "adamw_apply_matches_rule",
    "other_gradient": "gradient_matches_reference",
    "one_gradient_twice_as_long": "gradient_matches_reference",
    "z_loss_off": "loss_terms_match_reference",
    "dropped": "no_dropped_tokens",
}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    """The benchmark's ``correct`` after step 0: sound inputs pass every
    check, and each broken piece fails the check that names it."""
    checks = moe_step.step0_checks(*_step0_inputs(fault), RULE,
                                    64 * 1024)["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    if fault is None:
        assert not failed
    elif fault == "clip_per_tensor":   # the witnesses' scales differ too
        assert STEP0_FAULTS[fault] in failed
    else:
        assert failed == {STEP0_FAULTS[fault]}


def test_cell_traffic_is_what_issue_28_named(listed_for):
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark/traffic/s4096.zipf.json")
    with open(path) as f:
        traffic = json.load(f)
    assert "pool" not in traffic.pop("rehearse")
    assert traffic.pop("loss_step") in moe_step.LOSS_STEPS
    for prose in ("pool_why", "loss_step_why"):
        traffic.pop(prose)
    assert traffic == {
        "per_chip_batch": 2, "seq_len": 4096, "attn": "flash",
        "ids": {"kind": "zipf", "s": 1.0}, "input": "direct",
        "pool": "fresh", "block_steps": 4, "warmup_steps": 4,
        "trace_blocks": 2}
    assert {"throughput", "loss_at_n"} <= {
        m["moves"] for m in listed_for("olmoe-1b-7b.s4096.zipf")}


@pytest.mark.parametrize("seed", [7, 2**31 + 99])
def test_fresh_stream_never_repeats_a_batch(seed):
    """The cell's stream under ``"pool": "fresh"``: the same for the same
    seed, another for another, no batch twice over three draws of the
    generator and more, targets the inputs shifted by one, and every draw
    over the same permutation of the ids (the most frequent id stays the
    most frequent)."""
    shape = (2, 512, 64, 1.0)
    count = 3 * moe_step.DRAWN_AT_A_TIME + 2
    stream = list(itertools.islice(moe_step.fresh_batches(*shape, seed),
                                   count))
    again = list(itertools.islice(moe_step.fresh_batches(*shape, seed), 3))
    other = next(moe_step.fresh_batches(*shape, seed + 1))
    for b, a in zip(stream, again):
        assert np.array_equal(b["inputs"], a["inputs"])
        assert np.array_equal(b["targets"], a["targets"])
    assert not np.array_equal(stream[0]["inputs"], other["inputs"])
    assert stream[0]["inputs"].shape == (2, 512)
    assert stream[0]["inputs"].dtype == np.int32
    for b in stream:
        assert np.array_equal(b["inputs"][:, 1:], b["targets"][:, :-1])
    # no batch twice, and no sequence of any batch twice
    rows = {(b["inputs"].tobytes(), b["targets"].tobytes()) for b in stream}
    assert len(rows) == count
    sequences = {row.tobytes() for b in stream for row in b["inputs"]}
    assert len(sequences) == 2 * count
    tops = {np.bincount(np.stack([b["inputs"] for b in part]).ravel(),
                        minlength=64).argmax()
            for part in (stream[:16], stream[16:32], stream[32:])}
    assert len(tops) == 1


def test_family_refuses_a_pool_it_would_have_to_cycle():
    with pytest.raises(ValueError, match="re-uses no batch"):
        moe_step.build({"model": "olmoe"},
                       {"ids": {"kind": "zipf"}, "input": "direct",
                        "pool": 16}, 1, 0)


def test_config_refuses_what_the_model_does_not_compute():
    with pytest.raises(ValueError, match="hidden_act"):
        olmoe.OlmoeConfig.from_dict({**SIZES, "hidden_act": "gelu"})
    with pytest.raises(ValueError, match="multi-head"):
        olmoe.OlmoeConfig.from_dict({**SIZES, "num_key_value_heads": 2})
