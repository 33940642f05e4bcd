"""Ouro (``ps_tpu/models/ouro.py``: a stack of layers run ``total_ut_steps``
times on the same weights under sandwich norms, a readout and an exit gate
after every pass, a loss weighted by the exit distribution) against its plain
reference (``benchmark/families/ouro_reference.py``: a Python loop over the
passes and the layers), at small sizes on the CPU with seeded weights; the
shared weight's gradient as the sum of its uses'; then the configuration, the
cell and the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to 3e-5 of their
largest entry (seen: 2.2e-6). The weights are scaled up from the cell's 0.02
so that every layer application moves the loss by far more than that.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import ouro_reference as reference
from benchmark.families import ouro_step
from jaxpr_tools import checkpoint_names, flash_calls, primitives
from ps_tpu.models import ouro
from ps_tpu.ops.flash_attention import KEPT

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "ouro-2.6b.s8192.b1.zipf"
CONFIG = "benchmark/configs/ouro-2.6b.json"
#: the cell's stack in small: three layers run four times, 4 heads of 16 on
#: 4 K/V heads, a 96-wide SwiGLU
SIZES = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=3,
    layer_types=["full_attention"] * 3, num_attention_heads=4,
    num_key_value_heads=4, head_dim=16, total_ut_steps=4, rope_theta=1e6,
    rms_norm_eps=1e-6, rope_scaling=None, sliding_window=None,
    use_sliding_window=False, tie_word_embeddings=False, hidden_act="silu",
    exit_entropy_beta=0.05, dtype="float32")


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def _setup(passes=4, seed=0, batch=2, seq=128):
    sizes = {**SIZES, "total_ut_steps": passes}
    cfg = ouro.OuroConfig.from_dict(sizes)
    params = jax.jit(lambda k: ouro.init_params(k, cfg))(jax.random.key(seed))
    # away from the cell's 0.02: every application then matters to the loss
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"],
                       size=(batch, seq + 1)).astype(np.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


@functools.lru_cache(maxsize=None)
def _plain(passes=4):
    """``((loss, aux), grads)`` of the reference."""
    sizes, _, params, batch = _setup(passes)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, sizes), has_aux=True))(
                params)


@functools.lru_cache(maxsize=None)
def _system(form="scan", attn="full", passes=4):
    _, cfg, params, batch = _setup(passes)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            ouro.make_loss_fn(cfg, attn=attn, passes=form), has_aux=True))(
                params, batch)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _assert_grads_close(grads, ref_grads, tol=3 * F32_TOL):
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(ref_grads)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(r))) > 0, jax.tree_util.keystr(path)
        assert _rel(g, r) <= tol, (jax.tree_util.keystr(path), _rel(g, r))


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("form,attn", [("scan", "full"), ("unroll", "full"),
                                       ("scan", "flash")])
def test_system_matches_reference(form, attn):
    """The objective, the five ``aux`` entries and every leaf's gradient
    (each the sum of four uses), the passes scanned and unrolled, through
    XLA's attention and through the flash kernel in interpret mode."""
    (ref_loss, ref_aux), ref_grads = _plain()
    (loss, aux), grads = _system(form, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    assert set(aux) == set(ref_aux) == {
        "ce", "ce_pass", "exit_mass", "exit_entropy", "expected_passes"}
    for name, want in ref_aux.items():
        assert aux[name].shape == want.shape == (
            (4,) if name in ("ce_pass", "exit_mass") else ()), name
        assert _rel(aux[name], want) <= F32_TOL, name
    assert float(loss) == pytest.approx(
        float(aux["ce"]) - 0.05 * float(aux["exit_entropy"]), rel=1e-6)
    _assert_grads_close(grads, ref_grads)


def test_scanned_and_unrolled_passes_agree():
    """The two forms of the passes are one mathematics: the same loss, the
    same gradients to f32 roundoff, whichever the chip runs."""
    (scan_loss, _), scan_grads = _system("scan")
    (loop_loss, _), loop_grads = _system("unroll")
    assert abs(float(scan_loss) - float(loop_loss)) <= 1e-6 * float(loop_loss)
    _assert_grads_close(scan_grads, loop_grads, tol=F32_TOL)
    _, cfg, params, batch = _setup()
    # the readouts' one map over blocks, behind the passes, is a scan of
    # its own in both forms
    for form, scans in (("scan", 2), ("unroll", 1)):
        names = primitives(jax.make_jaxpr(
            ouro.make_loss_fn(cfg, passes=form))(params, batch).jaxpr)
        assert names.count("scan") == scans, form
    with pytest.raises(ValueError, match="passes"):
        ouro.make_loss_fn(cfg, passes="fori")(params, batch)


def test_untied_copies_gradients_sum_to_the_shared_weights():
    """The reference on four **untied** copies of the weights, pass ``t``
    reading copy ``t``: the four copies' gradients, summed, are the shared
    weight's gradient as the system gives it (the scan sums the cotangents in
    its carry); a copy alone is not (the last pass's share of a layer's q is
    under two thirds of the whole)."""
    sizes, _, params, batch = _setup()
    with jax.default_matmul_precision("highest"):
        untied = jax.jit(jax.grad(
            lambda copies: reference.loss_fn(copies, batch, sizes)[0]))(
                [params] * 4)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *untied)
    _, grads = _system("scan")
    _assert_grads_close(grads, summed)
    # the embedding is read once, by pass 1; every other leaf by all four
    assert float(jnp.max(jnp.abs(untied[1]["embed"]["tokens"]))) == 0
    q = [np.asarray(c["layer0"]["attn"]["q"]["kernel"]) for c in untied]
    whole = np.asarray(grads["layer0"]["attn"]["q"]["kernel"])
    assert all(np.linalg.norm(g) > 0 for g in q)
    assert np.linalg.norm(q[-1]) < 0.67 * np.linalg.norm(whole)


def test_one_pass_is_a_plain_decoder():
    """``total_ut_steps`` 1: ``p_1 = 1``, ``H = 0``, the loss is the mean
    cross entropy of the one readout, as the reference's pieces give it for a
    decoder that runs its stack once."""
    sizes, cfg, params, batch = _setup(passes=1)
    (loss, aux), grads = _system("scan", passes=1)
    with jax.default_matmul_precision("highest"):
        def plain(p):
            (h,) = reference.passes(p, batch["inputs"], sizes)
            return jnp.mean(reference.position_nll(
                p["head"]["kernel"], h.reshape(-1, h.shape[-1]),
                batch["targets"].reshape(-1)))
        want, want_grads = jax.jit(jax.value_and_grad(plain))(params)
    assert abs(float(loss) - float(want)) <= F32_TOL * float(want)
    assert float(loss) == float(aux["ce"]) == float(aux["ce_pass"][0])
    assert aux["exit_mass"].tolist() == [1.0]
    assert float(aux["exit_entropy"]) == 0 and float(
        aux["expected_passes"]) == 1
    # the gate is read by nothing: no gradient reaches it
    assert float(jnp.max(jnp.abs(grads["gate"]["kernel"]))) == 0
    for part in ("embed", "head", "final_norm", "layer2"):
        _assert_grads_close(grads[part], want_grads[part])


@pytest.mark.parametrize("passes", [1, 2, 4, 7])
def test_exit_distribution_sums_to_one_and_its_entropy_is_bounded(passes):
    """Whatever the gates, the last among them: ``sum_t p_t = 1`` (the last
    pass takes what is left), ``0 <= H(p) <= log T``, gates of one half give
    (1/2, 1/4, .., 2^-(T-1), 2^-(T-1)), a gate shut or open gives no NaN;
    the system's and the reference's are one distribution."""
    rng = np.random.default_rng(passes)
    lam = jnp.asarray(rng.uniform(size=(passes, 3, 16)), jnp.float32)
    lam = lam.at[0, 0, :4].set(0.0).at[0, 0, 4:8].set(1.0)
    p = ouro.exit_distribution(lam)
    assert p.shape == lam.shape and float(jnp.min(p)) >= 0
    assert float(jnp.max(jnp.abs(jnp.sum(p, 0) - 1))) <= 1e-6
    assert float(jnp.max(jnp.abs(p - reference.exit_distribution(
        lam.reshape(passes, -1)).reshape(lam.shape)))) <= 1e-7
    h = ouro.entropy(p)
    assert bool(jnp.all(jnp.isfinite(h))) and float(jnp.min(h)) >= 0
    assert float(jnp.max(h)) <= np.log(passes) + 1e-6
    assert float(jnp.max(jnp.abs(h - reference.entropy(p)))) <= 1e-7
    half = ouro.exit_distribution(jnp.full((passes, 1), 0.5))[:, 0]
    want = [2.0 ** -(t + 1) for t in range(passes - 1)]
    assert half.tolist() == want + [1 - sum(want)]
    # the last gate is read by nothing
    other = ouro.exit_distribution(lam.at[-1].set(0.123))
    assert passes == 1 or bool(jnp.all(other == p))
    grad = jax.grad(lambda x: jnp.sum(ouro.entropy(ouro.exit_distribution(
        x))))(lam)
    assert bool(jnp.all(jnp.isfinite(grad)))


REFUSED = {"a_window": {"use_sliding_window": True},
           "a_window's_width": {"sliding_window": 4096},
           "a_scaled_rotation": {"rope_scaling": {"rope_type": "yarn"}},
           "a_tied_head": {"tie_word_embeddings": True},
           "another_activation": {"hidden_act": "gelu"},
           "another_kind_of_layer": {
               "layer_types": ["full_attention", "sliding_attention",
                               "full_attention"]},
           "a_layer_without_its_kind": {"num_hidden_layers": 4},
           "no_pass": {"total_ut_steps": 0}}


@pytest.mark.parametrize("change", sorted(REFUSED))
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        ouro.OuroConfig.from_dict({**SIZES, **REFUSED[change]})
    assert ouro.OuroConfig.from_dict(SIZES).total_ut_steps == 4


def test_a_layer_application_keeps_the_flash_residuals_and_nothing_else():
    """The policy of the one ``jax.checkpoint`` around a layer application
    names the flash call's output and logsumexp (``KEPT``) and no product
    (``PRODUCTS_KEPT`` is empty: the arithmetic in the module's docstring is
    for 32 applications), the same in every pass: the traced pass bears
    those names and the gradient's program holds one forward flash call a
    layer and the backward's (one call where a tile spans the 128
    positions), and none again for the recomputation."""
    assert ouro.PRODUCTS_KEPT == () and len(KEPT) == 2
    _, cfg, params, batch = _setup()
    for form, copies in (("scan", 1), ("unroll", 4)):
        grad = jax.make_jaxpr(jax.grad(lambda p: ouro.make_loss_fn(
            cfg, attn="flash", passes=form)(p, batch)[0]))(params).jaxpr
        assert set(KEPT) <= checkpoint_names(grad)
        assert checkpoint_names(grad) == set(KEPT)
        assert flash_calls(grad) == copies * cfg.num_hidden_layers * 2, form


# -- the blocks ------------------------------------------------------------------

def test_the_readouts_read_one_head_a_block_of_positions_at_a_time():
    """Four readouts, one ``head/kernel``, one blocked call behind the
    passes: no [B, S, V] array stands in the traced loss, only [T x B,
    block / T, V] ones (``HEAD_BLOCK`` positions in all), and the head's
    gradient is one sum over the passes and the blocks."""
    _, cfg, params, batch = _setup(seq=256)
    b, s, v = 2, 256, SIZES["vocab_size"]
    try:
        ouro.HEAD_BLOCK, kept = 64, ouro.HEAD_BLOCK
        text = str(jax.make_jaxpr(jax.grad(lambda p: ouro.make_loss_fn(cfg)(
            p, batch)[0]))(params))
    finally:
        ouro.HEAD_BLOCK = kept
    assert f"f32[{4 * b},16,{v}]" in text
    for whole in (f"[{b},{s},{v}]", f"[4,{b},{s},{v}]", f"[{4 * b},{s},{v}]"):
        assert whole not in text, whole


# -- the configuration, the cell, the family ----------------------------------------

def test_the_cell_is_what_issue_63_named(listed_for):
    """One configuration, one cell on one chip under the Nemotron cell's
    traffic file as it stands (``loss_step`` 96: at 48 six seeds spread 2.1%,
    as ISSUE 63 foresaw); of the manifest's lists the cell is in the two that
    read any decoder's facts. What else lists it (the ``ouro.*`` names of
    ``layer_metrics/ouro.py`` among them) is the manifest's to say:
    ``tests/test_phases.py`` holds every name listed for the cell to what
    its rehearsal gives, and the manifest to its 128 names at most."""
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    # the eighteenth cell on the fourteenth configuration; later PRs append
    assert cell == manifest["workloads"][17]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "s8192.b1.zipf.n96", 1)
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == cell["config"]] == [CELL]
    entry = manifest["configs"][13]
    assert (entry["name"], entry["file"]) == ("ouro-2.6b", CONFIG)
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    # the depth, and the list that is as long as the depth (the driver
    # compares every key of the catalog's config that is not listed)
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert {"throughput", "setup_s"} <= {m["moves"] for m in listed_for(CELL)}
    own = [m for m in listed_for(CELL) if "workloads" in m]
    assert {m["name"] for m in own} >= {"step.mfu", "kernel.flash_roofline"}
    assert all(CELL in m["workloads"] for m in own)
    assert len(manifest["workloads"]) >= 18 and len(manifest["configs"]) >= 14


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` as published; the depth, and the
    list of layer kinds that follows it, alone differ; 612,438,017 parameters in the
    store, as ISSUE 63 counted them."""
    config = _json(CONFIG)
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    was = config["published"]
    assert set(was) == {"num_hidden_layers", "layer_types"} == set(
        next(c for c in _json("BENCHMARK.json")["configs"]
             if c["name"] == "ouro-2.6b")["reduced"])
    assert (was["num_hidden_layers"], config["num_hidden_layers"]) == (48, 8)
    assert was["layer_types"] == ["full_attention"] * 48
    assert config["layer_types"] == ["full_attention"] * 8
    assert config["exit_entropy_beta"] == 0.05
    assert len(config["reduced"]) >= 2 and len(config["assumed"]) >= 8
    assert "six pipeline stages" in config["deployment"]
    cfg = ouro.OuroConfig.from_dict(config)
    shapes = jax.eval_shape(lambda k: ouro.init_params(k, cfg),
                            jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == 612_438_017 == config["parameters"]
    assert count(shapes["layer0"]) == 51_388_416
    assert count(shapes["layer0"]["attn"]) == 4 * 4_194_304
    assert count(shapes["layer0"]["ffn"]) == 3 * 11_534_336
    assert count(shapes["embed"]) == count(shapes["head"]) == 100_663_296
    assert count(shapes["gate"]) == 2049 and count(shapes["final_norm"]) == 2048
    # the whole model: 48 layers
    whole = ouro.OuroConfig.from_dict({**config, **was})
    assert count(jax.eval_shape(lambda k: ouro.init_params(k, whole),
                                jax.random.key(0))) == 2_667_974_657
    # the operations from shapes, at the cell's sizes: ISSUE 63's arithmetic
    flops = ouro_step.dense_flops(config, 8192, 8192)
    assert flops == pytest.approx(1.27e14, rel=0.005)
    readouts = 3 * 4 * 2 * 100_663_296 * 8192
    assert readouts / flops == pytest.approx(0.156, abs=0.002)
    rehearsal = ouro.OuroConfig.from_dict({**config, **config["rehearse"]})
    assert count(jax.eval_shape(lambda k: ouro.init_params(k, rehearsal),
                                jax.random.key(0))) \
        == config["rehearse"]["parameters"]


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json(CONFIG)
    traffic = _json("benchmark/traffic/s8192.b1.zipf.n96.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        ouro_step.build(config, {**traffic, "pool": 16}, 1, 0)
    with pytest.raises(ValueError, match="knows no model"):
        ouro_step.build({**config, "model": "granite_h"}, traffic, 1, 0)


def test_reference_in_blocks_as_in_one(monkeypatch):
    """The reference's attention in blocks of query rows and its loss in
    blocks of positions (what lets 8,192 positions fit on the chip) are each
    in one block; its witnesses are its own gradients of those leaves."""
    sizes, _, params, batch = _setup()
    (ref_loss, ref_aux), ref_grads = _plain()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "LOSS_BLOCK", 64)
    names = ("layer1/attn/q/kernel", "layer2/ffn_out_norm/scale",
             "gate/kernel", "head/kernel")
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(lambda p: reference.witness_grads(
            p, batch, sizes, names))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    assert _rel(aux["exit_mass"], ref_aux["exit_mass"]) <= 1e-6
    for name, g in grads.items():
        want = functools.reduce(lambda t, part: t[part], name.split("/"),
                                ref_grads)
        assert _rel(g, want) <= 3 * F32_TOL, name


def _step0_inputs(fault=None):
    """What ``ouro_step.step0_checks`` reads, made by hand: witnesses whose
    gradient is the reference's, halved by the clip, AdamW applied by the
    rule, and an ``aux`` that is the reference's; ``fault`` spoils one
    thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    witnesses = {}
    scale = 0.5
    for name in ouro_step.GRAD_COSINE:
        before = rng.normal(size=(16, 8)) * 0.02
        ref_grad = rng.normal(size=(16, 8))
        grad = ref_grad * scale
        if fault == "direction" and name.endswith("attn_out_norm/scale"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "length" and name == "final_norm/scale":
            grad = grad * 0.25       # one pass's cotangent of four
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = ouro_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("attn/q/kernel"):
            after = ouro_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    want = {"loss": 10.74, "ce": 10.8, "ce_pass": [10.8, 10.8, 10.8, 10.8],
            "exit_mass": [0.5, 0.25, 0.125, 0.125], "exit_entropy": 1.2,
            "expected_passes": 1.875}
    got = dict(want)
    if fault == "mass":              # the last pass took its own gate's share
        got.update(exit_mass=[0.5, 0.25, 0.125, 0.0625],
                   expected_passes=1.625)
    if fault == "objective":         # the entropy added, not taken away
        got["loss"] = 10.86
    return witnesses, 1.3 if fault == "clip" else 1.0, rule, got, want


STEP0_FAULTS = {None: None, "direction": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm",
                "mass": "exit_distribution_matches_reference",
                "objective": "objective_matches_reference"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    checks = ouro_step.step0_checks(*_step0_inputs(fault))["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if fault else set())
    # without an aux (a gradient's case of the checker) three checks stand
    assert len(ouro_step.step0_checks(
        *_step0_inputs(fault)[:3])["checks"]) == 3
