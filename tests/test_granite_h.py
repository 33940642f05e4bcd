"""Granite-4.0-H (``ps_tpu/models/granite_h.py``; the Mamba-2 mixer it shares
with Nemotron-H, ``ps_tpu/models/blocks.py::mamba_block``; the chunked scan of
``ps_tpu/ops/ssd.py`` over blocks of heads) against its plain reference
(``benchmark/families/granite_h_reference.py``: the scan token by token, whole
rows of attention times ``attention_multiplier``), at small sizes on the CPU
with seeded weights; the four multipliers and the gate's place each caught;
then the configuration, the cell and the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to 3e-5 of their
largest entry (seen: 1.4e-5, on an ``A_log`` whose eight entries are sums of
terms near 1e-6; the matrices' under 5e-6). The weights are scaled up from
the cell's 0.02 so that every mixer, the attention's scale and every
multiplier move the loss by far more than that.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import granite_h_reference as reference
from benchmark.families import granite_h_step
from benchmark.families.nemotron_h_step import ssd_cost
from ps_tpu.models import granite_h
from ps_tpu.models.blocks import token_ce
from ps_tpu.ops import ssd as ssd_module

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "granite-4.0-h-micro.s8192.b1.zipf"
CONFIG = "benchmark/configs/granite-4.0-h-micro.json"
#: where the one attention layer stands among three Mamba-2 layers
PATTERNS = {
    "first": ("attention", "mamba", "mamba", "mamba"),
    "last": ("mamba", "mamba", "mamba", "attention"),
    "absent": ("mamba", "mamba", "mamba")}
#: the cell's layer in small: 8 Mamba heads of 16 on one B/C group over a
#: state of 16 in chunks of 64 (Nemotron's tests run 32), 4 query heads of 16
#: on 2 K/V heads, a 96-wide SwiGLU, the four published multipliers
SIZES = dict(
    vocab_size=256, hidden_size=64, mamba_n_heads=8, mamba_d_head=16,
    mamba_n_groups=1, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=64,
    mamba_expand=2, num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=96, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=0.015625, logits_scaling=8,
    rms_norm_eps=1e-5, num_local_experts=0, num_experts_per_tok=0,
    position_embedding_type="nope", rope_scaling=None, attention_bias=False,
    mamba_proj_bias=False, mamba_conv_bias=True, tie_word_embeddings=True,
    hidden_act="silu", normalization_function="rmsnorm", dtype="float32")


def _sizes(pattern="last", **changes):
    kinds = PATTERNS[pattern]
    return {**SIZES, "layer_types": list(kinds),
            "num_hidden_layers": len(kinds), **changes}


def _setup(seed=0, batch=2, seq=128, pattern="last", **changes):
    """``(sizes, config, params, batch)``; ``test_kept_names.py`` traces the
    loss's gradient at these."""
    sizes = _sizes(pattern, **changes)
    cfg = granite_h.GraniteHConfig.from_dict(sizes)
    params = jax.jit(lambda k: granite_h.init_params(k, cfg))(
        jax.random.key(seed))
    # away from the cell's 0.02: every part then matters to the loss (the
    # embedding less: it is multiplied by 12 already)
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    params["embed"]["tokens"] = params["embed"]["tokens"] / 5
    rng = np.random.default_rng(seed)
    for i, kind in enumerate(cfg.layer_types):
        if kind == "mamba":   # a filter bias and a norm scale of their own
            mixer = params[f"layer{i}"]["mamba"]
            mixer["conv"]["bias"] = jnp.asarray(0.3 * rng.normal(
                size=mixer["conv"]["bias"].shape), jnp.float32)
            mixer["out_norm"]["scale"] = jnp.asarray(1 + 0.2 * rng.normal(
                size=mixer["out_norm"]["scale"].shape), jnp.float32)
    ids = rng.integers(0, sizes["vocab_size"],
                       size=(batch, seq + 1)).astype(np.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


def _system(cfg, params, batch, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            granite_h.make_loss_fn(cfg, attn=attn)))(params, batch)


def _plain(sizes, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, sizes)))(params)


@functools.lru_cache(maxsize=None)
def _base(pattern="last"):
    sizes, cfg, params, batch = _setup(pattern=pattern)
    return sizes, cfg, params, batch, _plain(sizes, params, batch)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _assert_grads_close(grads, ref_grads, tol=3 * F32_TOL):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(r))) > 0, jax.tree_util.keystr(path)
        assert _rel(g, r) <= tol, (jax.tree_util.keystr(path), _rel(g, r))


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("attn,pattern", [
    ("full", "last"), ("flash", "last"), ("full", "first"),
    ("flash", "first"), ("full", "absent")])
def test_system_matches_reference(attn, pattern):
    """Loss and every gradient (the tied embedding's among them), with the
    attention layer first, last and absent, through XLA's attention and
    through the flash kernel in interpret mode."""
    sizes, cfg, params, batch, (ref_loss, ref_grads) = _base(pattern)
    loss, grads = _system(cfg, params, batch, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(ref_grads)
    _assert_grads_close(grads, ref_grads)


def _norm_before_gate(y, z, scale, groups, eps):
    seq, inner = y.shape
    y = reference.rms_norm(y.reshape(seq, groups, -1),
                           scale.reshape(groups, -1), eps)
    return y.reshape(seq, inner) * jax.nn.silu(z)


@functools.lru_cache(maxsize=None)
def _reference_logits():
    sizes, _, params, batch, _ = _base()
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p: reference.logits_fn(p, batch["inputs"],
                                                     sizes))(params)


def test_logits_match_reference():
    _, cfg, params, batch, _ = _base()
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: granite_h.logits_of(
            p, granite_h.apply(p, batch["inputs"], cfg), cfg))(params)
    assert _rel(logits, _reference_logits()) <= F32_TOL


#: the five faults of ISSUE 56: a constant read as another model's, or the
#: gate's place; each as a change to the reference, which the system then has
#: to be far from
FAULTS = {"residual_multiplier_read_as_1": {"residual_multiplier": 1.0},
          "embedding_not_times_12": {"embedding_multiplier": 1.0},
          "logits_not_divided_by_8": {"logits_scaling": 1.0},
          "attention_scaled_by_an_eighth": {"attention_multiplier": 0.125},
          "norm_before_the_gate": {}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_constant_and_the_gates_place_move_the_logits(fault,
                                                           monkeypatch):
    """The reference with one fault planted lies a thousand tolerances from
    the logits the system matched: no multiplier and no order of gate and norm
    hides inside ``F32_TOL``. (The loss is blunt here as in the cell: small
    logits give every token nearly the entropy of the vocabulary.)"""
    sizes, _, params, batch, _ = _base()
    want = _reference_logits()
    if fault == "norm_before_the_gate":
        monkeypatch.setattr(reference, "gated_norm", _norm_before_gate)
    with jax.default_matmul_precision("highest"):
        spoiled = jax.jit(lambda p: reference.logits_fn(
            p, batch["inputs"], {**sizes, **FAULTS[fault]}))(params)
    assert _rel(spoiled, want) > 1e3 * F32_TOL


def test_the_tied_embeddings_gradient_is_the_sum_of_both_uses():
    """``embed/tokens`` is read twice a step, by the lookup and, transposed,
    by the head: its gradient is the lookup's plus the head's, each of which
    alone is not nothing."""
    _, cfg, params, batch = _setup()
    _, grads = _system(cfg, params, batch)

    def two_tensors(lookup, head):
        hidden = granite_h.apply({**params, "embed": {"tokens": lookup}},
                                 batch["inputs"], cfg)
        return token_ce(granite_h.logits_of(
            {**params, "embed": {"tokens": head}}, hidden, cfg),
            batch["targets"])

    tied = params["embed"]["tokens"]
    with jax.default_matmul_precision("highest"):
        of_lookup, of_head = jax.jit(jax.grad(two_tensors, (0, 1)))(tied, tied)
    assert float(jnp.max(jnp.abs(of_lookup))) > 0
    assert float(jnp.max(jnp.abs(of_head))) > 0
    assert _rel(grads["embed"]["tokens"], of_lookup + of_head) <= 1e-6
    assert _rel(grads["embed"]["tokens"], of_head) > 1e-2


# -- the scan at several heads on one group -----------------------------------

def _scan_inputs(seq=128, heads=8, width=16, state=16, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, seq, heads, width)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(batch, seq, heads)),
                     jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, size=(heads,)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(batch, seq, 1, state)), jnp.float32)
            for _ in range(2))
    return x, dt, a, b, c


@pytest.mark.parametrize("chunk", [128, 64, 32])
def test_ssd_at_one_group_of_heads_is_the_token_by_token_recurrence(chunk):
    """Eight heads on one B/C group, the sequence as one chunk (no state is
    carried), in two chunks of 64 and in four of 32 (Nemotron-H's tests run
    their own chunk and two groups), against the reference's token-by-token
    scan: values and the gradients of all five inputs, B's and C's summed
    over the eight heads that read them."""
    x, dt, a, b, c = _scan_inputs()
    weights = jnp.asarray(np.random.default_rng(1).normal(size=x.shape),
                          jnp.float32)

    def chunked(x, dt, a, b, c):
        return jnp.sum(ssd_module.ssd(x, dt, a, b, c, chunk=chunk) * weights)

    def plain(x, dt, a, b, c):
        out = jax.vmap(lambda x, dt, b, c: reference.selective_scan(
            x, dt, a, jnp.repeat(b, 8, axis=1), jnp.repeat(c, 8, axis=1)))(
                x, dt, b, c)
        return jnp.sum(out * weights)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(chunked, range(5)))(
            x, dt, a, b, c)
        want, ref_grads = jax.jit(jax.value_and_grad(plain, range(5)))(
            x, dt, a, b, c)
    assert abs(float(got) - float(want)) <= F32_TOL * abs(float(want))
    for g, r in zip(grads, ref_grads):
        assert float(jnp.max(jnp.abs(r))) > 0 and _rel(g, r) <= 3 * F32_TOL


def test_the_scan_is_one_form_whatever_the_heads():
    """``ops/ssd.py`` has one form and no switch: two heads and sixty-four on
    one group trace to the same operations in the same order, one carry over
    the chunks among them, and no checkpoint or map of the scan's own."""
    def operations(heads):
        x, dt, a, b, c = _scan_inputs(seq=64, heads=heads, width=8, batch=1)
        jaxpr = jax.make_jaxpr(functools.partial(ssd_module.ssd, chunk=32))(
            x, dt, a, b, c)
        return [str(eqn.primitive) for eqn in jaxpr.eqns]

    few, many = operations(2), operations(64)
    assert few == many
    assert few.count("scan") + few.count("while") == 1
    assert not [name for name in few if "checkpoint" in name
                or "remat" in name]


# -- the configuration and the cell -------------------------------------------

@pytest.mark.parametrize("change", [
    {"num_local_experts": 8}, {"num_experts_per_tok": 2},
    {"position_embedding_type": "rope"},
    {"rope_scaling": {"type": "linear", "factor": 2.0}},
    {"attention_bias": True}, {"mamba_proj_bias": True},
    {"mamba_conv_bias": False}, {"tie_word_embeddings": False},
    {"hidden_act": "gelu"}, {"normalization_function": "layernorm"},
    {"mamba_n_heads": 4}, {"mamba_n_groups": 3},
    {"num_key_value_heads": 3}, {"num_hidden_layers": 5},
    {"layer_types": ["mamba", "mamba", "mamba", "moe"]}],
    ids=lambda c: "{}={}".format(*next(iter(c.items()))))
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        granite_h.GraniteHConfig.from_dict({**_sizes(), **change})


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_the_cell_is_what_issue_56_named(listed_for):
    """One configuration, one cell on one chip under the Kimi cell's traffic
    file as it stands; of the manifest's lists the cell is in the two that
    read any decoder's facts (the step's share of the peak, the flash calls'
    of their roofline). What else lists it is the manifest's to say:
    ``tests/test_phases.py`` holds every name listed for the cell to what
    its rehearsal gives, and the manifest to its 128 names at most."""
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "s8192.b1.zipf", 1)
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == cell["config"]] == [CELL]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    assert entry["file"] == CONFIG
    assert entry["source"] == ("https://huggingface.co/ibm-granite/"
                               "granite-4.0-h-micro/blob/main/config.json")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "vocab_size"]
    assert {"throughput", "setup_s"} <= {m["moves"] for m in listed_for(CELL)}
    assert {m["name"] for m in listed_for(CELL) if "workloads" in m} >= {
        "step.mfu", "kernel.flash_roofline"}


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` as published; the three cuts and
    only they differ; 772,160,448 parameters in the store, as ISSUE 56
    counted them."""
    config = _json(CONFIG)
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True}
    assert {k: config[k] for k in published} == published
    was = config["published"]
    assert set(was) == {"num_hidden_layers", "layer_types", "vocab_size"} \
        == set(next(c for c in _json("BENCHMARK.json")["configs"]
                    if c["name"] == "granite-4.0-h-micro")["reduced"])
    assert (was["num_hidden_layers"], config["num_hidden_layers"]) == (40, 10)
    assert (was["vocab_size"], config["vocab_size"]) == (100352, 12544)
    kinds = was["layer_types"]
    assert len(kinds) == 40 and (kinds.count("mamba"),
                                 kinds.count("attention")) == (36, 4)
    # one whole period, published layers 1-10
    assert config["layer_types"] == kinds[:10] == ["mamba"] * 5 + [
        "attention"] + ["mamba"] * 4
    assert len(config["assumed"]) >= 6
    assert "eight chips share the vocabulary" in config["deployment"]
    cfg = granite_h.GraniteHConfig.from_dict(config)
    assert (cfg.mamba_inner, cfg.conv_dim, cfg.head_dim) == (4096, 4352, 64)
    shapes = jax.eval_shape(lambda k: granite_h.init_params(k, cfg),
                            jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == 772_160_448 == config["parameters"]
    assert {kind: count(shapes[layer]) for layer, kind in (
        ("layer0", "mamba"), ("layer5", "attention"))} == {
            "mamba": 76_182_976, "attention": 60_821_504}
    assert count(shapes["layer0"]["mamba"]) == 25_847_232
    assert count(shapes["layer5"]["attn"]) == 10_485_760
    assert count(shapes["layer0"]["ffn"]) == 50_331_648
    assert count(shapes["embed"]) + count(shapes["final_norm"]) \
        == 12_544 * 2048 + 2048
    assert shapes["layer0"]["mamba"]["in_proj"]["kernel"].shape == (2048,
                                                                    8512)
    # the operations from shapes, at the cell's sizes: ISSUE 56's arithmetic
    flops = granite_h_step.dense_flops(config, 8192, 8192)
    assert flops == pytest.approx(40e12, rel=0.03)
    scan_flops, scan_bytes = ssd_cost(1, 8192, 64, 64, 1, 128, 256, 9)
    assert scan_flops == pytest.approx(0.70e12, rel=0.01)
    assert scan_bytes == pytest.approx(3.19e9, rel=0.01)


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json(CONFIG)
    traffic = _json("benchmark/traffic/s8192.b1.zipf.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        granite_h_step.build(config, {**traffic, "pool": 16}, 1, 0)


# -- the family's pieces ------------------------------------------------------

def test_reference_in_blocks_as_in_one(monkeypatch):
    """The reference's attention in blocks of query rows, its recurrence in
    blocks of tokens and its loss in blocks of positions (what lets 8,192
    positions fit on the chip) are each in one block; its witnesses are its
    own gradients of those leaves."""
    sizes, _, params, batch, (ref_loss, ref_grads) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    monkeypatch.setattr(reference, "LOSS_BLOCK", 64)
    names = ("layer0/mamba/A_log", "layer3/attn/q/kernel", "embed/tokens")
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p: reference.witness_grads(
            p, batch, sizes, names))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    for name, g in grads.items():
        want = functools.reduce(lambda t, part: t[part], name.split("/"),
                                ref_grads)
        assert _rel(g, want) <= 3 * F32_TOL, name


def _step0_inputs(fault=None):
    """What ``granite_h_step.step0_checks`` reads, made by hand: witnesses
    whose gradient is the reference's, halved by the clip, and AdamW applied
    by the rule; ``fault`` spoils one thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    witnesses = {}
    scale = 0.5
    for name in granite_h_step.GRAD_COSINE:
        before = rng.normal(size=(16, 8)) * 0.02
        ref_grad = rng.normal(size=(16, 8))
        grad = ref_grad * scale
        if fault == "direction" and name.endswith("A_log"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "length" and name == "embed/tokens":
            grad = grad * 1.5        # one use of two counted twice
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = granite_h_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("attn/q/kernel"):
            after = granite_h_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    return witnesses, 1.3 if fault == "clip" else 1.0, rule


STEP0_FAULTS = {None: None, "direction": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    checks = granite_h_step.step0_checks(*_step0_inputs(fault))["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if fault else set())
