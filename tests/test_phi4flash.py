"""Phi-4-mini-flash (``ps_tpu/models/phi4flash.py``; the differential
attention and the gated memory unit of ``ps_tpu/models/blocks.py``; the
selective scan of ``ps_tpu/ops/selective_scan.py``) against its plain
reference (``benchmark/families/phi4flash_reference.py``: the scan token by
token, whole rows of attention under an explicit mask), at small sizes on the
CPU with seeded weights; the layer pattern and the cut; what the second half
reads of the first, with a fault planted each way; then the configuration,
the cell and the family's pieces.

Tolerances. Both sides compute in f32 here and differ only in the order of
their sums: losses agree to a few f32 roundoffs, gradients to 3e-5 of their
largest entry (seen: 9e-6). Every leaf is moved away from its initial value
(biases and norms off 0 and 1) so that each enters the loss.
"""

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import phi4flash_reference as reference
from benchmark.families import phi4flash_step
from jaxpr_tools import checkpoint_names, flash_calls
from ps_tpu.models import blocks, phi4flash
from ps_tpu.models.blocks import make_attn_fn
from ps_tpu.ops.flash_attention import KEPT

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 1e-5
CELL = "phi-4-mini-flash-reasoning.s16384.b1.zipf"
CONFIG = "benchmark/configs/phi-4-mini-flash-reasoning.json"
#: the cell's cut in small: published layers 14-19 of 32, 4 query heads on 2
#: K/V heads of 16 (one pair of K/V heads serving two pairs of query heads), a
#: window of 48, a state of 4
SIZES = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=96, sliding_window=48,
    mamba_d_state=4, num_hidden_layers=6, first_layer=14, mb_per_layer=2,
    layer_norm_eps=1e-5, published={"num_hidden_layers": 32},
    embd_pdrop=0, resid_pdrop=0, hidden_act="silu", mlp_bias=False,
    lm_head_bias=False, tie_word_embeddings=True, dtype="float32")
TABLE = (["mamba", "window"] * 8 + ["mamba_memory", "full"]
         + ["gmu", "cross"] * 7)


def _setup(seed=0, batch=2, seq=128, **changes):
    """``(sizes, config, params, batch)``."""
    sizes = {**SIZES, **changes}
    cfg = phi4flash.Phi4FlashConfig.from_dict(sizes)
    params = jax.jit(lambda k: phi4flash.init_params(k, cfg))(
        jax.random.key(seed))
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    # away from the cell's 0.02, 0 and 1: every leaf then matters to the loss
    params = jax.tree_util.tree_unflatten(tree, [
        (3 * leaf if leaf.ndim > 1 else leaf)
        + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, sizes["vocab_size"],
                                   size=(batch, seq + 1)), jnp.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


def _system(cfg, params, batch, attn="full"):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            phi4flash.make_loss_fn(cfg, attn=attn)))(params, batch)


def _plain(sizes, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, sizes)))(params)


@functools.lru_cache(maxsize=None)
def _base():
    sizes, cfg, params, batch = _setup()
    return sizes, cfg, params, batch, _plain(sizes, params, batch)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _assert_grads_close(grads, ref_grads, tol=3 * F32_TOL):
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat) == len(ref)
    for path, g in flat:
        assert _rel(g, ref[path]) <= tol, jax.tree_util.keystr(path)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_reference(attn):
    """The loss and every leaf's gradient, the attention closure plain and
    through the interpreted kernel (the band step under the window; keys of
    16 against values of 32)."""
    sizes, cfg, params, batch, (ref_loss, ref_grads) = _base()
    loss, grads = _system(cfg, params, batch, attn)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    _assert_grads_close(grads, ref_grads)


# -- the pattern and the cut ---------------------------------------------------

def test_kinds_are_the_published_table():
    """``kinds(32, 2)``: 9 mamba (8 and the memory's), 8 window, 1 full, 7
    gmu, 7 cross, by index; the reference's own table is the same; the cut
    holds layers 14 to 19."""
    whole = phi4flash.kinds(32, 2)
    assert list(whole) == TABLE == reference.kinds(32, 2)
    assert {k: TABLE.count(k) for k in phi4flash.KINDS} == {
        "mamba": 8, "window": 8, "mamba_memory": 1, "full": 1, "gmu": 7,
        "cross": 7}
    assert (whole.index("mamba_memory"), whole.index("full")) == (16, 17)
    cfg = phi4flash.Phi4FlashConfig.from_dict(SIZES)
    assert [kind for _, kind in cfg.layers] == list(whole[14:20]) == [
        "mamba", "window", "mamba_memory", "full", "gmu", "cross"]
    assert [i for i, _ in cfg.layers] == list(range(14, 20)) \
        == [i for i, _ in reference.held_layers(SIZES)]
    # the constant counts in the whole model, not in the cut
    assert [round(phi4flash.lambda_init(i), 3) for i in (15, 17, 19)] == [
        0.793, 0.796, 0.798]
    assert round(phi4flash.lambda_init(1), 2) == 0.36


@pytest.mark.parametrize("change,match", [
    ({"num_hidden_layers": 30, "first_layer": 0,
      "published": {"num_hidden_layers": 30}}, "multiple of four"),
    ({"mb_per_layer": 4}, "every second"),
    ({"first_layer": 17, "num_hidden_layers": 3}, "holds no 'mamba_memory'"),
    ({"first_layer": 18, "num_hidden_layers": 2}, "holds no"),
    ({"first_layer": 30, "num_hidden_layers": 6}, "of a model of 32"),
    ({"resid_pdrop": 0.1}, "resid_pdrop"), ({"mlp_bias": True}, "mlp_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"num_key_value_heads": 1}, "pairs of heads")], ids=str)
def test_config_refuses_what_the_model_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        phi4flash.Phi4FlashConfig.from_dict({**SIZES, **change})


def test_the_cut_is_the_models_layers():
    """Layers 14 to 19 of the uncut stack of 32, given the stream that
    reaches layer 14, are the cut's six given the same: the same kinds, the
    same ``lambda_init``, the same leaves under the same names."""
    sizes, _, whole_params, _ = _setup(first_layer=0, num_hidden_layers=32)

    def config(first, layers):
        return phi4flash.Phi4FlashConfig.from_dict(
            {**sizes, "first_layer": first, "num_hidden_layers": layers})

    cut = config(14, 6)
    cut_params = {name: whole_params[name]
                  for name in ("embed", "final_norm")
                  + tuple(f"layer{i}" for i in range(14, 20))}
    assert jax.tree_util.tree_structure(cut_params) \
        == jax.tree_util.tree_structure(jax.eval_shape(
            lambda k: phi4flash.init_params(k, cut), jax.random.key(0)))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    def run(held):
        return jax.jit(lambda p, x: phi4flash.run_layers(p, x, held))

    with jax.default_matmul_precision("highest"):
        reached = run(config(0, 14))(whole_params, x)
        by_cut = run(cut)(cut_params, reached)
        whole = run(config(0, 20))(whole_params, x)
    # two programs: the same numbers within the order of f32's sums
    assert _rel(by_cut, whole) <= F32_TOL


# -- differential attention ------------------------------------------------------

@pytest.mark.parametrize("window", [None, 128], ids=["full", "window 128"])
def test_differential_attention_through_the_kernel_at_64_and_128(window):
    """``blocks.diff_attention_block`` at the cell's head sizes (keys of 64
    against values of 128, 4 pairs of query heads on 2 pairs of K/V heads)
    through ``attn: flash``, interpreted, and ``full`` alike, and both the
    two maps written out: values and the gradients of q, k, v and the four
    vectors."""
    rng = np.random.default_rng(0)
    b, s, d = 1, 256, 64
    q = jnp.asarray(rng.normal(size=(b, s, 8, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, s, 4, d)), jnp.float32)
            for _ in range(2))
    lp = {f"lambda_{n}": jnp.asarray(0.3 * rng.normal(size=d), jnp.float32)
          for n in ("q1", "k1", "q2", "k2")}
    lp["head_norm"] = {"scale": jnp.asarray(
        1 + 0.1 * rng.normal(size=2 * d), jnp.float32)}
    weights = jnp.asarray(rng.normal(size=(b, s, 4 * 2 * d)), jnp.float32)
    init = phi4flash.lambda_init(17)

    def block(attn):
        def out(lp, q, k, v):
            return jnp.sum(weights * blocks.diff_attention_block(
                lp, q, k, v, make_attn_fn(attn), lambda_init=init, eps=1e-5,
                core="ps.attn/full", window=window))
        return out

    def written_out(lp, q, k, v):
        config = {"num_attention_heads": 8, "num_key_value_heads": 4,
                  "hidden_size": 8 * d, "layer_norm_eps": 1e-5}
        return jnp.sum(weights[0] * reference.diff_attention(
            lp, q[0].reshape(s, -1), k[0].reshape(s, -1),
            v[0].reshape(s, -1), 17, window, config))

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(written_out, argnums=range(4))(
            lp, q, k, v)
        for attn in ("full", "flash"):
            got, grads = jax.value_and_grad(block(attn), argnums=range(4))(
                lp, q, k, v)
            assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want))
            _assert_grads_close(grads, want_grads, tol=1e-4)


def test_the_two_maps_are_one_kernel_call_a_layer():
    """A differential layer's two softmax maps enter the kernel once, 2 h
    heads on 2 h_kv: three Mosaic calls a layer (forward, dq, dk / dv), nine
    in the cut's three attention layers, and the window layer's alone are the
    band's."""
    _, cfg, params, batch = _setup()
    jaxpr = jax.make_jaxpr(jax.grad(phi4flash.make_loss_fn(
        cfg, attn="flash")))(params, batch)
    assert flash_calls(jaxpr.jaxpr) == 3 * 3


# -- what the second half reads of the first -------------------------------------

def _tool():
    spec = importlib.util.spec_from_file_location(
        "nemotron_grad_check",
        os.path.join(_REPO, "tools", "nemotron_grad_check.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


FAULTS = sorted(_tool().MODELS["phi4flash"]["faults"])


@pytest.mark.parametrize("fault", FAULTS)
def test_each_hand_over_and_each_constant_moves_the_loss(fault, monkeypatch):
    """The reference with one fault planted (``tools/nemotron_grad_check.py``'s
    ten) is no longer the system. Eight (the memory taken after the gate or
    without its skip, the cross layer reading the window layer's K and V,
    the window read as full, ``lambda_init`` at the cut's depth, the head
    norm or ``1 - lambda_init`` left out, an RMSNorm for a LayerNorm) move
    the loss by ten times the tolerance and more (2.6e-4 of it at the least,
    the memory after its gate; 1.3e-2 at the most); two leave it whole and
    drop or negate the lambda vectors' gradient."""
    sizes, cfg, params, batch, (ref_loss, ref_grads) = _base()
    planted = _tool().MODELS["phi4flash"]["faults"][fault]
    if callable(planted):
        for name, fn in planted(reference).items():
            monkeypatch.setattr(reference, name, fn)
    else:
        sizes = {**sizes, **planted}
    if fault.startswith("lambda_gradient"):
        # the two that leave the forward pass whole: the loss is the
        # reference's and so is every gradient but the lambda vectors',
        # which are none or the opposite (``LAMBDA_WITNESSES`` are theirs)
        times = 0.0 if fault.endswith("dropped") else -1.0
        names = ("layer17/attn/lambda_k2", "layer17/attn/head_norm/scale")
        with jax.default_matmul_precision("highest"):
            faulty, grads = reference.witness_grads(params, batch, sizes,
                                                    names)
        assert float(faulty) == float(ref_loss)
        want = ref_grads["layer17"]["attn"]
        assert _rel(grads[names[1]], want["head_norm"]["scale"]) <= F32_TOL
        if times:
            assert _rel(grads[names[0]], times * want["lambda_k2"]) <= F32_TOL
        else:
            assert not np.any(np.asarray(grads[names[0]]))
        return
    with jax.default_matmul_precision("highest"):
        faulty = reference.loss_fn(params, batch, sizes)
    loss, _ = _system(cfg, params, batch)
    assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
    assert abs(float(faulty) - float(ref_loss)) > 10 * F32_TOL * float(ref_loss)


def test_the_memory_is_the_scan_before_the_gate_and_kv_the_full_layers():
    """What ``_layer`` hands on: a ``mamba_memory`` layer's second output is
    the scan's ``y`` with the ``D x`` skip and without ``silu(z)``; a
    ``full`` layer's K and V are its own projections; every other kind hands
    on what came in."""
    sizes, cfg, params, _ = _setup()
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 64, 64)), jnp.float32)
    attn_fn = make_attn_fn("full")
    with jax.default_matmul_precision("highest"):
        _, memory, kv = phi4flash._layer(
            params["layer16"], x, None, None, "mamba_memory", 16, cfg,
            attn_fn)
        normed = reference.layer_norm(x[0], params["layer16"]["norm"], 1e-5)
        _, want = reference.mamba_mixer(params["layer16"]["mamba"], normed,
                                        sizes)
        assert kv is None and _rel(memory[0], want) <= F32_TOL
        _, same, kv = phi4flash._layer(
            params["layer17"], x, memory, None, "full", 17, cfg, attn_fn)
        np.testing.assert_array_equal(np.asarray(same), np.asarray(memory))
        normed = reference.layer_norm(x[0], params["layer17"]["norm"], 1e-5)
        qkv = reference.linear(params["layer17"]["attn"]["qkv"], normed)
        assert _rel(kv[0].reshape(64, -1), qkv[:, 64:96]) <= F32_TOL
        assert _rel(kv[1].reshape(64, -1), qkv[:, 96:]) <= F32_TOL
        for name, kind, depth in (("layer18", "gmu", 18),
                                  ("layer19", "cross", 19),
                                  ("layer15", "window", 15)):
            _, m, pair = phi4flash._layer(params[name], x, memory, kv, kind,
                                          depth, cfg, attn_fn)
            for got, came in zip((m, *pair), (memory, *kv)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(came), kind)


def test_producers_gradients_are_the_sum_over_their_readers():
    """The memory layer's in projection is read by its own layer and by the
    gmu, the full layer's K and V by its own core and the cross layer's: with
    a reader's weights zeroed downstream (its out projection), the
    producer's gradient loses that reader's share and no other."""
    sizes, cfg, params, batch, (_, ref_grads) = _base()

    def without(layer, part):
        cut = jax.tree_util.tree_map(lambda x: x, params)
        cut[layer][part] = {**cut[layer][part], "out_proj" if part == "gmu"
                            else "out": jax.tree_util.tree_map(
                                jnp.zeros_like, cut[layer][part][
                                    "out_proj" if part == "gmu" else "out"])}
        return cut

    for layer, part, producer in (
            ("layer18", "gmu", ("layer16", "mamba", "in_proj", "kernel")),
            ("layer19", "attn", ("layer17", "attn", "qkv", "kernel"))):
        cut = without(layer, part)
        _, grads = _system(cfg, cut, batch)
        _, want = _plain(sizes, cut, batch)
        got, whole = (functools.reduce(lambda t, k: t[k], producer, g)
                      for g in (grads, ref_grads))
        assert _rel(got, functools.reduce(lambda t, k: t[k], producer,
                                          want)) <= 3 * F32_TOL
        assert _rel(got, whole) > 1e-3


def test_the_tied_embeddings_gradient_is_the_sum_of_both_uses():
    """One tensor embeds and is the head under the slice: its gradient is the
    lookup's plus the head's."""
    _, cfg, params, batch = _setup()
    loss_fn = phi4flash.make_loss_fn(cfg)

    def two_tensors(lookup, head):
        hidden = phi4flash.apply(
            {**params, "embed": {"tokens": lookup}}, batch["inputs"], cfg)
        logits = phi4flash.logits_of(
            {**params, "embed": {"tokens": head}}, hidden, cfg)
        return blocks.token_ce(logits, batch["targets"])

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        tied = grads["embed"]["tokens"]
        table = params["embed"]["tokens"]
        apart, (lookup, head) = jax.jit(jax.value_and_grad(
            two_tensors, argnums=(0, 1)))(table, table)
        assert abs(float(apart) - float(loss)) <= 1e-5
    assert _rel(tied, lookup + head) <= 3 * F32_TOL
    assert float(jnp.max(jnp.abs(lookup))) > 0 < float(jnp.max(jnp.abs(head)))


def test_a_layers_checkpoint_keeps_the_flash_residuals_and_what_it_lists():
    """The one ``jax.checkpoint`` around a layer keeps, by name, the flash
    call's output and logsumexp and ``PRODUCTS_KEPT``; the other names a
    layer gives are identities."""
    _, cfg, params, batch = _setup()
    grad = jax.make_jaxpr(jax.grad(phi4flash.make_loss_fn(
        cfg, attn="flash")))(params, batch)
    assert {"mamba_in", "ffn_in", "attn_qkv", *KEPT} \
        <= checkpoint_names(grad.jaxpr)
    assert phi4flash.PRODUCTS_KEPT == ("mamba_in", "attn_qkv")


# -- through the store -----------------------------------------------------------

def test_fused_step_matches_reference():
    """Through ``KVStore.make_step``: the loss and, read from AdamW's first
    moment behind a clip that does not bite, every gradient; then AdamW's rule
    on the parameters. A batch of eight: the test mesh has eight devices
    along ``data``."""
    import optax

    import ps_tpu as ps

    sizes, cfg, params, batch = _setup(seed=1, batch=8, seq=64)
    ref_loss, ref_grads = _plain(sizes, params, batch)
    rule = dict(learning_rate=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    ps.init(backend="tpu")
    try:
        store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1e9,
                           placement="replicated", **rule)
        store.init(params)
        step = store.make_step(phi4flash.make_loss_fn(cfg))
        with jax.default_matmul_precision("highest"):
            loss, _ = step(store.shard_batch(batch))
        assert abs(float(loss) - float(ref_loss)) <= F32_TOL * float(ref_loss)
        flat = jax.tree_util.tree_leaves_with_path(ref_grads)
        assert len(flat) == len(store.keys())
        for path, r in flat:
            key = "/".join(p.key for p in path)
            state = store.optimizer_state(key)
            mu = optax.tree_utils.tree_get(state, "mu")
            assert _rel(mu / 0.1, r) <= 3 * F32_TOL, key
            before = functools.reduce(lambda t, p: t[p.key], path, params)
            want = phi4flash_step.adamw_first_step(
                before, mu, optax.tree_utils.tree_get(state, "nu"), **rule)
            np.testing.assert_allclose(store.pull(key), want, atol=1e-6)
    finally:
        ps.shutdown()


def test_a_checkpoint_round_trip_resumes_bit_for_bit(tmp_path):
    """Two steps, save, two more; restore into a fresh store, the same two
    more: the parameters of the four uninterrupted steps."""
    import ps_tpu as ps

    _, cfg, params, _ = _setup(seed=2)
    rng = np.random.default_rng(2)
    batches = [jnp.asarray(rng.integers(0, 256, size=(8, 17)), jnp.int32)
               for _ in range(4)]
    batches = [{"inputs": b[:, :-1], "targets": b[:, 1:]} for b in batches]

    def run(store, step, some):
        for b in some:
            _, out = step(store.shard_batch(b))
        return jax.tree_util.tree_map(np.asarray, out)

    def fresh():
        store = ps.KVStore(optimizer="adamw", learning_rate=1e-3,
                           clip_by_global_norm=1.0, placement="replicated")
        store.init(params)
        return store

    path = str(tmp_path / "ckpt")
    ps.init(backend="tpu")
    try:
        store = fresh()
        step = store.make_step(phi4flash.make_loss_fn(cfg))
        run(store, step, batches[:2])
        store.save(path)
        whole = run(store, step, batches[2:])
    finally:
        ps.shutdown()
    ps.init(backend="tpu")
    try:
        store = fresh()
        store.restore(path)
        assert store.step == 2
        resumed = run(store, store.make_step(phi4flash.make_loss_fn(cfg)),
                      batches[2:])
    finally:
        ps.shutdown()
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(resumed)):
        np.testing.assert_array_equal(a, b)


# -- the configuration and the cell ----------------------------------------------

def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def test_the_cell_is_what_issue_65_named(listed_for):
    """One configuration, one cell on one chip under a traffic file of its
    own; of the manifest's lists the cell is in the two that read any
    decoder's facts (the step's share of the peak, the flash calls' of their
    roofline). What else lists it is the manifest's to say:
    ``tests/test_phases.py`` holds every name listed for the cell to what
    its rehearsal gives, and the manifest to its 128 names at most."""
    manifest = _json("BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "s16384.b1.zipf", 1)
    assert [w["name"] for w in manifest["workloads"]
            if w["config"] == cell["config"]] == [CELL]
    # the nineteenth cell on the fifteenth configuration, the last of each
    # (a PR that appends moves these two counts, as this one moved Ouro's)
    assert manifest["workloads"][18] == cell and len(cell["why"]) <= 200
    assert len(manifest["workloads"]) == 19 and len(manifest["configs"]) == 15
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 3
    entry = manifest["configs"][14]
    assert entry["name"] == cell["config"] and entry["file"] == CONFIG
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/"
                               "config.json")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert {"throughput", "setup_s"} <= {m["moves"] for m in listed_for(CELL)}
    own = {m["name"]: m for m in listed_for(CELL) if "workloads" in m}
    assert set(own) >= {"step.mfu", "kernel.flash_roofline"}
    # in both lists behind the cell PR 63 appended
    for name in ("step.mfu", "kernel.flash_roofline"):
        cells = own[name]["workloads"]
        assert cells.index("ouro-2.6b.s8192.b1.zipf") < cells.index(CELL)
    traffic = _json("benchmark/traffic/s16384.b1.zipf.json")
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["attn"],
            traffic["input"], traffic["pool"]) == (1, 16384, "flash",
                                                   "direct", "fresh")
    # ISSUE 65's own value of the two it allowed
    assert (traffic["loss_step"], traffic["warmup_steps"],
            traffic["trace_blocks"]) == (48, 3, 2)


def test_configuration_holds_the_published_widths():
    """Every key of the catalog's ``config`` as published; the two cuts and
    only they differ; 697,094,272 parameters in the store, as ISSUE 65
    counted them, layer by layer."""
    config = _json(CONFIG)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    assert {k: config[k] for k in published} == published
    was = config["published"]
    assert {"num_hidden_layers", "vocab_size"} <= set(was)
    assert (was["num_hidden_layers"], config["num_hidden_layers"],
            config["first_layer"]) == (32, 6, 14)
    assert (was["vocab_size"], config["vocab_size"]) == (200064, 25008)
    assert was["vocab_size"] == 8 * config["vocab_size"]
    assert len(config["assumed"]) >= 8
    assert "eight chips share the vocabulary" in config["deployment"]
    cfg = phi4flash.Phi4FlashConfig.from_dict(config)
    assert (cfg.mamba_inner, cfg.dt_rank, cfg.head_dim, cfg.mamba_d_state,
            cfg.model_layers) == (5120, 160, 64, 16, 32)
    shapes = jax.eval_shape(lambda k: phi4flash.init_params(k, cfg),
                            jax.random.key(0))

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == 697_094_272 == config["parameters"]
    assert {kind: count(shapes[f"layer{i}"]) for i, kind in cfg.layers} == {
        "mamba": 119_895_040, "window": 98_322_304,
        "mamba_memory": 119_895_040, "full": 98_322_304,
        "gmu": 104_867_840, "cross": 91_766_144}
    assert count(shapes["embed"]) + count(shapes["final_norm"]) \
        == 25_008 * 2560 + 5120
    assert shapes["layer14"]["mamba"]["A_log"].shape == (5120, 16)
    assert shapes["layer14"]["mamba"]["x_proj"]["kernel"].shape == (5120, 192)
    # the whole model by the same sums: the published 3.85B
    whole = (9 * 119_895_040 + 9 * 98_322_304 + 7 * 104_867_840
             + 7 * 91_766_144 + 200_064 * 2560 + 5120)
    assert whole == pytest.approx(3.85e9, rel=0.005)
    # the rehearsal's sizes are a model too
    small = phi4flash.Phi4FlashConfig.from_dict(
        {**config, **config["rehearse"]})
    assert count(jax.eval_shape(
        lambda k: phi4flash.init_params(k, small), jax.random.key(0))) \
        == config["rehearse"]["parameters"]
    # the operations from shapes, at the cell's sizes: ISSUE 65's arithmetic
    flops = phi4flash_step.dense_flops(config, 16384, 16384)
    assert flops == pytest.approx(87e12, rel=0.03)
    costs = phi4flash_step.flash_costs(config, 1, 16384)
    assert costs["flash"][0] == pytest.approx(2 * 8.9e12, rel=0.01)
    assert costs["window_flash"][0] / costs["flash"][0] \
        == pytest.approx(512 / 16384 * 2 / 2, rel=0.05)
    scan_flops, scan_bytes = phi4flash_step.scan_cost(1, 16384, 5120, 16, 2)
    assert scan_flops == 24.0 * 2 * 16384 * 5120 * 16
    assert scan_bytes == pytest.approx(4.37e9, rel=0.01)


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json(CONFIG)
    traffic = _json("benchmark/traffic/s16384.b1.zipf.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        phi4flash_step.build(config, {**traffic, "pool": 16}, 1, 0)
    with pytest.raises(ValueError, match="knows no model"):
        phi4flash_step.build({**config, "model": "granite_h"}, traffic, 1, 0)


# -- the family's pieces -----------------------------------------------------------

def test_reference_in_blocks_as_in_one(monkeypatch):
    """The reference's attention in blocks of query rows, its recurrence in
    blocks of tokens and of channels and its loss in blocks of positions
    (what lets 16,384 positions fit on the chip) are each in one block; its
    witnesses are its own gradients of those leaves."""
    sizes, _, params, batch, (ref_loss, ref_grads) = _base()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 16)
    monkeypatch.setattr(reference, "CHANNEL_BLOCK", 32)
    monkeypatch.setattr(reference, "LOSS_BLOCK", 64)
    names = ("layer14/mamba/A_log", "layer19/attn/q/kernel",
             "layer17/attn/lambda_k2", "embed/tokens")
    assert set(names) - set(phi4flash_step.GRAD_COSINE) == {names[2]}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p: reference.witness_grads(
            p, batch, sizes, names))(params)
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * float(ref_loss)
    for name, g in grads.items():
        want = functools.reduce(lambda t, part: t[part], name.split("/"),
                                ref_grads)
        assert _rel(g, want) <= 3 * F32_TOL, name


def _step0_inputs(fault=None):
    """What ``phi4flash_step.step0_checks`` reads, made by hand: witnesses
    whose gradient is the reference's, halved by the clip, and AdamW applied
    by the rule; ``fault`` spoils one thing."""
    rng = np.random.default_rng(0)
    rule = {"name": "adamw", "learning_rate": 4e-4, "b1": 0.9, "b2": 0.95,
            "eps": 1e-8, "weight_decay": 0.1, "clip_by_global_norm": 1.0}
    witnesses = {}
    scale = 0.5
    for name in phi4flash_step.WITNESSES:
        before = rng.normal(size=(16, 8)) * 0.02
        ref_grad = rng.normal(size=(16, 8))
        grad = ref_grad * scale
        if name == "layer15/attn/lambda_q1":
            # a scalar read 28% off is rounding (seen); none or its
            # opposite is a fault
            grad = grad * {"lambda_rounded": 0.72, "lambda_dropped": 0.0,
                           "lambda_sign": -1.0}.get(fault, 1.0)
        if fault == "direction" and name.endswith("A_log"):
            grad = grad + 0.5 * scale * rng.normal(size=grad.shape)
        if fault == "length" and name == "layer17/attn/qkv/kernel":
            grad = grad * 0.5        # one reader of two forgotten
        mu, nu = (1 - rule["b1"]) * grad, (1 - rule["b2"]) * grad ** 2
        after = phi4flash_step.adamw_first_step(before, mu, nu, **rule)
        if fault == "apply" and name.endswith("attn/q/kernel"):
            after = phi4flash_step.adamw_first_step(
                before, (1 - rule["b1"]) * mu, nu, **rule)
        witnesses[name] = {"before": before, "after": after, "mu": mu,
                           "nu": nu, "reference_grad": ref_grad}
    return witnesses, 1.3 if fault == "clip" else 1.0, rule


STEP0_FAULTS = {None: None, "lambda_rounded": None,
                "direction": "gradient_matches_reference",
                "length": "gradient_matches_reference",
                "lambda_dropped": "gradient_matches_reference",
                "lambda_sign": "gradient_matches_reference",
                "apply": "adamw_apply_matches_rule",
                "clip": "gradient_clipped_to_global_norm"}


@pytest.mark.parametrize("fault", STEP0_FAULTS, ids=str)
def test_step0_checks_name_the_fault(fault):
    checks = phi4flash_step.step0_checks(*_step0_inputs(fault))["checks"]
    failed = {name for name, ok in checks.items() if not ok}
    assert failed == ({STEP0_FAULTS[fault]} if STEP0_FAULTS[fault] else set())
