"""Tensor parallelism over the 'model' mesh axis — SURVEY.md §2 note on
parallelism strategies, VERDICT r2 weak #5.

Two claims made testable:

1. PLACEMENT: explicit ``partition_rules`` put each tensor exactly where
   Megatron-style TP wants it (column-parallel in-projections, row-parallel
   out-projections), the heuristic default picks the same dims for the
   standard transformer shapes, and the optimizer moments land on their
   param's sharding.
2. NUMERICS: a dp×tp mesh trains bit-compatibly with a pure-dp mesh at the
   same global batch — GSPMD inserts the activation collectives; the PS
   semantics don't change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import ps_tpu as ps

D, FF = 32, 128  # model dim, FFN dim (divisible by tp=2 and dp=4)


def _block_params(seed=0):
    """A transformer block's worth of parameter shapes (no flax needed —
    placement policy operates on raw trees)."""
    rng = np.random.default_rng(seed)

    def t(*shape):
        return jnp.asarray(rng.normal(0, 0.05, shape).astype(np.float32))

    return {
        "attn": {
            "qkv": {"kernel": t(D, 3 * D), "bias": t(3 * D)},
            "out": {"kernel": t(D, D), "bias": t(D)},
        },
        "mlp": {
            "in": {"kernel": t(D, FF), "bias": t(FF)},
            "out": {"kernel": t(FF, D), "bias": t(D)},
        },
    }


# Megatron placement: in-projections column-parallel (shard the output dim;
# their biases shard with it), out-projections row-parallel (shard the input
# dim; their biases replicate — they add after the contraction's psum).
RULES = [
    (r"attn/qkv/kernel$", (None, "model")),
    (r"attn/qkv/bias$", ("model",)),
    (r"attn/out/kernel$", ("model", None)),
    (r"mlp/in/kernel$", (None, "model")),
    (r"mlp/in/bias$", ("model",)),
    (r"mlp/out/kernel$", ("model", None)),
    (r"(attn/out|mlp/out)/bias$", (None,)),
]


def _loss_fn(params, batch):
    x, y = batch  # x: [B, D], y: [B, D]
    a = x @ params["attn"]["qkv"]["kernel"] + params["attn"]["qkv"]["bias"]
    a = jnp.tanh(a[:, :D])  # use the q slice as a stand-in mixing step
    a = a @ params["attn"]["out"]["kernel"] + params["attn"]["out"]["bias"]
    h = jnp.tanh(a @ params["mlp"]["in"]["kernel"] + params["mlp"]["in"]["bias"])
    out = h @ params["mlp"]["out"]["kernel"] + params["mlp"]["out"]["bias"]
    return jnp.mean((out - y) ** 2)


def _batches(n, gb=16, seed=1):
    rng = np.random.default_rng(seed)
    return [
        (jnp.asarray(rng.normal(0, 1, (gb, D)).astype(np.float32)),
         jnp.asarray(rng.normal(0, 1, (gb, D)).astype(np.float32)))
        for _ in range(n)
    ]


def test_partition_rules_place_megatron_style():
    params = _block_params()
    ps.init(backend="tpu", mesh_shape={"data": 4, "model": 2})
    store = ps.KVStore(optimizer="adam", learning_rate=1e-3,
                       placement="replicated", partition_rules=RULES)
    store.init(params)
    spec = {k: v.sharding.spec for k, v in store._engine._params.items()}
    assert spec["attn/qkv/kernel"] == P(None, "model")   # column-parallel
    assert spec["attn/qkv/bias"] == P("model")
    assert spec["attn/out/kernel"] == P("model", None)   # row-parallel
    assert spec["attn/out/bias"] == P()                  # post-psum add
    assert spec["mlp/in/kernel"] == P(None, "model")
    assert spec["mlp/out/kernel"] == P("model", None)
    # adam moments follow their param's RULE (whole-tree state paths are
    # normalized so $-anchored key rules still match) — attn/out/bias is the
    # discriminating case: its rule says replicate, the heuristic would
    # shard the divisible vector on 'model'
    mu = store._engine._state[0].mu
    assert mu["attn/qkv/kernel"].sharding.spec == P(None, "model")
    assert mu["mlp/out/kernel"].sharding.spec == P("model", None)
    assert mu["attn/out/bias"].sharding.spec == P()      # rule, not heuristic
    assert mu["attn/qkv/bias"].sharding.spec == P("model")
    assert store._engine._state[0].count.sharding.spec == P()
    ps.shutdown()


def test_heuristic_matches_megatron_for_standard_shapes():
    """The largest-divisible-dim default == the explicit Megatron rules for
    every KERNEL of the standard transformer shapes (the wide dim is the one
    worth splitting); biases differ (heuristic shards any divisible vector,
    harmless under GSPMD) — kernels are what set the collective pattern."""
    params = _block_params()
    ps.init(backend="tpu", mesh_shape={"data": 4, "model": 2})
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1,
                       placement="replicated")  # no rules: heuristic
    store.init(params)
    spec = {k: v.sharding.spec for k, v in store._engine._params.items()}
    assert spec["attn/qkv/kernel"] == P(None, "model")  # 3D > D: output dim
    assert spec["mlp/in/kernel"] == P(None, "model")    # FF > D: output dim
    assert spec["mlp/out/kernel"] == P("model", None)   # FF > D: input dim
    ps.shutdown()


@pytest.mark.parametrize("rules", [None, RULES], ids=["heuristic", "rules"])
def test_tp_times_dp_matches_pure_dp(rules):
    """4×2 (dp×tp) == 8×1 (pure dp) at the same global batch, step for step."""
    params = _block_params()
    batches = _batches(4)

    def train(mesh_shape, use_rules):
        ps.init(backend="tpu", mesh_shape=mesh_shape)
        kw = {"partition_rules": use_rules} if use_rules else {}
        store = ps.KVStore(optimizer="adam", learning_rate=1e-3,
                           placement="sharded", **kw)
        store.init(params)
        run = store.make_step(_loss_fn)
        losses, out = [], None
        for b in batches:
            loss, out = run(store.shard_batch(b))
            losses.append(float(loss))
        out = jax.tree_util.tree_map(np.asarray, out)
        ps.shutdown()
        return losses, out

    dp_losses, dp_params = train({"data": 8}, None)
    tp_losses, tp_params = train({"data": 4, "model": 2}, rules)
    np.testing.assert_allclose(tp_losses, dp_losses, rtol=1e-5, atol=1e-7)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        dp_params, tp_params,
    )


def test_bad_rules_fail_loudly():
    from ps_tpu.parallel.sharding import _rule_sharding

    params = _block_params()
    ps.init(backend="tpu", mesh_shape={"data": 4, "model": 2})
    with pytest.raises(ValueError, match="not in"):
        s = ps.KVStore(optimizer="sgd", learning_rate=0.1,
                       partition_rules=[(r"qkv/kernel$", (None, "tensor"))])
        s.init(params)
    mesh = ps.current_context().mesh
    odd = jax.ShapeDtypeStruct((5, 7), jnp.float32)
    with pytest.raises(ValueError, match="divisible"):
        _rule_sharding(mesh, odd, "w", [("w", ("model", None))])  # 5 % 2
    # a matching rule of the wrong rank is skipped (optimizer scalars under
    # a matrix param's rule), not an error
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    assert _rule_sharding(mesh, scalar, "w", [("w", ("model", None))]) is None
    # pre-compiled regexes work exactly like strings
    import re

    mat = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    got = _rule_sharding(mesh, mat, "blk/kernel",
                         [(re.compile(r"kernel$"), (None, "model"))])
    assert got.spec == P(None, "model")
    ps.shutdown()


def test_bare_string_spec_rejected():
    """A spec like \"model\" (instead of (\"model\",)) must fail loudly at
    construction — tuple('model') would silently become per-char junk."""
    ps.init(backend="tpu", mesh_shape={"data": 4, "model": 2})
    with pytest.raises(ValueError, match="tuple of"):
        ps.KVStore(optimizer="sgd", learning_rate=0.1,
                   partition_rules=[(r"kernel$", "model")])
    ps.shutdown()


# -- the pull's sharding: gathered over 'data', untouched over 'model' ---------

@pytest.mark.parametrize("mesh_shape,placement,shape,rules,stored,gathered", [
    # ZeRO only: the data shards are gathered, the result is replicated
    ({"data": 8}, "sharded", (D, FF), None, P(None, "data"), P(None, None)),
    # tensor parallel only: the split is the model's own and stays
    ({"data": 4, "model": 2}, "replicated", (D, FF), None,
     P(None, "model"), P(None, "model")),
    # both: 'model' on the largest dim stays, 'data' on the next is gathered
    ({"data": 4, "model": 2}, "sharded", (D, FF), None,
     P("data", "model"), P(None, "model")),
    # nothing divides: replicated in, replicated out
    ({"data": 8}, "sharded", (10,), None, P(), P()),
    # a rule-placed tensor: both axes where the rule put them
    ({"data": 4, "model": 2}, "sharded", (4, D, FF),
     [(r"experts/kernel$", ("data", "model", None))],
     P("data", "model", None), P(None, "model", None)),
])
def test_gathered_sharding_table(mesh_shape, placement, shape, rules, stored,
                                 gathered):
    from jax.sharding import NamedSharding

    from ps_tpu.parallel.mesh import make_mesh
    from ps_tpu.parallel.sharding import gathered_sharding, param_sharding

    mesh = make_mesh(mesh_shape)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    sharding = param_sharding(mesh, leaf, placement, key="experts/kernel",
                              rules=rules)
    assert sharding.is_equivalent_to(NamedSharding(mesh, stored), len(shape))
    out = gathered_sharding(sharding)
    assert out.mesh is sharding.mesh
    assert out.spec == gathered, (sharding.spec, out.spec)


# -- a split over 'data' that a rule states: stored split, read split ----------

@pytest.mark.parametrize("axes,key,shape,rules,read", [
    # the placement's own split: ZeRO's, gathered to be read
    ({"data": 4}, "moe/dense/kernel", (D, 4 * D), None, P(None, None)),
    ({"data": 4}, "moe/dense/kernel", (D, 4 * D), "experts", P(None, None)),
    # a rule's split over 'data' is the model's own, as one over 'model' is
    ({"data": 4}, "moe/experts/w", (4, D, D), "experts",
     P("data", None, None)),
    ({"data": 4, "model": 2}, "moe/experts/w", (4, D, D), "both",
     P("data", "model", None)),
])
def test_a_ruled_split_is_read_as_it_is_stored(axes, key, shape, rules, read):
    """What ``kv/fused.py`` reads a leaf under: as stored where a rule placed
    it, ZeRO's gather elsewhere."""
    from ps_tpu.parallel.mesh import make_mesh
    from ps_tpu.parallel.sharding import (gathered_sharding, param_sharding,
                                          placed_by_rule)

    mesh = make_mesh(axes)
    rules = {None: None,
             "experts": [(r"experts/w$", ("data", None, None))],
             "both": [(r"experts/w$", ("data", "model", None))]}[rules]
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    stored = param_sharding(mesh, leaf, "sharded", key=key, rules=rules)
    assert "data" in stored.spec
    out = (stored if placed_by_rule(mesh, leaf, key, rules)
           else gathered_sharding(stored))
    assert out.spec == read


def test_placed_by_rule_says_whether_a_rule_places_the_leaf():
    from ps_tpu.parallel.mesh import make_mesh
    from ps_tpu.parallel.sharding import placed_by_rule as ruled

    mesh = make_mesh({"data": 4})
    rules = [(r"experts/w$", ("data", None, None))]
    stack = jax.ShapeDtypeStruct((4, D, D), jnp.float32)
    assert ruled(mesh, stack, "moe/experts/w", rules)
    assert not ruled(mesh, stack, "moe/dense/kernel", rules)
    # a rule of another rank is skipped, as for the moments' scalars
    assert not ruled(mesh, jax.ShapeDtypeStruct((D,), jnp.float32),
                     "moe/experts/w", rules)
    assert not ruled(mesh, stack, "moe/experts/w", None)


def _split_loss(params, batch):
    """Four 'experts' of [D, D] mixed by a gate every row computes: a leaf
    that a rule may split over 'data' beside dense ones."""
    x, y = batch
    h = jnp.tanh(x @ params["dense"]["kernel"])
    gate = jax.nn.softmax(x @ params["gate"]["kernel"], -1)       # [B, 4]
    out = jnp.einsum("be,edf,bd->bf", gate, params["experts"]["w"], h)
    return jnp.mean((out - y) ** 2)


@pytest.mark.parametrize("aggregate", ["mean", "sum"])
def test_a_leaf_split_by_rule_over_data_updates_as_on_one_device(aggregate):
    """``KVStore(placement="sharded", partition_rules=...)`` with a rule over
    the data axis: the leaf is stored split, the step reads it split (the
    sharding constraint it states is the stored one, where a ZeRO leaf's is
    the gathered one), its moments sit beside it, and after one AdamW step
    behind a clip that bites every parameter equals optax's on one device.
    The clip's global norm runs over leaves split by the rule and by ZeRO
    alike: the clipped gradient, read from the first moments, is as long as
    numpy says; ``aggregate="sum"`` scales both kinds alike."""
    import optax

    rng = np.random.default_rng(3)

    def t(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape).astype(np.float32))

    params = {"dense": {"kernel": t(D, D)}, "gate": {"kernel": t(D, 4)},
              "experts": {"w": t(4, D, D)}}
    batch = _batches(1, seed=5)[0]
    rule = dict(learning_rate=1e-2, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    clip = 0.05
    scale = 4.0 if aggregate == "sum" else 1.0
    grads = jax.tree.map(lambda g: scale * g,
                         jax.grad(_split_loss)(params, batch))
    norm = float(np.sqrt(sum(float(np.sum(np.square(np.asarray(g, np.float64))))
                             for g in jax.tree.leaves(grads))))
    assert norm > clip                      # the clip bites
    opt = optax.chain(optax.clip_by_global_norm(clip), optax.adamw(**rule))
    updates, _ = opt.update(grads, opt.init(params), params)
    want = optax.apply_updates(params, updates)

    ps.init(backend="tpu", mesh_shape={"data": 4})
    try:
        store = ps.KVStore(
            optimizer="adamw", placement="sharded", aggregate=aggregate,
            clip_by_global_norm=clip,
            partition_rules=[(r"experts/w$", ("data", None, None))], **rule)
        store.init(params)
        assert store.pull("experts/w").sharding.spec == P("data", None, None)
        assert store.pull("dense/kernel").sharding.spec == P("data", None)
        step = store.make_step(_split_loss)
        placed = store.shard_batch(batch)
        stated = step.lower(placed).as_text()
        # what the step states before the loss reads each leaf
        assert stated.count("sharding_constraint") >= 6
        step(placed)
        for key in ("experts/w", "dense/kernel", "gate/kernel"):
            leaf = want
            for part in key.split("/"):
                leaf = leaf[part]
            # AdamW's first step is lr * g / (|g| + eps): where g is tiny a
            # rounding of it shows; a leaf not reduced or not scaled is 1e-2 off
            np.testing.assert_allclose(store.pull(key), leaf, atol=5e-5,
                                       err_msg=key)
            mu = optax.tree_utils.tree_get(store.optimizer_state(key), "mu")
            assert mu.sharding.spec == store.pull(key).sharding.spec, key
        clipped = np.sqrt(sum(
            float(np.sum(np.square(np.asarray(
                optax.tree_utils.tree_get(store.optimizer_state(k), "mu"),
                np.float64)))) for k in store.keys())) / (1 - rule["b1"])
        np.testing.assert_allclose(clipped, clip, rtol=1e-5)
    finally:
        ps.shutdown()


def test_the_step_gathers_a_zero_leaf_and_not_a_ruled_one():
    """The compiled step of the store above: the dense leaf is all-gathered
    to be read and its gradient comes back as its owner's shard; no
    all-gather has the ruled stack's shape."""
    rng = np.random.default_rng(4)
    params = {"dense": {"kernel": jnp.asarray(rng.normal(size=(D, D)),
                                              jnp.float32)},
              "gate": {"kernel": jnp.asarray(rng.normal(size=(D, 4)),
                                             jnp.float32)},
              "experts": {"w": jnp.asarray(rng.normal(size=(4, D, 3 * D)),
                                           jnp.float32)}}

    def loss(params, batch):
        # the stack under shard_map over 'data', as an expert layer reads it
        from jax import shard_map

        x, y = batch
        h = jnp.tanh(x @ params["dense"]["kernel"])

        def local(h, w):        # [B / 4, D], [1, D, 3D]: this chip's expert
            return (h @ w[0])[:, :D]

        out = shard_map(local, mesh=ps.api.current_context().mesh,
                        in_specs=(P("data"), P("data")),
                        out_specs=P("data"))(h, params["experts"]["w"])
        return jnp.mean((out - y) ** 2) + 0 * jnp.sum(
            params["gate"]["kernel"])

    ps.init(backend="tpu", mesh_shape={"data": 4})
    try:
        store = ps.KVStore(
            optimizer="sgd", learning_rate=0.1, placement="sharded",
            partition_rules=[(r"experts/w$", ("data", None, None))])
        store.init(params)
        step = store.make_step(loss)
        hlo = step.compiled_text(store.shard_batch(_batches(1)[0]))
        gathers = [line for line in hlo.splitlines()
                   if "all-gather" in line and " = " in line]
        assert any(f"[{D},{D}]" in g for g in gathers), gathers
        assert not [g for g in gathers if f"{D},{3 * D}]" in g], gathers
    finally:
        ps.shutdown()
