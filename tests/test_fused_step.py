"""The one fused step (``ps_tpu/kv/fused.py``) behind its two entry points,
``KVStore.make_step`` and ``ps.make_composite_step``: what the composite
path gets from sharing the dense path's lines, what the dense path must not
pay for the tables it does not have, and where the seam between ``train.py``
and the ``kv`` package lies.
"""

import ast
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.backends.tpu import TpuBackend
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.parallel import collectives

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCH, ROWS, DIM = 16, 64, 4
PARAMS = {"w": jnp.full((DIM, 8), 0.5), "v": jnp.ones((8, 1))}


def _store(devices, **store_kw):
    ps.init(backend="tpu", mesh_shape={"data": devices})
    store = ps.KVStore(**{"optimizer": "sgd", "learning_rate": 0.1,
                          **store_kw})
    store.init(PARAMS)
    return store


def _dense(devices=8, **store_kw):
    """``(store, {}, run, batch)`` of a tiny ``make_step``."""
    store = _store(devices, **store_kw)
    run = store.make_step(lambda p, b: jnp.mean(
        (jnp.tanh(b["x"] @ p["w"]) @ p["v"] - b["y"]) ** 2))
    batch = _batch()
    del batch["ids"]  # jit drops an argument the program never reads
    return store, {}, run, store.shard_batch(batch)


def _composite(devices=8, **store_kw):
    """``(store, tables, run, batch)`` of a tiny ``make_composite_step``."""
    store = _store(devices, **store_kw)
    emb = SparseEmbedding(ROWS, DIM, optimizer="adagrad", learning_rate=0.05)
    emb.init(jax.random.key(1), scale=0.5)
    run = ps.make_composite_step(
        store, {"emb": emb},
        lambda p, rows, b: jnp.mean(
            (jnp.tanh((rows["emb"] + b["x"]) @ p["w"]) @ p["v"]
             - b["y"]) ** 2),
        lambda b: {"emb": b["ids"]})
    return store, {"emb": emb}, run, store.shard_batch(_batch())


def _batch():
    rng = np.random.default_rng(0)
    return {"ids": rng.integers(0, ROWS, BATCH).astype(np.int32),
            "x": rng.normal(size=(BATCH, DIM)).astype(np.float32),
            "y": rng.normal(size=(BATCH, 1)).astype(np.float32)}


BUILDERS = {"make_step": _dense, "make_composite_step": _composite}


@pytest.fixture(autouse=True)
def _shutdown():
    yield
    ps.shutdown()


def test_composite_aggregate_sum_is_mean_scaled_by_the_worker_count():
    """``KVStore(aggregate="sum")`` reaches the composite step's dense half
    as it reaches ``make_step``'s (tests/test_parity_gaps.py): under SGD a
    gradient times the worker count is the learning rate times it. The
    tables have no aggregation of their own and must not move."""
    nw, out = 4, {}
    for kind, kw in (("sum", {"aggregate": "sum", "learning_rate": 0.1}),
                     ("mean", {"aggregate": "mean",
                               "learning_rate": 0.1 * nw})):
        store, tables, run, batch = _composite(devices=nw, **kw)
        for _ in range(3):
            run(batch)
        out[kind] = (jax.device_get(store.params()),
                     np.asarray(tables["emb"].table))
        ps.shutdown()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7),
        out["sum"], out["mean"])
    # and the scale is there at all: three steps at 0.1 are not at 0.4
    store, _, run, batch = _composite(devices=nw, learning_rate=0.1)
    for _ in range(3):
        run(batch)
    assert not np.allclose(jax.device_get(store.params())["w"],
                           out["sum"][0]["w"], rtol=1e-3)


def _entry_signature(lowered):
    """(number of parameters, number of results) of the lowered module's
    public entry function."""
    sig = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{$",
                    lowered.as_text(), re.M | re.S)
    return (len(re.findall(r"%arg\d+:", sig.group(1))),
            len(re.findall(r"tensor<", sig.group(2))))


def test_dense_step_has_no_argument_and_no_result_for_absent_tables():
    """The dense step is the composite step with no tables: the empty
    dicts add nothing to the program's entry."""
    store, _, run, batch = _dense(optimizer="adam")
    n_params = len(jax.tree_util.tree_leaves(PARAMS))
    n_state = len(jax.tree_util.tree_leaves(
        store._engine.get_tree_and_state()[1]))
    n_batch = len(jax.tree_util.tree_leaves(batch))
    assert _entry_signature(run.lower(batch)) == (
        n_params + n_state + n_batch, n_params + n_state + 1)  # + the loss
    ps.shutdown()
    # one table brings itself and its row-wise state in, and back out with
    # the count of dropped rows
    store, tables, run, batch = _composite(optimizer="adam")
    n_table = 1 + len(jax.tree_util.tree_leaves(tables["emb"].state()))
    n_batch = len(jax.tree_util.tree_leaves(batch))
    assert _entry_signature(run.lower(batch)) == (
        n_params + n_state + n_table + n_batch,
        n_params + n_state + 1 + n_table + 1)


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_run_asks_the_failure_detector_before_it_launches(kind, monkeypatch):
    """A dead peer is a typed error from ``run``, not a collective that
    never returns: the wrapper consults ``check_health`` first."""
    class PeerDied(RuntimeError):
        pass

    def dead():
        raise PeerDied("worker 3")

    # the store takes its backend's check when it is made
    monkeypatch.setattr(TpuBackend, "check_health", lambda self: dead())
    store, _, run, batch = BUILDERS[kind]()
    with pytest.raises(PeerDied):
        run(batch)
    assert store.step == 0


@pytest.mark.parametrize("placement", ["replicated", "sharded"])
@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_counters_after_three_steps_are_three_times_the_constants(
        kind, placement):
    """What a step counts is fixed when the tree is registered and the
    id lists are first seen; three steps count three times that."""
    k = 8
    store, tables, run, batch = BUILDERS[kind](devices=k,
                                               placement=placement)
    for _ in range(3):
        run(batch)
    tree = collectives.tree_bytes(PARAMS)
    assert store.step == 3
    assert (store.bytes_pushed, store.bytes_pulled) == (3 * tree, 3 * tree)
    ring = {"replicated": collectives.allreduce_bytes(PARAMS, k),
            "sharded": collectives.reduce_scatter_bytes(PARAMS, k)
            + collectives.all_gather_bytes(PARAMS, k)}[placement]
    assert ring > 0 and store.collective_bytes == 3 * ring
    assert store._engine.apply_count == 3
    for emb in tables.values():
        rows = BATCH * DIM * 4
        assert (emb.bytes_pushed, emb.bytes_pulled) == (3 * rows, 3 * rows)
        assert (emb.push_count, emb.rows_pushed) == (3, 3 * BATCH)
        # gather exchange: every (id, row gradient) pair to every shard
        assert emb.collective_bytes == 3 * int(
            BATCH * 4 * (DIM + 1) * (k - 1) / k)
        assert emb.dropped_rows == 0


def _donated(store, tables):
    """The leaves of the step's four donated arguments as they are now."""
    return jax.tree_util.tree_leaves(
        (store._engine.get_tree_and_state(),
         {n: (emb.table, emb.state()) for n, emb in tables.items()}))


def _same_place(leaves, shardings):
    # equivalent, not equal: the compiler spells a table's P('data', None)
    # as P('data'), which is the same placement and the same jit cache key
    return all(x.sharding.is_equivalent_to(s, x.ndim)
               for x, s in zip(leaves, shardings, strict=True))


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_sharded_step_hands_back_the_shardings_it_took(kind):
    """Every step's donated outputs are the next step's inputs. The dense
    tree's shardings are stated; the tables' are the compiler's to choose,
    and it must choose the ones they came in with: otherwise step 2 sees
    new argument shardings and compiles a second program, or reshards."""
    compiled = []

    def heard(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(heard)
    try:
        store, tables, run, batch = BUILDERS[kind](
            devices=8, optimizer="adam", placement="sharded")
        before = [x.sharding for x in _donated(store, tables)]
        assert not store._engine.get_tree_and_state()[0][
            "w"].sharding.is_fully_replicated
        run(batch)
        assert _same_place(_donated(store, tables), before)
        n = len(compiled)
        assert n >= 1  # the listener hears this jax's compiles
        for _ in range(2):
            run(batch)
        assert _same_place(_donated(store, tables), before)
        assert len(compiled) == n
    finally:
        jax.monitoring.unregister_event_duration_listener(heard)


# -- the pull of the distinct rows (ISSUE 55) -----------------------------------

#: the Wide&Deep cell's rehearsal size (benchmark/configs, "rehearse")
WD_BATCH, WD_FEATURES, WD_VOCAB, WD_DIM = 64, 26, 1000, 8
WD_ROWS, WD_PAIRS = WD_FEATURES * WD_VOCAB, WD_BATCH * WD_FEATURES


def _wide_deep(devices, tier, names=("deep", "wide"), ids_fn=None):
    """The lowered text of the composite step of ``names``' tables at the
    rehearsal size: ``ids_fn`` defaults to the model's, which hands every
    table the same array."""
    from ps_tpu.models.wide_deep import (WideDeep, WideDeepConfig,
                                         make_ids_fn, make_wide_deep_loss_fn)

    ps.init(backend="tpu", mesh_shape={"data": devices})
    cfg = WideDeepConfig(num_dense=13, num_sparse=WD_FEATURES,
                         per_feature_vocab=WD_VOCAB, embed_dim=WD_DIM,
                         mlp=(32, 16))
    model = WideDeep(cfg)
    shape = (2, cfg.num_sparse, cfg.embed_dim)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, cfg.num_dense)),
                        jnp.zeros(shape), jnp.zeros(shape[:2] + (1,))
                        )["params"]
    dense = ps.KVStore(optimizer="adam", learning_rate=0.001,
                       placement="sharded")
    dense.init(params)
    tables = {}
    for i, (name, dim, rule) in enumerate((("deep", WD_DIM, "adagrad"),
                                           ("wide", 1, "sgd"))):
        if name in names:
            tables[name] = SparseEmbedding(cfg.total_rows, dim,
                                           optimizer=rule, fused_apply=tier)
            tables[name].init(jax.random.key(i + 1))
    loss_fn = make_wide_deep_loss_fn(model)
    if "wide" not in names:
        def loss_fn(p, rows, b, whole=loss_fn):
            return whole(p, {**rows, "wide": rows["deep"][..., :1]}, b)
    run = ps.make_composite_step(dense, tables, loss_fn,
                                 ids_fn or make_ids_fn(cfg))
    rng = np.random.default_rng(0)
    batch = {"dense": rng.normal(size=(WD_BATCH, 13)).astype(np.float32),
             "sparse": rng.integers(0, WD_VOCAB, (WD_BATCH, WD_FEATURES)
                                    ).astype(np.int32),
             "label": rng.integers(0, 2, WD_BATCH).astype(np.float32)}
    text = run.lower(dense.shard_batch(batch)).as_text()
    ps.shutdown()
    return text.splitlines(), cfg


def _indent(line):
    return len(line) - len(line.lstrip())


def _enclosing(lines, i):
    """The op (or function) whose region line ``i`` stands in: the nearest
    line above that is indented less and is no ``cond {`` / ``} do {`` /
    ``}, {`` between an op's regions."""
    for j in range(i - 1, -1, -1):
        if _indent(lines[j]) < _indent(lines[i]) and (
                " = " in lines[j] or "func.func" in lines[j]):
            return lines[j]
    return ""


def _gathers_of(lines, operand):
    """``(gather line, the op enclosing it or its function's call)`` of
    every ``stablehlo.gather`` that reads an ``operand``-typed tensor;
    ``jnp.take`` lowers to a private function around its gather."""
    found = []
    for i, ln in enumerate(lines):
        if "stablehlo.gather" in ln and f": (tensor<{operand}>" in ln:
            func = _enclosing(lines, i)
            if "func.func private" in func:
                name = re.search(r"@(\w+)\(", func).group(1)
                calls = [j for j, c in enumerate(lines)
                         if f"call @{name}(" in c]
                found += [(ln, _enclosing(lines, j)) for j in calls]
            else:
                found.append((ln, func))
    return found


def test_composite_step_on_one_chip_gathers_a_tables_distinct_rows_once():
    """At the rehearsal size, one chip: the deep table is gathered by one
    ``gather`` of ``chunk_len(N)`` slots, ascending and stated so, in a
    ``while`` whose trip count comes from the data; the push has
    no gather of it. The only other one stands in the branch of a ``case``
    for a batch with an id the table lacks, and reads all N pairs as
    ``lookup`` does."""
    from ps_tpu.ops.sparse_apply import chunk_len

    lines, _ = _wide_deep(1, "auto")
    c = chunk_len(WD_PAIRS)
    assert WD_PAIRS > c  # a loop, not the one-chunk path
    gathers = _gathers_of(lines, f"{WD_ROWS}x{WD_DIM}xf32")
    in_case = [g for g, op in gathers if "stablehlo.case" in op]
    walked = [(g, op) for g, op in gathers if "stablehlo.case" not in op]
    assert len(in_case) == 1 and len(walked) == 1, gathers
    assert f"tensor<{WD_BATCH}x{WD_FEATURES}x{WD_DIM}xf32>" in in_case[0]
    gather, loop = walked[0]
    assert f"-> tensor<{c}x{WD_DIM}xf32>" in gather
    # (StableHLO's gather has no attribute for ``unique_indices``: the
    # hint is in the jaxpr and in the transpose's scatter only)
    assert "indices_are_sorted = true" in gather
    assert "stablehlo.while" in loop
    # the bound the counter is compared with enters the loop as a value
    # computed from the ids, not as a constant
    at = lines.index(loop)
    compare = next(ln for ln in lines[at:] if "stablehlo.compare" in ln)
    bound = re.search(r"LT, %\w+, (%\w+),", compare).group(1)
    init = re.search(rf"{re.escape(bound)} = (%\w+)", loop).group(1)
    assert not init.startswith("%c"), loop


def test_tables_handed_one_id_array_share_one_plan():
    """``ids_fn`` hands ``deep`` and ``wide`` the same array: the step
    sorts for one table (the ids, the distinct ids to the front, the
    pairs' slots) however many tables read it; handed two arrays it sorts
    for each."""
    def sorts(lines):
        # ``jnp.sort`` is a call of a private function around its op
        return sum("call @sort" in ln or (
            "stablehlo.sort" in ln
            and "func.func private" not in _enclosing(lines, i))
            for i, ln in enumerate(lines))

    one, cfg = _wide_deep(1, "auto", names=("deep",))
    both, _ = _wide_deep(1, "auto")
    apart, _ = _wide_deep(1, "auto", ids_fn=lambda b: {
        "deep": cfg.global_ids(b["sparse"]),
        "wide": cfg.global_ids(b["sparse"])})
    assert sorts(one) == 3
    assert sorts(both) == sorts(one)
    assert sorts(apart) == 2 * sorts(one)


@pytest.mark.parametrize("devices,tier", [(8, "auto"), (1, "off"),
                                          (8, "off")])
def test_composite_step_across_chips_or_off_looks_every_pair_up(devices, tier):
    """Where the owner's distinct rows are known only after the exchange,
    and on the tier that promises the legacy program, the step is
    ``lookup`` + ``apply`` as before: one gather of all N pairs a table,
    at the top of the program, no plan before the loss and no ``case``.
    (Against the parent commit's text, by hand, PR 55: the 'off' tier's
    byte for byte on one device and on eight; the fused tier's on eight
    but for the order in which ``fused_sparse_apply`` states its sort and
    its sums.)"""
    lines, _ = _wide_deep(devices, tier)
    assert not [ln for ln in lines if "stablehlo.case" in ln]
    pulls = [(g, op) for g, op in _gathers_of(
        lines, f"{WD_ROWS}x{WD_DIM}xf32")
        if f"tensor<{WD_BATCH}x{WD_FEATURES}x{WD_DIM}xf32>" in g]
    assert len(pulls) == 1
    assert "func.func public @main" in pulls[0][1]
    first_sort = next((i for i, ln in enumerate(lines)
                       if "stablehlo.sort" in ln), len(lines))
    assert first_sort > lines.index(pulls[0][1])


# -- the seam -----------------------------------------------------------------

def _private_attributes_of_others(tree):
    """``x._name`` reads and writes where ``x`` is not ``self`` / ``cls``."""
    return [f"{ast.unparse(node)} (line {node.lineno})"
            for node in ast.walk(tree)
            if isinstance(node, ast.Attribute)
            and node.attr.startswith("_") and not node.attr.startswith("__")
            and not (isinstance(node.value, ast.Name)
                     and node.value.id in ("self", "cls"))]


def _imported_modules(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_train_reaches_into_no_neighbour_and_kv_never_imports_train():
    """``ps_tpu/train.py`` is an entry point: it touches no underscore
    attribute of another module's object, and nothing under ``ps_tpu/kv/``
    imports it, so the fused step stays written in ``kv`` alone."""
    with open(os.path.join(_REPO, "ps_tpu", "train.py")) as f:
        train = ast.parse(f.read())
    assert _private_attributes_of_others(train) == []
    for name in ("jit", "program_span"):  # no program, no span of its own
        assert not [n for n in ast.walk(train)
                    if isinstance(n, ast.Attribute) and n.attr == name]
    kv = os.path.join(_REPO, "ps_tpu", "kv")
    for fname in sorted(os.listdir(kv)):
        if fname.endswith(".py"):
            with open(os.path.join(kv, fname)) as f:
                imported = set(_imported_modules(ast.parse(f.read())))
            assert "ps_tpu.train" not in imported, fname
