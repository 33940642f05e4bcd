"""Compiled-collective assertions — VERDICT r4 item 5, SURVEY.md §8 P1.

Numerics tests cannot tell an efficient lowering from a degenerate one:
'sharded' (ZeRO-1) placement that silently regressed to
all-reduce-everything + no sharding would still produce bit-correct
parameters while moving ~Nx the bytes. Only the compiled (post-GSPMD) HLO
shows the difference, so these tests pin it textually:

- replicated: gradients ride one (variadic) full-size all-reduce; no
  parameter all-gather exists (nothing is sharded, nothing to gather).
- sharded: parameters materialize via all-gather at their full shapes, the
  LARGEST gradient is never full-size all-reduced (its reduction must be
  scatter-shaped: a literal reduce-scatter on TPU, or GSPMD's all-to-all +
  local-sum decomposition on the CPU backend), and the stored param
  buffers are physically shard-shaped.
- sharded + tensor parallel: collectives run on BOTH mesh axes (distinct
  replica_groups), i.e. the model axis really partitions the matmuls.

The exact spelling of a scatter-reduction is backend-dependent, so the
assertions pin the invariants, not one backend's instruction choice. Since
the fused step states its shardings (PR 26) a gradient is summed at its
parameter's gathered shape and then constrained to the stored shard. The
partitioner writes that as an all-reduce whose result every consumer
dynamic-slices to its own shard; the chip's compiler fuses the pair into
one reduce-scatter (``all-reduce-scatter`` fusions in the v5e's HLO,
PERF.md §6), the CPU pipeline has no such pass and leaves it spelled out.
``_only_sliced`` accepts exactly that spelling and no other full-size
all-reduce. (Before PR 26 the CPU partitioner chose an all-to-all
decomposition for w1's gradient: still accepted.)

- sharded, transformer shapes ([B,S,H] against [H,4H] and [4H,H]: the
  weights' 'data' dim is the matmul's output dim, as in BERT): no collective
  carries the batch or the sequence. On the chip the compiler had resolved
  those matmuls by moving activations, twenty times the bytes.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.kv.sparse import SparseEmbedding

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual mesh"
)

W1, W2 = (256, 256), (256, 128)  # largest param 65536 elems, second 32768


def _make_run(placement, model_axis=1):
    if model_axis > 1:
        ps.init(backend="tpu",
                mesh_shape={"data": 8 // model_axis, "model": model_axis})
    else:
        ps.init(backend="tpu")
    params = {"w1": jnp.zeros(W1), "w2": jnp.zeros(W2)}
    store = ps.KVStore(optimizer="momentum", learning_rate=0.1, momentum=0.9,
                       placement=placement)
    store.init(params)

    def loss_fn(p, batch):
        x, y = batch
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    run = store.make_step(loss_fn)
    batch = store.shard_batch((jnp.zeros((64, W1[0])), jnp.zeros((64, W2[1]))))
    return store, run, batch


def _dims(result):
    """Every array shape in an instruction's result type, as dim tuples
    (a variadic collective's tuple gives one per element)."""
    return [tuple(int(d) for d in sh.split(",") if d)
            for sh in re.findall(r"\w+\[([0-9,]*)\]", result)]


def _collective_lines(txt):
    """[(op, [element_counts...], line)] for every collective instruction.
    Variadic (tuple-shaped) collectives contribute every element shape."""
    out = []
    ops = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all")
    for line in txt.splitlines():
        line = line.strip()
        m = re.match(r"%?\S+ = (.+?) (all-reduce|reduce-scatter|all-gather|"
                     r"all-to-all)(-start)?\(", line)
        if not m:
            continue
        out.append((m.group(2),
                    [int(np.prod(dims)) for dims in _dims(m.group(1))], line))
    return out


def _reads(text, value):
    """The instructions of ``text`` that take ``value`` as an operand."""
    operand = re.compile(re.escape(value) + r"[,)]")
    return [ln for ln in text.splitlines()
            if operand.search(ln.partition(" = ")[2])]


def _only_sliced(txt, line, n):
    """Whether every ``n``-element result of the all-reduce on ``line`` is
    read only by dynamic-slices, directly or as a fusion's parameter: the
    spelled-out reduce-scatter (all-reduce, then each device keeps its
    shard). A consumer that reads the whole tensor makes it a real
    full-size all-reduce."""
    name, result = re.match(r"(%\S+) = (.+?) all-reduce", line).groups()
    shapes = _dims(result)
    values = [name]
    if len(shapes) > 1:  # variadic: the pieces come out of the tuple
        values = []
        for i, dims in enumerate(shapes):
            if int(np.prod(dims)) == n:
                piece = re.search(
                    r"(%\S+) = \S+ get-tuple-element\(" + re.escape(name)
                    + r"\), index=" + str(i) + r"\b", txt)
                if not piece:
                    return False
                values.append(piece.group(1))
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^(%\S+) \([^\n]*\{\n(.*?)^\}", txt, re.M | re.S)}
    for value in values:
        users = _reads(txt, value)
        if not users:
            return False
        for ln in users:
            if " dynamic-slice(" in ln:
                continue
            fusion = re.search(r" fusion\((.*?)\), kind=.*calls=(%[\w.\-]+)",
                               ln)
            if not fusion:
                return False
            at = [o.strip() for o in fusion.group(1).split(",")].index(value)
            inner = bodies[fusion.group(2)]
            param = re.search(r"(%\S+) = \S+ parameter\(" + str(at) + r"\)",
                              inner).group(1)
            inside = _reads(inner, param)
            if not inside or any(" dynamic-slice(" not in x for x in inside):
                return False
    return True


def test_replicated_is_one_full_allreduce_no_gather():
    store, run, batch = _make_run("replicated")
    txt = run.compiled_text(batch)
    coll = _collective_lines(txt)
    ar_elems = sum(sum(sizes) for op, sizes, _ in coll if op == "all-reduce")
    # every grad element is all-reduced (w1 + w2 + the loss scalar ride it)
    assert ar_elems >= np.prod(W1) + np.prod(W2), coll
    # nothing is sharded, so nothing may be gathered or scattered
    assert not any(op in ("all-gather", "reduce-scatter")
                   for op, _, _ in coll), coll
    # and the stored buffers are physically full-shaped on each device
    w1 = store.params()["w1"]
    assert w1.addressable_shards[0].data.shape == W1


def test_sharded_scatters_largest_grad_and_gathers_params():
    store, run, batch = _make_run("sharded")
    txt = run.compiled_text(batch)
    coll = _collective_lines(txt)
    # params must materialize from shards: full-shape all-gathers exist
    ag_sizes = {s for op, sizes, _ in coll if op == "all-gather"
                for s in sizes}
    assert int(np.prod(W1)) in ag_sizes, coll
    assert int(np.prod(W2)) in ag_sizes, coll
    # the largest gradient must NOT be full-size all-reduced — that is the
    # degenerate pattern (replicated-grade traffic with extra gathers).
    # Its reduction must be scatter-shaped: literal reduce-scatter, or the
    # CPU partitioner's all-to-all decomposition.
    n = int(np.prod(W1))
    full_w1_allreduce = [line for op, sizes, line in coll
                         if op == "all-reduce" and n in sizes
                         and not _only_sliced(txt, line, n)]
    assert not full_w1_allreduce, full_w1_allreduce
    assert any(op in ("reduce-scatter", "all-to-all")
               or (op == "all-reduce" and n in sizes)
               for op, sizes, _ in coll), coll
    # and the stored buffers are physically shard-shaped (dim0 / 8)
    w1 = store.params()["w1"]
    assert w1.addressable_shards[0].data.shape == (W1[0] // 8, W1[1])


def test_sharded_tp_collectives_ride_both_axes():
    """With a data=4 x model=2 mesh, activation collectives must run on the
    model axis AND grad/param movement on the data axis — two distinct
    replica_groups partitions in the compiled text. A TP placement that
    silently replicated over 'model' would leave only one."""
    store, run, batch = _make_run("sharded", model_axis=2)
    txt = run.compiled_text(batch)
    coll = _collective_lines(txt)
    groups = set()
    for _, _, line in coll:
        m = re.search(r"replica_groups=(\S+?),", line)
        if m:
            groups.add(m.group(1))
    assert len(groups) >= 2, (groups, coll)
    # params shard over BOTH axes: w1 [256,256] splits model on one dim,
    # data (ZeRO) on the other -> per-device shard 1/8 of the elements
    w1 = store.params()["w1"]
    assert int(np.prod(w1.addressable_shards[0].data.shape)) == \
        int(np.prod(W1)) // 8


def test_sharded_largest_param_never_pays_double_traffic():
    """The byte-level reason sharded placement exists, pinned on the tensor
    where it dominates: the LARGEST param must never hit the degenerate
    combination (full-size all-reduce of its grad AND full-size all-gather
    of its value) — that is replicated-grade reduce traffic plus a gather
    on top. Smaller tensors are left to the partitioner's cost model (the
    CPU backend legally picks all-gather + partial all-reduce for w2)."""
    store, run, batch = _make_run("sharded")
    txt = run.compiled_text(batch)
    coll = _collective_lines(txt)
    n = int(np.prod(W1))
    has_full_ar = any(op == "all-reduce" and n in sizes
                      and not _only_sliced(txt, line, n)
                      for op, sizes, line in coll)
    has_full_ag = any(op == "all-gather" and n in sizes
                      for op, sizes, _ in coll)
    assert has_full_ag and not has_full_ar, (
        f"largest param ({n} elems): full all-gather={has_full_ag}, "
        f"full all-reduce={has_full_ar} — degenerate pattern: {coll}"
    )


# -- transformer shapes: the weights' 'data' dim is the matmul's output dim ---

# global batch, sequence, hidden: chosen so that no activation dim (40, 5,
# 12, their products) equals a parameter dim (64, 256) or a shard of one
B, S, H = 40, 12, 64
PARAM_SHAPES = {"w_in": (H, 4 * H), "b_in": (4 * H,),
                "w_out": (4 * H, H), "b_out": (H,)}


def _transformer_run(placement, optimizer="momentum", **opt):
    """One feed-forward block on [B,S,H] activations: [H,4H] then [4H,H].
    ``param_sharding`` puts 'data' on the 4H of both matrices (8 divides it
    and it is the largest dim), the output dim of the first matmul."""
    ps.init(backend="tpu")
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(rng.normal(0, 0.05, shape), jnp.float32)
              for k, shape in PARAM_SHAPES.items()}
    store = ps.KVStore(optimizer=optimizer, placement=placement, **opt)
    store.init(params)

    def loss_fn(p, batch):
        x, y = batch
        h = jax.nn.gelu(x @ p["w_in"] + p["b_in"])
        return jnp.mean((x + h @ p["w_out"] + p["b_out"] - y) ** 2)

    run = store.make_step(loss_fn)

    def batches(n):
        for _ in range(n):
            yield store.shard_batch((
                jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32),
                jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)))

    return store, run, batches


def _collective_shapes(txt):
    """[(op, [dims...], line)]: every result shape of every collective."""
    out = []
    for op, _, line in _collective_lines(txt):
        out.append((op, _dims(re.match(r"%?\S+ = (.+?) " + op, line).group(1)),
                    line))
    return out


def test_sharded_transformer_moves_weights_only():
    """ZeRO-1 on transformer shapes: every parameter is all-gathered at its
    full shape, and no collective carries the batch or the sequence, as a
    dim or as the element count of an activation."""
    store, run, batches = _transformer_run("sharded", "lamb",
                                           learning_rate=1e-2)
    batch = next(batches(1))
    coll = _collective_shapes(run.compiled_text(batch))
    activation_dims = {B, B // 8, S, B * S, B // 8 * S}
    activation_sizes = {b * S * h for b in (B, B // 8) for h in (H, 4 * H)}
    moved = [line for _, shapes, line in coll for dims in shapes
             if activation_dims & set(dims)
             or int(np.prod(dims)) in activation_sizes]
    assert not moved, moved
    gathered = {dims for op, shapes, _ in coll if op == "all-gather"
                for dims in shapes}
    assert set(PARAM_SHAPES.values()) <= gathered, (gathered, coll)
    # parameters and optimizer state leave the step as they were stored
    engine = store._engine
    before = jax.tree_util.tree_map(lambda x: x.sharding, engine._state)
    _, params = run(batch)
    for k, sharding in engine._shardings.items():
        assert "data" in tuple(sharding.spec), (k, sharding)
        assert params[k].sharding.is_equivalent_to(sharding, params[k].ndim)
    after = jax.tree_util.tree_map(lambda x: x.sharding, engine._state)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b, x: a.is_equivalent_to(b, x.ndim), before, after,
        engine._state))


def test_sharded_bert_labelled_head_moves_weights_only():
    """The four-device sharded BERT step with the head on the labelled
    positions (``make_mlm_loss_fn``): the rows are picked inside groups of
    whole consecutive sequences, so the batch's sharding carries through the
    selection and the collectives stay weights, gradients and scalars (the
    labels' count, the trips). No collective carries the sequence, a
    group's positions, a trip's rows or the batch's positions; and the loss
    is the one-device loss."""
    from ps_tpu.models.bert import (BertConfig, BertMLM, head_groups,
                                    make_mlm_loss_fn)

    b, s = 8, 512  # four groups of two sequences, one a device
    cfg = BertConfig.tiny(vocab_size=600, hidden_size=48, max_len=s,
                          intermediate_size=96)
    model = BertMLM(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(110, 600, size=(b, s)).astype(np.int32)
    masked = rng.random((b, s)) < 0.15
    batch = {"input_ids": np.where(masked, 103, ids).astype(np.int32),
             "labels": np.where(masked, ids, -100).astype(np.int32),
             "attention_mask": np.ones_like(ids)}
    params = model.init(jax.random.key(0), jnp.zeros((2, s), jnp.int32),
                        jnp.ones((2, s), jnp.int32))["params"]
    loss_fn = make_mlm_loss_fn(model)
    want = float(jax.jit(loss_fn)(params, batch))

    ps.init(backend="tpu", mesh_shape={"data": 4})
    store = ps.KVStore(optimizer="lamb", learning_rate=1e-3,
                       placement="sharded")
    store.init(params)
    run = store.make_step(loss_fn)
    placed = store.shard_batch(batch)
    per_group, rows = head_groups(b, s)
    assert (b // per_group, rows) == (4, 256)
    # no parameter has one of these as a dim (600, 48, 96, 4, 12, 2); the
    # sequence is a dim of the position embedding and of nothing else moved
    activation_dims = {s, rows, per_group * s, b * s}
    txt = run.compiled_text(placed)
    coll = _collective_shapes(txt)
    moved = [line for _, shapes, line in coll for dims in shapes
             if activation_dims & set(dims)
             and dims not in ((s, 48), (1, s, 48))]
    assert not moved, moved
    assert not re.search(r" (all-to-all|collective-permute)(-start)?\(", txt)
    gathered = {dims for op, shapes, _ in coll if op == "all-gather"
                for dims in shapes}
    assert (600, 48) in gathered, gathered  # the tied embedding, whole
    loss, _ = run(placed)
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)


def test_sharded_matches_replicated_on_transformer_shapes():
    """Eight LAMB steps: stating the shardings changes where the sums are
    taken, not the numbers (tests/test_bert.py's tolerance for sharded
    trust-ratio norms)."""
    results = {}
    for placement in ("replicated", "sharded"):
        store, run, batches = _transformer_run(placement, "lamb",
                                               learning_rate=1e-2)
        losses = [float(run(b)[0]) for b in batches(8)]
        results[placement] = (losses, jax.device_get(store.params()))
        ps.shutdown()
    (rep_losses, rep), (sh_losses, sh) = (results["replicated"],
                                          results["sharded"])
    assert sh_losses[-1] < sh_losses[0]
    np.testing.assert_allclose(sh_losses, rep_losses, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
        sh, rep)


@pytest.mark.parametrize("kind", ["make_step", "make_composite_step"])
@pytest.mark.parametrize("placement,devices,stated", [
    ("replicated", 8, False),
    ("replicated", 1, False),
    ("sharded", 1, False),
    ("sharded", 8, True),
])
def test_step_states_shardings_only_when_sharded_across_devices(
        placement, devices, stated, kind):
    """Plain data parallel and one-device steps lower without a single
    sharding constraint (their program is what it was before the step
    stated anything); ZeRO-1 across devices lowers with them. With tables
    or without: it is one step (ps_tpu/kv/fused.py), and the composite
    step's dense tower is placed by the same lines."""
    ps.init(backend="tpu", mesh_shape={"data": devices})
    store = ps.KVStore(optimizer="momentum", learning_rate=0.1,
                       placement=placement)
    store.init({"w1": jnp.zeros(W1), "w2": jnp.zeros(W2)})
    batch = store.shard_batch((jnp.zeros((64, W1[0])), jnp.zeros((64, W2[1]))))
    if kind == "make_step":
        run = store.make_step(lambda p, b: jnp.mean(
            (jnp.tanh(b[0] @ p["w1"]) @ p["w2"] - b[1]) ** 2))
    else:
        emb = SparseEmbedding(64, W1[0], optimizer="sgd")
        emb.init(jax.random.key(0))
        run = ps.make_composite_step(
            store, {"emb": emb},
            lambda p, rows, b: jnp.mean(
                (jnp.tanh((b[0] + rows["emb"]) @ p["w1"]) @ p["w2"]
                 - b[1]) ** 2),
            lambda b: {"emb": jnp.arange(64, dtype=jnp.int32)})
    txt = run.lower(batch).as_text()
    assert ("sharding_constraint" in txt or "@Sharding" in txt) == stated
