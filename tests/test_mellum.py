"""Mellum (``ps_tpu/models/mellum.py``: every layer sparse behind attention of
two kinds, each rotated by its own table) and the token exchange of
``ps_tpu/ops/moe.py`` against the plain reference
(``benchmark/families/mellum_reference.py``: all experts in one place), at
small sizes on the CPU's virtual devices, and the pieces of the benchmark
family (``benchmark/families/mellum_step.py``): the operations from shapes, the
configuration and the cell.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.families import mellum_reference as reference
from benchmark.families import mellum_step
from benchmark.layer_metrics import decoder
from ps_tpu.models import mellum
from ps_tpu.ops import moe

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 2e-5
CELL = "mellum2-12b-a2.5b.s8192.b1.zipf.x4"
CONFIG = "benchmark/configs/mellum2-12b-a2.5b.json"
WINDOWED, FULL = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
#: the cell's four-layer stack in small: a window of 48 keys in 128, 4 query
#: heads on 2 K/V heads, 16 experts (4 a chip of four), 4 picks
SIZES = dict(
    vocab_size=256, hidden_size=64, moe_intermediate_size=32,
    num_hidden_layers=4, layer_types=[WINDOWED] * 3 + [FULL],
    mlp_layer_types=["sparse"] * 4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, sliding_window=48, num_experts=16,
    num_experts_per_tok=4, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_parameters={FULL: YARN, WINDOWED: {"rope_type": "default",
                                            "rope_theta": 500000}},
    attention_bias=False, hidden_act="silu", tie_word_embeddings=False,
    use_sliding_window=True, qk_norm=True, router_aux_loss_coef=0.001,
    dtype="float32")
CHIPS = 4
#: XLA:CPU's scheduler for concurrency lets a layer's recomputed exchange
#: start beside another layer's trips, and its in-process collectives then
#: meet under one key (a rendezvous of five, or none). A chip runs its
#: collectives in one order; the CPU is told to keep to the program's.
IN_PROGRAM_ORDER = {"xla_cpu_enable_concurrency_optimized_scheduler": False}


def _json(path):
    with open(os.path.join(_REPO, path)) as f:
        return json.load(f)


def _setup(seed=0, batch=4, seq=128, skew=0.0, **changes):
    sizes = {**SIZES, **changes}
    cfg = mellum.MellumConfig.from_dict(sizes)
    params = jax.jit(lambda k: mellum.init_params(k, cfg))(
        jax.random.key(seed))
    # away from the cell's 0.02: every layer then matters to the loss
    params = jax.tree_util.tree_map(lambda x: 5 * x if x.ndim > 1 else x,
                                    params)
    if skew:
        # the four experts chip 2 holds score wider than the others: where
        # one of them scores high it is picked, about twice an even share
        for lp in params["layers"].values():
            k = lp["router"]["kernel"]
            lp["router"]["kernel"] = k.at[:, 8:12].multiply(skew)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, sizes["vocab_size"],
                       size=(batch, seq + 1)).astype(np.int32)
    return sizes, cfg, params, {"inputs": ids[:, :-1], "targets": ids[:, 1:]}


def _mesh():
    return Mesh(np.array(jax.devices()[:CHIPS]), ("data",))


def _on_mesh(mesh, params, batch):
    """The expert stacks split by expert, everything else whole on every
    chip, each chip its own sequences."""
    def place(path, x):
        split = "experts" in [p.key for p in path]
        return jax.device_put(x, NamedSharding(mesh, P("data") if split
                                               else P()))

    return (jax.tree_util.tree_map_with_path(place, params),
            jax.tree.map(lambda x: jax.device_put(
                x, NamedSharding(mesh, P("data"))), batch))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def _worst(got, want):
    return max(jax.tree.leaves(jax.tree.map(_rel, got, want)))


def _plain(sizes, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, sizes), has_aux=True))(params)


# -- (ii) the tie of the exchange to the model --------------------------------

@pytest.mark.parametrize("skew,buffer", [(0.0, None), (6.0, 8)],
                         ids=["even", "skewed"])
def test_exchanged_layers_equal_the_one_device_layers_and_the_reference(
        skew, buffer, monkeypatch):
    """The whole stack with its expert layers under ``shard_map`` on four
    devices gives what the one-device stack over all 16 experts gives and
    what the uncut reference gives: the loss, its two terms, every expert's
    count and every gradient, the expert stacks' (made where the experts
    live) among them. At an even load one trip; at a load skewed towards
    chip 2 with a first buffer of one even share (``buffer``: the grouped
    matmul's tile made small, the multiple 1) and further ones of a quarter
    of it the further trips run, forward and backward, and no pair is
    dropped."""
    if buffer:
        monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", buffer)
        monkeypatch.setattr(moe, "EXCHANGE_ROWS_OVER_EVEN", 1)
        assert moe.further_rows(64, 4, CHIPS) == 16
    # another eps, another configuration: ``_layer``'s checkpoint keeps its
    # trace by its static arguments, and the even case's holds the unpatched
    # buffer
    sizes, cfg, params, batch = _setup(seed=2, seq=64, skew=skew,
                                       rms_norm_eps=2e-6 if buffer else 1e-6)
    pairs = 64 * cfg.num_experts_per_tok
    assert moe.exchange_rows(64, 4, CHIPS) == (64 if buffer else pairs)
    (want, want_aux), want_grads = _plain(sizes, params, batch)
    mesh = _mesh()
    with jax.default_matmul_precision("highest"):
        (one, one_aux), one_grads = jax.jit(jax.value_and_grad(
            mellum.make_loss_fn(cfg), has_aux=True))(params, batch)
        (got, aux), grads = jax.jit(
            jax.value_and_grad(mellum.make_loss_fn(cfg, mesh=mesh),
                               has_aux=True),
            compiler_options=IN_PROGRAM_ORDER)(*_on_mesh(mesh, params, batch))
    for loss, a, g in ((one, one_aux, one_grads), (got, aux, grads)):
        assert abs(float(loss) - float(want)) <= F32_TOL * float(want)
        for term in ("ce", "load_balance"):
            assert _rel(a[term], want_aux[term]) <= F32_TOL
        np.testing.assert_array_equal(np.asarray(a["expert_tokens"]),
                                      np.asarray(want_aux["expert_tokens"]))
        assert _worst(g, want_grads) <= 5 * F32_TOL
    # dropless: sent = received = computed = routed, whatever the load
    received = np.asarray(aux["received_rows"])              # [L, chips]
    assert (received.sum(-1) == CHIPS * pairs).all()
    owners = np.asarray(aux["expert_tokens"]).reshape(4, CHIPS, -1).sum(-1)
    np.testing.assert_array_equal(received, owners)
    # the senders' figure, from their routing: every chip sent all its pairs
    assert (np.asarray(aux["sent_rows"]) == pairs).all()
    assert (np.asarray(aux["exchange_rows"]) <= pairs).all()
    trips = np.asarray(aux["exchange_trips"])
    if buffer:
        # what passes the first buffer of 64 goes sixteen rows a trip
        assert trips.min() >= 1 and trips.max() >= 2
        assert received[:, 2].max() > CHIPS * 64
    else:
        assert (trips == 0).all()
    assert (np.asarray(one_aux["exchange_rows"]) == 0).all()


@pytest.mark.parametrize("lost", ["sizes", "trips"])
def test_received_rows_are_counted_where_they_arrive(lost, monkeypatch):
    """``received_rows`` is the owners' count of the group sizes the exchange
    handed their grouped matmuls, not the senders' reckoning: an exchange
    that loses what one source sent, or a loop that stops a trip short,
    reads fewer rows computed than routed, and ``step0_checks``'
    ``no_dropped_tokens`` says so."""
    eps = {"sizes": 3e-6, "trips": 4e-6}[lost]   # a trace of its own
    if lost == "sizes":
        whole = moe.to_owners

        def to_owners(rows, sizes, axis_name):
            rows, sizes = whole(rows, sizes, axis_name)
            return rows, sizes.at[1].set(0)

        monkeypatch.setattr(moe, "to_owners", to_owners)
        sizes, cfg, params, batch = _setup(seed=2, seq=64, rms_norm_eps=eps)
    else:
        monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", 8)
        monkeypatch.setattr(moe, "EXCHANGE_ROWS_OVER_EVEN", 1)
        monkeypatch.setattr(moe, "_while_below",
                            lambda live, first, more: first)
        sizes, cfg, params, batch = _setup(seed=2, seq=64, skew=6.0,
                                           rms_norm_eps=eps)
    pairs = CHIPS * 64 * cfg.num_experts_per_tok
    mesh = _mesh()
    _, aux = jax.jit(mellum.make_loss_fn(cfg, mesh=mesh))(
        *_on_mesh(mesh, params, batch))
    aux = jax.device_get(aux)
    assert (aux["expert_tokens"].sum(-1) == pairs).all()
    assert (aux["sent_rows"].sum(-1) == pairs).all()
    assert (aux["received_rows"].sum(-1) < pairs).all()
    checks = mellum_step.step0_checks(
        {**aux, "ce": 1.0, "load_balance": 1.0},
        {"expert_tokens": aux["expert_tokens"], "ce": 1.0,
         "load_balance": 1.0},
        {"embed/tokens": {"mu": 0.1 * np.ones(3),
                          "reference_grad": np.ones(3)}}, 1.0,
        {"clip_by_global_norm": 1.0, "b1": 0.9}, pairs)
    assert not checks["checks"]["no_dropped_tokens"]
    assert checks["detail"]["pairs_sent_per_layer"] == [pairs] * 4
    assert checks["checks"]["expert_counts_match_reference"]


def _tagged(t, width=8):
    """Token rows that say whose they are: row ``t`` holds ``t + 1`` in its
    first place (no live row is all zero), noise behind it."""
    x = np.random.default_rng(t).normal(size=(t, width)).astype(np.float32)
    x[:, 0] = 1 + np.arange(t)
    return jnp.asarray(x)


def _pairs_in(buffers, sizes, k, experts):
    """The pairs ``t * k + j`` that ``send``'s ``buffers`` [n, C, D] of
    ``_tagged`` rows hold, by owner, read from the rows alone: a row's token
    from its first place, its expert from where it lies among the owner's
    group ``sizes`` [n, E / n]. The rows behind an owner's live ones have to
    be zero."""
    buffers, sizes = np.asarray(buffers), np.asarray(sizes)
    held = sizes.shape[-1]
    found = []
    for d, (rows, mine) in enumerate(zip(buffers, sizes)):
        live = mine.sum()
        assert not rows[live:].any()
        tokens = rows[:live, 0].astype(np.int64) - 1
        of = d * held + np.repeat(np.arange(held), mine)
        picks = np.argmax(experts[tokens] == of[:, None], axis=-1)
        assert (experts[tokens, picks] == of).all()   # the token picked it
        found.append(tokens * k + picks)
    return found


def test_a_trip_sends_each_owner_its_next_rows_in_expert_order():
    """``ops/moe.py::send`` by numpy, through the buffers it fills: the
    buffer of owner ``d`` holds the next ``C`` pairs of ``d``'s experts in
    expert order (and in the routing's order inside an expert), the sizes are
    the experts' counts clipped to the trip, zeros lie behind the live rows,
    and the trip's rows add up to every pair once."""
    rng = np.random.default_rng(7)
    t, k, e, rows = 32, 4, 16, moe.exchange_rows(32, 4, CHIPS)
    x = _tagged(t)
    routing = moe.route(x, jnp.asarray(rng.normal(size=(8, e)), jnp.float32),
                        k, renormalize=True)
    assert rows == t * k
    experts = np.asarray(routing.experts)
    trip = moe._trip(routing, 0, CHIPS)
    buffers = moe.send(x, trip)
    assert buffers.shape == (CHIPS, rows, 8)
    order = np.asarray(routing.order)
    seen = []
    for d, pairs in enumerate(_pairs_in(buffers, trip.sizes, k, experts)):
        mine = experts.reshape(-1)[pairs]
        assert ((mine // 4) == d).all() and (np.diff(mine) >= 0).all()
        np.testing.assert_array_equal(
            np.asarray(trip.sizes)[d], np.bincount(mine - 4 * d, minlength=4))
        np.testing.assert_array_equal(pairs, order[len(seen):][:len(pairs)])
        seen.extend(pairs.tolist())
    assert sorted(seen) == list(range(t * k))
    np.testing.assert_array_equal(np.asarray(moe.sent_rows(routing, 1, CHIPS)),
                                  np.asarray(trip.sizes))


def _skewed_trips(monkeypatch):
    """A routing of 32 tokens, four picks of 16, that chip 1's experts draw,
    behind first buffers of one even share and further ones of a quarter:
    the routing, the tokens, and each trip with its rows an owner."""
    monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", 4)
    monkeypatch.setattr(moe, "EXCHANGE_ROWS_OVER_EVEN", 1)
    rng = np.random.default_rng(9)
    t, k, e = 32, 4, 16
    first, more = moe.exchange_rows(t, k, CHIPS), moe.further_rows(t, k, CHIPS)
    assert (first, more) == (32, 8)
    x = _tagged(t)
    router = jnp.asarray(rng.normal(size=(8, e)), jnp.float32)
    routing = moe.route(x, router.at[:, 4:8].multiply(6.0), k)
    to_owner = np.asarray(routing.group_sizes).reshape(CHIPS, -1).sum(-1)
    count = 1 + -(-max(to_owner.max() - first, 0) // more)
    assert count >= 3
    trips = [(moe._trip(routing, i if i == 0 else jnp.int32(i), CHIPS),
              first if i == 0 else more) for i in range(count)]
    return routing, x, trips


def test_further_trips_take_up_where_the_first_stopped(monkeypatch):
    """The first trip's buffer and the further, smaller ones tile each
    owner's segment without a gap or a row twice, whatever the load: read
    from the rows ``send`` puts into them and from ``trip.sizes``."""
    routing, x, trips = _skewed_trips(monkeypatch)
    t, k = routing.experts.shape
    experts = np.asarray(routing.experts)
    order = np.asarray(routing.order)
    to_owner = np.asarray(routing.group_sizes).reshape(CHIPS, -1).sum(-1)
    begins = np.cumsum(to_owner) - to_owner
    seen, sizes, reached = [], 0, np.zeros(CHIPS, np.int64)
    for trip, rows in trips:
        buffers = moe.send(x, trip)
        assert buffers.shape == (CHIPS, rows, 8)
        for d, pairs in enumerate(_pairs_in(buffers, trip.sizes, k, experts)):
            # where the trips before stopped, in the owner's segment
            np.testing.assert_array_equal(
                pairs, order[begins[d] + reached[d]:][:len(pairs)])
            reached[d] += len(pairs)
            seen.extend(pairs.tolist())
        sizes = sizes + np.asarray(trip.sizes)
    assert sorted(seen) == list(range(t * k))
    np.testing.assert_array_equal(
        sizes, np.asarray(routing.group_sizes).reshape(CHIPS, -1))
    np.testing.assert_array_equal(
        np.asarray(moe.sent_rows(routing, len(trips), CHIPS)), sizes)


def test_the_buffers_are_runs_of_the_sorted_pairs_bit_for_bit(monkeypatch):
    """``send``'s buffers against a numpy construction from ``routing.order``
    at a skewed routing, the first trip and the further ones: row ``j`` of
    owner ``d`` is the token row of the pair at place ``first + j`` of ``d``'s
    segment, zero past the segment's end, to the bit. And ``receive`` of the
    very buffers gives every token its own row times the sum of its weights
    once the trips are added: a pair outside a trip reads zero there."""
    routing, x, trips = _skewed_trips(monkeypatch)
    k = routing.experts.shape[-1]
    order, tokens = np.asarray(routing.order), np.asarray(x)
    to_owner = np.asarray(routing.group_sizes).reshape(CHIPS, -1).sum(-1)
    begins = np.cumsum(to_owner) - to_owner
    first, back = 0, 0
    for trip, rows in trips:
        want = np.zeros((CHIPS, rows, tokens.shape[-1]), tokens.dtype)
        for d in range(CHIPS):
            live = int(np.clip(to_owner[d] - first, 0, rows))
            pairs = order[begins[d] + first:][:live]
            want[d, :live] = tokens[pairs // k]
        buffers = moe.send(x, trip)
        np.testing.assert_array_equal(np.asarray(buffers), want)
        back = back + moe.receive(buffers, trip)
        first += rows
    assert first >= to_owner.max()
    np.testing.assert_allclose(
        np.asarray(back),
        tokens * np.asarray(routing.weights).sum(-1, keepdims=True),
        rtol=1e-6)


def _instructions(hlo):
    """(name, result shape, opcode, operand names, op_name) of every
    instruction of a compiled module's text."""
    import re

    pattern = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]*)\]"
                         r"[^ ]* ([\w\-]+)\(([^)]*)\)")
    for line in hlo.splitlines():
        found = pattern.match(line)
        if found:
            name, dims, opcode, operands = found.groups()
            scope = re.search(r'op_name="([^"]*)"', line)
            yield (name, tuple(int(n) for n in dims.split(",") if n), opcode,
                   re.findall(r"%([\w.\-]+)", operands),
                   scope.group(1) if scope else "")


def test_the_source_side_gathers_pairs_and_copies_buffers(monkeypatch):
    """The expert layer on four devices, value and gradient, compiled on the
    CPU at 72 tokens a chip, four picks, buffers of 216 rows an owner and 24
    in a further trip: no ``sort`` has the ``n x C`` slots of the buffers for
    keys anywhere, and under ``ps.moe/dispatch`` and ``ps.moe/combine`` there
    is no ``sort`` and no ``scatter`` at all and no ``gather`` brings out
    ``n x C`` rows: a gather there moves the ``T x k`` pairs (by token, or by
    the slot that holds a pair's row) or fewer, and the buffers are made by
    ``dynamic-slice`` and ``select``."""
    from ps_tpu.obs import phases

    monkeypatch.setattr(moe, "GROUPED_MATMUL_ROWS", 8)
    tokens, k = 72, SIZES["num_experts_per_tok"]
    pairs = tokens * k
    first, more = (moe.exchange_rows(tokens, k, CHIPS),
                   moe.further_rows(tokens, k, CHIPS))
    slots = {CHIPS * first, CHIPS * more}
    assert (first, more) == (216, 24) and not slots & {tokens, pairs}
    sizes, cfg, params, _ = _setup(seed=5, rms_norm_eps=5e-6)
    mesh = _mesh()
    lp = params["layers"]["0"]
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(CHIPS, tokens, cfg.hidden_size)), jnp.float32)

    def loss(lp, x):
        return jnp.sum(mellum.moe_block(lp, x, cfg, mesh)[0] ** 2)

    placed = _on_mesh(mesh, {"layers": {"0": lp}}, {"x": x})
    hlo = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        placed[0]["layers"]["0"], placed[1]["x"]).compile().as_text()
    program = list(_instructions(hlo))
    shape_of = {name: shape for name, shape, *_ in program}
    under, sliced = [], 0
    for name, shape, opcode, operands, scope in program:
        rows = [shape[:1]] + [shape_of.get(o, ())[:1] for o in operands]
        if opcode == "sort":
            assert not any(r and r[0] in slots for r in rows), (name, rows)
        if not (phases.MOE_DISPATCH in scope or phases.MOE_COMBINE in scope):
            continue
        under.append(opcode)
        assert opcode not in ("sort", "scatter"), (name, scope)
        if opcode == "gather":
            assert shape[0] <= pairs and shape[0] not in slots, (name, shape)
        sliced += opcode == "dynamic-slice" and shape[:1] in ((first,),
                                                                (more,))
    assert "gather" in under and "select" in under
    # forward and the trip computed again, the first trip and the loop's
    assert sliced >= 2 * 2 * CHIPS


def test_over_trips_refuses_a_share():
    x = jnp.zeros((8, 4))
    routing = moe.route(x, jnp.zeros((4, 8)), 2, held=(0, 4))
    with pytest.raises(ValueError, match="over all experts"):
        moe.over_trips(None, routing, "data", x)


# -- (i) the model through the store on a four-device mesh ---------------------

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_fused_step_on_four_devices_matches_reference(attn):
    """Through ``KVStore.make_step`` with ``placement="sharded"`` and
    ``mellum_partition_rules()`` on a mesh of four: the loss, its two terms,
    the counts and, read from AdamW's first moment behind a clip that does
    not bite, every gradient; the expert stacks stored split by expert, their
    moments beside them, and read split (no all-gather of a stack in the
    step); then AdamW's rule on a ZeRO leaf and on a stack."""
    import optax

    import ps_tpu as ps

    sizes, cfg, params, batch = _setup(seed=1, seq=128)
    (ref_loss, ref_aux), ref_grads = _plain(sizes, params, batch)
    rule = dict(learning_rate=1e-3, b1=0.9, b2=0.95, eps=1e-8,
                weight_decay=0.1)
    ctx = ps.init(backend="tpu", mesh_shape={"data": CHIPS})
    try:
        store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1e9,
                           placement="sharded",
                           partition_rules=mellum.mellum_partition_rules(),
                           **rule)
        store.init(params)
        step = store.make_step(
            mellum.make_loss_fn(cfg, attn=attn, mesh=ctx.mesh), has_aux=True)
        placed = store.shard_batch(batch)
        stack = store.pull("layers/2/experts/w1")
        assert stack.sharding.spec == P("data", None, None)
        assert store.pull("head/kernel").sharding.spec == P(None, "data")
        hlo = step.compiled_text(placed)
        gathers = [line for line in hlo.splitlines()
                   if "all-gather" in line and " = " in line]
        assert gathers and not [g for g in gathers if "[16,64,32]" in g
                                or "[16,32,64]" in g]
        assert "all-to-all" in hlo
        with jax.default_matmul_precision("highest"):
            loss, _, aux = step(placed)
        tol = F32_TOL if attn == "full" else 5 * F32_TOL
        assert abs(float(loss) - float(ref_loss)) <= tol * float(ref_loss)
        for term in ("ce", "load_balance"):
            assert _rel(aux[term], ref_aux[term]) <= tol
        np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]),
                                      np.asarray(ref_aux["expert_tokens"]))
        flat = jax.tree_util.tree_leaves_with_path(ref_grads)
        assert len(flat) == len(store.keys())
        for path, r in flat:
            key = "/".join(p.key for p in path)
            state = store.optimizer_state(key)
            mu = optax.tree_utils.tree_get(state, "mu")
            assert mu.sharding.spec == store.pull(key).sharding.spec, key
            assert _rel(mu / 0.1, r) <= 10 * tol, key
        for key in mellum_step.APPLIED:
            state = store.optimizer_state(key)
            before = params
            for part in key.split("/"):
                before = before[part]
            want = mellum_step.adamw_first_step(
                before, optax.tree_utils.tree_get(state, "mu"),
                optax.tree_utils.tree_get(state, "nu"), **rule)
            np.testing.assert_allclose(store.pull(key), want, atol=1e-6)
    finally:
        ps.shutdown()


# -- (iii) a table a layer type -------------------------------------------------

def test_yarn_table_is_the_formula_and_the_sliding_one_is_plain():
    """``rope_table`` against YaRN's formula written out here, at the
    published numbers: 64 frequencies at theta 500,000, divided by 16 past
    dimension 35, as they are below 18, blended between; cos and sin carry
    the attention factor. The sliding layers' table is ``blocks.rope``'s
    own."""
    config = _json(CONFIG)
    cfg = mellum.MellumConfig.from_dict(config)
    theta, inv_freq, scale = mellum.rope_table(cfg, FULL)
    d, original, factor = 128, 8192, 16

    def dim_of(turns):
        return d * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (18, 35) and theta == 500000.0
    want = []
    for i in range(d // 2):
        extra = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extra / factor * ramp + extra * (1 - ramp))
    np.testing.assert_allclose(np.asarray(inv_freq), want, rtol=1e-6)
    assert scale == 1.2772588722239782
    np.testing.assert_allclose(
        np.asarray(reference.rope_table(
            config["rope_parameters"][FULL], d)[0]), want, rtol=2e-6)
    assert mellum.rope_table(cfg, WINDOWED) == (500000.0, None, None)


def test_a_full_layer_without_its_attention_factor_fails_the_loss_check():
    """The factor is in the mathematics: with it left out of the model, or
    with the table left plain, the loss leaves the reference's by several
    times what these tests allow it (``F32_TOL``), and the full layer's q
    gradient, the cell's witness of the scaled table, falls under its
    limit."""
    sizes, cfg, params, batch = _setup(seed=3, seq=128)
    (want, _), want_grads = _plain(sizes, params, batch)
    no_factor = {**sizes, "rope_parameters": {
        **sizes["rope_parameters"], FULL: {**YARN, "attention_factor": 1.0}}}
    plain_table = {**sizes, "rope_parameters": {
        **sizes["rope_parameters"], FULL: sizes["rope_parameters"][WINDOWED]}}
    for changed in (no_factor, plain_table):
        other = mellum.MellumConfig.from_dict(changed)
        with jax.default_matmul_precision("highest"):
            (got, _), grads = jax.jit(jax.value_and_grad(
                mellum.make_loss_fn(other), has_aux=True))(params, batch)
        assert abs(float(got) - float(want)) > 5 * F32_TOL * float(want)
        q = "layers/3/attn/q/kernel"
        assert mellum_step.cosine(
            grads["layers"]["3"]["attn"]["q"]["kernel"],
            want_grads["layers"]["3"]["attn"]["q"]["kernel"]
        ) < mellum_step.GRAD_COSINE[q]


def test_rope_leaves_its_old_call_alone_and_the_blocked_head_is_the_whole():
    """``blocks.rope`` without a table traces to what it traced to before it
    grew an argument (the other five decoders' programs; ``blocks.token_ce``
    is the parent's, untouched), and ``mellum.blocked_head_ce`` is the whole
    head's value and gradient."""
    from ps_tpu.models import blocks

    x = jnp.ones((2, 16, 2, 8), jnp.float32)
    text = str(jax.make_jaxpr(lambda x: blocks.rope(x, 10000.0))(x))
    assert "mul" in text and text == str(jax.make_jaxpr(
        lambda x: blocks.rope(x, 10000.0, inv_freq=None, scale=None))(x))
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 32, size=(2, 16)), jnp.int32)
    whole = jax.value_and_grad(
        lambda h, w: blocks.token_ce(h @ w, targets), (0, 1))(h, head)
    blocked = jax.value_and_grad(
        lambda h, w: mellum.blocked_head_ce(h, w, targets, 4), (0, 1))(
            h, head)
    assert _worst(blocked, whole) <= 1e-6
    with pytest.raises(ValueError, match="do not tile"):
        mellum.blocked_head_ce(h, head, targets, 5)
    # the blocked readout lives in blocks.py since PR 63 and Mellum's name
    # is the mean of its block sums
    summed = jax.value_and_grad(lambda h, w: jnp.sum(blocks.blocked_head_nll(
        h, w, targets, 4, summed=True)) / targets.size, (0, 1))(h, head)
    assert _worst(summed, blocked) == 0


#: sha256 of the jaxpr of value and gradient of Mellum's loss, one full layer
#: over [1, 4096] tokens (two blocks of the head), as the tree stood before
#: PR 63 moved the blocked readout into blocks.py (parent 9583dc0)
MELLUM_JAXPR = "588fbf82266be705c2c0ea813249ed9ab98ddcc4728d63000c85d9c8e4c74711"


def test_mellums_loss_traces_to_the_program_it_had():
    """Moving ``blocked_head_ce``'s body into ``blocks.blocked_head_nll`` and
    giving it a form that returns a position's loss changed nothing Mellum
    traces: the jaxpr of its loss's value and gradient, head in two blocks,
    is the parent commit's, by a pinned digest."""
    from jaxpr_tools import digest

    _, cfg, params, batch = _setup(
        seq=4096, batch=1, num_hidden_layers=1, layer_types=[FULL],
        mlp_layer_types=["sparse"])
    assert batch["inputs"].shape[1] % mellum.HEAD_BLOCK == 0
    assert digest(jax.value_and_grad(mellum.make_loss_fn(cfg), has_aux=True),
                  params, batch) == MELLUM_JAXPR


# -- (iv) the parameters, counted from shapes -----------------------------------

@pytest.mark.parametrize("layers,total", [(4, 2_123_977_984),
                                          (28, 12_149_923_072)])
def test_parameter_count_from_shapes(layers, total):
    """``init_params``' shapes alone (``jax.eval_shape``: nothing is made) at
    the published widths: ISSUE 46's table to the digit at the cut, the
    name's 12B at 28 layers, 2.44B of them a token's."""
    config = _json(CONFIG)
    config.update(num_hidden_layers=layers,
                  layer_types=config["published"]["layer_types"][:layers],
                  mlp_layer_types=config["published"]["mlp_layer_types"][
                      :layers])
    cfg = mellum.MellumConfig.from_dict(config)
    shapes = jax.eval_shape(lambda k: mellum.init_params(k, cfg),
                            jax.random.key(0))
    count = sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(shapes))
    assert count == total == mellum_step.param_count(config)
    layer = sum(math.prod(leaf.shape)
                for leaf in jax.tree.leaves(shapes["layers"]["0"]))
    experts = sum(math.prod(leaf.shape)
                  for leaf in jax.tree.leaves(shapes["layers"]["0"]["experts"]))
    assert (layer, experts) == (417_747_712, 396_361_728)
    if layers == 28:
        a_token = total - layers * experts * 56 // 64
        assert 2.4e9 < a_token < 2.5e9


# -- (v) what the model does not compute ----------------------------------------

@pytest.mark.parametrize("change", [
    {"num_nextn_predict_layers": 1},
    {"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
    {"mlp_layer_types": ["sparse"] * 3},
    {"attention_bias": True},
    {"tie_word_embeddings": True},
    {"hidden_act": "gelu"},
    {"use_sliding_window": False},
    {"rope_parameters": {**SIZES["rope_parameters"],
                         FULL: {**YARN, "rope_type": "llama3"}}},
    {"rope_parameters": {FULL: YARN}},
    {"layer_types": [WINDOWED] * 3 + ["chunked_attention"]},
    {"num_hidden_layers": 5},
], ids=lambda c: next(iter(c)))
def test_config_refuses_what_the_model_does_not_compute(change):
    with pytest.raises(ValueError):
        mellum.MellumConfig.from_dict({**SIZES, **change})


def test_qk_norm_false_leaves_the_two_scales_out():
    sizes, cfg, params, batch = _setup(seed=4, seq=64, qk_norm=False)
    assert "q_norm" not in params["layers"]["0"]["attn"]
    (want, _), want_grads = _plain(sizes, params, batch)
    with jax.default_matmul_precision("highest"):
        (got, _), grads = jax.jit(jax.value_and_grad(
            mellum.make_loss_fn(cfg), has_aux=True))(params, batch)
    assert abs(float(got) - float(want)) <= F32_TOL * float(want)
    assert _worst(grads, want_grads) <= 5 * F32_TOL


# -- the benchmark's pieces -------------------------------------------------------

def test_configuration_file_states_the_cut(listed_for):
    """The file holds every number of the published config, the three cut
    keys with what was published, the assumptions and the deployment."""
    config = _json(CONFIG)
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types"]
    assert entry["source"] in config["source"]
    published = config["published"]
    assert set(published) == set(entry["reduced"])
    assert published["num_hidden_layers"] == 28 == len(
        published["layer_types"])
    assert config["layer_types"] == published["layer_types"][:4]
    for key, value in dict(hidden_size=2304, moe_intermediate_size=896,
                           num_experts=64, num_experts_per_tok=8,
                           vocab_size=98304, head_dim=128, sliding_window=1024,
                           num_attention_heads=32, num_key_value_heads=4,
                           intermediate_size=7168).items():
        assert config[key] == value
    assert len(config["assumed"]) >= 3 and "four chips" in config["deployment"]
    cell = next(w for w in _json("BENCHMARK.json")["workloads"]
                if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "s8192.b1.zipf.x4.n96"
    traffic = _json("benchmark/traffic/s8192.b1.zipf.x4.n96.json")
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 8192)
    # not 48, so the file's name carries it
    assert traffic["loss_step"] == 96 in mellum_step.LOSS_STEPS
    assert "block_steps_why" in traffic and "loss_step_why" in traffic
    assert "pool" not in traffic["rehearse"]
    assert {"throughput", "loss_at_n"} <= {
        m["moves"] for m in listed_for(CELL)}


def test_operations_from_shapes():
    """The step's FLOPs a chip by hand at the cell's shapes, and the bytes of
    the exchange."""
    config = _json(CONFIG)
    tokens = seq = 8192
    d, f, v = 2304, 896, 98304
    proj = 6.0 * d * (2 * 4096 + 2 * 512 + 64)
    band = 1024 * 1025 // 2 + (seq - 1024) * 1024
    cores = 12.0 * 32 * 128 * (3 * band + seq * (seq + 1) // 2)
    dense = tokens * (4 * proj + 6.0 * d * v) + cores
    assert mellum_step.dense_flops(config, tokens, seq) == dense
    assert mellum_step.pair_flops(config) == 18.0 * d * f
    assert mellum_step.step_flops(config, tokens, seq) == (
        dense + tokens * 8 * 4 * 18.0 * d * f)
    assert mellum_step.exchange_bytes(config, 1000, 6) == 1000 * d * 2 * 6.0
    assert 0.22 < band / (seq * (seq + 1) // 2) < 0.24


def test_scopes_are_the_readers_copy():
    from ps_tpu.obs import phases

    assert set(phases.MELLUM_SCOPES) <= set(decoder.METRICS)
    assert decoder.scope_of(
        "%all-to-all.3", "jit(f)/ps.grad/ps.moe/dispatch/ps.moe/exchange/x"
    ) == phases.MOE_EXCHANGE
    assert decoder.scope_of(
        "%fusion.1", "ps.grad/ps.attn/ps.attn/full/dot") == phases.ATTN_FULL


def test_family_refuses_a_pool_it_would_have_to_cycle():
    config = _json(CONFIG)
    traffic = _json("benchmark/traffic/s8192.b1.zipf.x4.n96.json")
    with pytest.raises(ValueError, match="re-uses no batch"):
        mellum_step.build(config, {**traffic, "pool": 16}, 4, 0)
