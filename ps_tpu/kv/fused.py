"""The fused train step: the whole PS protocol as one donated XLA program.

Lookup (the sparse pull), gradient, aggregation collective, the server's
dense apply, the row exchange with its scatter-apply, and the pull of the
new parameters compile into one program over the mesh. It is written here
once. ``KVStore.make_step`` is this step with no tables — empty dicts add no
HLO parameter and no output — and ``ps_tpu.make_composite_step`` is this
step with them (the reference's Wide-&-Deep worker, SURVEY.md §4c).

Gradients w.r.t. embeddings are taken against the *gathered rows* (shape
[N, D]), never the full table: that IS the sparse push payload, and it keeps
the backward pass free of dense [V, D] gradient materialization.

The step pulls distinct rows. Where a table lives on one chip and applies
through the fused tier (``SparseEmbedding.pulls_distinct``: read from the
store, no knob), the dedupe the push needs anyway runs in front of the loss
and serves three things: the pull gathers each distinct row of the batch
once, the loss reads the rows through an expansion out of that batch-sized
buffer, and the push applies to the rows the pull already holds without
reading the table again — the reference's worker, which pulls a sorted list
of distinct keys and pushes the same list. Across chips, where an owner's
distinct rows are known only after the row exchange, and on the 'off' tier,
the step looks every (id, slot) pair up and the push dedupes for itself, as
before. The rows the loss reads, the sums and every written bit are the
same either way (tests/test_sparse_apply.py).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax

from ps_tpu import obs
from ps_tpu.kv import keys as keymod
from ps_tpu.obs import pace, phases
from ps_tpu.parallel.sharding import gathered_sharding, placed_by_rule


def make_fused_step(dense_store, emb_stores: Dict[str, "SparseEmbedding"],
                    loss_fn: Callable, ids_fn: Callable,
                    has_aux: bool = False):
    """Build ``run(batch, *extra) -> (loss, dense_params[, aux])``.

    Args:
      dense_store: initialized sync ``KVStore`` on the mesh backend.
      emb_stores: initialized ``SparseEmbedding`` stores by name; may be
        empty.
      loss_fn: ``loss_fn(dense_params, rows, batch, *extra)`` with ``rows``
        being ``{name: table[ids]}`` in the shapes ``ids_fn`` produced;
        returns a scalar loss, or ``(loss, aux)`` with ``has_aux``.
      ids_fn: ``ids_fn(batch) -> {name: int32 ids}``, one entry per table.

    ``run`` carries ``lower``, ``cost_analysis`` and ``compiled_text``, which
    take the arguments of ``run``. What the program states about shardings,
    and what it donates, is in ``KVStore.make_step``'s docstring; the
    updated tables stay inside their stores.
    """
    engine = dense_store._engine
    if not hasattr(engine, "get_tree_and_state"):
        raise NotImplementedError(
            "make_composite_step requires the tpu (mesh) backend"
        )
    dense_store._require_init()
    treedef, key_order = dense_store._treedef, dense_store._key_order
    tree_bytes = dense_store._tree_bytes
    names = sorted(emb_stores)

    def kv_loss(params_kv, rows, batch, *extra):
        return loss_fn(keymod.unflatten(treedef, params_kv, key_order),
                       rows, batch, *extra)

    # ZeRO-1 across chips: the step states where each tensor lives
    # instead of leaving it to GSPMD, which on BERT's shapes kept the
    # weights split and moved the activations (PERF.md, PR 26). Neither
    # fact is an option: the store holds both.
    stored = gathered = out_shardings = None
    # num_workers is the size of the mesh's data axis
    if dense_store.placement == "sharded" and engine.num_workers > 1:
        held = engine.get_tree_and_state()
        stored, state_shardings = jax.tree_util.tree_map(
            lambda x: x.sharding, held)
        gathered = {
            k: stored[k] if placed_by_rule(
                engine.mesh, leaf, k, engine.partition_rules)
            else gathered_sharding(stored[k])
            for k, leaf in held[0].items()}
        # tables, their state, loss, aux, row counts: left to the compiler
        out_shardings = (stored, state_shardings) + (None,) * (
            2 + 3 * len(names))

    # Not named ``fused`` as before the scopes: jax leaves metadata out
    # of the compile cache's key, so under the old name an executable
    # cached without the phase marks would be served for this one.
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                       out_shardings=out_shardings)
    def fused_step(params_kv, state, tables, estates, batch, *extra):
        pulled = params_kv
        if gathered is not None:
            # the pull: the tail of the server's apply in this protocol
            with jax.named_scope(phases.APPLY):
                pulled = jax.lax.with_sharding_constraint(
                    params_kv, gathered)
        ids = ids_fn(batch)
        # the sparse pull. A table on one chip is pulled as the reference's
        # worker pulls: the ids deduped first, each distinct row gathered
        # once and held for the push. Tables handed the same id array share
        # its plan, by the array's identity: one sort in the program
        # whatever the compiler's CSE finds
        rows, held, plans = {}, {}, {}
        for n in names:
            store = emb_stores[n]
            if store.pulls_distinct:
                key = (id(ids[n]), store.rows_per_shard)
                if key not in plans:
                    plans[key] = store.plan_pull(ids[n])
                rows[n], rows_held = store.lookup_distinct(
                    tables[n], ids[n], plans[key])
                held[n] = (plans[key], rows_held)
            else:
                rows[n] = store.lookup(tables[n], ids[n])
        with jax.named_scope(phases.GRAD):
            out, (grads, grows) = jax.value_and_grad(
                kv_loss, argnums=(0, 1), has_aux=has_aux
            )(pulled, rows, batch, *extra)
            loss, aux = out if has_aux else (out, None)
        with jax.named_scope(phases.APPLY):
            if stored is not None:
                # the push: a gradient is a sum over the chips' batch
                # slices at the shape its parameter was read in, and
                # each owner keeps its shard of it: all-reduce then
                # slice, which the compiler fuses to a reduce-scatter
                grads = jax.lax.with_sharding_constraint(
                    jax.lax.with_sharding_constraint(grads, gathered),
                    stored)
            params_kv, state = engine.apply_rule(params_kv, state, grads)
        new_tables, new_estates, row_counts = [], [], []
        for n in names:
            store = emb_stores[n]
            if n in held:
                table, estate, counts = store.apply_held(
                    tables[n], estates[n], *held[n],
                    grows[n].reshape(-1, store.dim))
            else:
                table, estate, counts = store.apply(
                    tables[n], estates[n], ids[n].reshape(-1),
                    grows[n].reshape(-1, store.dim))
            new_tables.append(table)
            new_estates.append(estate)
            row_counts.append(counts)
        # flat, so that with no table the results are the dense step's,
        # place for place (a result's place is in the compile cache's key)
        return (params_kv, state, *new_tables, *new_estates, loss, aux,
                *row_counts)

    def step_args(batch, extra):
        params_kv, state = engine.get_tree_and_state()
        return (params_kv, state,
                {n: emb_stores[n].table for n in names},
                {n: emb_stores[n].state() for n in names}, batch, *extra)

    check_health = dense_store._check_health
    span = obs.tracer().program_span
    n_ids: Dict[str, int] = {}  # id-list sizes are static: probed once
    # whether the chip was waiting for each launch: asked twice a step
    account = pace.StepPace()

    def run(batch, *extra):
        with span(phases.STEP_RUN, step=dense_store.step) as whole:
            check_health()  # dead peer -> typed error, not a hung psum
            if names and not n_ids:
                n_ids.update(
                    (n, math.prod(ids.shape))
                    for n, ids in jax.eval_shape(ids_fn, batch).items())
            args = step_args(batch, extra)
            with span(phases.STEP_LAUNCH, step=dense_store.step,
                      **account.launching()):
                params_kv, state, *rest = fused_step(*args)
            k = len(names)
            loss, aux = rest[2 * k:2 * k + 2]
            engine.set_tree_and_state(params_kv, state)
            dense_store.bytes_pushed += tree_bytes  # the gradients out
            dense_store.bytes_pulled += tree_bytes  # the parameters back
            dense_store.step += 1
            for n, table, estate, counts in zip(
                    names, rest[:k], rest[k:2 * k], rest[2 * k + 2:]):
                store = emb_stores[n]
                distinct = store.pulls_distinct
                # the program's own lookup
                store.count_pull(n_ids[n], held=distinct)
                store.adopt_push(table, estate, counts, n_ids[n],
                                 store.rows_nbytes(n_ids[n]), held=distinct)
            params = keymod.unflatten(treedef, params_kv, key_order)
            account.ran(loss, whole.args["step"], whole.t0)
        if has_aux:
            return loss, params, aux
        return loss, params

    def lower(batch, *extra):
        """The fused step lowered for this batch, not compiled and not
        run: a ``jax.stages.Lowered``. Its ``as_text()`` holds the
        sharding constraints the step states, before the partitioner
        resolves them."""
        return fused_step.lower(*step_args(batch, extra))

    def cost_analysis(batch, *extra):
        """XLA HLO cost analysis of the whole fused step (lookup + gradient
        + aggregation + server apply + row exchange/apply + pull) — no
        execution, no extra compile: lowering stops at pre-optimization
        HLO, so 'flops' is the exact model+optimizer arithmetic while
        'bytes accessed' is an unfused upper bound. ``tools/measure_flops.py``
        turns this into the benchmark's FLOP constants."""
        return lower(batch, *extra).cost_analysis()

    def compiled_text(batch, *extra) -> str:
        """Post-GSPMD optimized HLO of the fused step, as text — the
        compiled collective pattern (reduce-scatter/all-gather vs
        all-reduce) that tests/test_hlo_collectives.py pins so a
        placement regression in ``param_sharding`` is a loud failure,
        not a silent 8x traffic increase: on a two-matrix MLP
        (``test_sharded_scatters_largest_grad_and_gathers_params``) and
        on transformer shapes, where the weights' 'data' dim is the
        output dim and the regression was activations moved in place
        of weights (``test_sharded_transformer_moves_weights_only``)."""
        return lower(batch, *extra).compile().as_text()

    run.lower = lower
    run.cost_analysis = cost_analysis
    run.compiled_text = compiled_text
    return run
