"""Composite fused train step: dense KVStore + sparse embedding stores.

The reference's Wide-&-Deep worker pushes BOTH dense grads (MLP/wide weights
→ dense PS servers) and sparse row grads (embedding tables → range-sharded
servers) each step (SURVEY.md §4c). Here the entire composite protocol —
lookup (sparse pull), loss/grad, dense psum+apply, sparse row exchange +
scatter-apply — compiles into ONE donated XLA program over the mesh.

Gradients w.r.t. embeddings are taken against the *gathered rows* (shape
[N, D]), never the full table: that IS the sparse push payload, and it keeps
the backward pass free of dense [V, D] gradient materialization.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import optax

from ps_tpu import obs
from ps_tpu.kv import keys as keymod
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.kv.store import KVStore, _nbytes
from ps_tpu.obs import phases


def make_composite_step(
    dense_store: KVStore,
    emb_stores: Dict[str, SparseEmbedding],
    loss_fn: Callable,
    ids_fn: Callable,
    has_aux: bool = False,
):
    """Build ``run(batch, *extra)`` fusing dense + sparse PS updates.

    Args:
      dense_store: initialized KVStore on the tpu backend (dense params).
      emb_stores: initialized SparseEmbedding stores by name.
      loss_fn: ``loss_fn(dense_params, rows, batch, *extra)`` where ``rows``
        is ``{name: table[ids] }`` with the shapes ``ids_fn`` produced;
        returns a scalar loss (or ``(loss, aux)`` with has_aux).
      ids_fn: ``ids_fn(batch) -> {name: int32 ids}`` (any shape; flattened
        for the row exchange). Ids must be valid rows of the named table.

    Returns:
      ``run(batch, *extra) -> (loss, dense_params[, aux])``; the updated
      tables stay inside the stores (read via ``store.table``).
    """
    engine = dense_store._engine
    if not hasattr(engine, "get_tree_and_state"):
        raise NotImplementedError(
            "make_composite_step requires the tpu (mesh) backend"
        )
    dense_store._require_init()
    treedef = dense_store._treedef
    key_order = list(dense_store._key_order)
    opt = dense_store._opt
    names = sorted(emb_stores)

    def kv_loss(params_kv, rows, batch, *extra):
        params = keymod.unflatten(treedef, params_kv, key_order)
        out = loss_fn(params, rows, batch, *extra)
        return out

    # not named ``fused`` as before the scopes: see KVStore.make_step
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def fused_composite_step(params_kv, state, tables, estates, batch,
                             *extra):
        ids = ids_fn(batch)
        rows = {n: emb_stores[n].lookup(tables[n], ids[n]) for n in names}
        with jax.named_scope(phases.GRAD):
            if has_aux:
                (loss, aux), (gkv, grows) = jax.value_and_grad(
                    kv_loss, argnums=(0, 1), has_aux=True
                )(params_kv, rows, batch, *extra)
            else:
                loss, (gkv, grows) = jax.value_and_grad(
                    kv_loss, argnums=(0, 1)
                )(params_kv, rows, batch, *extra)
                aux = None
        with jax.named_scope(phases.APPLY):
            updates, state = opt.update(gkv, state, params_kv)
            params_kv = optax.apply_updates(params_kv, updates)
        dropped = {}
        for n in names:
            store = emb_stores[n]
            flat_ids = ids[n].reshape(-1)
            flat_grows = grows[n].reshape(-1, store.dim)
            tables[n], estates[n], dropped[n] = store.apply(
                tables[n], estates[n], flat_ids, flat_grows
            )
        return params_kv, state, tables, estates, loss, aux, dropped

    sizes: Dict[str, int] = {}

    span = obs.tracer().program_span

    def run(batch, *extra):
        import numpy as np

        with span(phases.STEP_RUN, step=dense_store.step):
            if not sizes:  # id-list sizes are static; probe once for accounting
                for n, ids in ids_fn(batch).items():
                    sizes[n] = int(np.prod(np.shape(ids)))
            params_kv, state = engine.get_tree_and_state()
            tables = {n: emb_stores[n].table for n in names}
            estates = {n: emb_stores[n]._state for n in names}
            with span(phases.STEP_LAUNCH, step=dense_store.step):
                (params_kv, state, tables, estates, loss, aux,
                 dropped) = fused_composite_step(
                    params_kv, state, tables, estates, batch, *extra)
            engine.set_tree_and_state(params_kv, state)
            nbytes = sum(_nbytes(v) for v in params_kv.values())
            dense_store.bytes_pushed += nbytes
            dense_store.bytes_pulled += nbytes
            dense_store.step += 1
            for n in names:
                store = emb_stores[n]
                store._table, store._state = tables[n], estates[n]
                store.record_dropped(dropped[n])  # sync-free; read at log time
                row_bytes = (sizes[n] * store.dim
                             * np.dtype(store.dtype).itemsize)
                store.bytes_pushed += row_bytes   # row grads out
                store.bytes_pulled += row_bytes   # gathered rows in
                store._account_push(sizes[n])
                store.push_count += 1
            params = keymod.unflatten(treedef, params_kv, key_order)
        if has_aux:
            return loss, params, aux
        return loss, params

    def cost_analysis(batch, *extra):
        """XLA HLO cost analysis of the whole composite step (lookup +
        grad + dense apply + row exchange/apply) — no execution; same
        contract as ``KVStore.make_step``'s hook. Benchmarks turn 'flops'
        into MFU."""
        params_kv, state = engine.get_tree_and_state()
        tables = {n: emb_stores[n].table for n in names}
        estates = {n: emb_stores[n]._state for n in names}
        return fused_composite_step.lower(
            params_kv, state, tables, estates, batch, *extra).cost_analysis()

    run.cost_analysis = cost_analysis
    return run
