"""Composite fused train step: dense KVStore + sparse embedding stores.

The reference's Wide-&-Deep worker pushes BOTH dense grads (MLP/wide weights
→ dense PS servers) and sparse row grads (embedding tables → range-sharded
servers) each step (SURVEY.md §4c). Here the entire composite protocol —
lookup (sparse pull), loss/grad, dense psum+apply, sparse row exchange +
scatter-apply — compiles into ONE donated XLA program over the mesh: the
fused step of ``ps_tpu/kv/fused.py``, which ``KVStore.make_step`` builds
without tables.
"""

from __future__ import annotations

from typing import Callable, Dict

from ps_tpu.kv.fused import make_fused_step
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.kv.store import KVStore


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
      tables stay inside the stores (read via ``store.table``). ``run``
      carries ``lower``, ``cost_analysis`` and ``compiled_text`` as
      ``KVStore.make_step``'s does, and the dense half follows the dense
      store's ``placement`` and ``aggregate`` as it does there.
    """
    return make_fused_step(dense_store, emb_stores, loss_fn, ids_fn, has_aux)
