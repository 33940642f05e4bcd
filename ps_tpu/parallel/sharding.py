"""Parameter-placement policy: the TPU translation of key→server sharding.

The reference range-partitions parameter keys across server processes
(SURVEY.md §3 row 4). Here a parameter "lives on a server" by being sharded
over the mesh's data axis; the optimizer state shards identically (state
"next to" the param, as on a PS server). Tensors too small to split evenly
stay replicated — the analogue of small keys living whole on one server,
minus the load imbalance.

Tensor parallelism ('model' axis): by default the largest divisible dim is
sharded — which IS the Megatron placement for the common transformer shapes
(MLP in [d,4d] → column-parallel, MLP out [4d,d] → row-parallel, fused QKV
[d,3d] → column-parallel, embeddings [V,d] → vocab-sharded), because the
wide dimension is the one worth splitting. Where the heuristic is blind
(square kernels, unusual layouts), pass explicit ``partition_rules`` —
``[(key_regex, spec_tuple)]``, first match wins — through
``KVStore(partition_rules=...)``; the optimizer state follows the same
rule as the param it sits next to.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ps_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

# [(key regex, per-dim spec)] — spec entries are mesh axis names or None,
# e.g. [("attn/out/kernel$", ("model", None))] for row-parallel projections.
PartitionRules = Sequence[Tuple[str, Tuple[Optional[str], ...]]]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _rule_sharding(mesh: Mesh, leaf: Any, key: str,
                   rules: PartitionRules) -> Optional[NamedSharding]:
    """Explicit placement for `key`, or None when no rule fits. A matching
    rule whose rank differs from the leaf's is skipped (optimizer scalars
    under a matrix param's rule); a rule naming an unknown mesh axis or an
    indivisible dim is a hard error — explicit placement fails loudly.
    Patterns may be strings or pre-compiled regexes."""
    ndim = getattr(leaf, "ndim", 0)
    for pattern, spec in rules:
        hit = (pattern.search(key) if hasattr(pattern, "search")
               else re.search(pattern, key))
        if not hit:
            continue
        if len(spec) != ndim:
            continue
        out = []
        for i, ax in enumerate(spec):
            if ax is None:
                out.append(None)
                continue
            if ax not in mesh.shape:
                raise ValueError(
                    f"partition rule {pattern!r} names axis {ax!r}, not in "
                    f"mesh axes {tuple(mesh.shape)}"
                )
            n = mesh.shape[ax]
            if n > 1 and leaf.shape[i] % n != 0:
                raise ValueError(
                    f"partition rule {pattern!r}: dim {i} of {key!r} "
                    f"(size {leaf.shape[i]}) is not divisible by "
                    f"axis {ax!r} (size {n})"
                )
            out.append(ax if n > 1 else None)
        if all(s is None for s in out):
            return replicated(mesh)
        return NamedSharding(mesh, P(*out))
    return None


def _pick_dim(shape, n, taken=None):
    """Largest dim divisible by n (ties toward the leading dim), skipping
    dims already assigned to another mesh axis. None if no dim qualifies."""
    order = sorted(range(len(shape)), key=lambda i: (-shape[i], i))
    for i in order:
        if taken is not None and i in taken:
            continue
        if shape[i] % n == 0 and shape[i] >= n:
            return i
    return None


def param_sharding(mesh: Mesh, leaf: Any, placement: str,
                   axis: str = DATA_AXIS, key: Optional[str] = None,
                   rules: Optional[PartitionRules] = None) -> NamedSharding:
    """Choose a NamedSharding for one parameter tensor.

    - 'replicated': every device holds the full tensor along the data axis
      (pure data parallel; grads psum, update computed everywhere).
    - 'sharded': split the largest dimension divisible by the data-axis size
      (ZeRO-1-style; grads reduce-scatter to the owner shard, the update runs
      shard-local, pulls all-gather). Falls back to replicated for tensors
      with no evenly divisible dimension. This function only chooses where a
      tensor is STORED. The gather and the reduce-scatter are stated where
      the fused step reads and writes it (``KVStore.make_step``): each
      parameter is constrained to :func:`gathered_sharding` of its stored
      sharding before the loss reads it, each gradient to the stored
      sharding before the optimizer does. Left unstated, GSPMD resolves a
      matmul whose operands are both split over 'data' by moving the
      activations (PERF.md, PR 26).

    If the mesh carries a 'model' axis of size > 1, tensors additionally
    shard one dimension over it (tensor parallelism: GSPMD partitions the
    matmuls and inserts the activation collectives). Under 'sharded' the
    model axis takes the largest dim and ZeRO takes the next; the two axes
    never share a dimension. Explicit ``rules`` (matched against ``key``)
    override everything — see :data:`PartitionRules`.
    """
    if placement not in ("replicated", "sharded"):
        raise ValueError(f"unknown placement {placement!r}")
    ndim = getattr(leaf, "ndim", 0)
    if not ndim:
        return replicated(mesh)
    if rules and key is not None:
        ruled = _rule_sharding(mesh, leaf, key, rules)
        if ruled is not None:
            return ruled
    spec = [None] * ndim
    taken = set()
    m = mesh.shape.get(MODEL_AXIS, 1)
    if m > 1:
        i = _pick_dim(leaf.shape, m)
        if i is not None:
            spec[i] = MODEL_AXIS
            taken.add(i)
    if placement == "sharded":
        n = mesh.shape[axis]
        i = _pick_dim(leaf.shape, n, taken)
        if i is not None:
            spec[i] = axis
    if all(s is None for s in spec):
        return replicated(mesh)
    return NamedSharding(mesh, P(*spec))


def placed_by_rule(mesh: Mesh, leaf: Any, key: str,
                   rules: Optional[PartitionRules]) -> bool:
    """Whether a ``partition_rules`` entry places ``leaf`` under ``key``. A
    split that such an entry states is the model's own whatever its axis, as
    a ``MODEL_AXIS`` entry already is: ``KVStore.make_step`` reads the leaf,
    and writes its gradient, as it is stored (expert stacks split by expert
    over ``DATA_AXIS``, ``models/mellum.py``); ZeRO's gather
    (:func:`gathered_sharding`) is for the split the placement chose."""
    return bool(rules) and _rule_sharding(mesh, leaf, key, rules) is not None


def gathered_sharding(stored: NamedSharding) -> NamedSharding:
    """The sharding the loss reads a stored parameter under: the pull.

    Every ``DATA_AXIS`` entry of the stored spec becomes ``None`` (the ZeRO
    shards are all-gathered), every other entry stays (a tensor-parallel
    split over ``MODEL_AXIS`` is the model's own and is not undone). On a
    data-only mesh the result is replicated. ``KVStore.make_step`` states it
    on every parameter before the loss reads it, but on one a rule placed
    (:func:`placed_by_rule`).
    """
    # param_sharding and its rules give a dim one axis or none
    return NamedSharding(stored.mesh, P(*(
        None if entry == DATA_AXIS else entry for entry in stored.spec)))


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dimension over the data axis."""
    return NamedSharding(mesh, P(axis))


def sharded_opt_init(opt_init, params: Any, mesh: Mesh, placement: str,
                     key: Optional[str] = None,
                     rules: Optional[PartitionRules] = None) -> Any:
    """Initialize optimizer state with EXPLICIT placement.

    ``jit(opt.init)`` alone leaves output shardings to the compiler, which
    (observed on the pinned jax) puts every state leaf on one device —
    uncommitted, so it happens to run, but a checkpoint restore brings the
    same leaves back *committed* and the placement mismatch becomes an
    error. Instead the state is placed by the same policy as the params it
    sits next to: moment tensors (param-shaped) shard exactly like their
    param under 'sharded' (ZeRO-1 — state partitioned across servers),
    scalars (adam's ``count``) replicate. Live and restored placement are
    then identical by construction.

    Rule matching: for a per-key state (``key`` given), rules match against
    that key; for a whole-tree state, each leaf's pytree path — which embeds
    the param key — is matched, so a param's rule carries to its moments.
    """
    import jax

    shapes = jax.eval_shape(opt_init, params)
    if rules:
        def path_name(path) -> str:
            # "/"-joined path components, so a param key like
            # 'attn/out/bias' appears verbatim in its moments' names
            # ("0/mu/attn/out/bias") and $-anchored rules keep matching —
            # raw keystr would yield "[0].mu['attn/out/bias']"
            parts = []
            for p in path:
                if hasattr(p, "key"):
                    parts.append(str(p.key))
                elif hasattr(p, "name"):
                    parts.append(str(p.name))
                elif hasattr(p, "idx"):
                    parts.append(str(p.idx))
                else:
                    parts.append(str(p))
            return "/".join(parts)

        def leaf_sharding(path, leaf):
            name = key if key is not None else path_name(path)
            return param_sharding(mesh, leaf, placement, key=name, rules=rules)

        shardings = jax.tree_util.tree_map_with_path(leaf_sharding, shapes)
    else:
        shardings = jax.tree_util.tree_map(
            lambda leaf: param_sharding(mesh, leaf, placement), shapes
        )
    return jax.jit(opt_init, out_shardings=shardings)(params)
