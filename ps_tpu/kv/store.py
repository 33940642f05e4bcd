"""KVStore — the user-facing worker API (push/pull over parameter keys).

Mirrors the reference's ``KVWorker::Push/Pull`` surface (SURVEY.md §3 rows
2-3) on top of whichever backend :func:`ps_tpu.init` selected:

- local backend: calls go straight to an in-process :class:`LocalServer`.
- tpu backend: the whole protocol compiles into one fused XLA step —
  push = staging (or reduce-scatter), apply = sharded optax update,
  pull = (all-gather of) the post-apply parameters.

Byte counters for every push/pull feed the "push/pull GB/s" metric the
reference reports (BASELINE.json metric line).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Union

import jax
import numpy as np
import optax

from ps_tpu import obs
from ps_tpu.api import current_context
from ps_tpu.kv import fused
from ps_tpu.kv import keys as keymod
from ps_tpu.obs import phases
from ps_tpu.optim import make_optimizer


def _nbytes(x) -> int:
    return int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize if hasattr(x, "shape") else 0


class KVStore:
    """A named parameter store with PS push/pull semantics.

    Args:
      optimizer: name ('sgd'|'momentum'|'adam'|'lamb') or optax transformation
        — the *server-side* update rule.
      mode: 'sync' | 'async' | None (inherit from Config).
      aggregate: 'mean' (data-parallel pmean semantics, default) or 'sum'.
      placement: tpu backend only — 'replicated' (pure DP: psum grads, every
        device applies the full update) or 'sharded' (PS-faithful: parameters
        and optimizer state partitioned over the mesh's data axis, grads
        reduce-scattered to their owner shard, pulls all-gather — the TPU
        equivalent of key→server sharding, ZeRO-1 style).
      **opt_kwargs: forwarded to the named optimizer factory (e.g. learning_rate).
    """

    def __init__(
        self,
        optimizer: Union[str, optax.GradientTransformation] = "sgd",
        mode: Optional[str] = None,
        aggregate: str = "mean",
        placement: str = "replicated",
        partition_rules=None,
        **opt_kwargs,
    ):
        ctx = current_context()
        self._ctx = ctx
        self._opt = make_optimizer(optimizer, **opt_kwargs)
        if placement not in ("replicated", "sharded"):
            raise ValueError("placement must be 'replicated' or 'sharded'")
        self.placement = placement
        if partition_rules is not None:
            # patterns: strings or pre-compiled regexes. Specs must be
            # SEQUENCES of per-dim entries — a bare string like "model"
            # would tuple() into per-character junk and silently never
            # match any rank ("explicit placement fails loudly")
            checked = []
            for p, s in partition_rules:
                if isinstance(s, str) or not all(
                        e is None or isinstance(e, str) for e in s):
                    raise ValueError(
                        f"partition rule {p!r}: spec must be a tuple of "
                        f"axis names / None per dim, e.g. (None, 'model') "
                        f"— got {s!r}"
                    )
                checked.append((p, tuple(s)))
            partition_rules = checked
        if ctx.config.backend == "local":
            if partition_rules:
                raise ValueError(
                    "partition_rules need the mesh backend (backend='tpu')"
                )
            self._engine = ctx.backend.create_server(self._opt, mode=mode, aggregate=aggregate)
        else:
            self._engine = ctx.backend.create_server(
                self._opt, mode=mode, aggregate=aggregate, placement=placement,
                partition_rules=partition_rules,
            )
        self._treedef = None
        self._key_order: List[str] = []
        self._async_params: Dict[int, Any] = {}
        self.bytes_pushed = 0
        self.bytes_pulled = 0
        self.step = 0
        # every protocol entry point consults the failure detector (when
        # enabled) so a dead peer surfaces as a typed error BEFORE the next
        # collective can hang on it
        self._check_health = getattr(ctx.backend, "check_health", None) or (
            lambda: None
        )

    # -- registration -------------------------------------------------------

    def init(self, params: Any) -> Any:
        """Register a parameter pytree with the server; returns the params as
        the server placed them (device-put/sharded for the tpu backend)."""
        if self._treedef is not None:
            raise RuntimeError("KVStore.init already called")
        with obs.tracer().program_span(phases.SETUP_STORE_INIT) as span:
            kv, treedef = keymod.flatten_with_keys(params)
            self._treedef = treedef
            self._key_order = list(kv)
            # what one whole-tree push or pull moves: a constant from here on
            self._tree_bytes = sum(_nbytes(v) for v in kv.values())
            span.set(leaves=len(kv), nbytes=self._tree_bytes)
            if hasattr(self._engine, "register_tree"):
                return self._engine.register_tree(kv, treedef,
                                                  self._key_order)
            for k, v in kv.items():
                self._engine.register(k, v)
            return self.params()

    def keys(self) -> List[str]:
        return list(self._key_order)

    # -- per-key protocol ---------------------------------------------------

    def push(self, key: str, grad: jax.Array, worker: int = 0) -> None:
        """Send a gradient for one key to its server (stages or applies,
        depending on mode/backend)."""
        self._check_health()
        self.bytes_pushed += _nbytes(grad)
        self._engine.push(key, grad, worker=worker)

    def pull(self, key: str, worker: int = 0) -> jax.Array:
        """Fetch the current (post-apply) value of one key."""
        self._check_health()
        val = self._engine.pull(key, worker=worker)
        self.bytes_pulled += _nbytes(val)
        return val

    # -- whole-tree protocol ------------------------------------------------

    def _require_init(self) -> None:
        if self._treedef is None:
            raise RuntimeError("KVStore.init(params) must be called first")

    def push_all(self, grads: Any, worker: int = 0) -> None:
        """Push every key of a gradient pytree (structure must match init).

        Engines with a fused whole-tree apply (``push_tree``) get ONE
        dispatch for the full push — the async bucketing path; others get
        the per-key protocol in key order.
        """
        self._require_init()
        kv, _ = keymod.flatten_with_keys(grads)
        if set(kv) != set(self._key_order):
            raise ValueError("gradient pytree structure does not match registered params")
        push_tree = getattr(self._engine, "push_tree", None)
        if push_tree is not None:
            self._check_health()
            self.bytes_pushed += sum(_nbytes(v) for v in kv.values())
            push_tree(kv, worker=worker)
            return
        for k in self._key_order:
            self.push(k, kv[k], worker=worker)

    def pull_all(self, worker: int = 0) -> Any:
        """Pull every key and rebuild the parameter pytree (one atomic
        snapshot on engines with ``pull_tree``)."""
        self._require_init()
        pull_tree = getattr(self._engine, "pull_tree", None)
        if pull_tree is not None:
            self._check_health()
            kv = pull_tree(worker=worker)
            self.bytes_pulled += sum(_nbytes(v) for v in kv.values())
        else:
            kv = {k: self.pull(k, worker=worker) for k in self._key_order}
        return keymod.unflatten(self._treedef, kv, self._key_order)

    def push_pull(self, grads: Any, worker: int = 0) -> Any:
        """Fused push+apply+pull for a whole gradient pytree.

        On the tpu backend this is ONE jitted SPMD step (collective + sharded
        apply); on the local backend it is the per-key protocol in a loop.
        With multiple logical workers, the sync barrier fires on the last
        worker's push — earlier workers' pulls would block, so call
        ``push_all`` for them and ``pull_all`` after the last push.
        """
        self._require_init()
        if hasattr(self._engine, "update_tree"):
            self._check_health()
            kv, _ = keymod.flatten_with_keys(grads)
            if set(kv) != set(self._key_order):
                raise ValueError("gradient pytree structure does not match registered params")
            nbytes = sum(_nbytes(v) for v in kv.values())
            self.bytes_pushed += nbytes
            self.bytes_pulled += nbytes
            out = self._engine.update_tree(kv)
            self.step += 1
            return keymod.unflatten(self._treedef, out, self._key_order)
        self.push_all(grads, worker=worker)
        self.step += 1
        return self.pull_all(worker=worker)

    # -- fused train step ---------------------------------------------------

    def make_step(self, loss_fn, has_aux: bool = False):
        """Build a train-step callable.

        ``loss_fn(params, batch, *extra)`` must return a scalar loss, meaned
        over the *global* batch — or, with ``has_aux=True``, a ``(loss, aux)``
        pair where ``aux`` is any pytree of auxiliary outputs (e.g. flax
        mutable collections such as BatchNorm ``batch_stats``, or metrics).
        ``run(batch, *extra) -> (loss, params)`` (or ``(loss, params, aux)``).
        Extra positional args flow through to ``loss_fn`` untouched, so
        non-optimized model state can thread through the step.

        On the tpu backend the whole PS protocol — gradient, aggregation
        collective, server apply, pull — compiles into ONE donated XLA
        program (the north-star fusion); on the local backend it runs the
        explicit per-key protocol. Under ``placement="sharded"`` on a mesh
        whose data axis is larger than 1 that program states its own
        shardings (``with_sharding_constraint`` under the ``ps.apply``
        scope, ``out_shardings`` on the jit): parameters are all-gathered
        to ``gathered_sharding`` before the loss reads them (the pull),
        gradients are summed at that shape and constrained to the stored
        sharding before the optimizer reads them (the push: a
        reduce-scatter), and parameters and optimizer state leave in the
        shardings they came in with. Activations stay split over the batch
        from end to end. Under ``"replicated"``, or on one device, the
        program holds no constraint.

        Donation note (tpu): each step donates the previous parameter and
        optimizer-state buffers. References obtained from earlier
        ``pull``/``params()`` calls become invalid once the step runs; use
        the params returned by ``run``.
        """
        self._require_init()
        engine = self._engine
        if getattr(engine, "mode", "sync") == "async":
            raise RuntimeError(
                "make_step is the sync fused path; in async mode use "
                "make_async_step (or push_all/pull_all directly)"
            )
        if not hasattr(engine, "get_tree_and_state"):
            grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=has_aux))
            nw = engine.num_workers

            def run_local(batch, *extra):
                params = self.params()
                if nw == 1:
                    if has_aux:
                        (loss, aux), grads = grad_fn(params, batch, *extra)
                        return loss, self.push_pull(grads), aux
                    loss, grads = grad_fn(params, batch, *extra)
                    return loss, self.push_pull(grads)

                # num_workers > 1: the batch is the GLOBAL batch; each
                # logical worker grads its equal slice and pushes, the
                # server aggregates on the last push — the reference's
                # per-worker trainer loop driven from one host. Loss (and
                # aux, e.g. BN stats) are worker-means, matching the
                # server's 'mean' aggregation of the gradients.
                def slice_w(x, w):
                    n = x.shape[0]
                    if n % nw:
                        raise ValueError(
                            f"global batch dim {n} not divisible by "
                            f"num_workers={nw}"
                        )
                    r = n // nw
                    return x[w * r:(w + 1) * r]

                losses, auxes = [], []
                for w in range(nw):
                    shard = jax.tree_util.tree_map(
                        lambda x, _w=w: slice_w(x, _w), batch
                    )
                    if has_aux:
                        (loss, aux), grads = grad_fn(params, shard, *extra)
                        auxes.append(aux)
                    else:
                        loss, grads = grad_fn(params, shard, *extra)
                    losses.append(loss)
                    self.push_all(grads, worker=w)
                self.step += 1
                new_params = self.pull_all()
                loss = sum(losses) / nw
                if has_aux:
                    aux = jax.tree_util.tree_map(
                        lambda *xs: sum(xs) / nw, *auxes
                    )
                    return loss, new_params, aux
                return loss, new_params

            return run_local

        # the fused step with no table: no rows to look up or to push
        return fused.make_fused_step(
            self, {},
            lambda params, rows, batch, *extra: loss_fn(params, batch, *extra),
            lambda batch: {}, has_aux)

    def make_async_step(self, loss_fn, has_aux: bool = False):
        """Build the async worker cycle ``run(batch, *extra, worker=w)``.

        The reference's async flow (SURVEY.md §4d): a worker computes
        gradients against the parameters it LAST pulled — stale by however
        many whole-model versions other workers pushed since — pushes them
        (the server applies immediately with the DC-ASGD correction), then
        pulls the current version for its next cycle. Drive workers
        round-robin (or from separate host threads) to accrue staleness;
        ``staleness(w)`` reports each worker's current τ.
        """
        self._require_init()
        if getattr(self._engine, "mode", "sync") != "async":
            raise RuntimeError(
                "make_async_step requires mode='async' "
                "(ps_tpu.init(..., mode='async') or KVStore(mode='async'))"
            )
        grad_fn = jax.jit(jax.value_and_grad(loss_fn, has_aux=has_aux))

        def run(batch, *extra, worker: int = 0):
            params = self._async_params.get(worker)
            if params is None:
                params = self.pull_all(worker=worker)
            if has_aux:
                (loss, aux), grads = grad_fn(params, batch, *extra)
            else:
                loss, grads = grad_fn(params, batch, *extra)
                aux = None
            self.push_all(grads, worker=worker)
            self._async_params[worker] = self.pull_all(worker=worker)
            self.step += 1
            if has_aux:
                return loss, aux
            return loss

        return run

    def staleness(self, worker: int = 0) -> int:
        """Async mode: whole-model versions behind the server this worker's
        cached parameters are (0 in sync mode)."""
        fn = getattr(self._engine, "staleness", None)
        return fn(worker) if fn else 0

    @property
    def staleness_histogram(self) -> Dict[int, int]:
        """Async mode: ``{τ: count}`` of whole-tree pushes by the staleness
        they were applied at (empty in sync mode / on engines without
        version tracking)."""
        hist = getattr(self._engine, "staleness_hist", None)
        return dict(hist) if hist else {}

    def shard_batch(self, batch: Any) -> Any:
        """Place a host batch on the mesh, sharded over the data axis
        (identity on the local backend).

        Single-process: pass the GLOBAL batch; it is device_put sharded.
        Multi-process (``jax.distributed`` initialized): pass this process's
        LOCAL slice of the global batch — the slices are assembled into one
        global ``jax.Array`` spanning all processes' devices, exactly how
        the reference's per-worker data loaders feed a distributed job.
        """
        if self._ctx.mesh is None:
            return batch
        sharding = self._ctx.backend.batch_sharding()
        if jax.process_count() > 1:
            return jax.tree_util.tree_map(
                lambda x: jax.make_array_from_process_local_data(
                    sharding, np.asarray(x)
                ),
                batch,
            )
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)

    # -- checkpoint/resume --------------------------------------------------

    def save(self, path: str) -> None:
        """Checkpoint the full server state to ``path`` (orbax pytree +
        JSON sidecar): params, optimizer state, and — in async mode — every
        worker's stale snapshot and the version vector. See
        ps_tpu/checkpoint.py for the format; restore with :meth:`restore`
        after an identical ``init``."""
        from ps_tpu import checkpoint as ckpt

        self._require_init()
        arrays, meta = self._engine.state_dict()
        # async workers' cached pulls, saved exactly (not inferred): a worker
        # that pulled manually without caching must resume cache-less too.
        # A cached leaf is usually the very array recorded as that worker's
        # stale snapshot (pull_all does both) — store those as references
        # into the stale group instead of a second copy.
        stale = getattr(self._engine, "_stale", {})
        cache, aliased = {}, []
        for w, params in self._async_params.items():
            kv, _ = keymod.flatten_with_keys(params)
            for k, v in kv.items():
                s = ckpt.encode_stale_key(w, k)
                if stale.get((w, k)) is v:
                    aliased.append(s)
                else:
                    cache[s] = v
        arrays["worker_cache"] = cache
        meta["store"] = {
            "step": self.step,
            "bytes_pushed": self.bytes_pushed,
            "bytes_pulled": self.bytes_pulled,
            "key_order": self._key_order,
            "cache_keys": sorted(cache),
            "cache_stale_aliases": sorted(aliased),
        }
        ckpt.save(path, arrays, meta)

    def restore(self, path: str, elastic: bool = False) -> Any:
        """Restore a checkpoint written by :meth:`save` into this store.

        Must be called after ``init(params)`` with the same parameter
        structure and optimizer, so shardings and state wiring exist; every
        value is then overwritten in place and training resumes
        bit-identically (tests/test_checkpoint.py). Returns the restored
        parameter pytree.

        Elastic resume (SURVEY.md §6 "elastic resharding"): the restore
        targets carry the LIVE mesh's shardings, so a checkpoint written on
        one mesh size restores onto another (8→4, 4→8) with identical
        values — orbax reshards on read. ``elastic=True`` additionally
        relaxes the async ``num_workers`` equality check: surviving workers
        keep their version-vector entries and stale snapshots, removed
        workers' are dropped, and new workers join fresh (their first pull
        sets their version; pull before pushing, as make_async_step does).
        """
        from ps_tpu import checkpoint as ckpt

        self._require_init()
        meta = ckpt.read_meta(path)
        saved_order = meta["store"]["key_order"]
        if saved_order != self._key_order:
            diff = sorted(set(saved_order) ^ set(self._key_order))[:4]
            raise ValueError(
                f"checkpoint parameter keys do not match this store: saved "
                f"{len(saved_order)} keys, registered {len(self._key_order)}"
                + (f"; differing keys include {diff}" if diff
                   else "; same keys in a different order")
            )
        nw = getattr(self._engine, "num_workers", None)
        abstract = self._engine.abstract_state_dict(meta, elastic=elastic)
        ab_params = abstract["params"]
        # dropped workers' caches are excluded from the restore targets too:
        # an elastic shrink never reads ex-workers' bytes off disk
        abstract["worker_cache"] = {
            s: ab_params[ckpt.decode_stale_key(s)[1]]
            for s in meta["store"]["cache_keys"]
            if ckpt.keep_worker(ckpt.decode_stale_key(s)[0], nw, elastic)
        }
        arrays = ckpt.restore(path, abstract, meta)
        cache = arrays.pop("worker_cache")
        self._engine.load_state_dict(arrays, meta, elastic=elastic)
        st = meta["store"]
        self.step = int(st["step"])
        self.bytes_pushed = int(st["bytes_pushed"])
        self.bytes_pulled = int(st["bytes_pulled"])
        stale = getattr(self._engine, "_stale", {})
        by_worker: Dict[int, Dict[str, Any]] = {}
        for s, v in cache.items():
            w, k = ckpt.decode_stale_key(s)
            by_worker.setdefault(w, {})[k] = v
        for s in st.get("cache_stale_aliases", []):
            w, k = ckpt.decode_stale_key(s)
            if ckpt.keep_worker(w, nw, elastic):
                by_worker.setdefault(w, {})[k] = stale[(w, k)]
        self._async_params = {
            w: keymod.unflatten(self._treedef, kv, self._key_order)
            for w, kv in by_worker.items()
        }
        return self.params()

    # -- introspection ------------------------------------------------------

    def params(self) -> Any:
        """Current server-side parameter pytree — introspection only: no byte
        accounting and no protocol side effects (an async worker's snapshot
        is recorded by ``pull``/``pull_all``, never by this)."""
        self._require_init()
        read = getattr(self._engine, "peek", None) or self._engine.pull
        kv = {k: read(k) for k in self._key_order}
        return keymod.unflatten(self._treedef, kv, self._key_order)

    def optimizer_state(self, key: str):
        return self._engine.optimizer_state(key)

    @property
    def collective_bytes(self) -> int:
        """Analytic per-device ICI bytes moved by the server's collectives so
        far (the 'push/pull GB/s over ICI' numerator; 0 on the local backend,
        which moves no inter-device traffic)."""
        return getattr(self._engine, "collective_bytes", 0)

    @property
    def num_workers(self) -> int:
        return self._engine.num_workers
