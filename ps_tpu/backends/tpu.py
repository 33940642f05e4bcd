"""SPMD mesh backend — the TPU-native parameter server.

This is the north-star translation (BASELINE.json): the reference's
intra-node NCCL reduce + cross-node ZMQ push/pull + C++ server apply collapse
into one jitted XLA program over a device mesh:

- push      = gradient reduction (psum, inserted by XLA; reduce-scatter when
              parameters are sharded)
- server    = the mesh's data axis; each device owns a shard of the
              parameter + optimizer-state pytree ('sharded' placement) or a
              full replica ('replicated')
- apply     = optax update on the (sharded) pytree, compiled to TPU
- pull      = the post-apply parameters (all-gather on demand when sharded)

Multi-host: ``Config.coordinator_uri`` triggers ``jax.distributed.initialize``
— XLA's coordination service is the scheduler/rendezvous equivalent
(SURVEY.md §3 row 10).

Worker identity: in SPMD there is one controller; the 'worker' argument of
the per-key API is accepted for source compatibility and ignored — the worker
set IS the data axis, and per-worker gradients exist only inside the fused
step (before the automatic reduction).
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import optax

from ps_tpu.config import Config
from ps_tpu.parallel import collectives
from ps_tpu.parallel.mesh import DATA_AXIS, make_mesh
from ps_tpu.parallel.sharding import (
    batch_sharding,
    param_sharding,
    sharded_opt_init,
)


from ps_tpu.backends.common import (
    AsyncStagingMixin,
    PeekMixin,
    make_jit_dc_apply_tree,
)
from ps_tpu.checkpoint import CheckpointMixin


class AsyncTpuServer(PeekMixin, AsyncStagingMixin, CheckpointMixin):
    """Mesh-placed parameter server with ASYNC (stale, delay-compensated)
    apply — reference workload config 5 (SURVEY.md §4d).

    Semantics mirror the local backend's async mode exactly (the spec; parity
    asserted in tests/test_async_tpu.py): every whole-tree push applies
    immediately with the DC-ASGD correction against the pusher's last-pulled
    snapshot of that key; per-key pushes stage and commit as one tree
    (AsyncStagingMixin). The difference is placement: params and state live
    on the mesh (replicated or ZeRO-1 sharded), and each worker's gradient
    computation runs SPMD over the mesh — the mesh plays the reference's
    intra-node GPU set (the grad psum = NCCL reduce), while the *logical*
    workers (``Config.num_workers``) are the asynchronously-pushing nodes.

    Version accounting is at TREE granularity: ``version`` advances once per
    whole-model apply (a ``push_tree``, or a full tree's worth of per-key
    pushes); ``worker_version[w]`` records the version worker w last pulled,
    so ``staleness(w) = version_at_push - worker_version[w]``. Partial-tree
    pushes never produce fractional versions.

    Thread safety: the apply/pull paths serialize on a server-side lock —
    the TPU translation of the reference server's sequential per-key apply
    loop — so host threads can drive workers concurrently
    (tests/test_async_stress.py).
    """

    mode = "async"

    def __init__(self, optimizer: optax.GradientTransformation, mesh,
                 num_workers: int, placement: str = "replicated",
                 dc_lambda: float = 0.04, partition_rules=None):
        import collections
        import threading

        self._opt = optimizer
        self.mesh = mesh
        self.placement = placement
        self.partition_rules = partition_rules
        self.num_workers = num_workers
        self.dc_lambda = dc_lambda
        self._params: Dict[str, jax.Array] = {}
        self._state: Dict[str, Any] = {}
        self._stale: Dict[tuple, jax.Array] = {}
        self._staged_async: Dict[int, Dict[str, Any]] = {}  # per-key staging
        self._worker_version: Dict[int, int] = {}
        self._applies = 0          # total per-key applies (any granularity)
        self._version = 0          # whole-model versions
        self.apply_count: Dict[str, int] = {}
        self.collective_bytes = 0
        self.staleness_hist = collections.Counter()  # τ -> whole-tree pushes
        self._lock = threading.RLock()

        self._jit_apply_dc_tree = make_jit_dc_apply_tree(optimizer)

    @property
    def version(self) -> int:
        """Server version in whole-model steps."""
        return self._version

    def register_tree(self, kv: Dict[str, Any], treedef, key_order: List[str]):
        if self._params:
            raise RuntimeError("server already holds a registered tree")
        shardings = {
            k: param_sharding(self.mesh, v, self.placement, key=k,
                              rules=self.partition_rules)
            for k, v in kv.items()
        }
        self._params = {
            k: jax.device_put(np.asarray(v), shardings[k]) for k, v in kv.items()
        }
        for k, v in self._params.items():
            self._state[k] = sharded_opt_init(
                self._opt.init, v, self.mesh, self.placement,
                key=k, rules=self.partition_rules,
            )
            self.apply_count[k] = 0
        from ps_tpu.kv import keys as keymod

        return keymod.unflatten(treedef, self._params, key_order)

    def keys(self):
        return list(self._params)

    def _check_worker(self, worker: int) -> None:
        from ps_tpu.backends.common import AGG_WORKER_BASE

        # ids at/past AGG_WORKER_BASE are aggregator identities (a host
        # group's merged pushes — backends/aggregator.py): legal pushers
        # with their own staleness/dedup slots, deliberately outside the
        # data-sharding denominator num_workers counts
        if worker >= AGG_WORKER_BASE:
            return
        if not (0 <= worker < self.num_workers):
            raise ValueError(f"worker {worker} out of range [0, {self.num_workers})")

    def push(self, key: str, grad: Any, worker: int = 0) -> None:
        """Per-key compatibility path: stages per worker and commits the
        whole tree through ONE fused dispatch when this worker's last key
        arrives (AsyncStagingMixin — N-key push costs one dispatch, and the
        version/staleness sample is attributed to the completing worker)."""
        if key not in self._params:
            raise KeyError(f"unregistered key {key!r}")
        self._check_worker(worker)
        with self._lock:
            self._stage_async_push(key, grad, worker)

    def push_tree(self, grads_kv: Dict[str, Any], worker: int = 0) -> None:
        """Fused whole-tree async push: ONE XLA dispatch applies every key's
        DC-corrected update (the async bucketing pass — SURVEY.md §3 row 11).
        Numerically identical to pushing each key (keys are independent under
        per-tensor optimizers)."""
        if set(grads_kv) != set(self._params):
            raise ValueError("gradient keys do not match registered keys")
        self._check_worker(worker)
        with self._lock:
            self._commit_tree(grads_kv, worker)

    def push_subtree(self, grads_kv: Dict[str, Any], worker: int = 0) -> None:
        """One fused DC apply of a SUBSET of keys — the live-migration
        replay path (ps_tpu/elastic): a logical push retried across a
        range move owes an apply only to the keys whose per-key dedup
        token missed it, and keys are independent under per-tensor
        optimizers, so applying exactly that subset is numerically the
        replay of exactly those keys."""
        missing = [k for k in grads_kv if k not in self._params]
        if missing:
            raise KeyError(f"unregistered keys {missing[:3]}")
        self._check_worker(worker)
        with self._lock:
            self._commit_tree(grads_kv, worker)

    def _commit_tree_accounting(self, grads_kv) -> None:
        self._applies += len(grads_kv)
        k = self.mesh.shape[DATA_AXIS]
        self.collective_bytes += collectives.allreduce_bytes(
            {key: self._params[key] for key in grads_kv}, k
        )

    def pull(self, key: str, worker: int = 0) -> jax.Array:
        if key not in self._params:
            raise KeyError(f"unregistered key {key!r}")
        with self._lock:
            self._flush_staged(worker)  # pull ends this worker's push phase
            self._stale[(worker, key)] = self._params[key]
            self._worker_version[worker] = self.version
            return self._params[key]

    def pull_tree(self, worker: int = 0) -> Dict[str, Any]:
        """Atomic whole-tree pull: the snapshot and the version record come
        from ONE server state — a concurrent push cannot interleave between
        two keys of the same pull (the torn-read hazard of per-key pulls)."""
        with self._lock:
            self._flush_staged(worker)  # pull ends this worker's push phase
            for k, v in self._params.items():
                self._stale[(worker, k)] = v
            self._worker_version[worker] = self.version
            return dict(self._params)

    def staleness(self, worker: int) -> int:
        """Whole-model versions the server advanced since this worker's last
        pull (the τ of the DC-ASGD correction)."""
        return self.version - self._worker_version.get(worker, 0)

    def optimizer_state(self, key: str):
        return self._state[key]

    # -- elastic membership hooks (ps_tpu/elastic) ---------------------------
    # Live key-range migration moves whole OWNERSHIP UNITS between engines:
    # the parameter, its per-key optimizer state, every worker's stale
    # snapshot of it, and its apply count. Keys are independent under
    # per-tensor optimizers (the property the whole fused-apply design
    # already rests on), which is exactly what makes a key's history
    # portable between engines bit-for-bit.

    def export_keys(self, keys):
        """Full migration rows for ``keys`` (CALLER holds the lock).

        Optimizer state travels flattened (``{leaf-path: array}`` in
        flatten order) — the recipient rebuilds the pytree against a
        fresh ``opt.init`` of the adopted param, so treedefs never
        cross the wire."""
        from ps_tpu.kv import keys as keymod

        out = {}
        for k in keys:
            if k not in self._params:
                raise KeyError(f"unregistered key {k!r}")
            state_kv, _ = keymod.flatten_with_keys(self._state[k])
            out[k] = {
                "param": self._params[k],
                "state": state_kv,
                "stale": {w: v for (w, kk), v in self._stale.items()
                          if kk == k},
                "apply_count": self.apply_count.get(k, 0),
            }
        return out

    def adopt_key(self, k: str, param, state_kv, stale,
                  apply_count: int = 0) -> None:
        """Install one migrated row (CALLER holds the lock): place the
        param per this engine's policy, rebuild the optimizer state from
        the donor's flattened leaves over a fresh-init structure, and
        seed the stale snapshots so the DC correction resumes where the
        donor left it."""
        from ps_tpu.kv import keys as keymod

        if k in self._params:
            raise KeyError(f"key {k!r} already registered")
        sh = param_sharding(self.mesh, np.asarray(param), self.placement,
                            key=k, rules=self.partition_rules)
        p = jax.device_put(np.asarray(param), sh)
        fresh = sharded_opt_init(self._opt.init, p, self.mesh,
                                 self.placement, key=k,
                                 rules=self.partition_rules)
        fkv, fdef = keymod.flatten_with_keys(fresh)
        order = list(fkv)
        if sorted(fkv) != sorted(state_kv):
            raise ValueError(
                f"optimizer-state structure mismatch for {k!r}: donor "
                f"sent {sorted(state_kv)[:3]}, this engine expects "
                f"{sorted(fkv)[:3]} — donor and recipient must run the "
                f"same optimizer"
            )
        merged = {}
        for sk, like in fkv.items():
            v = np.asarray(state_kv[sk])
            if tuple(v.shape) != tuple(np.shape(like)):
                raise ValueError(
                    f"optimizer-state leaf {sk!r} of {k!r} has shape "
                    f"{v.shape}, expected {np.shape(like)}"
                )
            merged[sk] = jax.device_put(v, like.sharding)
        self._params[k] = p
        self._state[k] = keymod.unflatten(fdef, merged, order)
        for w, v in stale.items():
            self._stale[(int(w), k)] = jax.device_put(np.asarray(v), sh)
        self.apply_count[k] = int(apply_count)

    def evict_keys(self, keys) -> None:
        """Drop migrated-away keys (CALLER holds the lock): params, state,
        stale snapshots, apply counts — and any per-key async staging of
        them (a staged partial tree must not commit a key this engine no
        longer owns)."""
        gone = set(keys)
        for k in gone:
            if k not in self._params:
                raise KeyError(f"unregistered key {k!r}")
        for k in gone:
            del self._params[k]
            del self._state[k]
            self.apply_count.pop(k, None)
        for wk in [wk for wk in self._stale if wk[1] in gone]:
            del self._stale[wk]
        for staged in self._staged_async.values():
            for k in gone & set(staged):
                del staged[k]

    # -- checkpoint hooks (CheckpointMixin) ---------------------------------
    # SURVEY.md §6: async mode checkpoints server-side state + every worker's
    # stale snapshots + the per-worker version vector.

    engine_name = "tpu_async"

    def _checkpoint_meta(self):
        return {
            "applies": self._applies,
            "version": self._version,
            "staleness_hist": {str(t): n for t, n in self.staleness_hist.items()},
            "num_workers": self.num_workers,
            "worker_version": {str(w): v for w, v in self._worker_version.items()},
            "apply_count": dict(self.apply_count),
            "collective_bytes": self.collective_bytes,
        }

    def _check_checkpointable(self):
        self._check_staged_async()

    def _validate_checkpoint_meta(self, meta, elastic=False):
        if meta["num_workers"] != self.num_workers and not elastic:
            raise ValueError(
                f"checkpoint was written with num_workers={meta['num_workers']} "
                f"but this store runs num_workers={self.num_workers} — "
                f"staleness semantics would differ (restore(elastic=True) "
                f"remaps: surviving workers keep their versions, removed "
                f"workers' state is dropped, new workers join fresh)"
            )

    def _load_checkpoint_meta(self, meta, elastic=False):
        import collections

        from ps_tpu.checkpoint import keep_worker

        self._worker_version = {
            int(w): int(v) for w, v in meta["worker_version"].items()
            if keep_worker(int(w), self.num_workers, elastic)
        }
        self._applies = int(meta["applies"])
        # .get defaults accept checkpoints from before tree-granularity
        # version accounting (whose version was applies // key count)
        self._version = int(
            meta.get("version", self._applies // max(len(self._params), 1))
        )
        self.staleness_hist = collections.Counter(
            {int(t): int(n) for t, n in meta.get("staleness_hist", {}).items()}
        )
        self.apply_count = {k: int(v) for k, v in meta["apply_count"].items()}
        self.collective_bytes = int(meta["collective_bytes"])


class TpuServer(PeekMixin, CheckpointMixin):
    """Mesh-sharded parameter/optimizer-state store with PS semantics.

    Holds the parameter dict ``{key: jax.Array}`` placed per the placement
    policy, plus ONE whole-tree optax state (numerically identical to the
    local backend's per-key states for per-tensor optimizers; asserted by the
    parity tests).
    """

    def __init__(self, optimizer: optax.GradientTransformation, mesh,
                 placement: str = "replicated", aggregate: str = "mean",
                 mode: str = "sync", partition_rules=None):
        assert mode == "sync", "async mode is handled by AsyncTpuServer"
        if aggregate not in ("mean", "sum"):
            raise ValueError("aggregate must be 'mean' or 'sum'")
        self._opt = optimizer
        self.mesh = mesh
        self.placement = placement
        self.partition_rules = partition_rules
        self.aggregate = aggregate
        self.mode = mode
        self.num_workers = mesh.shape[DATA_AXIS]
        self._params: Dict[str, jax.Array] = {}
        self._state = None
        self._shardings: Dict[str, Any] = {}
        self._staged: Dict[str, Any] = {}
        # analytic ICI traffic (bytes per device) accumulated across updates
        self.collective_bytes = 0
        self._apply_fn = None
        self._update_bytes = 0  # per-device ICI bytes of one update
        self.apply_count = 0

    # -- registration -------------------------------------------------------

    def register_tree(self, kv: Dict[str, Any], treedef, key_order: List[str]):
        if self._params:
            raise RuntimeError("server already holds a registered tree")
        self._shardings = {
            k: param_sharding(self.mesh, v, self.placement, key=k,
                              rules=self.partition_rules)
            for k, v in kv.items()
        }
        # np.asarray forces a fresh device buffer: device_put of an array that
        # already matches the sharding would alias the caller's buffer, and
        # the fused step donates (frees) server buffers every update.
        self._params = {
            k: jax.device_put(np.asarray(v), self._shardings[k])
            for k, v in kv.items()
        }
        # whole-tree state, placed by the same policy as the params it sits
        # next to (ZeRO-1: moment tensors shard with their param, scalars
        # replicate) — explicit so checkpoint restore lands identically
        self._state = sharded_opt_init(
            self._opt.init, self._params, self.mesh, self.placement,
            rules=self.partition_rules,
        )

        # what one update moves over ICI per device: a constant of the tree
        k = self.num_workers
        if self.placement == "replicated":
            # grads were all-reduced across the data axis
            self._update_bytes = collectives.allreduce_bytes(self._params, k)
        else:
            # reduce-scatter grads to owners + all-gather params for next fwd
            self._update_bytes = (
                collectives.reduce_scatter_bytes(self._params, k)
                + collectives.all_gather_bytes(self._params, k))

        # No donation here: this apply backs the per-key/push_pull
        # compatibility path, whose callers may legitimately hold pulled
        # arrays across steps. The fused make_step path owns its buffers
        # exclusively and donates there instead (2x transient memory here is
        # the price of the compatibility semantics).
        self._apply_fn = jax.jit(self.apply_rule)
        from ps_tpu.kv import keys as keymod

        return keymod.unflatten(treedef, self._params, key_order)

    def keys(self):
        return list(self._params)

    # -- fused whole-tree update -------------------------------------------

    @property
    def grad_scale(self) -> float:
        """Aggregation-semantics factor applied to incoming global-mean
        gradients: 1 for 'mean'; num_workers for 'sum' (the local backend's
        sum of per-worker grads equals the global mean times the worker
        count when worker batches are equal — parity tested)."""
        return float(self.num_workers) if self.aggregate == "sum" else 1.0

    def apply_rule(self, params, state, grads):
        """The server's update rule, ``(params, state)`` after ``grads``:
        scale, ``opt.update``, ``optax.apply_updates``. A pure function,
        traced by the per-key path's jitted apply and, inside its
        ``ps.apply`` scope, by the fused step (ps_tpu/kv/fused.py)."""
        scale = self.grad_scale
        if scale != 1.0:  # aggregate='sum' semantics
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        updates, state = self._opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    def update_tree(self, grads_kv: Dict[str, Any]) -> Dict[str, Any]:
        """One server step: aggregate(implicit) + apply; returns new params.

        Gradients are expected to be *global* gradients (mean over the global
        batch — XLA already reduced them inside the caller's jitted grad
        computation, which is where the reference's NCCL+ZMQ push lived).
        """
        self.set_tree_and_state(
            *self._apply_fn(self._params, self._state, grads_kv))
        return dict(self._params)

    # -- per-key protocol (stages, flushes at full-tree granularity) --------

    def push(self, key: str, grad: Any, worker: int = 0) -> None:
        del worker  # SPMD single-controller: the worker set is the data axis
        if key not in self._params:
            raise KeyError(f"unregistered key {key!r}")
        if key in self._staged:
            raise RuntimeError(f"key {key!r} already staged this step")
        self._staged[key] = grad
        if len(self._staged) == len(self._params):
            staged, self._staged = self._staged, {}
            self.update_tree(staged)

    def pull(self, key: str, worker: int = 0) -> jax.Array:
        del worker
        if key not in self._params:
            raise KeyError(f"unregistered key {key!r}")
        if self._staged:
            missing = sorted(set(self._params) - set(self._staged))
            shown = ", ".join(missing[:3]) + (", ..." if len(missing) > 3 else "")
            raise RuntimeError(
                f"pull({key!r}) would block: the tpu backend applies at "
                f"full-tree granularity and keys [{shown}] have not been "
                f"pushed this step"
            )
        return self._params[key]

    def optimizer_state(self, key: str):
        """Per-key view into the whole-tree state (PS-API compatibility).

        The whole-tree optax state embeds copies of the registered param
        dict (mu/nu/trace), recognizable as dicts carrying EXACTLY the full
        key set — an optimizer state field that merely happens to contain a
        same-named entry does not match (the tree_map-on-'contains' trap)."""
        full_keys = set(self._params)

        def is_param_dict(x):
            return isinstance(x, dict) and set(x) == full_keys

        return jax.tree_util.tree_map(
            lambda leaf: leaf[key] if is_param_dict(leaf) else leaf,
            self._state,
            is_leaf=is_param_dict,
        )

    # -- checkpoint hooks (CheckpointMixin) ---------------------------------

    engine_name = "tpu_sync"

    def _check_checkpointable(self):
        if self._staged:
            raise RuntimeError(
                f"cannot checkpoint mid-step: keys {sorted(self._staged)} "
                f"are staged but unapplied"
            )

    def _checkpoint_meta(self):
        return {
            "apply_count": self.apply_count,
            "collective_bytes": self.collective_bytes,
        }

    def _load_checkpoint_meta(self, meta, elastic=False):
        del elastic  # sync SPMD state is topology-free: shardings are live
        self._staged = {}
        self.apply_count = int(meta["apply_count"])
        self.collective_bytes = int(meta["collective_bytes"])

    # no _validate_checkpoint_meta: nothing topology-bound to refuse

    # -- internals for the fused train step ---------------------------------

    def get_tree_and_state(self):
        return dict(self._params), self._state

    def set_tree_and_state(self, params, state):
        """Adopt one update's result and count it."""
        self._params, self._state = dict(params), state
        self.apply_count += 1
        self.collective_bytes += self._update_bytes


# Coordination-service handles parked by shutdown(abort=True): destroying one
# cancels all in-flight RPCs, which peers' poll threads treat as fatal. Kept
# alive until process exit instead.
_LEAKED_SERVICES: list = []


def _coordination_seam():
    """The module object holding jax's distributed-runtime-client factory
    (the private ``jax._src.distributed._jax``) and the factory itself.
    Returns ``(owner, factory)``; raises AttributeError when jax moves the
    seam (the tests turn that into a loud failure)."""
    from jax._src import distributed as _dist

    owner = _dist._jax
    return owner, owner.get_distributed_runtime_client


#: the recoverable-task client options and their values
_RECOVERABLE_OPTS = {"recoverable": True, "shutdown_on_destruction": False}


@contextlib.contextmanager
def _coordination_client_options():
    """Within the block, ``jax.distributed.initialize`` builds its
    coordination client as a *recoverable* task with
    ``shutdown_on_destruction=False``. Recoverable means the coordination
    service does NOT propagate one task's death to the others (jax's default
    reaction is a LOG(FATAL) from the error-poll thread — it would kill the
    survivors our failure detector is trying to hand a typed error), and the
    distributed shutdown barrier no longer blocks on dead peers. Dropping
    the client handle is barrier-free, which is what ``shutdown(abort=True)``
    relies on. Wraps a private jax seam (:func:`_coordination_seam`). If the
    seam moves or a kwarg is refused, initialization falls back to jax's
    defaults with a warning — and
    ``tests/test_failure.py::test_coordination_seam_accepts_recoverable_kwargs``
    / ``::test_coordination_client_options_inject_without_degrading``
    construct a client through this exact path so the degradation is a loud
    CI failure, not only a runtime warning."""
    try:
        owner, orig = _coordination_seam()
    except (ImportError, AttributeError) as e:
        import warnings

        warnings.warn(
            "jax private coordination seam moved "
            f"({e!r}); shutdown(abort=True) loses its barrier-free "
            "recoverable semantics and peer death may LOG(FATAL) survivors"
        )
        yield
        return

    def patched(*args, **kwargs):
        try:
            return orig(*args, **{**kwargs, **_RECOVERABLE_OPTS})
        except TypeError:
            import warnings

            warnings.warn(
                "jax coordination client no longer accepts recoverable/"
                "shutdown_on_destruction; clean aborts will degrade to "
                "jax defaults (LOG(FATAL) on peer death)"
            )
            return orig(*args, **kwargs)

    owner.get_distributed_runtime_client = patched
    try:
        yield
    finally:
        owner.get_distributed_runtime_client = orig


def _place_compile_cache() -> None:
    """Give XLA's persistent compile cache a home before anything compiles
    (README "Running on the chip"). Where ``JAX_COMPILATION_CACHE_DIR`` is
    set JAX reads it itself and nothing is touched; otherwise the cache is
    ``<checkout>/.jax_cache``, found from this package's own location — a
    fixed path, so a second process in the same checkout hits what the
    first one compiled."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(checkout, ".jax_cache"))


class TpuBackend:
    """Backend for ``ps_tpu.init(backend='tpu')``. Despite the name it runs
    anywhere JAX has devices — on CPU it uses virtual devices (tests), on a
    TPU slice it uses the real chips over ICI."""

    def __init__(self, config: Config):
        _place_compile_cache()
        self.config = config
        self._owns_distributed = False
        self.failure_detector = None
        all_peers = config.heartbeat_peers()
        detector_on = all_peers is not None and config.num_processes > 1
        if config.coordinator_uri is not None:
            # With the failure detector on, it owns failure handling: the
            # typed WorkerFailureError surfaces in the training thread and
            # the job exits through shutdown(abort=True). jax's default
            # coordination client would instead LOG(FATAL) the process from
            # its error-poll thread on any peer death/teardown, and its
            # destructor would block in the shutdown barrier — both defeat
            # the clean abort path, so swap in recoverable client options.
            opts = (_coordination_client_options() if detector_on
                    else contextlib.nullcontext())
            with opts:
                jax.distributed.initialize(
                    coordinator_address=config.coordinator_uri,
                    num_processes=config.num_processes,
                    process_id=config.process_id,
                )
            self._owns_distributed = True
        if detector_on:
            from ps_tpu.control import FailureDetector

            my_port = all_peers[config.process_id][1]
            peers = {i: hp for i, hp in all_peers.items()
                     if i != config.process_id}
            try:
                self.failure_detector = FailureDetector(
                    node_id=config.process_id,
                    peers=peers,
                    port=my_port,
                    bind=config.resolved_heartbeat_bind(),
                    interval_ms=config.heartbeat_interval_ms,
                    timeout_ms=config.heartbeat_timeout_ms,
                )
                self.failure_detector.wait_for_peers()
            except Exception:
                # failed init must not leave beat threads running (peers
                # would see us alive while we never joined) or the
                # coordination service up
                if self.failure_detector is not None:
                    self.failure_detector.close()
                    self.failure_detector = None
                if self._owns_distributed:
                    jax.distributed.shutdown()
                    self._owns_distributed = False
                raise
        self.mesh = make_mesh(config.mesh_shape)
        self.num_workers = self.mesh.shape.get(DATA_AXIS, 1)

    def check_health(self) -> None:
        """Raise WorkerFailureError if a peer process died (no-op when the
        failure detector is disabled)."""
        if self.failure_detector is not None:
            self.failure_detector.check()

    def create_server(self, optimizer, mode: Optional[str] = None,
                      aggregate: str = "mean", placement: str = "replicated",
                      partition_rules=None):
        mode = mode or self.config.mode
        if mode == "async":
            return AsyncTpuServer(
                optimizer,
                self.mesh,
                num_workers=self.config.num_workers,
                placement=placement,
                dc_lambda=self.config.dc_lambda,
                partition_rules=partition_rules,
            )
        return TpuServer(
            optimizer,
            self.mesh,
            placement=placement,
            aggregate=aggregate,
            mode=mode,
            partition_rules=partition_rules,
        )

    def batch_sharding(self):
        return batch_sharding(self.mesh)

    def shutdown(self, abort: bool = False) -> None:
        """Tear down. ``abort=True`` is the post-failure path: announce a
        goodbye so fellow survivors don't also flag THIS exit as a death,
        then drop the ``jax.distributed`` connection WITHOUT the distributed
        shutdown barrier — with a peer dead, that barrier can never complete
        and would hang every survivor."""
        if self.failure_detector is not None:
            self.failure_detector.close(goodbye=True)
            self.failure_detector = None
        if self._owns_distributed:
            if abort:
                from jax._src import distributed as _dist

                # This client was built recoverable (see
                # _coordination_client_options): its shutdown RPC skips the
                # all-process barrier, so disconnecting here cannot hang on
                # the dead peer. The coordination SERVICE handle (process 0)
                # is deliberately leaked instead of destroyed — its
                # destructor cancels every in-flight RPC, which other
                # processes' error-poll threads answer with LOG(FATAL);
                # the OS reclaims it at exit, after everyone disconnected.
                # Known limit: if the coordinator PROCESS itself is the one
                # that died, survivors' poll threads may still terminate
                # them before this runs (scheduler SPOF, as in the
                # reference family).
                _dist.global_state.preemption_sync_manager = None
                try:
                    if _dist.global_state.client is not None:
                        _dist.global_state.client.shutdown()
                except Exception:
                    pass  # service already gone: the disconnect is moot
                _dist.global_state.client = None
                if _dist.global_state.service is not None:
                    _LEAKED_SERVICES.append(_dist.global_state.service)
                    _dist.global_state.service = None
                _dist.global_state.coordinator_address = None
                _dist.global_state.process_id = 0
            else:
                jax.distributed.shutdown()
            self._owns_distributed = False
