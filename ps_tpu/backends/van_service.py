"""Shared serve/accept/drain machinery for van-backed PS services.

Both cross-process services (dense-async :class:`AsyncPSService`, sparse
:class:`SparsePSService`) are the same shape: a TCP listener, one serve
thread per worker connection, a request→reply loop over framed tensor
messages, and a stop that must never tear a reply off the wire. This base
class owns that shape; subclasses provide only the protocol dispatch
(:meth:`_handle`) and the commit gate (:meth:`_set_draining`).

The drain contract (VERDICT r4 item 1 — the round-4 flake was ``stop()``
severing a ``PUSH_PULL`` reply mid-send):

1. ``stop()`` first stops admitting connections (accept thread joined,
   listener closed), so the channel set is frozen;
2. then waits (bounded by ``grace``) for every IN-FLIGHT request — one
   whose frame has been received — to finish its reply send;
3. only then flips the draining flag (refusing any straggler commit under
   the subclass's apply lock) and severs the remaining channels, which at
   that point are idle in ``recv``.

A request whose processing has begun (its serve thread is past the
in-flight mark) therefore completes: its push is applied and its reply
arrives intact at the worker. A request still RACING ``stop()`` — sent
concurrently, or whose frame arrived in the microseconds before the sever
(TCP offers no atomic "refuse from now", so that window cannot be closed,
only shrunk — the drain wait double-checks stability across a confirm
delay) — may instead fail at the worker with a typed
:class:`~ps_tpu.backends.remote_async.ServerFailureError`. Workers that
need a clean end must quiesce first by sending ``SHUTDOWN``
(``worker.close()`` does), which is counted in :attr:`goodbyes` so a
server can :meth:`wait_for_goodbyes` before stopping; after the goodbye
no request of that worker can race anything.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Dict, List, Optional

from ps_tpu import obs
from ps_tpu.backends.common import BucketAssembler, send_payload
from ps_tpu.control import tensor_van as tv
from ps_tpu.utils.metrics import TransportStats


class NotServingError(RuntimeError):
    """Raised inside a handler when this service must refuse the request
    retryably (it was fenced mid-request, or flipped out of primary). The
    serve loop encodes it as an ERR reply carrying ``backup: True`` — the
    same retry-able shape an unpromoted backup sends — so the worker's
    failover loop re-routes instead of failing the job."""


class StaleTableError(RuntimeError):
    """Raised inside a handler when the request's key range is not (or no
    longer) served here because the SHARD TABLE moved — a live rebalance
    migrated keys between shards (ps_tpu/elastic). Typed apart from
    :class:`NotServingError` because the remedy differs: the server is
    healthy, only the assignment changed, so the worker must re-fetch the
    table from the coordinator and re-split — NOT cycle this shard's
    replica set. The serve loop encodes it as an ERR reply carrying
    ``moved: True`` plus this service's ``table_epoch``."""


class RingLog:
    """Fixed-size tail of an append-only log, plus the total count.

    A 10⁶-apply server must not hold O(applies) memory: the services'
    ``apply_log``/``event_log`` default to this ring (most recent
    ``maxlen`` entries retained, ``total`` counts everything ever
    appended). ``record_full_history=True`` swaps in :class:`FullLog`
    for the replay-parity tests, which genuinely need every entry.
    """

    def __init__(self, maxlen: int = 4096):
        import collections

        self._d = collections.deque(maxlen=int(maxlen))
        self.total = 0

    def append(self, x) -> None:
        self._d.append(x)
        self.total += 1

    def __len__(self) -> int:
        return len(self._d)

    def __iter__(self):
        return iter(self._d)

    def __repr__(self) -> str:
        return (f"RingLog(tail={len(self._d)}/{self._d.maxlen}, "
                f"total={self.total})")


class FullLog(list):
    """Unbounded history (``record_full_history=True``): a plain list —
    json-serializable, as the replay-parity subprocess dumps require —
    with the same ``total`` surface as :class:`RingLog`."""

    @property
    def total(self) -> int:
        return len(self)


def make_history_log(record_full_history: bool, maxlen: int = 4096):
    return FullLog() if record_full_history else RingLog(maxlen)


#: how many trailing log entries a STATS reply ships — bounded even when
#: the service records full history, so stats frames never grow multi-MB
STATS_LOG_TAIL = 4096


def log_tail(log, n: int = STATS_LOG_TAIL) -> list:
    """The last ``n`` entries of a RingLog/FullLog as a json-ready list."""
    entries = list(log)
    return entries[-n:] if len(entries) > n else entries


def resolve_ckpt_dir(root: Optional[str], client_dir: str) -> str:
    """Resolve a client-supplied CHECKPOINT dir under the service's
    ``ckpt_root``.

    With no root configured the legacy behavior stands (the client names an
    arbitrary server-host path — loopback-bind deployments only). With a
    root, the client path must be relative and may not escape: absolute
    paths and ``..`` traversals are refused, so an unauthenticated peer can
    never direct the server's filesystem writes outside the root.
    """
    if root is None:
        return client_dir
    if os.path.isabs(client_dir):
        raise ValueError(
            f"absolute checkpoint path {client_dir!r} refused: this server "
            f"confines checkpoints under ckpt_root={root!r} — pass a "
            f"relative path"
        )
    norm = os.path.normpath(client_dir)
    if norm == ".." or norm.startswith(".." + os.sep):
        raise ValueError(
            f"checkpoint path {client_dir!r} escapes ckpt_root={root!r}"
        )
    return os.path.join(root, norm)


class _DaemonPool:
    """Tiny reusable-thread pool of DAEMON workers for the native-loop
    punt path. Spawns a worker per submit only while none is idle (up to
    ``max_workers``); excess tasks queue. Daemon threads on purpose: a
    punted request can legitimately park forever (a pause nothing ever
    resumes after ``kill()``), and that must never block interpreter
    exit — the same reason per-connection serve threads are daemons. No
    shutdown needed or offered; an exhausted-and-parked pool only queues
    work that would have parked anyway, and the draining flag wakes
    parked tasks into refusal on a normal ``stop()``."""

    def __init__(self, max_workers: int = 32, name: str = "pool"):
        import queue

        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._max = int(max_workers)
        self._name = name
        self._lock = threading.Lock()
        self._nthreads = 0
        self._idle = 0

    def submit(self, fn, *args) -> None:
        # spawn BEFORE queuing: if Thread.start() raises (thread
        # exhaustion), the exception must reach the caller with the task
        # NOT enqueued — queue-then-fail would leave a stale task that an
        # existing worker later runs against state the caller's error
        # path already released. `idle` may be stale by one task either
        # way — worst case an extra worker spawns (capped) or a task
        # briefly queues.
        with self._lock:
            if self._idle == 0 and self._nthreads < self._max:
                threading.Thread(
                    target=self._run, daemon=True,
                    name=f"{self._name}-{self._nthreads}",
                ).start()
                self._nthreads += 1  # only counted once start succeeded
        self._q.put((fn, args))

    def _run(self) -> None:
        while True:
            with self._lock:
                self._idle += 1
            fn, args = self._q.get()
            with self._lock:
                self._idle -= 1
            try:
                fn(*args)
            except Exception:
                logging.getLogger(__name__).exception(
                    "punted van request failed")


class VanService:
    """One listener + per-connection serve threads over the tensor van.

    Subclass obligations:
      - call ``VanService.__init__(port, bind)`` LAST in your ``__init__``
        (it starts accepting immediately — your state must be ready);
      - implement ``_handle(kind, worker, tensors, extra) -> bytes``
        returning the encoded reply (raise to send an ERR reply);
      - implement ``_set_draining()``: under your apply lock, set the flag
        your commit path checks so no push lands after ``stop()`` returns.
    """

    def __init__(self, port: int = 0, bind: str = "127.0.0.1",
                 writev: Optional[bool] = None,
                 shm: Optional[bool] = None,
                 backup: bool = False,
                 native_loop: Optional[bool] = None,
                 loop_threads: Optional[int] = None):
        from ps_tpu.config import env_flag

        # vectored replies (scatter-gather send of live snapshot tensors —
        # no staging bytearray) and willingness to accept a worker's
        # same-host shared-memory lane offer. None = the PS_WRITEV /
        # PS_SHM env defaults; PS_SHM=0 is the job-wide lane off-switch
        # (workers then never offer, and this side also refuses — note the
        # asymmetric defaults: workers only OFFER on explicit PS_SHM=1,
        # servers ACCEPT offers unless explicitly told not to).
        self.writev = (env_flag("PS_WRITEV", True)
                       if writev is None else bool(writev))
        self._shm_accept = (env_flag("PS_SHM", True)
                            if shm is None else bool(shm))
        # priority bucket scheduling, server half: bucket replies carry
        # their bucket index into the native loop's priority writev drain
        # (front-of-model bytes flush before tail layers' when several
        # conns back up). Off = every reply at priority 0 = FIFO drain.
        self._bucket_priority = env_flag("PS_BUCKET_PRIORITY", True)
        self._listener = tv.Listener(port=port, bind=bind)
        self._stop = threading.Event()
        self._chan_lock = threading.Lock()
        self._conns: List[threading.Thread] = []
        self._channels: List[tv.Channel] = []
        # requests whose frame arrived but whose reply is not yet fully
        # sent — what stop() waits out before severing anything
        self._inflight = 0
        self._inflight_cond = threading.Condition()
        # of those, how many are parked on a checkpoint-pause condition
        # (not executing): stop()'s drain wait subtracts them instead of
        # burning the full grace on requests that can only finish once the
        # draining flag wakes them into refusal
        self._pause_blocked = 0
        # multi-bucket push staging (BUCKET_PUSH / ROW_BUCKET_PUSH): one
        # in-flight epoch per worker; only a COMPLETE epoch is handed to the
        # subclass's apply, so a torn multi-bucket push is never observable
        self._stage_lock = threading.Lock()
        self._push_stage: Dict[int, BucketAssembler] = {}
        # server-side transport accounting: stale-epoch drops (observable
        # via STATS and the worker's StepLogger line), codec seconds for
        # compressed pushes/pulls, and the zero-copy lane counters (shm
        # frames, spill, vectored-reply bytes, recv-pool hit rate)
        self.transport = TransportStats()
        # freshness plane (README "Online serving & freshness"): the
        # staleness bound served ages are judged against — the
        # within-bound share is ps_top's age% column
        from ps_tpu.config import env_float

        self._fresh_slo = env_float("PS_FRESHNESS_SLO", 0.5, lo=1e-3)
        # reusable receive buffers for the serve loop: a request frame is
        # provably dead once its reply is sent, so the loop borrows and
        # returns per request instead of allocating per frame
        self._recv_pool = tv.RecvBufferPool(stats=self.transport)
        # checkpoint ownership token (issued at pause, validated by every
        # later phase, cleared at resume) — shared bookkeeping for both
        # concrete services; mutated only under the subclass's apply lock
        self._ckpt_token: Optional[int] = None
        self._ckpt_seq = 0
        # shard replication & failover (ps_tpu/replica): a backup-role
        # service applies REPLICA_APPEND events and refuses worker traffic
        # until promoted; a primary may attach_backup() a session. The
        # epoch is the shard-table fencing token — promotion bumps it, and
        # workers refuse to re-route to a lower-epoch (zombie) server.
        self.role = "backup" if backup else "primary"
        self.epoch = 0
        # elastic membership (ps_tpu/elastic): the shard-table epoch this
        # service last observed (0 = static topology). Migration commits
        # advance it; stale-table refusals carry it so workers know which
        # epoch to wait past when they re-fetch from the coordinator.
        self.table_epoch = 0
        self._primary_epoch = 0       # backup: learned at REPLICA_HELLO
        self._replica_applied_seq = 0  # backup: last applied stream seq
        self._replica_attached = False
        self._backup_session = None    # primary: BackupSession or None
        self.promote_reason: Optional[str] = None
        self.promotion_s: Optional[float] = None  # promote() call duration
        self.goodbyes = 0  # workers that sent SHUTDOWN (clean departures)
        self._goodbye_cond = threading.Condition()
        # chaos fault-injection hook (ps_tpu/chaos, README "Autopilot &
        # chaos"): when set, every dispatched frame is offered to the
        # hook FIRST — a returned reply short-circuits the handler
        # (blackhole refusals, fault drills); None serves normally.
        # Harness-only surface: nothing in the serving path ever sets it.
        self.chaos = None
        # observability (ps_tpu/obs): request counter into the process
        # registry (several services in one process merge by name), and
        # the opt-in /metrics endpoint — a no-op unless PS_METRICS_PORT
        # is set (start_metrics_server is idempotent per process)
        self._req_counter = obs.default_registry().counter(
            "ps_server_requests_total", "frames served (all kinds)")
        obs.start_metrics_server()
        # native epoll event-loop data plane (README "Native event loop"):
        # accept, frame reads, and scatter-gather reply writes run on a
        # small fixed pool of native threads with the GIL out of the hot
        # path; ONE Python pump thread drains batches of complete requests
        # through the same _dispatch the threaded path uses, so typed
        # refusals, replica forwarding, dedup tokens and tracing spans are
        # identical by construction. None = the PS_VAN_NATIVE_LOOP env
        # default (off); non-Linux (or a van build without the nl_* ABI)
        # falls back to thread-per-connection with a log line.
        from ps_tpu.control import native_loop as nlmod

        want_loop = (env_flag("PS_VAN_NATIVE_LOOP", False)
                     if native_loop is None else bool(native_loop))
        if loop_threads is None:
            # validated service-level read (pslint PSL406): env_int
            # clamps to Config.van_loop_threads' [1, 64] with a warning,
            # so a value that bypassed Config cannot abort server
            # startup with an opaque nl_start failure
            from ps_tpu.config import env_int

            loop_threads = env_int("PS_VAN_LOOP_THREADS", 1, lo=1, hi=64)
        if not (1 <= loop_threads <= 64):
            # explicit arguments clamp to the same bound, same warning
            logging.getLogger(__name__).warning(
                "van loop_threads %d outside [1, 64]; clamping", loop_threads)
            loop_threads = min(max(loop_threads, 1), 64)
        self._nloop = None
        self._pump_thread = None
        self._accept_thread = None
        # requests that can BLOCK commit kinds (a punted CHECKPOINT whose
        # pause flag is not yet visible): raised by the pump before the
        # blocker thread starts, so the punt decision never races the flag
        self._loop_blockers = 0
        # kill() flips this so the pump DROPS queued read-ahead frames
        # instead of applying them — the SIGKILL-equivalence contract
        self._pump_abort = False
        # of _pause_blocked, how many parks sit on native-loop punted
        # threads (each holding one claimed loop body) — the native
        # drain's nl_pending discount
        self._loop_pause_parked = 0
        if want_loop:
            if not nlmod.available():
                logging.getLogger(__name__).warning(
                    "van_native_loop requested but the native event loop "
                    "is unavailable on this platform — falling back to "
                    "thread-per-connection serving"
                )
            else:
                try:
                    self._nloop = nlmod.NativeEventLoop(
                        self._listener, threads=loop_threads)
                except OSError as e:
                    # genuine nl_start failure (fd exhaustion:
                    # epoll/eventfd creation) — the documented contract
                    # is degrade to thread-per-connection, never abort
                    # server startup
                    logging.getLogger(__name__).warning(
                        "native event loop failed to start (%s); falling "
                        "back to thread-per-connection serving", e)
        # high-QPS read path (README "Read path"): generation counter for
        # native read-cache invalidation. Every committed state change a
        # cached READ reply could observe bumps it (_invalidate_reads);
        # READ handlers capture it UNDER their apply lock with the
        # snapshot (_read_gen_snapshot) and the pump publishes the reply
        # at that generation — a put superseded by an apply is refused at
        # the native floor, so a stale reply can never park in the cache.
        self._read_gen = 0
        self._read_gen_lock = threading.Lock()
        self._read_pub = threading.local()
        self._read_pub_version = 0  # version of the last published snapshot
        self._native_read_cache = False
        if self._nloop is not None:
            from ps_tpu.config import env_int as _env_int

            # validated service-level read (pslint PSL406): the native
            # read-cache byte budget; 0 disables hot-key serving and
            # every READ takes the pump path
            cache_bytes = _env_int("PS_NATIVE_READ_CACHE_BYTES", 64 << 20,
                                   lo=0)
            if cache_bytes:
                self._nloop.cache_config(tv.READ, cache_bytes)
                self._native_read_cache = True
        # zero-upcall push plane (README "Push path"): the loop classifies
        # steady-state push frames against a per-worker (nonce, settled
        # seq) ledger mirror ON THE OWNER THREAD — pure replays acked
        # natively with the recorded dedup template, role refusals
        # (backup/fenced) answered natively with the pump's exact bytes,
        # fresh pushes admission-stamped so the apply can skip the dedup
        # scan. off|on|auto (auto == on wherever the loop runs); the pump
        # path stays the drop-in parity oracle, and blocker kinds,
        # aggregator rounds, and paused/draining states always punt.
        self._native_admit = False
        if self._nloop is not None:
            from ps_tpu.config import env_str as _env_str

            # validated service-level read (pslint PSL406): mirrors
            # Config.push_native_admit; an unknown token warns and keeps
            # the default instead of taking the service down
            admit_mode = (_env_str("PS_PUSH_NATIVE_ADMIT", "auto")
                          or "auto").strip().lower()
            if admit_mode not in ("off", "on", "auto"):
                logging.getLogger(__name__).warning(
                    "PS_PUSH_NATIVE_ADMIT=%r not in off|on|auto; keeping "
                    "'auto'", admit_mode)
                admit_mode = "auto"
            admit_kind = self._admit_kind()
            if admit_mode != "off" and admit_kind is not None:
                self._nloop.admit_config(admit_kind)
                self._native_admit = True
                # seed the mirror from the engine's settled ledger (a
                # checkpoint-restored or backup service starts with
                # history; a fresh one arms the role refusal only)
                self._admit_sync()
        # in-loop native telemetry (README "Native observability"):
        # PS_NL_STATS arms the loop's own lock-free histograms (frame
        # read, queue wait, native read-hit serve, tail flush — the
        # ps_nl_* families) and PS_NL_SLOW_FRAME_MS the slow-frame
        # watchdog; both validated service-level reads (pslint PSL406),
        # strict=False — observability knobs must never take a service
        # down with them
        self._nl_stats = False
        if self._nloop is not None:
            from ps_tpu.config import env_float as _env_float

            self._nl_stats = env_flag("PS_NL_STATS", True)
            slow_ms = _env_float("PS_NL_SLOW_FRAME_MS", 250.0, lo=0.0,
                                 strict=False)
            self._nloop.telemetry_config(
                self._nl_stats,
                int(slow_ms * 1e6) if self._nl_stats else 0)
        if self._nloop is not None:
            self._loop_conn_gauge = obs.default_registry().gauge(
                "ps_van_live_connections",
                "connections registered in the native event loop")
            self._loop_iter_gauge = obs.default_registry().gauge(
                "ps_van_loop_iterations_total",
                "cumulative native-loop epoll iterations")
            self._loop_req_gauge = obs.default_registry().gauge(
                "ps_van_loop_requests_total",
                "cumulative frames read by the native loop")
            self._read_hits_gauge = obs.default_registry().gauge(
                "ps_pull_native_hits_total",
                "READ frames answered by the native read cache with "
                "zero upcalls")
            self._read_miss_gauge = obs.default_registry().gauge(
                "ps_pull_native_misses_total",
                "cacheable READ frames that fell through to the pump")
            self._read_lag_gauge = obs.default_registry().gauge(
                "ps_pull_cache_version_lag",
                "engine versions the cached READ snapshot trails by "
                "(0 = fresh or empty)")
            self._padm_acks_gauge = obs.default_registry().gauge(
                "ps_push_native_acks_total",
                "push replays acked by the native admission ledger with "
                "zero upcalls")
            self._padm_ref_gauge = obs.default_registry().gauge(
                "ps_push_native_refusals_total",
                "push frames refused natively (backup/fenced role) with "
                "zero upcalls")
            self._pump_thread = threading.Thread(
                target=self._loop_pump, daemon=True
            )
            self._pump_thread.start()
        else:
            self._accept_thread = threading.Thread(
                target=self._accept_loop, daemon=True
            )
            self._accept_thread.start()

    @property
    def native_loop(self) -> bool:
        """True when this service serves through the native epoll loop."""
        return self._nloop is not None

    @property
    def port(self) -> int:
        return self._listener.port

    # -- to be provided by the concrete service -------------------------------

    def _handle(self, kind: int, worker: int, tensors, extra) -> bytes:
        raise NotImplementedError

    def _set_draining(self) -> None:
        raise NotImplementedError

    # replication hooks (only services that support primary/backup pairs
    # implement these; the base dispatch never calls them otherwise)

    def _service_lock(self):
        """The apply lock replication serializes against (dense: the
        engine lock; sparse: the table lock)."""
        raise NotImplementedError

    def _replica_hello_extra(self) -> dict:
        """Primary: the attach-time topology + state-point description
        (called under the apply lock by :meth:`attach_backup`)."""
        raise NotImplementedError

    def _replica_validate(self, extra: dict) -> Optional[str]:
        """Backup: refuse a mismatched stream (error string) or accept
        (None). Must check topology AND the state point — a backup that
        did not start from the primary's exact state diverges silently."""
        raise NotImplementedError

    def _replica_apply(self, op: str, worker: int, tensors, extra) -> None:
        """Backup: apply one replicated event through the local engine.
        Called with :meth:`_service_lock` HELD (stream order is engine
        order); must not re-acquire it."""
        raise NotImplementedError

    def _replica_seed(self, worker: int, tensors, extra):
        """Backup: install the full state point a re-seeding primary
        shipped (``RESEED`` → ``REPLICA_SEED``, the autopilot's replica
        heal). Returns an error string to refuse, None to accept. The
        base refuses — only services whose state fits the row codec
        (dense) opt in."""
        return "this service does not support re-seed"

    # -- replication / promotion ----------------------------------------------

    _REPLICA_KINDS = frozenset({tv.REPLICA_HELLO, tv.REPLICA_APPEND,
                                tv.REPLICA_PROMOTE, tv.REPLICA_STATE,
                                tv.REPLICA_SEED})

    def _dispatch(self, kind: int, worker: int, tensors, extra) -> bytes:
        """Route one request: replication-plane kinds are handled here;
        data-plane kinds reach the subclass only on a serving primary — a
        backup refuses them with a typed, retry-able reply (the worker's
        failover loop keys off ``extra["backup"]`` to wait out the
        promotion instead of failing the job)."""
        # chaos hook first (both serve paths funnel through here): an
        # injected fault answers INSTEAD of the handler, so a drill
        # exercises the worker's real refusal/retry machinery — the
        # exact frames a genuinely broken shard would emit
        hook = self.chaos
        if hook is not None:
            reply = hook(self, kind, worker, extra)
            if reply is not None:
                return reply
        # server-side tracing hook — THE one chokepoint every kind passes
        # through: a frame whose header carries a propagated trace
        # context gets a span named for its kind, parented to the
        # sender's span (the worker op, or the primary's apply for
        # replica appends). Untraced frames cost one dict lookup.
        ctx = obs.from_wire(extra)
        if ctx is not None:
            with obs.tracer().span(tv.kind_name(kind), cat="server",
                                   parent=ctx).set(worker=worker,
                                                   role=self.role):
                return self._dispatch_traced(kind, worker, tensors, extra)
        return self._dispatch_traced(kind, worker, tensors, extra)

    def _dispatch_traced(self, kind: int, worker: int, tensors,
                         extra) -> bytes:
        if kind in self._REPLICA_KINDS:
            return self._handle_replica(kind, worker, tensors, extra)
        if self.role != "primary" and kind != tv.STATS:
            if kind == tv.READ and self.role == "backup":
                # replica reads (README "Read path"): a BACKUP answers
                # side-effect-free READs from its replicated state — the
                # reply's version stamp is what lets workers enforce the
                # bounded-staleness contract (PS_READ_STALENESS) and fall
                # back to the primary when the bound is exceeded. Fenced
                # zombies stay refused: their version stream is dead, and
                # routing reads at them would only burn a fallback.
                return self._handle(kind, worker, tensors, extra)
            return tv.encode(tv.ERR, worker, None, extra={
                "error": (f"shard backup is not serving worker traffic "
                          f"(role={self.role}, epoch {self.epoch}) — "
                          f"retry after promotion"),
                "backup": True, "epoch": self.epoch,
            })
        return self._handle(kind, worker, tensors, extra)

    def _handle_replica(self, kind: int, worker: int, tensors,
                        extra) -> bytes:
        if kind == tv.REPLICA_STATE:
            return tv.encode(tv.OK, worker, None, extra=self.replica_state())
        if kind == tv.REPLICA_PROMOTE:
            if self.role != "backup":
                return tv.encode(tv.ERR, worker, None, extra={
                    "error": f"cannot promote a {self.role} service",
                })
            epoch = self.promote(reason=str(extra.get("reason", "request")))  # pslint: disable=PSL203 -- REPLICA_PROMOTE is an operator/test-sent frame; in-tree promotion goes through PromotionWatch.promote(), so no in-tree encoder produces "reason"
            return tv.encode(tv.OK, worker, None,
                             extra={"epoch": epoch, "role": self.role})
        if self.role != "backup":
            # a zombie primary still appending after this backup promoted:
            # refuse WITH the fencing signal — the zombie's session calls
            # its on_fenced hook and the old primary stops serving workers
            # instead of forking history (split-brain). The fence lands on
            # the zombie's next commit attempt; workers that re-routed are
            # protected sooner by the epoch check in their failover loop.
            return tv.encode(tv.ERR, worker, None, extra={
                "error": (f"replication stream refused: this service is "
                          f"{self.role} (epoch {self.epoch}), not a backup"),
                "fenced": True, "epoch": self.epoch,
            })
        if kind == tv.REPLICA_SEED:
            # full state-point install onto an EMPTY spare (autopilot
            # re-seed, README "Autopilot & chaos"): the quiesced primary
            # shipped its whole state in one frame; install it so the
            # REPLICA_HELLO that follows validates against an exact copy
            err = self._replica_seed(worker, tensors, extra)
            if err is not None:
                return tv.encode(tv.ERR, worker, None,
                                 extra={"error": err})
            return tv.encode(tv.OK, worker, None,
                             extra={"epoch": self.epoch})
        if kind == tv.REPLICA_HELLO:
            err = self._replica_validate(extra)
            if err is not None:
                return tv.encode(tv.ERR, worker, None, extra={"error": err})
            with self._service_lock():
                self._primary_epoch = int(extra.get("epoch", 0))
                self._replica_applied_seq = int(extra.get("start_seq", 0))
                self._replica_attached = True
            return tv.encode(tv.OK, worker, None, extra={
                "applied_seq": self._replica_applied_seq,
                "epoch": self.epoch,
            })
        # REPLICA_APPEND
        seq = int(extra["seq"])
        with self._service_lock():
            if self.role != "backup":
                return tv.encode(tv.ERR, worker, None, extra={
                    "error": "promoted mid-append: stream refused",
                })
            if not self._replica_attached:
                return tv.encode(tv.ERR, worker, None, extra={
                    "error": "REPLICA_APPEND before REPLICA_HELLO",
                })
            if seq != self._replica_applied_seq + 1:
                return tv.encode(tv.ERR, worker, None, extra={
                    "error": (f"replication gap: expected seq "
                              f"{self._replica_applied_seq + 1}, got {seq}"),
                })
            self._replica_apply(str(extra["op"]),
                                int(extra.get("w", worker)), tensors, extra)
            self._replica_applied_seq = seq
        return tv.encode(tv.OK, worker, None, extra={"applied_seq": seq})

    # -- high-QPS read path (README "Read path") ------------------------------

    def _read_version(self):
        """Subclass hook: the engine version a READ reply is stamped with
        (dense: engine.version; sparse: summed table versions). None =
        this service serves no READ kind."""
        return None

    def _read_gen_snapshot(self) -> int:
        """The current read-cache publish generation. READ handlers call
        this UNDER their apply lock, atomically with the snapshot they
        serialize, and hand the pair to :meth:`_note_read_snapshot` — the
        ordering that makes invalidation-on-apply airtight."""
        with self._read_gen_lock:
            return self._read_gen

    def _invalidate_reads(self, tags=None) -> None:
        """Invalidation-on-apply: call after ANY committed state change a
        cached READ reply could observe (engine applies, replica-stream
        applies, migration cutovers, promotion, drain — and tiered-
        embedding tier moves, whose demotion victims fall OUTSIDE the
        triggering push's id-set: the sparse service unions their row
        tags in before calling here, because a tier move IS a state
        change under this contract). ``tags``
        optionally names the touched state slice (the sparse service's
        per-(table, row) hashes): the publish floor still rises — an
        in-flight pre-apply publish is refused either way — but only
        cached entries whose tag set intersects are dropped, so hot
        id-sets disjoint from the apply keep serving natively. None (the
        dense services, and every structural change) drops everything.
        The native push-admission mirror rides the same generation: the
        bump raises its floor too (dropping the version-stamped ack
        template, which the post-apply :meth:`_admit_publish` re-arms),
        so a pre-apply classification can never ack a post-apply replay.
        Cheap no-op when both native mirrors are off."""
        if not (self._native_read_cache or self._native_admit):
            return
        with self._read_gen_lock:
            self._read_gen += 1
            gen = self._read_gen
        nloop = self._nloop
        if nloop is not None:
            if self._native_read_cache:
                nloop.cache_invalidate(gen, tags=tags)
            if self._native_admit:
                nloop.admit_invalidate(gen)

    def _note_serve_age(self, birth: Optional[dict],
                        tier: Optional[str] = None) -> None:
        """Record one serve's data age (``now - version birth``) into
        ``ps_read_staleness_seconds``. READ handlers call this with the
        birth record they just encoded into the reply; the tier defaults
        to this endpoint's serving role — ``pump`` on a primary (the
        Python serve path; zero-upcall native hits re-serve the same
        stamped bytes), ``replica`` on a backup."""
        if birth is None:
            return
        from ps_tpu.obs import freshness

        age, src, clamped = freshness.age_of(birth)
        self.transport.record_read_age(
            age, src=src,
            tier=tier or ("pump" if self.role == "primary" else "replica"),
            bound=self._fresh_slo, clamped=clamped)

    def _note_read_snapshot(self, gen: int, version: int,
                            tags=None) -> None:
        """READ handlers record the (generation, version) their reply
        serializes — plus, optionally, the invalidation ``tags`` naming
        the rows it covers; the pump publishes the encoded frame into the
        native cache under exactly that generation (and those tags).
        Thread-local: handlers run on the pump or punted threads."""
        self._read_pub.gen = gen
        self._read_pub.version = int(version)
        self._read_pub.tags = tags

    # -- zero-upcall push plane (README "Push path") ---------------------------

    def _admit_kind(self) -> Optional[int]:
        """Subclass hook: the ONE wire kind the native admission mirror
        may classify (dense: PUSH; sparse: ROW_PUSH). None = this service
        never admits natively — the aggregator's group rounds barrier on
        the pump, and bucketed/push-pull kinds carry replies no template
        can pre-encode, so they stay pump-only everywhere."""
        return None

    def _admit_entry(self, worker: int) -> Optional[tuple]:
        """Subclass hook: this worker's settled-ledger row as
        ``(nonce, lo, hi)`` — a replay at/below ``lo`` is fully applied
        (ackable), above ``hi`` is strictly fresh, between punts. None =
        not publishable (no uniform token across the served key range);
        the native loop then punts this worker's frames to the pump."""
        return None

    def _admit_entries(self):
        """Every publishable ledger row (for a full mirror reseed)."""
        out = []
        for w in list(getattr(self, "_applied_pseq", None) or ()):
            ent = self._admit_entry(int(w))
            if ent is not None:
                out.append((int(w), ent[0], int(ent[1]), int(ent[2])))
        return out

    def _admit_ack_bytes(self) -> Optional[bytes]:
        """Subclass hook: the encoded replay-ack reply (worker id 0 — the
        loop patches the requester's id in before sending), byte-for-byte
        what the pump would produce for a pure dedup replay RIGHT NOW.
        Version-stamped: every apply invalidates it at the native floor
        and the post-apply publish re-arms it, so a native ack can never
        carry a superseded version stamp."""
        return None

    def _admit_refusal_bytes(self) -> Optional[bytes]:
        """The typed role refusal the native loop answers push frames
        with while this service is not serving worker traffic — the
        EXACT bytes of :meth:`_dispatch_traced`'s backup/fenced refusal
        (worker id 0; the loop patches the requester's id). None on a
        serving primary."""
        if self.role == "primary":
            return None
        return tv.encode(tv.ERR, 0, None, extra={
            "error": (f"shard backup is not serving worker traffic "
                      f"(role={self.role}, epoch {self.epoch}) — "
                      f"retry after promotion"),
            "backup": True, "epoch": self.epoch,
        })

    def _admit_sync(self, locked: bool = False) -> None:
        """Structural reseed of the native admission mirror (promotion,
        fencing, checkpoint resume, migration cutover, startup): drop
        everything at a fresh generation, then republish the settled
        ledger — or arm the role refusal instead on a non-primary. Takes
        the service (apply) lock unless the caller already holds it, so
        the ledger it reads cannot move under the reseed."""
        if not self._native_admit or self._nloop is None:
            return
        if not locked:
            with self._service_lock():
                return self._admit_sync(locked=True)
        nloop = self._nloop
        with self._read_gen_lock:
            self._read_gen += 1
            gen = self._read_gen
        nloop.admit_reset(gen)
        refusal = self._admit_refusal_bytes()
        if refusal is not None:
            nloop.admit_set_refusal(refusal)
            return
        nloop.admit_set_refusal(b"")
        if getattr(self, "_paused", False) or getattr(self, "_draining",
                                                      False):
            return  # paused/draining: every push must reach the pump
        for w, nonce, lo, hi in self._admit_entries():
            nloop.admit_put(w, nonce, lo, hi, gen)
        ack = self._admit_ack_bytes()
        if ack is not None:
            nloop.admit_set_ack(ack, gen)

    def _admit_drop(self) -> None:
        """Suspend native admission (checkpoint pause, drain): drop the
        whole mirror at a fresh generation so every push frame punts to
        the pump until :meth:`_admit_sync` reseeds. Needs no service
        lock — the bump only ever makes classification MORE conservative."""
        if not self._native_admit or self._nloop is None:
            return
        with self._read_gen_lock:
            self._read_gen += 1
            gen = self._read_gen
        self._nloop.admit_reset(gen)

    def _admit_publish(self, *workers) -> None:
        """Per-apply incremental publish (call under the apply lock,
        AFTER the apply's :meth:`_invalidate_reads` bumped the
        generation): push the named workers' settled-ledger rows and the
        fresh replay-ack template to the native mirror at the post-apply
        generation. The floor the invalidation raised refuses any
        laggard publish from a superseded apply."""
        if (not self._native_admit or self._nloop is None
                or self.role != "primary"
                or getattr(self, "_paused", False)
                or getattr(self, "_draining", False)):
            return
        nloop = self._nloop
        with self._read_gen_lock:
            gen = self._read_gen
        for w in workers:
            if w is None:
                continue
            ent = self._admit_entry(int(w))
            if ent is not None:
                nloop.admit_put(int(w), ent[0], int(ent[1]), int(ent[2]),
                                gen)
        ack = self._admit_ack_bytes()
        if ack is not None:
            nloop.admit_set_ack(ack, gen)

    def _admit_fresh_hint(self) -> bool:
        """Consume this thread's native admission stamp: True iff the
        loop classified the frame strictly fresh AND no apply/reseed
        landed since (the stamp is floor+1 of its classification; every
        state change bumps the shared generation). Call under the apply
        lock — applies serialize there, so a True return proves the
        dedup scan would find nothing and can be skipped. Any staleness
        degrades to False: the full scan, never a double apply."""
        gen = getattr(self._read_pub, "admit", 0)
        if not gen:
            return False
        self._read_pub.admit = 0
        with self._read_gen_lock:
            return gen - 1 == self._read_gen

    def promote(self, reason: str = "request") -> int:
        """The backup→primary transition (idempotent): under the apply
        lock — so no replica append is mid-apply and no worker push is
        admitted across the flip — bump the shard-table epoch past the
        primary's and start serving. Everything the primary committed
        (sync ack: everything it ever ACKNOWLEDGED to a worker) is already
        in this engine; there is nothing to rebuild, which is what makes
        promotion a millisecond flip instead of a restart."""
        import time as _time

        t0 = _time.perf_counter()
        with self._service_lock():
            if self.role == "primary":
                return self.epoch
            self.role = "primary"
            self.epoch = self._primary_epoch + 1
            self.promote_reason = reason
        # role flipped: a cached reply published as a backup must not
        # outlive the promotion (its bytes are still correct state, but
        # freshness semantics changed — republish as primary)
        self._invalidate_reads()
        # re-seed the admission mirror from the replicated ledger: the
        # promoted backup suppresses exactly the replays its dead primary
        # would have, natively, from the first post-promotion frame —
        # and stops answering the backup refusal
        self._admit_sync()
        self.promotion_s = _time.perf_counter() - t0
        obs.record_event("promotion", reason=reason, epoch=self.epoch,
                         promotion_s=round(self.promotion_s, 6))
        logging.getLogger(__name__).warning(
            "backup promoted to primary (reason=%s, epoch %d) in %.1fms",
            reason, self.epoch, self.promotion_s * 1e3,
        )
        return self.epoch

    def attach_backup(self, host: str, port: int, ack: str = "sync",
                      window: int = 256, compress=None,
                      stall_timeout: float = 30.0):
        """Primary: attach a warm backup and start replicating every
        commit to it. Attach BEFORE admitting worker traffic (or from a
        quiesced state): the handshake validates that both replicas stand
        at the same state point and refuses otherwise — the deltas-only
        stream cannot catch a backup up past missed commits.

        ``ack="sync"``: push/pull replies wait for the backup's ack —
        promotion is bitwise-identical to what workers observed.
        ``ack="async"``: replies return immediately; the backup trails by
        at most ``window`` commits (metrics-visible ``repl_lag``).
        ``compress`` optionally runs the replica stream through a
        stateless gradient codec (ps_tpu/compress)."""
        from ps_tpu.replica.session import BackupSession

        if self.role != "primary":
            raise RuntimeError("only a primary can attach a backup")
        with self._service_lock():
            old = self._backup_session
            if old is not None and not old.degraded:
                raise RuntimeError("a live backup session is already "
                                   "attached")
            if old is not None:
                old.close()  # degraded: replaceable — redundancy must be
                # restorable without restarting the primary (quiesce,
                # checkpoint, seed the new backup from it, re-attach)
            hello = self._replica_hello_extra()
            hello.update({"epoch": self.epoch, "ack": ack})
            session = BackupSession(host, port, hello, ack=ack,  # pslint: disable=PSL101 -- attach-time only (before worker traffic, or quiesced): the dial+HELLO must be atomic with the state-point snapshot the lock protects, and connect_timeout_ms bounds it
                                    window=window, compress=compress,
                                    stats=self.transport,
                                    stall_timeout=stall_timeout)
            session.on_fenced = self._fence
            self._backup_session = session
        return session

    def _fence(self, peer_epoch: int) -> None:
        """Self-fencing: our backup promoted past us (it refused the
        replication stream as a primary of ``peer_epoch``). This service
        is a zombie — stop serving workers so history cannot fork; the
        retry-able refusal routes still-connected workers to the real
        primary through their replica sets."""
        with self._service_lock():
            if self.role != "primary":
                return
            self.role = "fenced"
        # a zombie's cached reads die with its serving rights — and its
        # admission mirror flips to the fenced refusal (native, byte-
        # identical to the pump's): no ledger row may ack a push here
        self._invalidate_reads()
        self._admit_sync()
        obs.record_event("self_fence", peer_epoch=int(peer_epoch),
                         epoch=self.epoch)
        logging.getLogger(__name__).error(
            "FENCED: this shard's backup promoted to primary (epoch %d) "
            "while we were still serving — refusing all worker traffic "
            "from now on (workers re-route via their replica sets)",
            peer_epoch,
        )

    def _replicate(self, op: str, worker: int, tensors=None,
                   meta: Optional[dict] = None) -> Optional[int]:
        """Primary commit hook (call under the apply lock): append one
        committed event to the replication stream. None = unreplicated
        (no session, or it degraded)."""
        s = self._backup_session
        if s is None or s.degraded:
            return None
        meta = dict(meta or {})
        # propagate the serve span (if this commit is being traced) so
        # the backup's replica_append span parents to THIS apply — the
        # worker→primary→backup chain stays one trace
        ctx = obs.tracer().current()
        if ctx is not None:
            meta[obs.WIRE_KEY] = [ctx.trace_id, ctx.span_id]
        return s.publish(op, worker, tensors, meta)

    def _await_replication(self, seq: Optional[int]) -> None:
        """Sync-ack gate (call OUTSIDE the apply lock, before sending the
        reply): block until the backup acked ``seq``. No-op for async ack,
        unreplicated commits, and degraded sessions — EXCEPT a session
        that degraded because the backup PROMOTED: then this zombie's
        commit never reached the real primary, so the reply must be a
        retryable refusal — the worker re-routes and replays the push at
        the promoted backup (dedup makes it exactly-once), and the commit
        survives the fence instead of dying with the zombie."""
        s = self._backup_session
        if s is None:
            return
        if seq is not None and s.ack_mode == "sync":
            # `child` piggybacks on the serve span: untraced requests get
            # the NOOP (never a fresh sampling decision mid-server)
            with obs.tracer().child("replica_ack_wait", cat="server"):
                s.wait_acked(seq)
        # checked for EVERY commit (even unreplicated ones after the
        # degrade): once fenced, no reply may tell a worker its commit
        # stuck at this zombie
        if s.fenced:
            raise NotServingError(
                "fenced mid-commit: this shard's backup promoted — retry "
                "at the new primary"
            )

    def replica_state(self) -> dict:
        """Role/epoch/replication introspection (REPLICA_STATE, and merged
        into both services' STATS replies)."""
        out = {"role": self.role, "epoch": self.epoch,
               # wall clock for the NTP-style trace-clock probe
               # (ps_tpu/obs/clock.py): REPLICA_STATE is the cheapest
               # round trip every role answers, so offsets ride it
               "now": time.time()}
        s = self._backup_session
        if s is not None:
            out["repl"] = s.state()
        if self._replica_attached:
            out["replica_applied_seq"] = self._replica_applied_seq
        if self.promote_reason is not None:
            out["promote_reason"] = self.promote_reason
            out["promotion_s"] = self.promotion_s
        out["dedup_hits"] = self.transport.dedup_hits
        v = self._read_version()
        if v is not None and "version" not in out:
            # the cheap per-role version probe the worker-side parameter
            # cache rides (REPLICA_STATE on the heartbeat cadence):
            # version bumps invalidate cached reads without a full pull
            out["version"] = v
        if self.transport.reads_served or self.transport.read_native_hits:
            # serve-path visibility (ps_top's read columns): READs this
            # endpoint answered in Python vs natively, and the native
            # cache's live footprint
            out["read"] = {
                "served": self.transport.reads_served,
                "native_hits": self.transport.read_native_hits,
                "native_misses": self.transport.read_native_misses,
                "entries": self.transport.read_cache_entries,
                # conditional reads: NOT_MODIFIED replies served (pump),
                # delta rows shipped, and native version-floor hits —
                # ps_top's nm% column sums pump NMs + native cond hits
                "nm": self.transport.read_not_modified,
                "delta_rows": self.transport.read_delta_rows,
                "native_cond_hits": self.transport.read_native_cond_hits,
            }
        f = self.transport.fresh_snapshot()
        if f is not None:
            # freshness plane (README "Online serving & freshness"):
            # ps_top's fresh/age% columns and ps_doctor's stalest-tier
            # section render this dict straight off the STATS frame
            out["fresh"] = f
        if self._nloop is not None:
            # native event-loop serve path: live connections + frames
            # read — the cell ps_top renders per shard (iterations and
            # upcall-batch distributions ride the /metrics gauges and
            # the fleet-telemetry counters instead) — plus the in-loop
            # p99s ps_top's nlp99/qw99 columns and ps_doctor's native
            # section render (µs: these are sub-ms surfaces)
            loop = {"conns": self.transport.loop_conns,
                    "requests": self.transport.loop_requests,
                    "slow_frames": self.transport.nl_slow_frames}
            s = self.transport.hist["nl_read_hit_s"].summary()
            if s:
                loop["nlp99_us"] = round(s["p99"] * 1e6, 1)
            s = self.transport.hist["nl_queue_wait_s"].summary()
            if s:
                loop["qw99_us"] = round(s["p99"] * 1e6, 1)
            t = self.transport
            classified = (t.push_native_acks + t.push_native_refusals
                          + t.push_native_fresh + t.push_native_punts)
            if classified:
                # push-admission visibility (ps_top's padm% column): how
                # much of the push plane the native mirror settled without
                # an upcall (acks + refusals), plus the raw counters
                loop["padm"] = {
                    "acks": t.push_native_acks,
                    "refusals": t.push_native_refusals,
                    "fresh": t.push_native_fresh,
                    "punts": t.push_native_punts,
                    "share": round((t.push_native_acks
                                    + t.push_native_refusals)
                                   / classified, 4),
                }
            out["loop"] = loop
        return out

    # -- bucketed-push staging -------------------------------------------------

    def _stage_bucket_push(self, worker: int, bucket: int, nbuckets: int,
                           epoch: int, raw, slices,
                           nonce: Optional[str] = None) -> Optional[dict]:
        """Stage one bucket of worker's multi-bucket push; returns the fully
        assembled ``{key: tensor}`` tree when this bucket completes the
        epoch, else None (reply with a plain ack).

        One epoch in flight per worker (the worker's sender serializes
        cycles, and waits out every bucket of an epoch before starting the
        next). A bucket of a different (epoch, incarnation-nonce) pair
        therefore always means the worker moved on — forward after
        abandoning a push mid-flight, or into a new incarnation after a
        restart/reconnect reset its epoch counter (its old connections are
        severed, so a genuine straggler of the staged epoch can no longer
        arrive; the nonce catches even an epoch-NUMBER collision between
        incarnations). Either way the incomplete epoch is dropped whole,
        never half-applied — and merged with nothing — and the new epoch
        stages fresh. A malformed bucket (duplicate, bad range) also drops
        the whole staged epoch, so a retry starts clean instead of
        completing against poisoned state.
        """
        stale = None  # (epoch, staged, nbuckets) of a dropped stale epoch
        try:
            with self._stage_lock:
                asm = self._push_stage.get(worker)
                if asm is not None and (asm.epoch != epoch
                                        or getattr(asm, "nonce",
                                                   None) != nonce):
                    # record the drop, but account/log it OUTSIDE the
                    # stage lock: metrics/flight/logging do their own
                    # locking and I/O, and every bucket of every worker
                    # serializes here
                    stale = (asm.epoch, len(asm._seen), asm.nbuckets)
                    asm = None
                if asm is None:
                    asm = BucketAssembler(epoch, nbuckets)
                    asm.nonce = nonce
                    self._push_stage[worker] = asm
                try:
                    complete = asm.add(bucket, raw, slices, epoch)
                except Exception:
                    self._push_stage.pop(worker, None)
                    raise
                if complete:
                    del self._push_stage[worker]
        finally:
            # finally, not fallthrough: a malformed first bucket of the
            # SUPERSEDING epoch raises out of the block above, and the
            # dropped stale epoch must still reach the black box — the
            # double-fault is exactly when the record matters most
            if stale is not None:
                # observable, not just a log line: STATS carries the
                # counts so a fleet-wide rash of abandoned pushes shows
                # up in the worker's StepLogger instead of only in
                # server stderr
                old_epoch, staged, nbuckets = stale
                self.transport.record_stale_epoch(staged)
                obs.record_event("stale_epoch", worker=worker,
                                 epoch=old_epoch, superseded_by=epoch,
                                 buckets=staged)
                logging.getLogger(__name__).warning(
                    "worker %d abandoned push epoch %d (%d/%d buckets); "
                    "superseded by epoch %d", worker, old_epoch,
                    staged, nbuckets, epoch,
                )
        return asm.finish() if complete else None

    # -- checkpoint ownership tokens ------------------------------------------

    def _ckpt_issue_token(self) -> Optional[int]:
        """Issue the pause ownership token (call under the apply lock);
        None when a checkpoint is already outstanding — the caller replies
        with :meth:`_ckpt_busy_error`."""
        if self._ckpt_token is not None:
            return None
        self._ckpt_seq += 1
        self._ckpt_token = self._ckpt_seq
        return self._ckpt_token

    def _ckpt_busy_error(self) -> str:
        return (f"checkpoint already in progress (token {self._ckpt_token} "
                f"outstanding) — serialize checkpoint coordinators")

    def _ckpt_token_error(self, phase: str, extra: dict) -> Optional[str]:
        """Error string when the phase's presented token does not match the
        outstanding one; None when it does. (``resume`` with ``force`` is
        the caller's deliberate bypass and skips this gate.)"""
        token = extra.get("token")
        token = None if token is None else int(token)
        if token != self._ckpt_token:
            return (f"checkpoint {phase} with invalid token {token!r} "
                    f"(outstanding: {self._ckpt_token!r})")
        return None

    def _ckpt_clear_token(self) -> None:
        """Call under the apply lock, at (any) resume."""
        self._ckpt_token = None

    # -- checkpoint-pause drain accounting ------------------------------------

    def _pause_wait_begin(self) -> None:
        """Subclass hook: call immediately before parking a serve thread on
        a checkpoint-pause condition (so stop() can discount it). Parks
        on native-loop punted threads are ALSO counted separately: each
        of those holds exactly one claimed loop body, which the native
        drain must discount from nl_pending — while a park on an
        shm-detached classic serve thread holds none."""
        with self._inflight_cond:
            self._pause_blocked += 1
            if getattr(threading.current_thread(), "_ps_loop_req", False):
                self._loop_pause_parked += 1
            self._inflight_cond.notify_all()

    def _pause_wait_end(self) -> None:
        with self._inflight_cond:
            self._pause_blocked -= 1
            if getattr(threading.current_thread(), "_ps_loop_req", False):
                self._loop_pause_parked -= 1
            self._inflight_cond.notify_all()

    # -- accept / serve --------------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            ch = self._listener.accept(timeout_ms=200)
            if ch is None:
                continue
            ch.stats = self.transport
            ch.pool = self._recv_pool
            with self._chan_lock:
                # prune finished serve threads so a long-lived server with
                # many reconnects doesn't accumulate dead Thread objects
                # (ident is None = appended but not yet started — keep: an
                # un-started thread also reports is_alive() False)
                self._conns = [t for t in self._conns
                               if t.ident is None or t.is_alive()]
                if self._stop.is_set():
                    ch.close()  # raced stop(): admit nothing new
                    return
                self._channels.append(ch)
                t = threading.Thread(
                    target=self._serve, args=(ch,), daemon=True
                )
                self._conns.append(t)
            t.start()

    def _try_shm_upgrade(self, ch: tv.Channel, worker: int, extra: dict):
        """Attach the worker's offered ring segments; returns
        ``(lane_or_None, reply_frame)`` — any failure becomes an ERR reply
        and the connection stays plain TCP."""
        from ps_tpu.control import shm_lane

        if not self._shm_accept:
            return None, tv.encode(tv.ERR, worker, None, extra={
                "error": "shm lane disabled on this server (PS_SHM=0)",
            })
        try:
            lane = shm_lane.accept_upgrade(ch, extra, stats=self.transport)
        except Exception as e:
            return None, tv.encode(tv.ERR, worker, None,
                                   extra={"error": repr(e)})
        return lane, tv.encode(tv.OK, worker, None, extra={"shm": True})

    @staticmethod
    def _send_reply(conn, reply) -> None:
        """Reply in either form: contiguous frame, or zero-copy
        ``(header, chunks)`` parts (vectored TCP send / one ring write)."""
        send_payload(conn, reply)

    def _serve(self, ch: tv.Channel, lane=None) -> None:
        # `conn` is the data plane: the TCP channel until a successful
        # SHM_SETUP, the shared-memory lane after (the lane's recv hands
        # out ring frames IN PLACE and polls the TCP side for oversize
        # spills and peer death; stop() still severs via the TCP channel).
        # `lane` is pre-set when the native event loop detached an
        # already-upgraded connection to this thread.
        conn = lane if lane is not None else ch
        try:
            while not self._stop.is_set():
                try:
                    msg = (conn.recv() if lane is None
                           else lane.recv(stop=self._stop.is_set))
                except tv.VanError:
                    return  # worker hung up (or stop() severed an idle conn)
                with self._inflight_cond:
                    self._inflight += 1
                try:
                    kind, worker, tensors, extra = tv.decode(msg)
                    self._req_counter.inc()
                    goodbye = kind == tv.SHUTDOWN
                    new_lane = None
                    if goodbye:
                        reply = tv.encode(tv.OK, worker, None)
                    elif kind == tv.SHM_SETUP and lane is None:
                        new_lane, reply = self._try_shm_upgrade(
                            ch, worker, extra)
                    else:
                        reply = self._dispatch_reply_payload(
                            kind, worker, tensors, extra)
                    try:
                        self._send_reply(conn, reply)
                    except tv.VanError:
                        if new_lane is not None:
                            # attached but never adopted (the OK reply
                            # died): release its mappings deterministically
                            new_lane.close()
                        return  # worker vanished mid-reply; nothing to tell
                    finally:
                        # ONLY now is the request frame provably dead: the
                        # reply may alias it (a handler may echo zero-copy
                        # views of the request), so the buffer goes back
                        # to the pool after the send attempt — success or
                        # failure — never before. The shm lane's ring
                        # bytes are likewise released at the NEXT recv.
                        tensors = None
                        self._recv_pool.ret(msg)
                        msg = None
                    if new_lane is not None:
                        conn = lane = new_lane  # data plane switches here
                finally:
                    with self._inflight_cond:
                        self._inflight -= 1
                        self._inflight_cond.notify_all()
                if goodbye:
                    with self._goodbye_cond:
                        self.goodbyes += 1
                        self._goodbye_cond.notify_all()
                    return
        finally:
            if lane is not None:
                lane.close()  # closes the TCP channel too
            else:
                ch.close()
            with self._chan_lock:
                try:
                    self._channels.remove(ch)
                except ValueError:
                    pass  # stop() snapshot may already hold it
                # self-prune: under a reconnect storm with NO later
                # accepts, the accept-loop prune never runs again — a
                # finished serve thread must not linger in _conns until
                # the next connection (or forever, on an idle listener)
                try:
                    self._conns.remove(threading.current_thread())
                except ValueError:
                    pass  # stop() snapshot may already hold it

    # -- native event-loop pump ------------------------------------------------

    #: data-plane kinds that can PARK inside their handler waiting for a
    #: FUTURE request of this same service (checkpoint pause wakes on
    #: resume; the sync replica-ack gate can stall on a hung backup):
    #: the single pump thread must never park, so these are punted to a
    #: short-lived thread exactly when they could block — everything
    #: else dispatches inline in the batch.
    _COMMIT_KINDS = frozenset({tv.PUSH, tv.PUSH_PULL, tv.BUCKET_PUSH,
                               tv.ROW_PUSH, tv.ROW_PUSH_PULL,
                               tv.ROW_BUCKET_PUSH})
    #: kinds whose handlers orchestrate long multi-request protocols
    #: (checkpoint phases park between coordinator requests; a rebalance /
    #: outbound migration runs for the whole move) — always punted.
    _PUNT_KINDS = frozenset({tv.CHECKPOINT, tv.MIGRATE_OUT,
                             tv.COORD_REBALANCE, tv.RESEED})
    #: subclass hook: kinds whose handlers can PARK waiting for ANOTHER
    #: member's future request of this same service (the aggregator's
    #: group barrier: a push waits for its host group's other pushes) —
    #: always punted to a FRESH thread, never the pool: at fan-in >
    #: pool-size, the round-completing push queued behind parked pool
    #: workers would deadlock the barrier it is supposed to release.
    _BARRIER_KINDS: frozenset = frozenset()

    def _loop_pump(self) -> None:
        """The ONE Python thread of the native-loop serve path: drain
        batches of complete requests from the native loop, dispatch each
        through the same `_dispatch` as the threaded path, reply via the
        loop's scatter-gather writer. Exits when the loop reports
        stopped (poll() -> None). A failure serving ONE request must
        never kill the pump (it is the only consumer): the per-request
        guard logs, releases the body (free is idempotent), and moves
        on — the threaded path's one-bad-connection blast radius."""
        nloop = self._nloop
        last_sync = 0.0
        while True:
            try:
                batch = nloop.poll(timeout_ms=100)
            except Exception:
                logging.getLogger(__name__).exception(
                    "native-loop poll failed; pump exiting")
                return
            # gauge sync is an O(conns) native lock sweep (nl_pending
            # touches every conn's write mutex): run it on idle ticks or
            # at most ~1/s under load — /metrics and ps_top refresh at
            # human timescales, the hot path must not pay per batch
            now = time.monotonic()
            if not batch or now - last_sync >= 1.0:
                last_sync = now
                st = nloop.stats()
                self.transport.set_loop_stats(st["iters"], st["requests"],
                                              st["conns"])
                self._loop_conn_gauge.set(st["conns"])
                self._loop_iter_gauge.set(st["iters"])
                self._loop_req_gauge.set(st["requests"])
                if self._native_read_cache:
                    cs = nloop.cache_stats()
                    self.transport.set_read_cache_stats(
                        cs["hits"], cs["misses"], cs["entries"],
                        cs["bytes"], cond_hits=cs.get("cond_hits", 0))
                    self._read_hits_gauge.set(cs["hits"])
                    self._read_miss_gauge.set(cs["misses"])
                    v = self._read_version()
                    # versions the cached snapshot trails the engine by
                    # (0 when empty — nothing stale is being served)
                    self._read_lag_gauge.set(
                        max(0, int(v) - self._read_pub_version)
                        if v is not None and cs["entries"] else 0)
                if self._native_admit:
                    asn = nloop.admit_stats()
                    self.transport.set_admit_stats(
                        asn["acks"], asn["refusals"], asn["fresh"],
                        asn["punts"])
                    self._padm_acks_gauge.set(asn["acks"])
                    self._padm_ref_gauge.set(asn["refusals"])
                if self._nl_stats:
                    self._sync_nl_telemetry(nloop)
            if batch is None:
                return
            if not batch:
                continue
            if self._pump_abort:
                # kill(): drop read-ahead frames unserved — engine state
                # must stay exactly as a SIGKILL would leave it
                for _, _, ptr, _ in batch:
                    nloop.free(ptr)
                continue
            self.transport.record_upcall(len(batch))
            with self._inflight_cond:
                self._inflight += len(batch)
            for cid, view, ptr, admit_gen in batch:
                try:
                    self._loop_serve_one(cid, view, ptr, admit_gen)
                except Exception:
                    logging.getLogger(__name__).exception(
                        "native-loop request failed; connection %d "
                        "continues", cid)
                    nloop.free(ptr)  # idempotent: no-op if already freed
                finally:
                    with self._inflight_cond:
                        self._inflight -= 1
                        self._inflight_cond.notify_all()

    def _sync_nl_telemetry(self, nloop) -> None:
        """Fold the loop's own telemetry into this service's stats (the
        pump's ~1/s gauge tick): the in-loop histograms land ABSOLUTE in
        the ps_nl_* TransportStats families — the native stripes own the
        counting — so they ride /metrics, STATS frames, and the
        delta-encoded fleet telemetry exactly like every Python-recorded
        surface; and the slow-frame ring drains into ``slow_frame``
        flight events, each with a reconstructed span when the frame
        carried a trace context (the zero-upcall path cannot open spans
        itself — this is where one hiccup on it becomes a traceable
        incident instead of a p999 mystery)."""
        self.transport.set_nl_hists(nloop.hist_snapshots())
        ns = nloop.stats_snapshot()
        self.transport.set_nl_stats(ns["slow_frames"],
                                    ns["tail_backlog_bytes"])
        for fr in nloop.slow_drain():
            total_ns = fr["read_ns"] + fr["wait_ns"] + fr["serve_ns"]
            obs.record_event(
                "slow_frame", conn=fr["conn"],
                wire_kind=tv.kind_name(fr["kind"]), size=fr["size"],
                read_ms=round(fr["read_ns"] / 1e6, 3),
                wait_ms=round(fr["wait_ns"] / 1e6, 3),
                serve_ms=round(fr["serve_ns"] / 1e6, 3),
                total_ms=round(total_ns / 1e6, 3),
                trace_id=fr["trace_id"] or None)
            if fr["trace_id"]:
                obs.tracer().record_external(
                    "slow_frame", "server", fr["trace_id"],
                    fr["span_id"] or None,
                    ts_us=time.time() * 1e6
                    - (fr["age_ns"] + total_ns) / 1e3,
                    dur_us=total_ns / 1e3,
                    t0=time.perf_counter()
                    - (fr["age_ns"] + total_ns) / 1e9,
                    conn=fr["conn"], wire_kind=tv.kind_name(fr["kind"]),
                    size=fr["size"],
                    read_us=round(fr["read_ns"] / 1e3, 1),
                    wait_us=round(fr["wait_ns"] / 1e3, 1),
                    serve_us=round(fr["serve_ns"] / 1e3, 1))

    def _punt_pool(self) -> "_DaemonPool":
        """Lazily-built pool for non-blocker punted requests (threads
        spawn on demand and are reused; only the pump calls this, so the
        lazy init needs no lock). 32 workers: parked pause-era pushes cap
        there and the rest queue — they would have parked anyway — while
        resume always arrives on a fresh thread. Daemon threads, NOT a
        ThreadPoolExecutor: its workers are joined at interpreter exit,
        so a task parked on a pause that nothing will ever resume (e.g.
        after kill()) would hang process shutdown — the exact hazard the
        threaded path avoids by making serve threads daemons."""
        pool = getattr(self, "_punt_executor", None)
        if pool is None:
            pool = _DaemonPool(max_workers=32, name="van-punt")
            self._punt_executor = pool
        return pool

    def _loop_close_conn(self, cid: int) -> None:
        """Drop one event-loop connection (malformed frame — the framing
        is gone, like the threaded path poisoning its channel)."""
        fd = self._nloop.detach(cid)
        if fd >= 0:
            os.close(fd)

    def _loop_serve_one(self, cid: int, msg, ptr: int,
                        admit_gen: int = 0) -> None:
        nloop = self._nloop
        if self._pump_abort:  # kill() landed mid-batch: drop, don't apply
            nloop.free(ptr)
            return
        try:
            kind, worker, tensors, extra = tv.decode(msg)
        except Exception:
            nloop.free(ptr)
            self._loop_close_conn(cid)
            return
        self._req_counter.inc()
        # a READ reaching the pump IS a native-cache miss: remember its
        # exact request bytes so the reply can be published into the
        # native cache (the next identical READ is answered inside the
        # loop with zero upcalls). The copy is tiny — READ requests are
        # a header + (sparse) an id list. The publish rides whichever
        # dispatch path the kind takes (inline here, or punted — the
        # aggregator barriers READs off-pump because its coalesced fetch
        # does upstream I/O).
        raw = (bytes(msg) if kind == tv.READ and self._native_read_cache
               else None)
        if kind == tv.SHUTDOWN:
            nloop.reply(cid, tv.encode(tv.OK, worker, None),
                        close_after=True)
            tensors = None
            nloop.free(ptr)
            with self._goodbye_cond:
                self.goodbyes += 1
                self._goodbye_cond.notify_all()
            return
        if kind == tv.SHM_SETUP:
            self._loop_shm_upgrade(cid, worker, extra, ptr)
            return
        barrier = kind in self._BARRIER_KINDS
        if kind in self._PUNT_KINDS or barrier or (
                kind in self._COMMIT_KINDS
                and (getattr(self, "_paused", False)
                     or self._loop_blockers > 0
                     or self._backup_session is not None)):
            # a request that may park must not park THE pump: hand it a
            # thread of its own (the threaded path's shape), bounded by
            # one in-flight request per connection. `_loop_blockers`
            # closes the pause TOCTOU: a punted CHECKPOINT sets `_paused`
            # on ITS thread, so the pump could otherwise inline-dispatch
            # a push in the race window and park forever on the pause
            # condition — the counter is raised HERE (synchronously,
            # before the blocker's thread even starts) and held until
            # that blocker's reply went out, so every commit the pump
            # sees after the blocker frame punts too.
            blocker = kind in self._PUNT_KINDS
            with self._inflight_cond:
                self._inflight += 1  # the punted task's share; pump's
                # own share is released when this method returns
                if blocker:
                    self._loop_blockers += 1
            try:
                if blocker or barrier or getattr(self, "_paused", False) \
                        or self._loop_blockers > 0:
                    # fresh threads whenever parking is on the table:
                    # blockers (a resume must never queue behind pool
                    # workers parked on the very pause it would lift),
                    # and EVERY commit while a pause/blocker is live —
                    # at >pool-size fan-in, a drain_to-admitted push
                    # queued behind parked pool workers would deadlock
                    # the checkpoint round until its timeout.
                    threading.Thread(
                        target=self._loop_dispatch_reply,
                        args=(cid, kind, worker, tensors, extra, ptr,
                              True, blocker, raw, admit_gen),
                        daemon=True,
                    ).start()
                else:
                    # steady-state punts (every replicated push) reuse a
                    # small pool — one fresh thread per request would be
                    # strictly worse churn than the thread-per-connection
                    # path this loop replaces. Pool exhaustion only
                    # queues work that genuinely only needs the engine
                    # lock (no parking condition is live on this branch).
                    self._punt_pool().submit(
                        self._loop_dispatch_reply, cid, kind, worker,
                        tensors, extra, ptr, True, False, raw, admit_gen)
            except Exception as e:  # thread exhaustion: refuse, don't die
                with self._inflight_cond:
                    self._inflight -= 1
                    if blocker:
                        self._loop_blockers -= 1
                    self._inflight_cond.notify_all()
                nloop.reply(cid, tv.encode(tv.ERR, worker, None,
                                           extra={"error": repr(e)}))
                tensors = None
                nloop.free(ptr)
            return
        self._loop_dispatch_reply(cid, kind, worker, tensors, extra, ptr,
                                  False, raw=raw, admit_gen=admit_gen)

    def _dispatch_reply_payload(self, kind: int, worker: int, tensors,
                                extra):
        """Dispatch + the typed-refusal ERR mapping, shared by BOTH serve
        paths so the frames can never drift (tests pin them
        byte-identical): NotServing -> retryable backup refusal,
        StaleTable -> re-route (the key range moved shards), anything
        else -> a plain ERR surfaced to the worker."""
        try:
            return self._dispatch(kind, worker, tensors, extra)
        except NotServingError as e:
            return tv.encode(tv.ERR, worker, None, extra={
                "error": str(e), "backup": True,
                "epoch": self.epoch,
            })
        except StaleTableError as e:
            return tv.encode(tv.ERR, worker, None, extra={
                "error": str(e), "moved": True,
                "table_epoch": self.table_epoch,
            })
        except Exception as e:
            return tv.encode(tv.ERR, worker, None,
                             extra={"error": repr(e)})

    def _reply_priority(self, kind: int, extra) -> int:
        """Native-loop writev priority of this request's reply: bucket
        frames drain front-of-model first (their bucket index), every
        other kind at 0 — PS_BUCKET_PRIORITY=0 restores the pure FIFO
        drain. Priorities only reorder tails across CONNECTIONS awaiting
        EPOLLOUT; per-connection reply order is untouched, so the framed
        request/reply contract cannot tear."""
        if not self._bucket_priority:
            return 0
        if kind in (tv.BUCKET_PULL, tv.BUCKET_PUSH, tv.ROW_BUCKET_PUSH):
            try:
                return int((extra or {}).get("bucket") or 0)
            except (TypeError, ValueError):
                return 0
        return 0

    def _loop_dispatch_reply(self, cid: int, kind: int, worker: int,
                             tensors, extra, ptr: int,
                             punted: bool, blocker: bool = False,
                             raw=None, admit_gen: int = 0) -> None:
        nloop = self._nloop
        prio = self._reply_priority(kind, extra)
        # mark this thread as serving a LOOP request for the dispatch's
        # duration, so a pause park inside the handler is counted toward
        # the native drain's claimed-body discount (reset in the finally:
        # pool threads are reused)
        this = threading.current_thread()
        this._ps_loop_req = True
        # the frame's native admission stamp (0 = unclassified) rides a
        # thread-local to the engine's apply, which consumes it via
        # _admit_fresh_hint — set unconditionally: pool/pump threads are
        # reused and a previous request's stamp must never leak forward
        self._read_pub.admit = int(admit_gen)
        try:
            if raw is not None:
                self._read_pub.gen = None  # pool/pump threads are reused:
                # never publish under a PREVIOUS request's generation
                self._read_pub.tags = None  # (nor its row tags)
            reply = self._dispatch_reply_payload(kind, worker, tensors,
                                                 extra)
            if raw is not None and isinstance(reply, (bytes, bytearray)):
                gen = getattr(self._read_pub, "gen", None)
                if gen is not None:
                    # publish-on-miss: the reply the pump is about to send
                    # becomes the native cache's entry for these request
                    # bytes — hit replies are bitwise identical to this
                    # pump reply BY CONSTRUCTION (the cache only echoes).
                    # A put raced by an apply is refused at the floor.
                    # Three shapes: a NOT_MODIFIED reply publishes as a
                    # version-floor entry (the request's cond digits are
                    # excised native-side so revalidators at ANY version
                    # >= the stamp share it); any OTHER reply to a
                    # conditional request is version-dependent (a delta,
                    # or a full payload for a lagging caller) and must
                    # not park under a key later conditionals would
                    # exact-match — skipped; unconditional replies keep
                    # the exact-byte publish unchanged.
                    tags = getattr(self._read_pub, "tags", None)
                    if len(reply) >= 1 and reply[0] == tv.NOT_MODIFIED:
                        if nloop.cache_put_cond(
                                raw, reply, gen, tags=tags,
                                vfloor=int(getattr(self._read_pub,
                                                   "version", 0))):
                            self._read_pub_version = int(
                                getattr(self._read_pub, "version", 0))
                    elif b'"cond":' in raw[-4096:]:
                        pass  # conditional miss: reply is caller-specific
                    elif nloop.cache_put(raw, reply, gen, tags=tags):
                        self._read_pub_version = int(
                            getattr(self._read_pub, "version", 0))
            try:
                nloop.reply(cid, reply, priority=prio)  # False = gone
            finally:
                # ONLY now is the request frame provably dead (the reply
                # may alias zero-copy views of it)
                tensors = None
                nloop.free(ptr)
        finally:
            this._ps_loop_req = False
            if punted:
                with self._inflight_cond:
                    self._inflight -= 1
                    if blocker:
                        self._loop_blockers -= 1
                    self._inflight_cond.notify_all()

    def _loop_shm_upgrade(self, cid: int, worker: int, extra: dict,
                          ptr: int) -> None:
        """SHM_SETUP on the event-loop path: detach the fd from the loop
        and serve the upgraded connection from a dedicated thread — the
        ring wait (tv_wait_u64) is already GIL-free native code, and epoll
        cannot wait on ring cursors, so the lane gains nothing from the
        loop. A refused upgrade keeps the connection on the thread too
        (plain TCP), mirroring the threaded path's behavior."""
        from ps_tpu.control import native_loop as nlmod

        nloop = self._nloop
        nloop.free(ptr)  # SHM_SETUP carries no tensors; extra is decoded
        fd = nloop.detach(cid)
        if fd < 0:
            return  # connection died under the request
        ch = nlmod.adopt_channel(fd)
        ch.stats = self.transport
        ch.pool = self._recv_pool
        lane, reply = self._try_shm_upgrade(ch, worker, extra)
        try:
            self._send_reply(ch, reply)
        except tv.VanError:
            if lane is not None:
                lane.close()
            else:
                ch.close()
            return
        with self._chan_lock:
            self._conns = [t for t in self._conns
                           if t.ident is None or t.is_alive()]
            if self._stop.is_set():
                (lane if lane is not None else ch).close()
                return
            self._channels.append(ch)
            t = threading.Thread(target=self._serve, args=(ch, lane),
                                 daemon=True)
            self._conns.append(t)
        t.start()

    # -- lifecycle -------------------------------------------------------------

    def wait_for_goodbyes(self, n: int, timeout: Optional[float] = None
                          ) -> bool:
        """Block until ``n`` workers have sent SHUTDOWN (clean departure).

        The quiescence signal a server should wait on before ``stop()``:
        a worker's ``close()`` sends SHUTDOWN only after every one of its
        pushes has been applied AND replied, so ``goodbyes == num_workers``
        implies no request is outstanding anywhere. Returns False on
        timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._goodbye_cond:
            while self.goodbyes < n:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._goodbye_cond.wait(left)
        return True

    def kill(self) -> None:
        """Simulate abrupt process death (failover drills / bench): sever
        the listener and every connection NOW — no drain, no goodbye, no
        draining flag, engine state left exactly as a SIGKILL would leave
        it. Workers observe the same typed connection failure a real
        primary death produces; an attached backup session degrades."""
        self._stop.set()
        if self._nloop is not None:
            self._pump_abort = True  # queued frames are DROPPED, not
            # applied: a kill must leave the engine as SIGKILL would
            self._nloop.stop_accept()
            self._nloop.shutdown_conns()
            self._nloop.begin_stop()
            self._pump_thread.join(timeout=5)
            if not self._pump_thread.is_alive():
                self._nloop.close()  # a pump stuck mid-apply keeps the
                # handle alive (its reply/free calls no-op after close)
        else:
            self._accept_thread.join(timeout=5)
        self._listener.close()
        s = self._backup_session
        if s is not None:
            s.close()
        with self._chan_lock:
            chans = list(self._channels)
        for ch in chans:
            ch.shutdown()  # serve threads wake with VanError and close

    def stop(self, grace: float = 10.0) -> None:
        """Graceful drain, then sever. No push is applied after this
        returns, and no reply in flight when it was called is torn.

        The guarantee has two legs: the in-flight wait lets every received
        request finish its reply (bounded by ``grace`` seconds), and the
        subclass's draining flag — set under its apply lock — refuses every
        later commit, so even a serve thread that outlives the bounded
        join (e.g. stuck in a minutes-long jit compile) can never land a
        push after this method returns.

        Requests parked on a checkpoint-pause condition do NOT count toward
        the drain wait (they cannot finish until the draining flag wakes
        them into refusal — a coordinator that died between pause and
        resume must not cost the full grace); they are woken by
        ``_set_draining`` and given a short bounded window to send their
        ERR replies before the sever."""
        self._stop.set()
        if self._nloop is not None:
            self._stop_native(grace)
            return
        # join BEFORE closing: the accept thread may be inside tv_accept on
        # the listener handle (its 200ms timeout bounds the wait); closing
        # first would hand it a freed pointer
        self._accept_thread.join(timeout=5)
        self._listener.close()
        deadline = time.monotonic() + grace
        while True:
            with self._inflight_cond:
                while (self._inflight - self._pause_blocked > 0
                       and time.monotonic() < deadline):
                    self._inflight_cond.wait(deadline - time.monotonic())
                drained = self._inflight - self._pause_blocked == 0
            if not drained:
                logging.getLogger(__name__).warning(
                    "request(s) still in flight after %.1fs drain grace; "
                    "severing anyway", grace
                )
                break
            # stability confirm: a serve thread whose recv JUST returned a
            # frame may not have reached its in-flight mark yet (the window
            # between recv returning and the increment cannot be closed —
            # TCP has no atomic refuse). Re-check after a beat; only a
            # stable zero proceeds to the sever.
            time.sleep(0.05)
            with self._inflight_cond:
                if self._inflight - self._pause_blocked == 0:
                    break
            if time.monotonic() >= deadline:
                break
        self._set_draining()
        # pause-parked requests just woke into refusal: give them a short
        # bounded window to send their ERR replies intact before severing
        with self._inflight_cond:
            end = min(deadline, time.monotonic() + 2.0)
            while self._inflight > 0 and time.monotonic() < end:
                self._inflight_cond.wait(max(end - time.monotonic(), 0.01))
        with self._chan_lock:
            chans = list(self._channels)
            conns = list(self._conns)
        for ch in chans:
            ch.shutdown()  # non-freeing sever; each serve thread closes own
        for t in conns:
            t.join(timeout=5)
        stragglers = [t for t in conns if t.is_alive()]
        if stragglers:
            logging.getLogger(__name__).warning(
                "%d serve thread(s) outlived the drain join; their pushes "
                "are refused by the draining flag", len(stragglers)
            )
        s = self._backup_session
        if s is not None:
            s.close()  # after the drain: every acked commit replicated

    def _stop_native(self, grace: float) -> None:
        """stop() for the native event-loop path — the same drain
        contract, over different machinery: "in flight" is the pump's
        accounting PLUS the loop's pending count (frames read but not yet
        handed out, claimed frames awaiting their reply, and unflushed
        reply tails), so a reply the loop has not finished writing is
        never torn by the sever."""
        nloop = self._nloop
        nloop.stop_accept()  # freeze the connection set
        deadline = time.monotonic() + grace

        def quiet() -> bool:
            with self._inflight_cond:
                infl = self._inflight - self._pause_blocked
                parked = self._loop_pause_parked
            # pause-parked LOOP requests each hold exactly one claimed
            # body (freed only at their reply), so they must be
            # discounted from the loop's pending count too — same
            # docstring promise as the threaded drain: a coordinator
            # dead between pause and resume must not cost the full
            # grace. Only loop parks count here: a park on an
            # shm-detached serve thread holds no loop body, and
            # over-discounting could mask a genuinely unflushed tail.
            return infl <= 0 and nloop.pending() - parked <= 0

        drained = False
        while time.monotonic() < deadline:
            if quiet():
                # stability confirm, as in the threaded drain: a frame
                # the loop JUST completed may not be counted yet
                time.sleep(0.05)
                if quiet():
                    drained = True
                    break
            else:
                time.sleep(0.02)
        if not drained:
            logging.getLogger(__name__).warning(
                "request(s) still in flight after %.1fs drain grace; "
                "severing anyway", grace
            )
        self._set_draining()
        # pause-parked punted requests just woke into refusal: bounded
        # window for their ERR replies, then for the loop to flush them
        with self._inflight_cond:
            end = min(deadline, time.monotonic() + 2.0)
            while self._inflight > 0 and time.monotonic() < end:
                self._inflight_cond.wait(max(end - time.monotonic(), 0.01))
        end = min(deadline, time.monotonic() + 0.5)
        while nloop.pending() > 0 and time.monotonic() < end:
            time.sleep(0.02)
        nloop.shutdown_conns()  # idle peers observe EOF now
        nloop.begin_stop()
        self._pump_thread.join(timeout=5)
        # shm-detached connections are classic serve threads: sever + join
        with self._chan_lock:
            chans = list(self._channels)
            conns = list(self._conns)
        for ch in chans:
            ch.shutdown()
        for t in conns:
            t.join(timeout=5)
        stragglers = [t for t in conns if t.is_alive()]
        if self._pump_thread.is_alive():
            stragglers.append(self._pump_thread)
        if stragglers:
            logging.getLogger(__name__).warning(
                "%d serve/pump thread(s) outlived the drain join; their "
                "pushes are refused by the draining flag", len(stragglers)
            )
        if not self._pump_thread.is_alive():
            nloop.close()  # frees the loop; skipped only while the pump
            # (the one poll() caller) could still touch the raw handle —
            # punted threads' reply/free calls no-op after close
        self._listener.close()
        s = self._backup_session
        if s is not None:
            s.close()  # after the drain: every acked commit replicated
