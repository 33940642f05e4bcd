"""Shared server-engine pieces (single source of truth for both backends).

The local backend's semantics are the spec the mesh backend must match
(asserted by tests/test_async_tpu.py); keeping the DC apply and the
introspection read in one place guarantees a fix to one cannot silently
break that parity.
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
import optax

from ps_tpu import obs
from ps_tpu.control import tensor_van as tv
from ps_tpu.optim.dc import delay_compensate
from ps_tpu.utils.metrics import TransportStats

class ServerFailureError(RuntimeError):
    """A remote PS server died mid-job (its connection failed).

    ``server`` (when known) is the failed server's index into the worker's
    address list — what the failover loop re-routes."""

    def __init__(self, message: str, server: Optional[int] = None):
        super().__init__(message)
        self.server = server


class TableMovedError(RuntimeError):
    """The shard TABLE moved under this worker (a live rebalance migrated
    keys between shards — ps_tpu/elastic). Typed apart from
    :class:`ServerFailureError` because the remedy differs: the server is
    alive and healthy, only the key→shard assignment changed, so the
    worker must re-fetch the table from its coordinator and re-route —
    cycling the shard's replica set (the primary-died remedy) would just
    find the same refusal at every member.

    ``table_epoch`` is the refusing server's table epoch: the worker
    waits for a FETCHED table past its own before retrying, so a refusal
    raced against the coordinator's publish converges instead of
    spinning."""

    def __init__(self, message: str, server: Optional[int] = None,
                 table_epoch: int = 0):
        super().__init__(message)
        self.server = server
        self.table_epoch = int(table_epoch)


class BackupNotServing(Exception):
    """A replica answered HELLO but is an unpromoted backup — retryable
    (the failover loop waits out the promotion)."""


class ReplicaRejected(Exception):
    """A replica answered HELLO but failed validation (stale epoch /
    mismatched topology) — skip it, keep cycling the set."""


def parse_replica_uri(uri: str):
    """``"h0:p0|b0:q0,h1:p1|b1:q1"`` → ``(primaries, replica_sets)``.

    Commas separate shards (as everywhere); ``|`` separates the members of
    one shard's replica set, preferred (primary) first. A plain
    ``host:port`` list parses to singleton sets — no failover."""
    primaries, sets = [], []
    for part in uri.split(","):
        cands = []
        for member in part.strip().split("|"):
            host, port = member.strip().rsplit(":", 1)
            cands.append((host, int(port)))
        primaries.append(cands[0])
        sets.append(cands)
    return primaries, sets


class _OpScope:
    """The per-op observability scope :meth:`BucketedTransportMixin._op`
    returns — a plain slotted object, not a generator contextmanager, so
    the unsampled hot path allocates one small object and nothing else."""

    __slots__ = ("_transport", "_name", "_sp", "_t0")

    def __init__(self, transport, name: str, sp):
        self._transport = transport
        self._name = name
        self._sp = sp

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._sp.__enter__()
        return self._sp

    def __exit__(self, *exc):
        try:
            self._sp.__exit__(*exc)
        finally:
            self._transport.record_op(
                self._name, time.perf_counter() - self._t0)
        return False


#: Default fusion-bucket size for the pipelined transport. ~4 MiB is the
#: ps-lite/BytePS sweet spot: large enough that per-message overhead (json
#: meta, syscalls) is noise, small enough that many buckets are in flight
#: per tree and the pipeline has something to overlap.
DEFAULT_BUCKET_BYTES = 4 << 20

#: Worker-id floor for aggregator identities (ps_tpu/backends/aggregator):
#: an aggregator pushes its group's MERGED gradient to the shards under a
#: synthetic worker id — group index offset past this base — so its
#: per-key dedup tokens and DC staleness bookkeeping never collide with a
#: real worker's slot (real ids live in [0, num_workers); the engines'
#: range check admits ids at or past this base explicitly).
AGG_WORKER_BASE = 1 << 20

#: Default drain_to deadline (checkpoint coordinators produce it on the
#: wire; servers fall back to it for hand-rolled frames). One constant so
#: the dense/sparse coordinators and both server sides cannot drift.
DRAIN_TO_TIMEOUT_S = 30.0

# one bucket slice: (key, dtype_str, shape, lo, hi) — byte range [lo, hi)
# within the key's contiguous row-major buffer
Slice = Tuple[str, str, list, int, int]


def payload_nbytes(payload) -> int:
    """Wire payload size of a frame in either form: a contiguous
    bytes/bytearray, or the zero-copy ``(header, chunks)`` parts tuple."""
    if isinstance(payload, tuple):
        header, chunks = payload
        return len(header) + sum(len(c) for c in chunks)
    return len(payload)


def send_payload(ch, payload) -> None:
    """Send either payload form on ``ch`` (vectored for parts)."""
    if isinstance(payload, tuple):
        ch.send_parts(*payload)
    else:
        ch.send(payload)


def request_payload(ch, payload):
    """``ch.request`` for either payload form; returns the reply frame."""
    if isinstance(payload, tuple):
        return ch.request_parts(*payload)
    return ch.request(payload)


class BucketPlan:
    """Slice a flat ``{key: tensor}`` payload into fixed-size fusion buckets.

    Keys are packed greedily in transport order (sorted — for slash-joined
    layer paths that is front-of-model first, which is the order the next
    step's forward needs them). A tensor larger than ``bucket_bytes`` is
    split across consecutive buckets; small tensors fuse into one bucket.
    Every bucket except the last holds exactly ``bucket_bytes`` payload
    bytes, so striping buckets round-robin over a connection pool balances
    it by construction.

    The encoded frame (:meth:`encode_bucket`) is self-describing: its
    ``extra["slices"]`` table carries (key, dtype, shape, lo, hi) per
    slice, so the receiving side reassembles with :class:`BucketAssembler`
    without any prior shape knowledge — worker and server never need to
    agree on a plan out of band.
    """

    def __init__(self, specs: Sequence[Tuple[str, str, list, int]],
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        """``specs``: ``(key, dtype_str, shape, nbytes)`` in transport order."""
        self.bucket_bytes = max(int(bucket_bytes), 1)
        buckets: List[List[Slice]] = []
        cur: List[Slice] = []
        fill = 0
        for key, dt, shape, nbytes in specs:
            shape = list(shape)
            if nbytes == 0:
                # zero-size tensors still travel (the key must appear)
                cur.append((key, dt, shape, 0, 0))
                continue
            off = 0
            while off < nbytes:
                if fill >= self.bucket_bytes:
                    buckets.append(cur)
                    cur, fill = [], 0
                take = min(nbytes - off, self.bucket_bytes - fill)
                cur.append((key, dt, shape, off, off + take))
                off += take
                fill += take
        buckets.append(cur)  # last (possibly empty for an empty payload)
        self.buckets = buckets
        self.total_bytes = sum(n for _, _, _, n in specs)

    @classmethod
    def from_arrays(cls, arrays: Dict[str, np.ndarray],
                    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                    order: Optional[Sequence[str]] = None) -> "BucketPlan":
        keys = list(order) if order is not None else sorted(arrays)
        specs = []
        for k in keys:
            a = np.asarray(arrays[k])
            specs.append((k, a.dtype.str, list(a.shape), a.nbytes))
        return cls(specs, bucket_bytes)

    @property
    def nbuckets(self) -> int:
        return len(self.buckets)

    def _bucket_chunks_meta(self, arrays: Dict[str, np.ndarray], b: int,
                            extra: Optional[dict]):
        chunks = []
        slices = self.buckets[b]
        for key, _, _, lo, hi in slices:
            a = np.ascontiguousarray(np.asarray(arrays[key]))
            chunks.append(memoryview(a.reshape(-1)).cast("B")[lo:hi])
        meta = {**(extra or {}),
                "bucket": b, "nbuckets": self.nbuckets,
                "slices": [[k, dt, shape, lo, hi]
                           for k, dt, shape, lo, hi in slices]}
        return chunks, meta

    def encode_bucket(self, kind: int, worker: int,
                      arrays: Dict[str, np.ndarray], b: int,
                      extra: Optional[dict] = None) -> bytearray:
        """Frame bucket ``b``: each slice's bytes are a ``memoryview`` of
        the live tensor, copied exactly once into the frame
        (:func:`~ps_tpu.control.tensor_van.encode_chunks`)."""
        chunks, meta = self._bucket_chunks_meta(arrays, b, extra)
        return tv.encode_chunks(kind, worker, chunks, meta)

    def encode_bucket_parts(self, kind: int, worker: int,
                            arrays: Dict[str, np.ndarray], b: int,
                            extra: Optional[dict] = None):
        """Zero-copy form of :meth:`encode_bucket`: ``(header, chunks)``
        with the slice views passed through UNstaged — the channel's
        vectored send (or the shm ring write) is the only copy the bucket's
        bytes ever see. The views pin their tensors until sent."""
        chunks, meta = self._bucket_chunks_meta(arrays, b, extra)
        return tv.encode_chunks_parts(kind, worker, chunks, meta)

    def bucket_encoder(self, writev: bool):
        """The ONE lane-selection point for bucket frames: zero-copy parts
        when ``writev`` is on, the staged legacy frame otherwise. Every
        sender resolves through here so the rule cannot drift per site."""
        return self.encode_bucket_parts if writev else self.encode_bucket


class BucketAssembler:
    """Reassemble a multi-bucket payload; a torn epoch is never observable.

    Buckets may arrive in any order (they are striped over a connection
    pool). Every slice carries the push epoch it belongs to; a slice from a
    different epoch is refused (the per-key epoch tag — a straggler bucket
    of an aborted push can never contaminate a later tree), a duplicate
    bucket is refused, and :meth:`finish` refuses any key whose byte
    coverage is incomplete. Only when all ``nbuckets`` buckets of ONE epoch
    have landed does :meth:`add` report completion — the caller applies the
    assembled tree atomically, so readers observe whole pushes or nothing.
    """

    def __init__(self, epoch: int, nbuckets: int):
        self.epoch = int(epoch)
        self.nbuckets = int(nbuckets)
        self._seen: set = set()
        self._flat: Dict[str, np.ndarray] = {}    # key -> uint8 buffer
        self._meta: Dict[str, Tuple[str, list, int]] = {}
        self._filled: Dict[str, int] = {}
        self._key_epoch: Dict[str, int] = {}

    def add(self, bucket: int, raw, slices, epoch: Optional[int] = None
            ) -> bool:
        """Stage one bucket; returns True when the epoch is complete."""
        if epoch is not None and int(epoch) != self.epoch:
            raise RuntimeError(
                f"bucket of epoch {epoch} offered to assembler of epoch "
                f"{self.epoch} — torn multi-bucket push refused"
            )
        b = int(bucket)
        if not (0 <= b < self.nbuckets):
            raise RuntimeError(f"bucket {b} out of range 0..{self.nbuckets-1}")
        if b in self._seen:
            raise RuntimeError(f"duplicate bucket {b} for epoch {self.epoch}")
        raw = np.frombuffer(raw, np.uint8) if not isinstance(raw, np.ndarray) \
            else raw.reshape(-1).view(np.uint8)
        off = 0
        for key, dt, shape, lo, hi in slices:
            if key not in self._flat:
                nbytes = (int(np.prod(shape, dtype=np.int64))
                          * np.dtype(dt).itemsize)
                self._flat[key] = np.empty(nbytes, np.uint8)
                self._meta[key] = (dt, list(shape), nbytes)
                self._filled[key] = 0
                self._key_epoch[key] = self.epoch
            n = hi - lo
            self._flat[key][lo:hi] = raw[off:off + n]
            self._filled[key] += n
            off += n
        self._seen.add(b)
        return len(self._seen) == self.nbuckets

    def finish(self) -> Dict[str, np.ndarray]:
        """The assembled ``{key: tensor}`` tree (buffers owned by the
        assembler's own allocations — safe to hold past frame lifetimes)."""
        if len(self._seen) != self.nbuckets:
            raise RuntimeError(
                f"epoch {self.epoch} incomplete: {len(self._seen)}/"
                f"{self.nbuckets} buckets"
            )
        out = {}
        for key, (dt, shape, nbytes) in self._meta.items():
            if self._filled[key] != nbytes:
                raise RuntimeError(
                    f"key {key!r} torn: {self._filled[key]}/{nbytes} bytes "
                    f"in epoch {self.epoch}"
                )
            out[key] = self._flat[key].view(np.dtype(dt)).reshape(shape)
        return out


class ChannelPump:
    """One persistent transport connection + its dedicated sender thread.

    The background half of the pipelined transport: callers ``submit``
    encoded frames and immediately get a Future for the reply; the pump
    thread drains the pending queue over its own
    :class:`~ps_tpu.control.tensor_van.Channel` (one driving thread per
    channel, as the van requires). Striping a plan's buckets round-robin
    over a pool of pumps gives per-server send/recv parallelism — the
    native sends release the GIL, so pumps genuinely overlap.

    The pending queue is a PRIORITY queue (ByteScheduler-style): each
    submit carries a small integer priority — lower drains first — and
    ties break on the enqueue sequence number, so equal-priority traffic
    stays exactly FIFO and the drain order is fully deterministic. Bucket
    senders pass the bucket index (front-of-model first, i.e. reverse of
    backprop completion order), so when a backlog forms, the tail
    layers' buckets stop serializing in front of the bytes the next
    step's forward needs first. All-default submits reproduce the
    legacy FIFO pump bit for bit.
    """

    def __init__(self, ch, on_io: Optional[Callable] = None):
        import concurrent.futures  # noqa: F401  (Future class used below)

        self._ch = ch
        self._on_io = on_io  # (bytes_out, bytes_in, seconds) per request
        self._cv = threading.Condition()
        self._heap: list = []   # (priority, seq, payload, fut)
        self._seq = 0
        self._closed = False
        self._t = threading.Thread(target=self._loop, daemon=True)
        self._t.start()

    def submit(self, payload, priority: int = 0):
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self._cv:
            if self._closed:
                # fail fast instead of queueing behind a dead thread — a
                # caller racing close() (e.g. a background cycle during
                # reconnect) gets a connection-shaped error, never a
                # forever-pending future
                fut.set_exception(tv.VanError("pump closed"))
                return fut
            self._seq += 1
            # the seq tie-break also guarantees (payload, fut) are never
            # compared by heapq
            heapq.heappush(self._heap,
                           (int(priority), self._seq, payload, fut))
            self._cv.notify()
        return fut

    def _loop(self) -> None:
        import time

        while True:
            with self._cv:
                while not self._heap and not self._closed:
                    self._cv.wait()
                if not self._heap:
                    return  # closed AND drained — same contract as the
                    # old stop sentinel: everything queued before close()
                    # still goes out
                _, _, payload, fut = heapq.heappop(self._heap)
            if not fut.set_running_or_notify_cancel():
                continue
            t0 = time.perf_counter()
            try:
                # parts tuples ride the vectored/shm zero-copy send;
                # contiguous frames keep the legacy path
                reply = request_payload(self._ch, payload)
            except BaseException as e:  # surfaced at the caller's wait
                fut.set_exception(e)
                continue
            dt = time.perf_counter() - t0
            if self._on_io is not None:
                try:
                    self._on_io(payload_nbytes(payload), len(reply), dt)
                except Exception:
                    pass  # accounting must never fail the transport
            fut.set_result(reply)

    def close(self) -> None:
        """Stop the thread (after the pending queue drains) and close the
        channel. Requests that slipped in behind the close are failed,
        never left as forever-pending futures."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._t.join(timeout=10)
        with self._cv:
            leftovers, self._heap = self._heap, []
        for _, _, _, fut in leftovers:
            fut.set_exception(tv.VanError("pump closed"))
        self._ch.close()


class BucketedTransportMixin:
    """Worker-side plumbing of the bucketed/pipelined transport, shared by
    the dense and sparse remote workers: pump-pool lifecycle, byte/timing
    accounting, background-handle bookkeeping, and the flush barrier.

    Contract: the concrete worker sets ``_addrs``, ``_bytes_lock``,
    ``bytes_pushed``/``bytes_pulled`` and calls :meth:`_init_transport`
    during its init, then :meth:`_open_pumps` once its channels are
    validated; it may override ``_failure_noun`` for error messages.
    """

    _failure_noun = "PS server"

    def _init_transport(self, bucket_bytes: Optional[int],
                        pool_size: Optional[int],
                        compress=None, writev: Optional[bool] = None,
                        shm: Optional[bool] = None,
                        shm_bytes: Optional[int] = None,
                        bucket_priority: Optional[bool] = None) -> None:
        import os
        import uuid

        from ps_tpu.config import env_flag, env_int
        from ps_tpu.control.shm_lane import DEFAULT_SHM_BYTES

        # <= 0 selects the serial transport, matching the PS_BUCKET_BYTES=0
        # convention everywhere (a literal 0 must never mean 1-byte buckets)
        self.bucket_bytes = (None if bucket_bytes is None
                             or int(bucket_bytes) <= 0 else int(bucket_bytes))
        # transport lanes (None = the PS_WRITEV / PS_SHM env defaults):
        # writev sends frames as kernel scatter-gather iovecs of the live
        # tensors (no staging bytearray); shm negotiates the same-host
        # shared-memory ring lane per connection, falling back to TCP
        # whenever negotiation fails
        self.writev = (env_flag("PS_WRITEV", True)
                       if writev is None else bool(writev))
        self.shm = env_flag("PS_SHM", False) if shm is None else bool(shm)
        # priority bucket scheduling (ByteScheduler-style): bucket flushes
        # carry their bucket index as the pump priority — front-of-model
        # buckets drain a backlog first, so the tail layers' grads stop
        # blocking the bytes the next step's forward needs. Off = every
        # submit at priority 0 = the legacy FIFO drain, bit for bit.
        self.bucket_priority = (env_flag("PS_BUCKET_PRIORITY", True)
                                if bucket_priority is None
                                else bool(bucket_priority))
        # validated service-level read (pslint PSL406): Config's >=64KiB
        # ring floor applies here too — an env value below it would
        # break the ring's wrap-sentinel framing math, not just be slow
        self.shm_bytes = (env_int("PS_SHM_BYTES", DEFAULT_SHM_BYTES,
                                  lo=1 << 16)
                          if shm_bytes is None else int(shm_bytes))
        # incarnation nonce, sent with every push bucket: a restarted (or
        # reconnected) worker reuses epoch NUMBERS from zero, so the server
        # must never complete a staged epoch of a dead incarnation with
        # buckets from a new one — the nonce makes the two distinguishable.
        # The (nonce, push-seq) pair is also the dedup token: servers skip
        # a push whose seq they already applied for this incarnation, so a
        # push replayed at a promoted replica lands exactly once.
        self._transport_nonce = uuid.uuid4().hex[:12]
        # per-worker push sequence (one per push/push_pull operation, the
        # same number on every shard's message of that operation): the seq
        # half of the dedup token, and — with the fanout set the sparse
        # worker attaches — what the sparse checkpoint drain compares
        # across shards
        self._push_seq = 0
        self.pool_size = max(int(pool_size), 1) if pool_size is not None \
            else (2 if self.bucket_bytes is not None else 1)
        self.transport = TransportStats()
        # reusable receive buffers for the hot pull path (frames whose
        # lifetime this layer controls: pump replies are consumed —
        # decoded + copied out — before the next borrow can alias them)
        self._recv_pool = tv.RecvBufferPool(stats=self.transport)
        self._push_epoch = 0
        self._pull_epoch = 0
        self._pumps: Dict[int, List[ChannelPump]] = {}
        self._bg_pool = None                    # background cycle orchestrator
        self._pending_cycles: List = []         # unobserved background handles
        # gradient compression (ps_tpu/compress): normalized spec dict or
        # None; the compressor holds the per-key policy AND the topk
        # error-feedback residuals, so it must survive reconnects (it is
        # part of _saved_transport_state)
        from ps_tpu.compress import CompressPolicy, GradCompressor, resolve_spec

        self.compress = resolve_spec(compress)
        if self.compress is not None and "seed" not in self.compress:
            # decorrelate int8 stochastic rounding across workers: with a
            # shared default seed every worker would draw the SAME uniform
            # sequence each step, so quantization errors add coherently and
            # the server-side average keeps full single-worker noise
            # variance instead of variance/N
            self.compress = dict(self.compress,
                                 seed=int(getattr(self, "worker", 0)))
        policy = CompressPolicy.from_spec(self.compress)
        self._compressor = (GradCompressor(policy, stats=self.transport)
                            if policy is not None else None)

    def _op(self, name: str, **args) -> "_OpScope":
        """One logical transport op's observability envelope: a root
        trace span (sampled per ``trace_sample`` — the NOOP singleton
        otherwise) AND an always-on latency histogram sample. Use::

            with self._op("push") as sp:
                ...  # sp.wire() propagates the context, None unsampled

        The span/histogram cover the op end to end, failover retries
        included — the latency a training loop actually feels.

        A nested hop — an op issued while a traced request is being
        SERVED on this thread (the aggregator's merged upstream flush,
        its coalesced pull) — parents to the open span instead of
        rooting a new trace: the worker→aggregator→shard chain stays ONE
        trace, and the aggregator's client ops never mint phantom
        \"steps\". Training threads have no open sampled span (the
        program's own ``step.run`` / ``input.*`` spans nest on a stack
        apart, which ``current()`` does not read), so ordinary worker ops
        root exactly as before."""
        parent = obs.tracer().current()
        sp = obs.tracer().span(name, cat="worker", parent=parent)
        if sp:
            sp.set(worker=getattr(self, "worker", 0), **args)
        return _OpScope(self.transport, name, sp)

    @staticmethod
    def _tc_extra(extra: Optional[dict], sp) -> Optional[dict]:
        """Merge a span's wire context into a frame's ``extra`` (returns
        ``extra`` unchanged — possibly None — when the op is unsampled,
        so untraced frames are byte-identical to the pre-obs wire)."""
        wire = sp.wire() if sp else None
        if wire is None:
            return extra
        out = dict(extra or {})
        out[obs.WIRE_KEY] = wire
        return out

    def _bucket_submit_priority(self, b: int) -> int:
        """The pump priority for bucket ``b`` of a plan: the bucket index
        itself (front-of-model first — plans pack keys in sorted order)
        when priority scheduling is on, else a constant 0 (pure FIFO, the
        parity baseline the scheduling tests diff against)."""
        return int(b) if self.bucket_priority else 0

    def _encode_push_tree(self, arrays: Dict[str, np.ndarray]
                          ) -> Tuple[Dict[str, np.ndarray], List[str]]:
        """Apply the compression policy to one server's push payload;
        returns the wire tree and the packed-key list for the header."""
        if self._compressor is None:
            return arrays, []
        return self._compressor.encode_tree(arrays)

    def _pull_compress_spec(self) -> Optional[dict]:
        """The codec spec pulls ask the server to apply to the return path
        (None unless the spec opts in with ``pull: true``). Error-feedback
        state lives at the SENDER, so pull compression is stateless by
        construction — topk would silently drop mass forever and is
        refused at connect time."""
        if not self.compress or not self.compress.get("pull"):
            return None
        return {k: v for k, v in self.compress.items() if k != "pull"}

    def _maybe_upgrade(self, ch):
        """Offer the peer the shared-memory lane for ``ch`` when the
        worker's ``shm`` knob is on; any negotiation failure keeps the
        plain TCP channel (identical semantics, slower bytes)."""
        if not self.shm:
            return ch
        from ps_tpu.control import shm_lane

        up = shm_lane.try_upgrade(ch, getattr(self, "worker", 0),
                                  self.shm_bytes, stats=self.transport)
        up.pool = getattr(ch, "pool", None)
        return up

    def _dial_transport_channel(self, host, port):
        """One data-plane connection: dialed, accounted (per-lane stats +
        receive pool), and shm-upgraded when negotiation succeeds."""
        ch = tv.Channel.connect(host, port)
        ch.stats = self.transport
        ch.pool = self._recv_pool
        try:
            return self._maybe_upgrade(ch)
        except tv.VanError:
            ch.close()
            raise

    def _open_pumps(self, indices) -> None:
        """Dial ``pool_size`` extra transport connections per server; the
        main channels stay free for control traffic (stats, checkpoints)."""
        for i in indices:
            host, port = self._addrs[i]
            # registered before filled so a failed dial mid-pool leaves
            # the already-opened pumps reachable by _close_transport
            self._pumps[i] = pumps = []
            for _ in range(self.pool_size):
                pumps.append(ChannelPump(
                    self._dial_transport_channel(host, port),
                    on_io=self._on_pump_io))

    def _release_frame(self, frame) -> None:
        """Return a fully-consumed reply frame's buffer to the receive
        pool (no-op for frames the pool did not issue)."""
        self._recv_pool.ret(frame)

    def _on_pump_io(self, sent: int, received: int, seconds: float) -> None:
        with self._bytes_lock:
            self.bytes_pushed += sent
            self.bytes_pulled += received
        self.transport.record_bucket(sent + received, seconds)

    def _close_transport(self) -> None:
        """Tear down pumps + orchestrator; safe on a partial construction."""
        if getattr(self, "_bg_pool", None) is not None:
            self._bg_pool.shutdown(wait=False)
            self._bg_pool = None
        for pumps in getattr(self, "_pumps", {}).values():
            for p in pumps:
                p.close()
        self._pumps = {}

    def _bg_executor(self):
        """The (lazily created) single background thread that runs whole
        transport cycles — ONE thread, so cycles serialize per worker and
        the per-worker push/pull order the staleness bound rests on is
        exactly the serial order."""
        if self._bg_pool is None:
            import concurrent.futures

            self._bg_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ps-transport"
            )
        return self._bg_pool

    def _bucket_reply(self, i: int, fut):
        """Resolve one pump future, mapping channel death to the same typed
        failure the serial path raises."""
        try:
            return fut.result()
        except tv.VanError as e:
            host, port = self._addrs[i]
            raise ServerFailureError(
                f"{self._failure_noun} {i} ({host}:{port}) failed "
                f"mid-job: {e}", server=i
            ) from e

    # -- replica sets & live failover (ps_tpu/replica, worker half) -----------

    def _init_failover(self, replica_sets, failover_timeout) -> None:
        """Record each shard's replica set (preferred/primary first) and
        the budget for riding out a promotion. Call after ``_addrs`` is
        set, before dialing."""
        import os

        n = len(self._addrs)
        if replica_sets is None:
            replica_sets = [[tuple(a)] for a in self._addrs]
        if len(replica_sets) != n:
            raise ValueError(
                f"replica_sets names {len(replica_sets)} shards but the "
                f"worker dialed {n}"
            )
        self._replica_sets = [[tuple(a) for a in s] for s in replica_sets]
        for i, s in enumerate(self._replica_sets):
            if tuple(self._addrs[i]) not in s:
                raise ValueError(
                    f"server {i}'s address {self._addrs[i]} is not in its "
                    f"replica set {s}"
                )
        if failover_timeout is None:
            from ps_tpu.config import env_float

            # validated service-level read (pslint PSL406); a negative
            # horizon would make every failover fail instantly
            failover_timeout = env_float("PS_FAILOVER_TIMEOUT_MS",
                                         10_000.0, lo=0.0) / 1e3
        self.failover_timeout = float(failover_timeout)
        self._epochs = [0] * n  # shard-table epochs, learned from HELLO

    def _next_push_seq(self) -> int:
        self._push_seq += 1
        return self._push_seq

    def _reply_error(self, i: int, extra: dict) -> BaseException:
        """The typed error for an ERR reply mid-stream: a 'not serving'
        refusal (an unpromoted backup, a zombie fenced mid-commit) maps to
        the same retryable failure a dead connection raises — the failover
        loop re-routes and replays; a 'moved' refusal (the shard table
        changed under a live rebalance) maps to the table-refresh path;
        anything else is a real application error and surfaces as-is."""
        host, port = self._addrs[i]
        if extra.get("moved"):
            return TableMovedError(
                f"{self._failure_noun} {i} ({host}:{port}) refused: "
                f"{extra.get('error')}", server=i,
                table_epoch=int(extra.get("table_epoch") or 0))
        if extra.get("backup"):
            return ServerFailureError(
                f"{self._failure_noun} {i} ({host}:{port}) is not "
                f"serving: {extra.get('error')}", server=i)
        return RuntimeError(f"server {i} error: {extra.get('error')}")

    def _hello(self, ch) -> dict:
        """One HELLO round trip; typed outcomes for the failover loop."""
        kind, _, _, extra = tv.decode(
            ch.request(tv.encode(tv.HELLO, self.worker, None))
        )
        if kind != tv.OK:
            if extra.get("backup"):
                raise BackupNotServing(extra.get("error"))
            raise ReplicaRejected(f"HELLO refused: {extra.get('error')}")
        return extra

    def _validate_failover_hello(self, i: int, extra: dict) -> Optional[str]:
        """Subclass hook: check a promoted replica's HELLO against what
        the worker validated at connect time (error string, or None)."""
        return None

    def _cycle_replica_set(self, i: int, deadline: float,
                           skip_current: bool = False, validate=None,
                           cause: Optional[BaseException] = None):
        """THE replica-set dial loop (shared by connect-time ``_hello_any``
        and mid-job ``_failover`` so retry/backoff/typed-outcome handling
        cannot drift between them): cycle server ``i``'s candidates until
        one answers HELLO as a serving primary and passes ``validate``
        (unpromoted backups and rejected members keep the loop going), or
        the deadline passes. Returns ``(channel, hello_extra, addr)``; the
        channel is stats-accounted but NOT pooled or shm-upgraded (main
        channels never attach the recv pool — their replies are consumed,
        not returned)."""
        import time

        cands = self._replica_sets[i]
        k = cands.index(tuple(self._addrs[i])) \
            if tuple(self._addrs[i]) in cands else 0
        if skip_current:
            k += 1
        last: Optional[BaseException] = cause
        while True:
            host, port = cands[k % len(cands)]
            k += 1
            try:
                ch = tv.Channel.connect(host, port, timeout_ms=2000,
                                        retries=2, max_wait_s=0.5)
                ch.stats = self.transport
                try:
                    extra = self._hello(ch)
                    if validate is not None:
                        err = validate(extra)
                        if err is not None:
                            raise ReplicaRejected(err)
                except BaseException:
                    ch.close()
                    raise
                return ch, extra, (host, port)
            except (BackupNotServing, ReplicaRejected, tv.VanError,
                    OSError) as e:
                last = e
            if time.monotonic() >= deadline:
                err = ServerFailureError(
                    f"no member of {self._failure_noun} {i}'s replica set "
                    f"{cands} is serving before the failover deadline: "
                    f"{last}", server=i)
                if cause is not None:
                    raise err from cause
                raise err
            time.sleep(0.05)

    def _hello_any(self, i: int):
        """Connect-time dial of server ``i``: its preferred address, or —
        when a replica set is configured — the first member that answers
        HELLO as a serving primary (an unpromoted backup keeps the loop
        cycling within the failover window, so a worker can join a shard
        mid-promotion). Returns ``(channel, hello_extra)``."""
        import time

        cands = getattr(self, "_replica_sets",
                        [[tuple(a)] for a in self._addrs])[i]
        if len(cands) == 1:
            host, port = cands[0]
            ch = tv.Channel.connect(host, port)
            ch.stats = self.transport
            try:
                return ch, self._hello(ch)
            except (BackupNotServing, ReplicaRejected) as e:
                ch.close()
                raise ServerFailureError(
                    f"{self._failure_noun} {i} ({host}:{port}) refused "
                    f"HELLO: {e}", server=i) from e
        deadline = time.monotonic() + self.failover_timeout
        ch, extra, addr = self._cycle_replica_set(i, deadline)
        self._addrs[i] = addr
        return ch, extra

    def _failover(self, i: int, cause: BaseException,
                  deadline: float) -> None:
        """Re-route shard ``i`` to a serving replica: tear down the dead
        transport, cycle the replica set (waiting out an in-flight
        promotion), refuse stale epochs (a zombie old primary must not win
        the race), revalidate the topology, and rebuild pumps. Raises the
        typed failure when nothing serves before ``deadline``."""
        import logging
        import time

        t0 = time.monotonic()
        logging.getLogger(__name__).warning(
            "%s %d (%s:%d) failed; trying its replica set (%d member(s))",
            self._failure_noun, i, *self._addrs[i],
            len(self._replica_sets[i]),
        )
        for p in self._pumps.pop(i, []):
            p.close()
        try:
            self._chs[i].close()
        except Exception:
            pass

        def validate(extra):
            epoch = int(extra.get("epoch") or 0)
            if epoch < self._epochs[i]:
                return (f"stale shard epoch {epoch} < {self._epochs[i]} "
                        f"(zombie old primary?)")
            return self._validate_failover_hello(i, extra)

        # start at the NEXT member: the preferred address just failed
        ch, extra, addr = self._cycle_replica_set(
            i, deadline, skip_current=True, validate=validate, cause=cause)
        try:
            ch = self._maybe_upgrade(ch)
        except tv.VanError as e:
            # the candidate died DURING shm negotiation (a mere refusal
            # falls back to TCP inside try_upgrade): treat it like any
            # dead candidate — the caller's retry loop fails over again
            # within the same deadline
            ch.close()
            raise ServerFailureError(
                f"{self._failure_noun} {i} died during lane negotiation: "
                f"{e}", server=i) from e
        self._chs[i] = ch
        self._addrs[i] = addr
        self._epochs[i] = int(extra.get("epoch") or 0)
        if self.bucket_bytes is not None:
            self._open_pumps([i])
        dt = time.monotonic() - t0
        self.transport.record_failover(dt)
        obs.record_event("failover", shard=i, addr=f"{addr[0]}:{addr[1]}",
                         epoch=self._epochs[i], seconds=round(dt, 4),
                         cause=repr(cause))
        logging.getLogger(__name__).warning(
            "%s %d re-routed to %s:%d (epoch %d) in %.2fs",
            self._failure_noun, i, *addr, self._epochs[i], dt,
        )

    def _on_table_moved(self, err: TableMovedError,
                        deadline: float) -> None:
        """Hook: refresh the shard table and re-route (elastic workers
        override). The default — a worker with no coordinator — cannot
        recover: the topology it was launched with is simply wrong now."""
        raise TableMovedError(
            f"{err} — this worker has no coordinator configured "
            f"(connect with coordinator=... / PS_COORD_URI for elastic "
            f"membership), so it cannot re-fetch the shard table",
            server=err.server, table_epoch=err.table_epoch) from err

    def _on_server_lost(self, err: ServerFailureError,
                        deadline: float) -> None:
        """Hook: a shard failed with NO replica left to cycle to — the
        last chance before the op surfaces the failure. Elastic workers
        override it to re-discover the fleet from their coordinator (a
        replacement member may have taken the dead shard's slot over);
        the default surfaces the failure unchanged."""
        raise err

    def _with_failover(self, fn):
        """Run one transport operation; on a typed server failure, fail
        the shard over to a replica — or, on a stale-table refusal,
        re-fetch the shard table from the coordinator and re-route — and
        retry the WHOLE operation. Safe because operations are
        idempotent: pulls are reads, and every push carries its (nonce,
        seq) dedup token — shards that already applied it (directly, via
        a dead primary's replication stream, or via a migrated key
        range's transferred tokens) ack without re-applying, so the retry
        is exactly-once everywhere. The total window (re-routes included,
        across every shard the retry trips over) is bounded by
        ``failover_timeout``."""
        import time

        try:
            return fn()
        except (ServerFailureError, TableMovedError) as e:
            err = e
        deadline = time.monotonic() + self.failover_timeout
        while True:
            if isinstance(err, TableMovedError):
                # "table moved" ≠ "primary died": the shard is healthy,
                # the ASSIGNMENT changed — re-fetch and re-split instead
                # of cycling its replica set
                self._on_table_moved(err, deadline)
            else:
                i = getattr(err, "server", None)
                if i is None or len(self._replica_sets[i]) <= 1:
                    # no replica to cycle to: the hook's last chance
                    # (elastic workers re-discover the fleet; the
                    # default raises err)
                    self._on_server_lost(err, deadline)
                else:
                    try:
                        self._failover(i, err, deadline)
                    except ServerFailureError as e:
                        # a candidate died mid-adoption (e.g. during lane
                        # negotiation): keep cycling within the SAME
                        # deadline; a deadline-expired failure propagates
                        if time.monotonic() >= deadline:
                            raise
                        err = e
                        continue
            try:
                return fn()
            except (ServerFailureError, TableMovedError) as e:
                if time.monotonic() >= deadline:
                    raise
                err = e

    def _track_pending(self, pending) -> None:
        """Register a background handle for flush(). Handles that resolved
        cleanly — or whose failure was already delivered through a wait() —
        are pruned here, so a long overlap run does not pin one params tree
        per step and a failure surfaces exactly once; failed-but-unobserved
        handles are kept for flush() to surface."""
        self._pending_cycles = [
            c for c in self._pending_cycles
            if not c.done() or (c._exc is not None
                                and not getattr(c, "_observed", False))
        ]
        self._pending_cycles.append(pending)

    def flush(self) -> None:
        """Barrier: wait until every background cycle has fully landed
        (pushes applied server-side AND any pulls merged), re-raising the
        first failure. After flush() the worker is in exactly the state a
        serial caller would be in — this is what preserves sync-SGD
        semantics for trainers that overlap."""
        cycles, self._pending_cycles = self._pending_cycles, []
        err = None
        for c in cycles:
            if getattr(c, "_observed", False):
                continue  # this failure was already delivered via wait()
            try:
                c.wait()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err = err or e
        if err is not None:
            raise err

    def _saved_transport_state(self) -> tuple:
        """Snapshot the identity that must survive a reconnect: cumulative
        wire counters, transport stats, the push/pull epoch streams, and
        the compressor (its topk error-feedback residuals are unsent
        gradient mass — dropping them on a re-dial would lose updates)."""
        return (self.bytes_pushed, self.bytes_pulled, self.collective_bytes,
                self.transport, self._push_epoch, self._pull_epoch,
                self._compressor)

    def _restore_transport_state(self, saved: tuple) -> None:
        (self.bytes_pushed, self.bytes_pulled, self.collective_bytes,
         self.transport, self._push_epoch, self._pull_epoch,
         self._compressor) = saved
        if self._compressor is not None:
            self._compressor.stats = self.transport
        # the re-dial built fresh accounting sinks against the NEW stats
        # object; re-point them at the restored one so lane/pool counters
        # stay continuous across a reconnect
        self._recv_pool.stats = self.transport

        def repoint(ch):
            while ch is not None:
                if getattr(ch, "stats", None) is not None:
                    ch.stats = self.transport
                ch = getattr(ch, "_ch", None)  # shm lane wraps the TCP ch

        for pumps in self._pumps.values():
            for p in pumps:
                repoint(p._ch)
        for ch in getattr(self, "_chs", []):
            repoint(ch)


def make_jit_dc_apply_tree(opt: optax.GradientTransformation):
    """Fused whole-tree async apply: ONE XLA dispatch per push_all.

    The per-key loop unrolls at trace time into a single program (the
    bucketing pass SURVEY.md §3 row 11 reserves for the async host path —
    XLA fuses the per-key DC corrections and updates instead of the host
    dispatching one apply per key). Numerically identical to the per-key
    sequence: keys are independent under per-tensor optimizers, asserted by
    tests/test_async_stress.py.

    ``fn(params, states, grads, stales, lam) -> (params, states)`` over
    ``{key: ...}`` dicts with per-key optimizer states.
    """

    def _apply_dc_tree(params, states, grads, stales, lam):
        new_p, new_s = {}, {}
        for k in params:  # unrolled at trace time
            g = delay_compensate(grads[k], params[k], stales[k], lam)
            updates, s = opt.update(g, states[k], params[k])
            new_p[k] = optax.apply_updates(params[k], updates)
            new_s[k] = s
        return new_p, new_s

    return jax.jit(_apply_dc_tree, static_argnums=(4,))


class PeekMixin:
    """Side-effect-free key read for introspection (KVStore.params()):
    never records async pull snapshots or checks aggregation state."""

    def peek(self, key: str) -> jax.Array:
        if key not in self._params:
            raise KeyError(f"unregistered key {key!r}")
        return self._params[key]


class AsyncStagingMixin:
    """Per-key async pushes stage per WORKER and commit as one fused tree
    apply when that worker's tree completes (SURVEY.md §3 row 11 bucketing:
    a logical push commits as a unit). This makes an N-key per-key push
    sequence cost ONE XLA dispatch instead of N (VERDICT r2 weak #7), and —
    because staging is per worker — the version bump and staleness sample
    are attributed to the worker that actually completed a tree, never to
    whichever worker happened to push last under interleaving (ADVICE r2).

    Liveness: a worker that pushes only a SUBSET of keys commits that
    partial tree the moment it pulls (the pull marks the end of its push
    phase in the PS cycle), so per-key callers that never touch every key
    still make progress — one dispatch per push-pull cycle. Keys are
    independent under per-tensor optimizers, so a partial commit is
    numerically the same as the old immediate per-key applies.

    Engine contract: ``self._staged_async``/``self._params``/``self._state``/
    ``self._stale`` dicts, ``self._jit_apply_dc_tree``, ``self.dc_lambda``,
    ``self.apply_count``, ``self.staleness_hist``, ``self._version`` exist;
    the caller holds the engine lock. Engines may override
    ``_commit_tree_accounting`` for extra per-commit counters.
    """

    def _stage_async_push(self, key, grad, worker) -> None:
        staged = self._staged_async.setdefault(worker, {})
        if key in staged:
            raise RuntimeError(
                f"worker {worker} pushed key {key!r} twice before committing "
                f"— per-key async pushes commit when the full tree is pushed "
                f"or at this worker's next pull (partial tree)"
            )
        staged[key] = grad
        if len(staged) == len(self._params):
            del self._staged_async[worker]
            self._commit_tree(staged, worker)

    def _flush_staged(self, worker) -> None:
        """Commit this worker's staged partial tree, if any (call at the top
        of every async pull, lock held)."""
        staged = self._staged_async.pop(worker, None)
        if staged:
            self._commit_tree(staged, worker)

    def _commit_tree(self, grads_kv, worker) -> None:
        """ONE fused DC apply of a (possibly partial) tree — lock held."""
        sub_p = {k: self._params[k] for k in grads_kv}
        sub_s = {k: self._state[k] for k in grads_kv}
        stales = {
            k: self._stale.get((worker, k), self._params[k]) for k in grads_kv
        }
        new_p, new_s = self._jit_apply_dc_tree(
            sub_p, sub_s, grads_kv, stales, self.dc_lambda
        )
        self._params.update(new_p)
        self._state.update(new_s)
        for k in grads_kv:
            self.apply_count[k] += 1
        self.staleness_hist[self.staleness(worker)] += 1
        self._version += 1
        self._commit_tree_accounting(grads_kv)

    def _commit_tree_accounting(self, grads_kv) -> None:
        """Engine hook: extra counters per committed tree (default none)."""

    def _check_staged_async(self) -> None:
        """Checkpoint guard: staged-but-uncommitted grads would be lost."""
        pending = {w: sorted(kv) for w, kv in self._staged_async.items() if kv}
        if pending:
            raise RuntimeError(
                f"cannot checkpoint mid-push: workers {sorted(pending)} have "
                f"staged but uncommitted per-key async pushes"
            )
