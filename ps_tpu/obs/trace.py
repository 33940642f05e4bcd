"""Distributed tracing for the PS data plane.

One worker push is a chain of work in three processes: the worker encodes
and sends, the primary stages/applies and replicates, the backup applies
and acks. SURVEY.md §6 names tracing a first-class build target; this
module is the minimal production shape of it:

- a :class:`TraceContext` ``(trace_id, span_id)`` travels in the van
  frame's ``extra`` header (key ``"tc"``) on push/pull/bucket/replica
  kinds, so each hop parents its span to the hop before it;
- spans land in a per-process bounded ring (the RingLog discipline — a
  long-lived server must never hold O(requests) trace memory);
- :meth:`Tracer.export_chrome` writes Chrome-trace-event JSON that
  Perfetto / ``chrome://tracing`` opens directly, and
  :func:`merge_chrome` concatenates several processes' exports into ONE
  timeline (after :class:`~ps_tpu.obs.clock.ClockSync` offsets align
  their wall clocks).

Sampling is decided ONCE, at the root span (the worker op): the
``trace_sample`` knob (env ``PS_TRACE_SAMPLE``, default 0) gates root
creation, and every downstream hop simply follows the header — an
unsampled op costs one dict lookup per hop and nothing else, so the off
path stays off the profile.

The program's own path is the exception: the few spans a training step
opens around the fused step and the input prefetch, the dozen a process
opens while it sets up, and jax's own trace / lower / compile events
(:mod:`ps_tpu.obs.phases`: ``HOST_SPANS``, ``SETUP_SPANS``,
``COMPILE_SPANS``) are recorded by :meth:`Tracer.program_span` and
:meth:`Tracer.record_program` into the same ring with no sampling decision,
because whoever reads them (the benchmark's ``host.*`` and ``setup.*``
metrics) cannot switch tracing on. Every span keeps its start on
``time.perf_counter`` (``Span.t0``) beside the wall clock, so it can be laid
on a device trace.

A span that somebody else timed (:mod:`ps_tpu.obs.compiles` hears of a
compile when it is over, on the thread that asked for it) is recorded from
its start and its length by :meth:`Tracer.record_program`, as a child of
the program span that is open **on the calling thread, on the program
spans' own stack** (never the sampled spans' stack): step 0's
``step.launch`` gets its ``compile.*`` children with no span opened inside
the step.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import List, NamedTuple, Optional

__all__ = [
    "TraceContext", "Span", "Tracer", "NOOP", "WIRE_KEY",
    "merge_chrome",
]

#: the van-frame ``extra`` key a propagated context rides under:
#: ``extra["tc"] == [trace_id, parent_span_id]``
WIRE_KEY = "tc"


class TraceContext(NamedTuple):
    """What a hop needs to parent its span to the hop before it."""

    trace_id: str
    span_id: str


def from_wire(extra: Optional[dict]) -> Optional[TraceContext]:
    """The propagated context of a received frame, or None (unsampled)."""
    tc = (extra or {}).get(WIRE_KEY)
    if not tc:
        return None
    try:
        return TraceContext(str(tc[0]), str(tc[1]))
    except (IndexError, TypeError):
        return None


class _NoopSpan:
    """The unsampled span: every method a real span has, all free.

    A singleton, so ``tracer.span(...)`` on the off path allocates
    nothing and the call sites need no ``if sampled`` branches."""

    __slots__ = ()

    def ctx(self) -> Optional[TraceContext]:
        return None

    def wire(self) -> Optional[list]:
        return None

    def set(self, **args) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __bool__(self) -> bool:
        return False


NOOP = _NoopSpan()


class Span:
    """One timed unit of work, parented into a trace.

    Use as a context manager; the span records wall-clock start
    (``time.time()`` µs — alignable across processes by a clock offset)
    and a monotonic duration, and lands in its tracer's ring on exit."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "args", "ts_us", "dur_us", "t0", "_tracer", "_tid")
    #: the thread-local stack of open spans that this kind nests on
    _STACK = "stack"

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 trace_id: str, span_id: str, parent_id: Optional[str]):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.args: dict = {}
        self.ts_us = 0.0
        self.dur_us = 0.0
        #: start on ``time.perf_counter`` (seconds): the clock a device
        #: trace is tied to, where ``ts_us`` is the wall clock
        self.t0 = 0.0
        self._tracer = tracer
        self._tid = 0

    def ctx(self) -> TraceContext:
        """The context downstream hops parent to."""
        return TraceContext(self.trace_id, self.span_id)

    def wire(self) -> list:
        """The ``extra[WIRE_KEY]`` value that propagates this span."""
        return [self.trace_id, self.span_id]

    def set(self, **args) -> "Span":
        """Attach key=value annotations (worker id, byte counts, ...)."""
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        self.ts_us = time.time() * 1e6
        self.t0 = time.perf_counter()
        self._tid = threading.get_ident()
        self._tracer._push_current(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.dur_us = (time.perf_counter() - self.t0) * 1e6
        if exc_type is not None:
            self.args.setdefault("error", repr(exc))
        self._tracer._pop_current(self)
        self._tracer._record(self)

    def __bool__(self) -> bool:
        return True


def _new_id() -> str:
    return os.urandom(8).hex()


class _ProgramSpan(Span):
    """A span of :meth:`Tracer.program_span`: off the sampled spans' stack."""

    __slots__ = ()
    _STACK = "program"


class Tracer:
    """Per-process span factory + bounded ring + exporter.

    ``sample`` gates ROOT spans only (a span created with an explicit
    ``parent`` context is always recorded — the root already paid for the
    trace). ``clock_offset_us`` is added to every exported timestamp so
    several processes' dumps merge onto one timeline (estimated by
    :class:`~ps_tpu.obs.clock.ClockSync` against a reference server)."""

    def __init__(self, service: str = "ps", capacity: int = 8192,
                 sample: float = 0.0):
        import collections

        import itertools

        self.service = service
        self.sample = float(sample)
        self._program_ids = itertools.count(1)
        self.clock_offset_us = 0.0
        self.pid = os.getpid()
        self._ring = collections.deque(maxlen=int(capacity))
        self._tls = threading.local()
        self.dropped = 0  # roots not sampled are NOT drops; ring evictions are
        self._total = 0

    # -- span creation ---------------------------------------------------------

    def span(self, name: str, cat: str = "ps",
             parent: Optional[TraceContext] = None):
        """A new span: child of ``parent`` when given, else a root that is
        sampled with probability ``sample`` (NOOP otherwise)."""
        if parent is None:
            if self.sample <= 0.0:
                return NOOP
            if self.sample < 1.0:
                import random

                if random.random() >= self.sample:
                    return NOOP
            return Span(self, name, cat, _new_id(), _new_id(), None)
        return Span(self, name, cat, parent.trace_id, _new_id(),
                    parent.span_id)

    def child(self, name: str, cat: str = "ps"):
        """A span under the CURRENT thread's open span — NOOP when no
        traced work is in progress (never a fresh sampling decision, so
        internal waits can't spawn orphan root traces)."""
        cur = self.current()
        return self.span(name, cat, parent=cur) if cur is not None else NOOP

    def program_span(self, name: str, cat: str = "program", **args) -> Span:
        """A span that is ALWAYS recorded: no sampling decision, ids from a
        process-local counter. For the few spans a step that the program
        puts on its own hot path and the dozen of its set-up
        (``ps_tpu.obs.phases``: ``HOST_SPANS``, ``SETUP_SPANS``), which a
        benchmark reads without being able to switch tracing on. Child
        of this thread's open program span, if any; ``args`` (``step=n``,
        ``seq=n``) are the identifier the spans of one step share.

        Program spans nest on a stack of their own: :meth:`current` and
        :meth:`child` never see them, so a worker op issued inside
        ``step.run`` still makes its own sampling decision and an unsampled
        one puts no context on the wire."""
        sid = f"p{next(self._program_ids):x}"
        stack = getattr(self._tls, "program", None)
        if stack:
            up = stack[-1]
            sp = _ProgramSpan(self, name, cat, up.trace_id, sid, up.span_id)
        else:
            sp = _ProgramSpan(self, name, cat, sid, sid, None)
        sp.args = args
        return sp

    def record_program(self, name: str, t0: float, dur_s: float,
                       **args) -> Span:
        """A program span from its start on ``time.perf_counter`` and its
        length in seconds, for an interval that was not timed by a ``with``
        of this tracer: jax's monitoring events name a duration once it is
        over, and the package's import starts before any tracer exists.
        Recorded now; child of this thread's open program span, like
        :meth:`program_span`."""
        sp = self.program_span(name, **args)
        sp.ts_us = (time.time() - (time.perf_counter() - t0)) * 1e6
        sp.dur_us = max(dur_s, 0.0) * 1e6
        sp.t0 = t0
        sp._tid = threading.get_ident()
        self._record(sp)
        return sp

    def open_program_spans(self) -> tuple:
        """This thread's open program spans, outermost first."""
        return tuple(getattr(self._tls, "program", None) or ())

    def current(self) -> Optional[TraceContext]:
        """The innermost open span's context on this thread, if any."""
        stack = getattr(self._tls, "stack", None)
        return stack[-1].ctx() if stack else None

    def _push_current(self, span: Span) -> None:
        stack = getattr(self._tls, span._STACK, None)
        if stack is None:
            stack = []
            setattr(self._tls, span._STACK, stack)
        stack.append(span)

    def _pop_current(self, span: Span) -> None:
        stack = getattr(self._tls, span._STACK, None)
        if stack and stack[-1] is span:
            stack.pop()
        elif stack and span in stack:  # exited out of order: still remove
            stack.remove(span)

    def _record(self, span: Span) -> None:
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
        self._ring.append(span)
        self._total += 1

    def record_external(self, name: str, cat: str, trace_id: str,
                        parent_id: Optional[str], ts_us: float,
                        dur_us: float, t0: float = 0.0, **args) -> "Span":
        """Record a span whose timing happened OUTSIDE Python — e.g. the
        native event loop's slow-frame capture, whose per-stage stamps
        were taken with no interpreter anywhere near the work. The span
        joins the given trace (always recorded: the propagated context
        means the root already paid the sampling decision) with explicit
        wall-clock start and duration instead of the context-manager
        timing. ``t0`` is the same start on ``time.perf_counter``, where
        the caller has it (0.0: not known, as a span that never began)."""
        sp = Span(self, name, cat, str(trace_id), _new_id(),
                  None if parent_id is None else str(parent_id))
        sp.ts_us = float(ts_us)
        sp.dur_us = max(float(dur_us), 0.0)
        sp.t0 = float(t0)
        sp._tid = threading.get_ident()
        sp.args.update(args)
        self._record(sp)
        return sp

    # -- introspection / export ------------------------------------------------

    def spans(self) -> List[Span]:
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0

    def chrome_events(self) -> List[dict]:
        """Chrome-trace ``X`` events (+ a process_name metadata record),
        timestamps shifted by ``clock_offset_us``."""
        events: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": self.pid, "tid": 0,
            "args": {"name": self.service},
        }]
        for s in self.spans():
            events.append({
                "ph": "X", "name": s.name, "cat": s.cat,
                "pid": self.pid, "tid": s._tid,
                "ts": s.ts_us + self.clock_offset_us,
                "dur": max(s.dur_us, 0.001),
                "args": {"trace_id": s.trace_id, "span_id": s.span_id,
                         "parent_id": s.parent_id, **s.args},
            })
        return events

    def export_chrome(self, path: Optional[str] = None) -> str:
        """Write the ring as Perfetto-openable JSON; returns the path
        (default: ``<trace_dir>/trace-<service>-<pid>.json``)."""
        if path is None:
            from ps_tpu.config import env_str

            base = env_str("PS_TRACE_DIR", ".")
            path = os.path.join(base, f"trace-{self.service}-{self.pid}.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events()}, f)
        return path


def merge_chrome(sources, path: str) -> str:
    """Concatenate several Chrome-trace exports (file paths, event lists,
    or ``{"traceEvents": ...}`` dicts) into one file — the whole-cluster
    timeline. Each process's export should already carry its clock offset
    (applied at export time); this is a pure concatenation."""
    events: List[dict] = []
    for src in sources:
        if isinstance(src, str):
            with open(src) as f:
                src = json.load(f)
        if isinstance(src, dict):
            src = src.get("traceEvents", [])
        events.extend(src)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path
