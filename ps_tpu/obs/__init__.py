"""Observability for the PS data plane (SURVEY.md §6 metrics/tracing).

Three layers, each usable alone, wired through every transport hot path:

- **Distributed tracing** (:mod:`ps_tpu.obs.trace`): a ``TraceContext``
  propagated in the van frame's ``extra`` header follows one worker push
  from the worker op through the primary's apply to the backup's ack;
  spans land in a bounded per-process ring and export as Chrome-trace /
  Perfetto JSON, alignable across processes via
  :class:`~ps_tpu.obs.clock.ClockSync`. Off by default
  (``trace_sample`` / ``PS_TRACE_SAMPLE`` = 0): the unsampled path is a
  no-op singleton and one dict lookup per hop. The fused steps and the
  input prefetch record their few spans a step always, and a process the
  dozen of its set-up (``Tracer.program_span``; names in
  :mod:`ps_tpu.obs.phases`, which also names the ``jax.named_scope`` phases
  inside the device program); jax's own trace / lower / compile events
  become their children, four ``ps_compile_*`` counters and the
  ``recompile`` flight event (:mod:`ps_tpu.obs.compiles`); each
  ``step.launch`` says how many of its wrapper's steps the chip still held
  (:mod:`ps_tpu.obs.pace`: ``ps_step_*`` and the ``slow_step`` event).
- **Metrics** (:mod:`ps_tpu.obs.metrics`): counters, gauges, and
  log2-bucket latency histograms (p50/p99/p999) that ``TransportStats``
  feeds; exported in the extended STATS frame, rendered live by
  ``tools/ps_top.py``, and served as Prometheus text on the opt-in
  ``/metrics`` endpoint (``metrics_port`` / ``PS_METRICS_PORT``).
- **Flight recorder** (:mod:`ps_tpu.obs.flight`): a bounded ring of
  typed events (failover, degrade, stale epoch, shm spill, reconnect,
  self-fence, promotion, peer death) dumped to JSONL on unhandled
  VanError, SIGUSR2, or on demand — the black box of a 3am shard death.
- **Fleet telemetry** (:mod:`ps_tpu.obs.tsdb` / ``collector`` /
  ``breakdown`` / ``straggler`` / ``slo``, README "Fleet telemetry"):
  members ship delta-encoded metric snapshots — raw log2 histogram
  buckets, losslessly mergeable — on the coordinator report cadence;
  the coordinator's bounded time-series ring answers fleet-quantile /
  breakdown queries (``COORD_TELEMETRY``, ``ps_top --fleet``,
  ``ps_doctor``) and runs straggler + SLO signals.

This module owns the per-process singletons; ``tracer()`` and
``flight()`` configure themselves from the environment on first use, and
:func:`configure` overrides programmatically (what ``Config`` carries).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ps_tpu.obs import compiles as compiles  # noqa: F401 — re-export the module
from ps_tpu.obs import trace as trace  # noqa: F401 — re-export the module
from ps_tpu.obs.breakdown import PHASES, TraceBreakdown, breakdown
from ps_tpu.obs.clock import ClockSync
from ps_tpu.obs.collector import DeltaDecoder, DeltaEncoder, collect_telemetry
from ps_tpu.obs.flight import FlightRecorder
from ps_tpu.obs.slo import SloEvaluator, SloRule, parse_rules
from ps_tpu.obs.straggler import StragglerDetector
from ps_tpu.obs.tsdb import FleetTSDB
from ps_tpu.obs.http import (
    MetricsServer,
    start_metrics_server,
    stop_metrics_server,
)
from ps_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
)
from ps_tpu.obs.trace import (
    NOOP,
    WIRE_KEY,
    Span,
    TraceContext,
    Tracer,
    from_wire,
    merge_chrome,
)

__all__ = [
    "TraceContext", "Tracer", "Span", "NOOP", "WIRE_KEY", "from_wire",
    "merge_chrome", "tracer",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "default_registry",
    "MetricsServer", "start_metrics_server", "stop_metrics_server",
    "FlightRecorder", "flight", "record_event",
    "ClockSync", "configure",
    # fleet telemetry (the coordinator-hosted aggregation pipeline)
    "FleetTSDB", "DeltaEncoder", "DeltaDecoder", "collect_telemetry",
    "StragglerDetector", "SloEvaluator", "SloRule", "parse_rules",
    "breakdown", "TraceBreakdown", "PHASES",
]

_lock = threading.Lock()
_tracer: Optional[Tracer] = None
_flight: Optional[FlightRecorder] = None


def tracer() -> Tracer:
    """The process tracer (created on first use; ``PS_TRACE_SAMPLE``
    seeds its sampling rate, 0 = off)."""
    global _tracer
    if _tracer is None:
        with _lock:
            if _tracer is None:
                from ps_tpu.config import env_float

                # strict=False: a garbage PS_TRACE_SAMPLE must never
                # take a service down with its observability (pslint
                # PSL406 — validated, warn-and-default on parse error)
                sample = env_float("PS_TRACE_SAMPLE", 0.0, lo=0.0,
                                   hi=1.0, strict=False)
                _tracer = Tracer(service=f"pid{os.getpid()}", sample=sample)
    return _tracer


def flight() -> FlightRecorder:
    """The process flight recorder (created on first use with its dump
    hooks armed; ``PS_FLIGHT_EVENTS`` sizes the ring)."""
    global _flight
    if _flight is None:
        with _lock:
            if _flight is None:
                from ps_tpu.config import env_int

                # strict=False, same contract as the tracer's knob
                cap = env_int("PS_FLIGHT_EVENTS", 4096, lo=1,
                              strict=False)
                fr = FlightRecorder(capacity=cap,
                                    service=f"pid{os.getpid()}")
                fr.install()
                _flight = fr
    return _flight


def record_event(kind: str, **fields) -> None:
    """Record one typed event into the process flight recorder — THE call
    every failure-path site uses (never raises)."""
    flight().record(kind, **fields)


def configure(sample: Optional[float] = None,
              trace_dir: Optional[str] = None,
              flight_events: Optional[int] = None,
              metrics_port: Optional[int] = None,
              service: Optional[str] = None) -> None:
    """Override the env-seeded defaults programmatically (what a launcher
    does with its :class:`~ps_tpu.config.Config` knobs). Only the
    arguments given change; ``metrics_port`` starts the /metrics endpoint
    immediately."""
    t = tracer()
    f = flight()
    if sample is not None:
        t.sample = float(sample)
    if service is not None:
        t.service = service
        f.service = service
    if trace_dir is not None:
        os.environ["PS_TRACE_DIR"] = trace_dir
        f.dir = trace_dir
    if flight_events is not None:
        import collections

        with f._lock:
            f.capacity = int(flight_events)
            f._ring = collections.deque(f._ring, maxlen=f.capacity)
    if metrics_port is not None:
        start_metrics_server(metrics_port)
