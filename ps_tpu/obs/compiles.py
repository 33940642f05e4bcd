"""The compiler's own events as program spans, counters and a flight event.

jax reports through ``jax.monitoring`` how long it traced, lowered and
compiled (or loaded from the persistent cache) each program, on the thread
that asked for it and once the interval is over. One listener, registered
when this module is imported (the package's import does that), turns each into a span of
:mod:`ps_tpu.obs.phases` ``COMPILE_SPANS`` in the process tracer's ring
(:meth:`~ps_tpu.obs.trace.Tracer.record_program`: start = now - duration,
child of the program span open on the calling thread), so that step 0's
``step.launch`` and the set-up spans get their children with no span opened
on a step's path: once every shape is warm the listener never fires.

jax traces a jitted function inside the function that calls it (``matmul``
inside ``my_step``; an optimizer's ``add`` and ``multiply`` once a leaf: a
five-layer step is thousands of them, most under 10 us, and the ring holds
8,192 spans). A trace that ends inside another adds nothing to the union a
reader takes, so only a thread's outermost trace becomes a span: jax
announces each interval's start too (a scalar event of the same name), and
the listener counts the depth a thread.

What an operator reads when the ring has long turned over comes from the
same calls: the four ``ps_compile_*`` counters of
:func:`~ps_tpu.obs.metrics.default_registry`, and a flight-recorder event
``recompile`` (``step``, ``fun``, ``seconds``, ``cache``) whenever a backend
compile lands inside a ``step.run`` past step 0: a retrace in the middle of
a job stalls it for as long as the compile takes, and this names the step
that paid.
"""

from __future__ import annotations

import threading
import time

import jax.monitoring

from ps_tpu.obs import phases
from ps_tpu.obs.metrics import default_registry

__all__ = ["COUNTERS"]

# jax's monitoring event names (jax/_src/dispatch.py, jax/_src/compiler.py)
_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": phases.COMPILE_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": phases.COMPILE_LOWER,
    "/jax/core/compile/backend_compile_duration": phases.COMPILE_BACKEND,
}
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

_reg = default_registry()
#: held here: the registry keeps its instruments by weak reference
COUNTERS = {
    "compiles": _reg.counter(
        "ps_compile_total", "programs compiled or loaded from the cache"),
    "seconds": _reg.counter(
        "ps_compile_seconds_total", "seconds compiling or loading"),
    "hit": _reg.counter(
        "ps_compile_cache_hits_total", "persistent compile cache hits"),
    "miss": _reg.counter(
        "ps_compile_cache_misses_total", "persistent compile cache misses"),
}

#: per thread: ``depth``, the traces that have begun and not ended, and what
#: the cache said about the compile that is running, ``cache`` ("hit" |
#: "miss") and ``load`` (start, seconds) of a retrieval, both taken by the
#: ``backend_compile_duration`` that follows them
_pending = threading.local()


def _on_start(event: str, _value, **_) -> None:
    if event == _TRACE_EVENT:
        _pending.depth = getattr(_pending, "depth", 0) + 1


def _on_event(event: str, **_) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is not None:
        _pending.cache = cache
        COUNTERS[cache].inc()


def _on_duration(event: str, seconds: float, **kw) -> None:
    name = _SPAN_OF.get(event)
    if name is None:
        if event == _CACHE_LOAD_EVENT:
            _pending.load = (time.perf_counter() - seconds, seconds)
        return
    if event == _TRACE_EVENT:
        _pending.depth = depth = max(getattr(_pending, "depth", 0) - 1, 0)
        if depth:  # ended inside another trace of this thread
            return
    from ps_tpu import obs

    tracer = obs.tracer()
    args = {"fun": kw.get("fun_name", "")}
    backend = name == phases.COMPILE_BACKEND
    cache = _pending.__dict__.pop("cache", None) if backend else None
    if cache is not None:
        args["cache"] = cache
    tracer.record_program(name, time.perf_counter() - seconds, seconds,
                          **args)
    if not backend:
        return
    load = _pending.__dict__.pop("load", None)
    if load is not None:
        tracer.record_program(phases.COMPILE_CACHE_LOAD, *load,
                              fun=args["fun"])
    COUNTERS["compiles"].inc()
    COUNTERS["seconds"].inc(seconds)
    for span in tracer.open_program_spans():
        if span.name == phases.STEP_RUN and span.args.get("step", 0) >= 1:
            obs.record_event("recompile", step=span.args["step"],
                             fun=args["fun"], seconds=round(seconds, 6),
                             cache=cache)
            break


# once a process: a module is imported once, and the package imports this one
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
