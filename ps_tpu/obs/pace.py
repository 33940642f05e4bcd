"""The step's pace: at every launch, whether the chip was waiting for it.

When the fused step's wrapper (``kv/fused.py::run``) makes its jitted call
it holds the losses of the steps it launched before, and a loss is ready
when its program has finished. :class:`StepPace` keeps those not yet seen
ready and is asked twice a step: :meth:`~StepPace.launching` right before
the call, for what ``step.launch`` carries as arguments, and
:meth:`~StepPace.ran` as the last line of ``step.run``. Nothing here waits,
no thread, no callback in the compiled program; always on, like the two
spans, so a run without a profiler has it.

- ``in_flight``: the wrapper's own steps still running or queued at the
  launch. The steps finish in order, so the account asks from the oldest
  and stops at the first that is not ready: one ``is_ready()`` a step, and
  one more for each step that finished since the last launch.
- a *drained* launch: ``in_flight == 0`` on any launch but the first. All
  that this wrapper had given the chip was finished before this step was
  handed over: the chip had nothing of this job to run. Its
  ``drained_at_most_ms`` is the time since the launch before it began, when
  that step cannot have been finished: no less than what the chip waited up
  to this launch, and stated as a bound. (A job that the host bounds drains
  every step, so no flight event: it would turn the black box over.)
- ``slow_step``: a ``step.run`` of more than ``SLOW_FACTOR`` times the median
  of the ``MEDIAN_OF`` before it, and of more than ``SLOW_FLOOR_S``, once
  ``SLOW_AFTER`` steps have been seen, and never a step in which a compile
  landed (that one is ``recompile``'s, ``obs/compiles.py``). What happens
  inside the wrapper: a launch that blocks on a full runtime queue, a
  collector's pause, the health check. A caller that comes late shows as the
  next launch's ``in_flight`` and ``drained_at_most_ms``, not here.

Names in :mod:`ps_tpu.obs.phases`; ``benchmark/layer_metrics/pace.py`` reads
the span arguments and applies the same rule to the ring's ``step.run``
spans (own copy of names and constants; ``tests/test_phases.py`` holds them
equal).
"""

from __future__ import annotations

import collections
import statistics
import time

from ps_tpu.obs import compiles, phases
from ps_tpu.obs.metrics import default_registry

__all__ = ["StepPace", "METERS", "MAX_IN_FLIGHT", "MEDIAN_OF", "SLOW_AFTER",
           "SLOW_FACTOR", "SLOW_FLOOR_S"]

#: losses kept at most: a wrapper that far ahead of the chip reads 64
MAX_IN_FLIGHT = 64
#: the ``slow_step`` rule
SLOW_FACTOR = 8.0
SLOW_FLOOR_S = 1e-3
MEDIAN_OF = 64
SLOW_AFTER = 8

_reg = default_registry()
#: held here: the registry keeps its instruments by weak reference. One of
#: each a process, whichever wrapper launched last
METERS = {
    "in_flight": _reg.gauge(
        phases.STEP_IN_FLIGHT,
        "steps launched and not yet finished, at the last launch"),
    "drained": _reg.counter(
        phases.STEP_DRAINED_LAUNCHES,
        "launches that found every earlier step finished"),
    "slow": _reg.counter(
        phases.STEP_SLOW, "step.run spans over 8x their running median"),
}


def _compiles() -> int:
    return compiles.COUNTERS["compiles"].value


def _record_event(kind: str, **fields) -> None:
    from ps_tpu import obs

    obs.record_event(kind, **fields)


def _finished(loss) -> bool:
    try:
        return loss.is_ready()
    except RuntimeError:  # deleted by its holder: nothing left to wait for
        return True


class StepPace:
    """The account of one step wrapper. ``clock``, ``compiles`` (programs
    compiled so far) and ``record_event`` are a test's to replace."""

    def __init__(self, clock=time.perf_counter, compiles=_compiles,
                 record_event=_record_event):
        self._clock = clock
        self._compiles = compiles
        self._record_event = record_event
        self._flying = collections.deque(maxlen=MAX_IN_FLIGHT)
        self._run_s = collections.deque(maxlen=MEDIAN_OF)
        self._launched_at = None    # the last launching(), on the clock
        self._in_flight = 0
        self._compiles_before = 0

    def launching(self) -> dict:
        """Right before the jitted call: the arguments of its
        ``step.launch``."""
        now = self._clock()
        flying = self._flying
        while flying and _finished(flying[0]):
            flying.popleft()
        self._in_flight = k = len(flying)
        METERS["in_flight"].set(k)
        args = {phases.IN_FLIGHT: k}
        if not k and self._launched_at is not None:
            METERS["drained"].inc()
            args[phases.DRAINED_AT_MOST_MS] = 1e3 * (now - self._launched_at)
        self._launched_at = now
        self._compiles_before = self._compiles()
        return args

    def ran(self, loss, step: int, t0: float) -> None:
        """The last line of a ``step.run`` that began at ``t0`` on the clock
        and launched ``loss``'s program."""
        self._flying.append(loss)
        seconds = self._clock() - t0
        if self._compiles() != self._compiles_before:
            return
        seen = self._run_s
        if seconds > SLOW_FLOOR_S and len(seen) >= SLOW_AFTER:
            median = statistics.median(seen)
            if seconds > SLOW_FACTOR * median:
                METERS["slow"].inc()
                self._record_event(
                    phases.SLOW_STEP, step=step, ms=round(1e3 * seconds, 3),
                    median_ms=round(1e3 * median, 3),
                    in_flight=self._in_flight)
        seen.append(seconds)
