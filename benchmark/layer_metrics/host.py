"""The host path into the fused step, from inside the program.

The program records a span at each boundary where host work happens
(``ps_tpu/obs/phases.py::HOST_SPANS``) into its own tracer's ring
(``ps_tpu.obs.tracer()``: bounded, always on for these spans, start on
``time.perf_counter`` like the loop's spans). This file reads the ring after
the run: medians over the spans that started inside the measured window, so
over the same steps as ``loop.dispatch_ms`` and ``input.wait_share``, with
warm-up, the traced steps and the steps past the window left out. The loop's
``loop.dispatch`` span contains ``step.run``, and its ``input.next`` span
contains ``input.source_wait`` and ``input.place``: these metrics split those
two by duration. ``input.produce`` runs in the producer thread, beside the
loop: against the step's period it says how far the producer is from
bounding the input path (``input.source_wait`` only shows it once it does).

A metric is left out where the program never opened its span: a cell whose
input does not go through ``threaded_source`` has no ``input.source_wait``
and no ``input.produce``, and a program from before these spans has none at
all.
"""

from __future__ import annotations

import statistics
import sys
from typing import Iterable, Optional, Tuple

# The names of ps_tpu/obs/phases.py that this file looks up, copied: the
# yardstick also reads trees that lack that file. tests/test_phases.py holds
# them equal.
STEP_RUN = "step.run"
STEP_LAUNCH = "step.launch"
INPUT_PLACE = "input.place"
INPUT_SOURCE_WAIT = "input.source_wait"
INPUT_PRODUCE = "input.produce"
HOST_SPANS = (STEP_RUN, STEP_LAUNCH, INPUT_PLACE, INPUT_SOURCE_WAIT,
              INPUT_PRODUCE)


def span_metrics(spans: Iterable,
                 window: Optional[Tuple[float, float]] = None) -> dict:
    """The metrics of this file from the spans of a ring: objects with
    ``name``, ``t0``, ``dur_us``, ``span_id``, ``parent_id`` and ``args``.
    ``window`` is ``(start, end)`` on ``perf_counter``: only spans that
    started in it count (``None``: every span)."""
    by_name = {n: [] for n in HOST_SPANS}
    for s in spans:
        if s.name in by_name and (
                window is None or window[0] <= s.t0 < window[1]):
            by_name[s.name].append(s)
    out = {}

    def median_ms(found):
        return 1e-3 * statistics.median(s.dur_us for s in found)

    # the wrapper's own Python is what of step.run is not its launch; as a
    # difference of medians, so that the two add up to the median step.run,
    # which lies inside the loop's dispatch span
    launches = {s.parent_id: s for s in by_name[STEP_LAUNCH]}
    runs = [s for s in by_name[STEP_RUN] if s.span_id in launches]
    if runs:
        launch_ms = median_ms(launches[s.span_id] for s in runs)
        out["host.step_launch_ms"] = launch_ms
        out["host.step_wrap_ms"] = median_ms(runs) - launch_ms
    if by_name[INPUT_PLACE]:
        out["host.input_place_ms"] = median_ms(by_name[INPUT_PLACE])
        out["host.input_mb_per_step"] = 1e-6 * statistics.median(
            s.args.get("nbytes", 0) for s in by_name[INPUT_PLACE])
    if by_name[INPUT_SOURCE_WAIT]:
        out["host.input_source_wait_ms"] = median_ms(
            by_name[INPUT_SOURCE_WAIT])
    if by_name[INPUT_PRODUCE]:
        out["host.input_produce_ms"] = median_ms(by_name[INPUT_PRODUCE])
    return out


def window_of(r: dict) -> Optional[Tuple[float, float]]:
    """The measured window on ``perf_counter``. ``r`` gives its place only
    from the run's start (``setup_s``, ``window_s``), and the run's start is
    the entry point's ``_T_START``; ``None`` where this is not run under
    ``benchmark/run.py``."""
    start = getattr(sys.modules.get("__main__"), "_T_START", None)
    if start is None or "setup_s" not in r or "window_s" not in r:
        return None
    return start + r["setup_s"], start + r["setup_s"] + r["window_s"]


def read(r: dict) -> dict:
    from ps_tpu import obs

    spans = obs.tracer().spans()
    window = window_of(r)
    out = span_metrics(spans, window)
    if window is not None and not out:
        # no span of the program started in the window (a rehearsal's
        # window can be shorter than one step): the whole ring, and say so
        window, out = None, span_metrics(spans)
    # for the reader of the run: the loop's spans that contain these
    outer = {name: 1e3 * statistics.median(durations)
             for name, durations in r.get("spans", {}).items() if durations}
    print(f"host: medians of the loop's own spans, ms: {outer}; of the "
          f"program's, over "
          f"{'the measured window' if window else 'the whole ring'}: {out}",
          file=sys.stderr)
    return out
