"""The step's pace, from inside the program: whether the chip was waiting for
a launch, for every step of the untraced window.

The fused step's wrapper puts on each ``step.launch`` span how many of its
own steps were still in flight when it made the jitted call (``in_flight``;
a loss is ready when its program has finished) and, where there was none on
a launch that is not its first, ``drained_at_most_ms``: the chip had nothing
of this job to run, for no longer than that
(``ps_tpu/obs/pace.py``; names in ``ps_tpu/obs/phases.py``). This file reads
the ring after the run, over the launches that started in the measured
window (``host.window_of``) but the first of them, and ``r["block_s"]``. The
loop enters its window behind a wait of its own (``harness/loop.py::_blocks``:
"on entry the device is idle and every loss so far is ready"), so the
window's first launch is drained by the benchmark, not by the program:

- ``pace.queue_depth_min``: the least ``in_flight`` over the window's
  launches; ``pace.drained_launches``: those of them that were drained;
- ``pace.slow_steps``: the window's ``step.run`` spans over the program's
  ``slow_step`` rule, applied here to the ring's spans in their order (more
  than 8 times the median of the 64 before it and than 1 ms, once eight were
  seen, never a step in which a compile ended), so that the ring and the
  flight event agree;
- ``pace.stall_host_share`` and ``pace.stall_device_share`` split what
  ``loop.stall_share`` reads from outside. A block is long when it lasts
  more than 1.25 median blocks; its excess over the median goes to the host
  if a launch that started in the block's interval was drained (the chip ran
  dry: the host launched late), else to the device's side: the chip was fed
  and the block was still long. That is either the device (or its runtime)
  answering late, or the host waking late from ``block_until_ready`` with the
  queue still full, which the next block gives back by being short: stderr
  says which, for each long block. Percent of the window, so both are 0 in a
  run without a long block.

On a program whose ``step.launch`` carries no ``in_flight`` nothing here finds
anything to read, and the metrics are left out.
"""

from __future__ import annotations

import bisect
import itertools
import statistics
import sys
from collections import deque
from typing import Iterable, Optional, Sequence, Tuple

from benchmark.layer_metrics import host

# The names and the rule of ps_tpu/obs/phases.py and ps_tpu/obs/pace.py that
# this file looks up, copied: the yardstick also reads trees that lack them.
# tests/test_phases.py holds them equal.
STEP_RUN = "step.run"
STEP_LAUNCH = "step.launch"
COMPILE_BACKEND = "compile.backend"
IN_FLIGHT = "in_flight"
DRAINED_AT_MOST_MS = "drained_at_most_ms"
SLOW_FACTOR = 8.0
SLOW_FLOOR_S = 1e-3
MEDIAN_OF = 64
SLOW_AFTER = 8
#: a block is long beyond this many median blocks
LONG_BLOCK = 1.25


def slow_runs(spans: Iterable) -> list:
    """The ``step.run`` spans of a ring that the program's ``slow_step`` rule
    marks, walked in the order they began."""
    runs = sorted((s for s in spans if s.name == STEP_RUN),
                  key=lambda s: s.t0)
    compiled_at = sorted(s.t0 + 1e-6 * s.dur_us for s in spans
                         if s.name == COMPILE_BACKEND)
    seen, slow = deque(maxlen=MEDIAN_OF), []
    for s in runs:
        seconds = 1e-6 * s.dur_us
        at = bisect.bisect_left(compiled_at, s.t0)
        if at < len(compiled_at) and compiled_at[at] <= s.t0 + seconds:
            continue     # a compile ended inside it: ``recompile``'s
        if (seconds > SLOW_FLOOR_S and len(seen) >= SLOW_AFTER
                and seconds > SLOW_FACTOR * statistics.median(seen)):
            slow.append(s)
        seen.append(seconds)
    return slow


def without_entry(spans: Iterable, window: Tuple[float, float]) -> list:
    """The ring without the first launch that began in the window: the one
    the loop makes behind its own wait at the end of warm-up."""
    spans = list(spans)
    entry = min((s for s in spans if s.name == STEP_LAUNCH
                 and window[0] <= s.t0 < window[1]),
                key=lambda s: s.t0, default=None)
    return [s for s in spans if s is not entry]


def span_metrics(spans: Iterable,
                 window: Optional[Tuple[float, float]] = None) -> dict:
    """The three metrics that need no block: from objects with ``name``,
    ``t0``, ``dur_us`` and ``args``. ``window`` is ``(start, end)`` on
    ``perf_counter``: only spans that started in it count (``None``: every
    span)."""
    spans = list(spans)

    def inside(s):
        return window is None or window[0] <= s.t0 < window[1]

    launches = [s for s in spans if s.name == STEP_LAUNCH and inside(s)
                and IN_FLIGHT in s.args]
    if not launches:
        return {}
    return {
        "pace.queue_depth_min": min(s.args[IN_FLIGHT] for s in launches),
        "pace.drained_launches": sum(DRAINED_AT_MOST_MS in s.args
                                     for s in launches),
        "pace.slow_steps": sum(inside(s) for s in slow_runs(spans))}


def long_blocks(spans: Iterable, block_s: Sequence[float],
                start: float) -> list:
    """One entry for each block of the window that lasted more than
    ``LONG_BLOCK`` medians, the window's first block beginning at ``start``
    on ``perf_counter``: ``{"block", "excess_s", "side", "drained"}``.
    ``drained`` holds ``drained_at_most_ms`` of the launches that began in
    the block's interval and were drained; ``side`` is ``"host"`` where
    there is one, else ``"given_back"`` where the next block is short of
    the median by half the excess or more, else ``"device"``."""
    if not block_s:
        return []
    median = statistics.median(block_s)
    ends = list(itertools.accumulate(block_s, initial=start))
    drained = sorted((s.t0, s.args[DRAINED_AT_MOST_MS]) for s in spans
                     if s.name == STEP_LAUNCH
                     and DRAINED_AT_MOST_MS in s.args)
    out = []
    for i, seconds in enumerate(block_s):
        if seconds <= LONG_BLOCK * median:
            continue
        excess = seconds - median
        lo = bisect.bisect_left(drained, (ends[i],))
        hi = bisect.bisect_left(drained, (ends[i + 1],))
        if hi > lo:
            side = "host"
        elif i + 1 < len(block_s) and median - block_s[i + 1] >= excess / 2:
            side = "given_back"
        else:
            side = "device"
        out.append({"block": i, "excess_s": excess, "side": side,
                    "drained": [ms for _, ms in drained[lo:hi]]})
    return out


def stall_shares(long: list, window_s: float) -> dict:
    by_side = {"host": 0.0, "device": 0.0}
    for b in long:
        by_side["host" if b["side"] == "host" else "device"] += b["excess_s"]
    return {"pace.stall_host_share": 100.0 * by_side["host"] / window_s,
            "pace.stall_device_share": 100.0 * by_side["device"] / window_s}


def read(r: dict) -> dict:
    from ps_tpu import obs

    spans = obs.tracer().spans()
    window = host.window_of(r)
    if window is not None:
        spans = without_entry(spans, window)
    out = span_metrics(spans, window)
    if window is not None and not out:
        # no launch started in the window (a rehearsal's window can be
        # shorter than one step): the whole ring, as ``host.read``
        out = span_metrics(spans)
    if not out:
        return {}    # a program whose launches do not say
    long = []
    if window is not None:     # the blocks have a place on the clock
        long = long_blocks(spans, r["block_s"], window[0])
        out.update(stall_shares(long, r["window_s"]))
    print(f"pace: over the measured window: {out}; blocks over "
          f"{LONG_BLOCK} medians: "
          + (", ".join(
              f"number {b['block']} by {1e3 * b['excess_s']:.1f} ms, "
              f"{b['side']}" + (f" (drained launches, at most ms: "
                                f"{[round(ms, 1) for ms in b['drained']]})"
                                if b["drained"] else "")
              for b in long) or "none"), file=sys.stderr)
    return out
