"""Set-up from inside the program: what ``setup_s`` is made of.

The program records a span around each stage of its own set-up
(``ps_tpu/obs/phases.py::SETUP_SPANS``) and turns jax's trace / lower /
compile-or-load events into spans as they end (``COMPILE_SPANS``,
``ps_tpu/obs/compiles.py``), all in its tracer's ring and on
``time.perf_counter``. Set-up is ``[_T_START, _T_START + setup_s)`` on that
clock, found as ``host.window_of`` finds the window. This file reads the
ring after the run: lengths of the set-up spans, unions of the compiler's
spans (its trace events nest, ``matmul`` inside ``my_step``, and the cache's
load lies inside the backend's interval, so never sums), and what no span
covers. Main thread only, but for the compiler's backend spans, which count
on whatever thread they ran so that ``setup.step_compile_or_load_s`` +
``setup.other_compile_s`` is the harness's own ``entry.compile_s``.

To stderr goes the whole of it: every instant of set-up belongs to the span
that started last among those open at it (a span's self time, its length
less what its children cover), to "(before the program)" or to
"(unspanned)", laid against the loop's four phases, so the rows add up to
``setup_s``.

A program from before these spans has no ``setup.import``: the metrics are
left out. So they are when the ring turned over and dropped it.
"""

from __future__ import annotations

import bisect
import sys
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import host

# The names of ps_tpu/obs/phases.py that this file looks up, copied: the
# yardstick also reads trees that lack them. tests/test_phases.py holds
# them equal.
SETUP_IMPORT = "setup.import"
SETUP_INIT = "setup.init"
SETUP_STORE_INIT = "setup.store_init"
SETUP_TABLE_INIT = "setup.table_init"
SETUP_SPANS = (SETUP_IMPORT, SETUP_INIT, SETUP_STORE_INIT, SETUP_TABLE_INIT)
COMPILE_TRACE = "compile.trace"
COMPILE_LOWER = "compile.lower"
COMPILE_BACKEND = "compile.backend"
COMPILE_CACHE_LOAD = "compile.cache_load"
COMPILE_SPANS = (COMPILE_TRACE, COMPILE_LOWER, COMPILE_BACKEND,
                 COMPILE_CACHE_LOAD)

#: every program span: what "spanned" means below
PROGRAM_SPANS = SETUP_SPANS + COMPILE_SPANS + host.HOST_SPANS
BEFORE = "(before the program)"
UNSPANNED = "(unspanned)"

Interval = Tuple[float, float]


def union_s(intervals: Iterable[Interval]) -> float:
    """The length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def _interval(s, end: float) -> Interval:
    return s.t0, min(s.t0 + 1e-6 * s.dur_us, end)


def self_times(spans: Sequence, start: float, ends: Sequence[float],
               program_from: float) -> Dict[str, List[float]]:
    """``{row: [seconds in each phase]}`` over ``[start, ends[-1])``, where
    phase i ends at ``ends[i]``. Every instant belongs to one row: the name
    of the span of ``spans`` that started last among those covering it;
    with none, ``BEFORE`` up to ``program_from`` and ``UNSPANNED`` after."""
    stop = ends[-1]
    # (time, 0 = a span ends | 1 = a span starts | 2 = a cut, -length, span):
    # of two spans that start together the longer is the parent
    marks = []
    for s in spans:
        a, b = _interval(s, stop)
        if start <= a < b:
            marks.append((a, 1, a - b, s))
            marks.append((b, 0, 0.0, s))
    marks.extend((t, 2, 0.0, None) for t in (program_from, *ends))
    marks.sort(key=lambda m: m[:3])
    rows: Dict[str, List[float]] = {}
    open_spans: list = []  # in order of start: the last one owns the instant
    at = start
    for t, kind, _, s in marks:
        if t > at:
            name = open_spans[-1].name if open_spans else (
                BEFORE if at < program_from else UNSPANNED)
            phase = min(bisect.bisect_right(ends, at), len(ends) - 1)
            rows.setdefault(name, [0.0] * len(ends))[phase] += t - at
            at = t
        if kind == 1:
            open_spans.append(s)
        elif kind == 0:
            open_spans.remove(s)
    return rows


def _of_set_up(spans: Iterable, start: float, end: float,
               main_tid: Optional[int]) -> Tuple[list, list]:
    """The program spans that started in ``[start, end)``: all of them, and
    the main thread's."""
    found = [s for s in spans
             if s.name in PROGRAM_SPANS and start <= s.t0 < end]
    return found, [s for s in found
                   if getattr(s, "_tid", main_tid) == main_tid]


def span_metrics(spans: Iterable, start: float, setup_s: float,
                 main_tid: Optional[int] = None) -> Optional[dict]:
    """The metrics of this file from the spans of a ring: objects with
    ``name``, ``t0``, ``dur_us``, ``args`` and the thread's ``_tid``
    (a span without one counts as the main thread's). Set-up is
    ``[start, start + setup_s)`` on ``perf_counter``; only spans that
    started in it count. ``None`` where there is no ``setup.import``."""
    end = start + setup_s
    found, main = _of_set_up(spans, start, end, main_tid)
    first = next((s for s in main if s.name == SETUP_IMPORT), None)
    if first is None:
        return None

    def named(*names, among=main):
        return [s for s in among if s.name in names]

    def length(found_spans):
        return sum(_interval(s, end)[1] - s.t0 for s in found_spans)

    # a compiler's span is the step's if it began inside a step.run of its
    # own thread (program spans nest on a stack a thread)
    runs: Dict[Optional[int], List[Interval]] = {}
    for s in named(host.STEP_RUN, among=found):
        runs.setdefault(getattr(s, "_tid", main_tid), []).append(
            _interval(s, end))

    def of_step(s) -> bool:
        return any(a <= s.t0 < b
                   for a, b in runs.get(getattr(s, "_tid", main_tid), ()))

    backends = named(COMPILE_BACKEND, among=found)
    out = {
        "setup.before_program_s": first.t0 - start,
        "setup.import_s": length([first]),
        "setup.init_s": length(named(SETUP_INIT)),
        "setup.store_init_s": length(named(SETUP_STORE_INIT,
                                           SETUP_TABLE_INIT)),
        "setup.step_trace_lower_s": union_s(
            _interval(s, end) for s in named(COMPILE_TRACE, COMPILE_LOWER)
            if of_step(s)),
        "setup.step_compile_or_load_s": union_s(
            _interval(s, end) for s in backends if of_step(s)),
        "setup.other_compile_s": union_s(
            _interval(s, end) for s in backends if not of_step(s)),
        "setup.cache_misses": float(sum(
            s.args.get("cache") == "miss" for s in backends)),
    }
    out["setup.unspanned_s"] = setup_s - out["setup.before_program_s"] \
        - union_s(_interval(s, end) for s in main)
    return out


def table(rows: Dict[str, List[float]], phases: Sequence[str]) -> str:
    """The stderr table of ``self_times``' rows."""
    order = [BEFORE, *PROGRAM_SPANS, UNSPANNED]
    names = sorted(rows, key=order.index)
    width = max(len(n) for n in names + ["total"])
    head = " ".join(f"{p:>18}" for p in (*phases, "all of set-up"))
    lines = [f"{'':{width}} {head}"]
    for n in names + ["total"]:
        cells = rows[n] if n != "total" else [
            sum(rows[m][i] for m in names) for i in range(len(phases))]
        lines.append(f"{n:{width}} " + " ".join(
            f"{c:18.3f}" for c in (*cells, sum(cells))))
    return "\n".join(lines)


def read(r: dict) -> dict:
    from ps_tpu import obs

    start = getattr(sys.modules.get("__main__"), "_T_START", None)
    if start is None or "setup_s" not in r:
        return {}
    tracer = obs.tracer()
    spans = tracer.spans()
    main_tid = threading.main_thread().ident
    out = span_metrics(spans, start, r["setup_s"], main_tid)
    dropped = getattr(tracer, "dropped", 0)
    if out is None:
        print("setup: no setup.import span in the ring ("
              + (f"it turned over: {dropped} spans dropped" if dropped
                 else "the program records none") + "): the setup.* metrics "
              "are left out", file=sys.stderr)
        return {}
    phases = r.get("setup_phases_s") or {"set-up": r["setup_s"]}
    ends, at = [], start
    for seconds in phases.values():
        at += seconds
        ends.append(at)
    _, main = _of_set_up(spans, start, ends[-1], main_tid)
    rows = self_times(main, start, ends,
                      start + out["setup.before_program_s"])
    compiler = sum(s.name in COMPILE_SPANS for s in spans)
    print(f"setup: self time of the program's spans on the main thread, s, "
          f"by the loop's phases ({len(spans)} spans in the ring, {compiler} "
          f"of them the compiler's, {dropped} dropped; {len(main)} in "
          f"set-up):\n{table(rows, list(phases))}\nsetup: {out}",
          file=sys.stderr)
    return out
