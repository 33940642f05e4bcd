"""From a profiler trace to busy/idle time, per-op time, exposed collective
time and idle gaps by host span. Reads ``*.xplane.pb`` with nothing but
``jax.profiler.ProfileData``; ``benchmark/check/`` holds a small recorded
trace and a script that checks this file on it.

A trace is reduced in two stages so that the arithmetic can be checked
without a chip: ``load`` turns the file into plain lists of
``(name, start_ns, duration_ns)``, ``reduce`` does the arithmetic.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_MARKS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: [Event]}, "modules": {plane: [Event]}}`` from
    an ``.xplane.pb``, or from the ``.json`` this function's result was saved
    as. Device events are those of the plane's "XLA Ops" line; modules are
    the whole programs of its "XLA Modules" line, one event a step."""
    if path.endswith(".json"):
        with open(path) as f:
            raw = json.load(f)
        return {key: {k: [tuple(e) for e in v] for k, v in raw[key].items()}
                for key in ("devices", "modules")}
    from jax.profiler import ProfileData

    def events(lines):
        return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ln in lines for ev in ln.events]

    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = list(plane.lines)
            devices[plane.name] = events(
                [ln for ln in lines if ln.name == OPS_LINE])
            modules[plane.name] = events(
                [ln for ln in lines if ln.name == MODULES_LINE])
    return {"devices": devices, "modules": modules}


def host_clock_offset(trace: dict, syncs: Sequence[Tuple[float, int]]
                      ) -> float:
    """Seconds to take from a host clock reading to land on the trace's
    clock. The profiler's host tracer is off (it slows a heavy input path
    17x, PERF.md PR 24), so the two clocks are tied at the loop's own sync
    points: ``syncs`` holds ``(host time at which block_until_ready
    returned, number of the traced step it waited for)``, and step j is the
    j-th run of the program that takes most of the device's time. The wait
    returns a little after the step's end on the device, never before, so
    the smallest difference is the offset (to about 0.1 ms). ``None`` where
    the trace does not hold those steps."""
    for mods in trace["modules"].values():
        by_name: Dict[str, float] = {}
        for name, _, dur in mods:
            by_name[name] = by_name.get(name, 0.0) + dur
        if not by_name:
            continue
        main = max(by_name, key=by_name.get)
        ends = sorted(s + d for n, s, d in mods if n == main)
        diffs = [host - ends[j] * 1e-9 for host, j in syncs if j < len(ends)]
        if diffs:
            return min(diffs)
    return None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Seconds per op name, each event counted without the events nested
    inside it (a ``while`` does not count its body twice)."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) * 1e-9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_CALLS = re.compile(r"calls=%([\w\-.]+)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def parts(name: str) -> Dict[str, str]:
    """What a device event says of itself. The trace names an event by its
    HLO line, ``%own = shape opcode(operands), attributes``: its own name,
    its opcode, the computation a fusion ``calls`` and a custom call's
    ``target``. The operands are left out on purpose: an operand that came
    out of a collective or a kernel does not make its consumer one."""
    own, _, rest = name.partition(" = ")
    found = {key: rx.search(rest) for key, rx in
             (("opcode", _OPCODE), ("calls", _CALLS), ("target", _TARGET))}
    return {"own": own, **{k: m.group(1) if m else ""
                           for k, m in found.items()}}


def is_collective(name: str) -> bool:
    """Whether a device event is a collective: by its own name, its opcode
    or the computation it calls, never by an operand."""
    p = parts(name)
    return any(mark in p[key] for key in ("own", "opcode", "calls")
               for mark in COLLECTIVE_MARKS)


def is_custom_call_to(name: str, targets: Sequence[str]) -> bool:
    """Whether a device event is itself a custom call to one of
    ``targets`` (``tpu_custom_call`` is a Mosaic kernel). XLA's own custom
    calls (``ConcatBitcast`` ...) and a fusion that reads a custom call's
    result are not."""
    p = parts(name)
    return p["opcode"] == "custom-call" and p["target"] in targets


def reduce(trace: dict, window_ns: Tuple[float, float] = None) -> dict:
    """The arithmetic. ``window_ns`` is the traced window on the trace's
    clock; by default from the first device event's start to the last one's
    end over all devices.

    Returns seconds: per device ``busy_s`` (union of op intervals),
    ``exposed_collective_s`` (self time of collective ops: no other op
    runs on that device meanwhile), ``ops`` (self time by name), ``gaps`` (idle
    intervals); and over devices ``window_s``, ``busy_s`` (mean),
    ``idle_share`` (worst device), ``idle_gaps`` (idle seconds of the worst
    device by the innermost host span over each gap's middle: of the spans
    that cover it the one that started last, the loop's own or, inside
    them, the program's)."""
    per_device = {}
    starts, ends = [], []
    for dev, events in trace["devices"].items():
        if not events:
            continue
        starts.append(min(e[1] for e in events))
        ends.append(max(e[1] + e[2] for e in events))
    if not starts:
        return {"devices": {}, "window_s": 0.0, "busy_s": 0.0}
    lo, hi = window_ns or (min(starts), max(ends))
    for dev, events in trace["devices"].items():
        clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi]
        busy = _union(clipped)
        ops = self_times(events)
        gaps, cur = [], lo
        for a, b in busy:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if hi > cur:
            gaps.append((cur, hi))
        per_device[dev] = {
            "busy_s": _length(busy) * 1e-9,
            # the ops of a device run one at a time (with a loop's body
            # nested in it), so a collective op's own time is time in which
            # nothing else runs there
            "exposed_collective_s": sum(
                sec for name, sec in ops.items() if is_collective(name)),
            "ops": ops,
            "gaps": gaps,
        }
    window_s = (hi - lo) * 1e-9
    worst = min(per_device, key=lambda d: per_device[d]["busy_s"])
    spans = sorted((s, s + d, n) for n, s, d in trace.get("host") or ())
    by_span: Dict[str, float] = {}
    for a, b in per_device[worst]["gaps"]:
        mid = (a + b) / 2
        # the innermost host span over the gap's middle: the one that
        # started last, the shorter of two that started together
        cover = [(-s, e - s, n) for s, e, n in spans if s <= mid < e]
        name = min(cover)[2] if cover else "(no span)"
        by_span[name] = by_span.get(name, 0.0) + (b - a) * 1e-9
    busy_mean = sum(d["busy_s"] for d in per_device.values()) / len(per_device)
    return {
        "devices": per_device,
        "window_s": window_s,
        "busy_s": busy_mean,
        "idle_share": 100.0 * (1.0 - per_device[worst]["busy_s"] / window_s),
        "idle_gaps": by_span,
    }


def op_seconds(reduced: dict, match) -> float:
    """Mean over devices of the self time of the ops whose name
    ``match(name)`` accepts."""
    devs = reduced["devices"].values()
    if not devs:
        return 0.0
    return sum(sec for d in devs for name, sec in d["ops"].items()
               if match(name)) / len(devs)


def top(mapping: Dict[str, float], n: int = 10, width: int = 96):
    ranked = sorted(mapping.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:width], sec] for name, sec in ranked]
