#!/usr/bin/env python3
"""Checks ``benchmark/layer_metrics/pace.py`` without a chip and without the
program. Run by hand:

    python3 benchmark/check/check_pace.py

A hand-made ring and ``block_s``: a window of twelve blocks of two steps from
100 s on, the median block 1 s, every number worked out by hand below.

- block 3 lasts 1.5 s and a launch inside it was drained: the host's, 0.5 s;
- block 6 lasts 1.4 s, no launch of it was drained and block 7 is a median
  block: the device's, 0.4 s;
- block 8 lasts 1.6 s, no launch of it was drained and block 9 lasts 0.4 s:
  a late wake-up that the next block gave back, 0.6 s on the device's side;
- block 10 lasts 1.2 s: under 1.25 medians, no stall;
- the window's first launch is drained, as the loop makes it (it enters
  the window behind a wait of its own), and is left out;
- outside the window: the wrapper's first launch (``in_flight`` 0 and no
  bound: never drained), a drained launch in warm-up and one past the window.

The ``step.run`` spans last 10 ms. One of 100 ms among the first eight is not
slow (the rule waits for eight); one in which a compile ended is not; one in
warm-up is, and is outside the window; one in the window is. A ring of 50 us
steps with one of 0.9 ms (18 medians, under the floor of 1 ms) and one of
1.1 ms: the second alone.
"""

import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.layer_metrics import pace  # noqa: E402

START = 100.0
BLOCK_S = [1.0, 1.0, 1.0, 1.5, 1.0, 1.0, 1.4, 1.0, 1.6, 0.4, 1.2, 1.0]


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def span(name, t0, dur_s, **args):
    return types.SimpleNamespace(name=name, t0=t0, dur_us=1e6 * dur_s,
                                 args=args)


def step(t0, run_s=0.010, **launch_args):
    """A step's two spans: the wrapper from ``t0`` and its launch 1 ms in."""
    return [span(pace.STEP_RUN, t0, run_s),
            span(pace.STEP_LAUNCH, t0 + 0.001, run_s / 2, **launch_args)]


def handmade():
    """``(ring, r)``: the spans and the loop's result, as ``pace.read``
    finds them."""
    ring = step(80.0, in_flight=0)                  # the first: no bound
    ring += step(81.0, run_s=0.100, in_flight=1)    # long, but the second seen
    for i in range(2, 12):
        ring += step(80.0 + i, in_flight=1)
    ring += step(92.0, run_s=3.0, in_flight=1)      # a compile ended in it
    ring.append(span(pace.COMPILE_BACKEND, 92.5, 2.0, fun="fused_step"))
    ring += step(96.0, run_s=0.100, in_flight=1)    # slow, in warm-up
    ring += step(99.0, in_flight=0, drained_at_most_ms=900.0)   # warm-up
    # the window's first launch: behind the loop's own wait, so drained
    ring += step(START + 0.0005, in_flight=0, drained_at_most_ms=5.0)
    at = START
    for i, seconds in enumerate(BLOCK_S):
        # the block's second step, then the next block's first
        for j, t0 in enumerate((at + 0.1 * seconds, at + 0.7 * seconds)):
            args = {"in_flight": 1 + j}
            if (i, j) == (3, 1):     # 104.05 s: the chip had run dry
                args = {"in_flight": 0, "drained_at_most_ms": 700.0}
            ring += step(t0, run_s=0.100 if (i, j) == (5, 0) else 0.010,
                         **args)
        at += seconds
    ring += step(at + 0.5, in_flight=0, drained_at_most_ms=400.0)  # traced
    window_s = sum(BLOCK_S)
    return ring, {"block_s": BLOCK_S, "window_s": window_s,
                  "window": (START, START + window_s)}


def check_handmade():
    whole, r = handmade()
    # with the window's first launch the loop's own drain counts
    assert pace.span_metrics(whole, r["window"])[
        "pace.drained_launches"] == 2
    ring = pace.without_entry(whole, r["window"])
    assert len(ring) == len(whole) - 1
    out = pace.span_metrics(ring, r["window"])
    assert out == {"pace.queue_depth_min": 0, "pace.drained_launches": 1,
                   "pace.slow_steps": 1}, out
    # the whole ring: the warm-up's and the traced blocks' too
    assert pace.span_metrics(ring) == {
        "pace.queue_depth_min": 0, "pace.drained_launches": 3,
        "pace.slow_steps": 2}
    # without the planted drain the window never saw fewer than one in flight
    fed = [s for s in ring if s.args.get("drained_at_most_ms") != 700.0]
    assert pace.span_metrics(fed, r["window"]) == {
        "pace.queue_depth_min": 1, "pace.drained_launches": 0,
        "pace.slow_steps": 1}
    long = pace.long_blocks(ring, r["block_s"], START)
    assert [(b["block"], b["side"]) for b in long] == [
        (3, "host"), (6, "device"), (8, "given_back")], long
    assert [round(b["excess_s"], 9) for b in long] == [0.5, 0.4, 0.6]
    assert long[0]["drained"] == [700.0] and not long[1]["drained"]
    shares = pace.stall_shares(long, r["window_s"])
    assert close(r["window_s"], 13.1)
    assert close(shares["pace.stall_host_share"], 100 * 0.5 / 13.1)
    assert close(shares["pace.stall_device_share"], 100 * 1.0 / 13.1)
    # no launch says how many were in flight (a program before the
    # account): nothing to read, and no error
    bare = [span(s.name, s.t0, 1e-6 * s.dur_us) for s in ring]
    assert pace.span_metrics(bare, r["window"]) == {}
    assert pace.long_blocks(bare, r["block_s"], START)[0]["side"] == "device"
    assert pace.long_blocks(ring, [], START) == []
    print("hand-made ring: queue depth, drained launches, slow steps, the "
          "stall's two sides: ok")


def check_floor():
    ring = []
    for i in range(40):
        seconds = {20: 0.9e-3, 30: 1.1e-3}.get(i, 50e-6)
        ring += step(float(i), run_s=seconds, in_flight=1)
    assert [s.t0 for s in pace.slow_runs(ring)] == [30.0]
    print("the rule's floor of 1 ms: ok")


if __name__ == "__main__":
    check_handmade()
    check_floor()
