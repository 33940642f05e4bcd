#!/usr/bin/env python3
"""Checks ``benchmark/harness/tracered.py`` without a chip. Run by hand:

    python3 benchmark/check/check_tracered.py

1. A recorded trace (``widedeep_3steps.trace.json``: three steps of the
   Wide&Deep cell on a TPU v5 lite, saved through ``tracered.load``): busy
   time against a second, independent computation on a 1 ns boundary sweep;
   per-op self times summing to the busy time; the idle share.
2. A recorded step of the BERT cell (``bert_1step.marked.trace.json``, TPU
   v5 lite): of one traced step, every event whose HLO line holds
   "custom-call", "attention", "flash" or "_fwd_kernel" anywhere, operands
   included (all but 24 of XLA's own zero-length custom calls left out, for
   size). The kernel's seconds are the twelve Mosaic calls' and nothing
   else's; a matcher over the whole line reads 1.9x as much.
3. A hand-made two-device trace with collectives alone, in a loop's body
   and as a fusion, a nested ``while``, custom calls and host spans, the
   program's nested inside the loop's: every number worked out by hand
   below.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.harness import tracered  # noqa: E402


def close(a, b, tol=1e-12):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def sweep_busy_ns(events):
    """Union length by counting open intervals at every boundary."""
    marks = sorted([(s, 1) for _, s, d in events]
                   + [(s + d, -1) for _, s, d in events])
    busy, depth, last = 0.0, 0, None
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def check_recorded():
    trace = tracered.load(os.path.join(HERE, "widedeep_3steps.trace.json"))
    red = tracered.reduce(trace)
    (dev, events), = trace["devices"].items()
    d = red["devices"][dev]
    busy = sweep_busy_ns(events) * 1e-9
    assert close(d["busy_s"], busy, 1e-9), (d["busy_s"], busy)
    assert close(sum(d["ops"].values()), busy, 1e-6), sum(d["ops"].values())
    assert close(red["busy_s"], busy, 1e-9)
    assert 0.0 <= red["idle_share"] < 5.0, red["idle_share"]
    assert d["exposed_collective_s"] == 0.0  # one chip: no collective
    top = tracered.top(d["ops"], 1)[0]
    assert "f32[33800000,32]" in top[0], top  # the scatter into the table
    # three steps of about 37 ms each
    assert 0.100 < red["window_s"] < 0.125, red["window_s"]
    syncs = [(5.0 + 1e-9 * (s + dur) + 0.0002, i) for i, (_, s, dur)
             in enumerate(sorted(trace["modules"][dev], key=lambda e: e[1]))]
    syncs[1] = (syncs[1][0] - 0.00015, 1)  # the promptest wait: 50 us late
    assert close(tracered.host_clock_offset(trace, syncs), 5.00005, 1e-9)
    print(f"recorded trace: busy {busy:.6f} s of {red['window_s']:.6f} s, "
          f"idle {red['idle_share']:.4f} %, {len(d['ops'])} op names: ok")


def check_kernel():
    from benchmark.families import flash
    from benchmark.layer_metrics import kernel

    trace = tracered.load(os.path.join(HERE, "bert_1step.marked.trace.json"))
    (dev, events), = trace["devices"].items()
    red = tracered.reduce(trace)
    targets = ["tpu_custom_call"]
    mosaic = [e for e in events if e[0].startswith("%attention.")]
    assert len(mosaic) == 12, len(mosaic)          # one a layer, forward only
    each = [d * 1e-9 for _, _, d in mosaic]
    assert max(each) / min(each) < 1.02, each      # 4.63 to 4.69 ms
    seconds = tracered.op_seconds(
        red, lambda n: tracered.is_custom_call_to(n, targets))
    assert close(seconds, sum(each), 1e-9), (seconds, sum(each))
    typical = sorted(each)[6]
    assert close(seconds, 12 * typical, 5e-3), (seconds, typical)
    # what the whole-line matcher of the first draft read: XLA's own custom
    # calls and every fusion with a %custom-call operand besides
    whole_line = sum(d for n, _, d in events if "custom-call" in n) * 1e-9
    assert 1.8 < whole_line / seconds < 2.0, whole_line / seconds
    own = [n for n, _, _ in events
           if tracered.parts(n)["opcode"] == "custom-call"
           and not n.startswith("%attention.")]
    assert own and not any(tracered.is_custom_call_to(n, targets)
                           for n in own)
    # the reader, on the cell's shapes: batch 32, 12 heads, 512 x 64, 12
    # layers; the step recorded is PR 24's, whose backward was no kernel
    flops, nbytes = flash.cost(32, 12, 12, 512, 64, 64, 12,
                               flash.seen_pairs(512, causal=False), None)
    assert (flops, nbytes) == (12 * 4.0 * 32 * 12 * 512 * 512 * 64,
                               12 * 32 * 12 * 512 * (4.0 * 64 * 2 + 4))
    got = kernel.read({
        "facts": {"flash_flops": flops, "flash_bytes": nbytes,
                  "kernel_targets": targets},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": red, "traced_steps": 1})["kernel.flash_roofline"]
    assert close(got, 100.0 * (flops / 197e12) / seconds, 1e-9), got
    assert 2.7 < got < 2.9, got
    print(f"recorded BERT step: 12 Mosaic calls of {typical * 1e3:.3f} ms, "
          f"kernel {seconds * 1e3:.2f} ms a step, roofline {got:.3f} %, "
          f"whole-line matcher {whole_line / seconds:.2f}x: ok")


def check_handmade():
    ms = 1e6  # ns
    trace = {
        "devices": {
            "/device:TPU:0": [
                ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", 0, 10 * ms),
                # a collective wholly by itself: 5 ms exposed
                ("%all-gather.1 = f32[32] all-gather(f32[8] %fusion.1)",
                 10 * ms, 5 * ms),
                # a while of 20 ms whose body ops take 13 ms of it, 1 ms of
                # them a collective
                ("%while.1 = (f32[8]) while((f32[8]) %t), body=%b", 20 * ms,
                 20 * ms),
                ("%dot.1 = f32[8] dot(f32[8] %a, f32[8] %b)", 21 * ms, 6 * ms),
                ("%all-reduce.7 = f32[8] all-reduce(f32[8] %dot.1)", 28 * ms,
                 1 * ms),
                ("%dot.1 = f32[8] dot(f32[8] %a, f32[8] %b)", 30 * ms, 6 * ms),
                # a collective fusion, 3 ms
                ("%fusion.9 = bf16[8] fusion(bf16[8] %x), kind=kCustom, "
                 "calls=%all-reduce-scatter.3", 40 * ms, 3 * ms),
                # consumes a collective's result: compute, not a collective
                ("%fusion.2 = f32[8] fusion(f32[32] %all-gather.1), "
                 "kind=kLoop", 50 * ms, 8 * ms),
                # a Mosaic kernel, one of XLA's own custom calls, and a
                # fusion that reads the kernel's result: 1 ms, 0.5 ms, 0.5 ms
                ("%attention.3 = bf16[8] custom-call(bf16[8] %q), "
                 "custom_call_target=\"tpu_custom_call\"", 58 * ms, 1 * ms),
                ("%custom-call.4 = f32[8] custom-call(f32[8] %attention.3), "
                 "custom_call_target=\"ConcatBitcast\"", 59 * ms, 0.5 * ms),
                ("%fusion.5 = f32[8] fusion(f32[8] %custom-call.4, bf16[8] "
                 "%attention.3), kind=kLoop", 59.5 * ms, 0.5 * ms),
            ],
            "/device:TPU:1": [
                ("%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop", 0, 30 * ms),
                ("%all-gather.1 = f32[32] all-gather(f32[8] %fusion.1)",
                 30 * ms, 30 * ms),
            ],
        },
        "modules": {},
        # gaps of device 0: 15-20 ms and 43-50 ms
        # the loop's spans and, nested in its dispatch, the program's pair
        # as harness/loop.py hands them over: the wrapper and its launch
        "host": [("loop.dispatch", 14 * ms, 4 * ms),
                 ("step.run", 14.5 * ms, 3.4 * ms),
                 ("step.launch", 16 * ms, 1.8 * ms),
                 ("loop.block", 40 * ms, 20 * ms),
                 ("input.next", 44 * ms, 1 * ms)],
    }
    red = tracered.reduce(trace)
    d0, d1 = red["devices"]["/device:TPU:0"], red["devices"]["/device:TPU:1"]
    assert close(red["window_s"], 0.060)
    assert close(d0["busy_s"], 0.048), d0["busy_s"]       # 15 + 23 + 10
    assert close(d1["busy_s"], 0.060)
    assert close(red["busy_s"], 0.054)                     # mean of chips
    assert close(red["idle_share"], 20.0), red["idle_share"]  # worst: dev 0
    # 5 ms alone + 1 ms in the loop's body + the 3 ms fusion
    assert close(d0["exposed_collective_s"], 0.009), d0["exposed_collective_s"]
    assert close(d1["exposed_collective_s"], 0.030)
    ops = d0["ops"]
    assert close(ops["%while.1 = (f32[8]) while((f32[8]) %t), body=%b"],
                 0.007)                                    # 20 - 6 - 1 - 6
    assert close(ops["%dot.1 = f32[8] dot(f32[8] %a, f32[8] %b)"], 0.012)
    # gap 15-20 ms: its middle, 17.5 ms, lies in loop.dispatch, in step.run
    # inside it and in step.launch inside that: the innermost, the one that
    # started last, takes it; gap 43-50 ms: its middle, 46.5 ms, lies in
    # loop.block only
    assert close(red["idle_gaps"]["step.launch"], 0.005)
    assert close(red["idle_gaps"]["loop.block"], 0.007)
    assert set(red["idle_gaps"]) == {"step.launch", "loop.block"}
    # without the launch the rest of the wrapper takes it; without the
    # program's spans the loop's own, as before PR 67
    for left, to in ((2, "step.run"), (1, "loop.dispatch")):
        fewer = {**trace, "host": trace["host"][:left] + trace["host"][3:]}
        assert close(tracered.reduce(fewer)["idle_gaps"][to], 0.005), to
    assert close(tracered.op_seconds(red, lambda n: "dot(" in n),
                 0.006)                                    # mean of chips
    assert close(tracered.op_seconds(red, lambda n: tracered.is_custom_call_to(
        n, ["tpu_custom_call"])), 0.0005)       # 1 ms on one chip of two
    print("hand-made trace: busy, idle, exposed collectives, self times, "
          "custom calls, gap attribution: ok")


if __name__ == "__main__":
    check_recorded()
    check_kernel()
    check_handmade()
