"""The driving loop of every cell: set-up timing, the correctness check,
warm-up, the window of blocks, the traced segment, and the host spans.

The program under test is reached only through the ``Cell`` a family builds
(``benchmark/families/<family>.py``): ``stream`` yields device-placed
batches for ever, ``step(batch)`` dispatches one fused step and returns its
loss as a device scalar, ``reference_loss(batch)`` is the plain forward pass
on the weights as they are before step 0.
"""

from __future__ import annotations

import dataclasses
import math
import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from . import stats, tracered

SPAN_NAMES = ("input.next", "loop.dispatch", "loop.block")


@dataclasses.dataclass
class Cell:
    """What a family's ``build`` hands to the loop."""

    samples_per_step_per_chip: int
    stream: Iterator                      # device-placed batches, for ever
    step: Callable                        # batch -> loss (device scalar)
    reference_loss: Callable              # batch -> float, before step 0
    tolerance: Tuple[float, str]          # relative tolerance and its reason
    counters: Callable[[], dict]          # the program's counters, at the end
    facts: dict                           # operations and bytes from shapes
    close: Callable[[], None]
    #: called once step 0 is done, where a family checks more than step 0's
    #: loss: ``{"checks": {name: bool}, "detail": {...}}``
    after_step0: Optional[Callable[[], dict]] = None


def seed_key(seed: int):
    """A jax PRNG key from ``--seed``, which may be a little over 2**31:
    more than the 32 signed bits a key's seed holds."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class Spans:
    """Host spans in memory: ``(start, duration)`` on ``perf_counter`` by
    name."""

    def __init__(self):
        self.each: Dict[str, List[Tuple[float, float]]] = {
            n: [] for n in SPAN_NAMES}

    def span(self, name: str):
        return _Span(self.each[name])

    def durations(self) -> Dict[str, List[float]]:
        return {n: [d for _, d in v] for n, v in self.each.items()}


class _Span:
    __slots__ = ("into", "t0")

    def __init__(self, into: list):
        self.into = into

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.into.append((self.t0, time.perf_counter() - self.t0))


def _blocks(cell, stream, k: int, spans: Spans, losses: list, compiles,
            compiled_steps: list, go_on, syncs: list = None) -> List[float]:
    """Blocks of K consecutive steps. A block ends when its last loss is
    ready; the host waits for it only after it has dispatched the next
    block's first step, so the device never drains at a block's end (a
    training loop does not wait at all). On entry the device is idle and
    every loss so far is ready. Runs while ``go_on(blocks done)``; on return
    one step past the last block is in flight. Returns each block's wall
    time, the first counted from entry; ``syncs`` gathers ``(host time at
    which the wait returned, index in losses of the step waited for)``."""
    block_s: List[float] = []
    t_mark = time.perf_counter()
    pending = None
    while True:
        for i in range(k):
            before = compiles.count
            with spans.span("input.next"):
                batch = next(stream)
            with spans.span("loop.dispatch"):
                loss = cell.step(batch)
            losses.append(loss)
            if compiles.count != before:
                compiled_steps.append(len(losses) - 1)
            if i == 0 and pending is not None:
                with spans.span("loop.block"):
                    pending.block_until_ready()
                now = time.perf_counter()
                if syncs is not None:
                    syncs.append((now, len(losses) - 2))
                block_s.append(now - t_mark)
                t_mark = now
                if not go_on(len(block_s)):
                    return block_s
        pending = loss


def program_spans(since: float, until: float) -> List[Tuple[str, float, float]]:
    """``(name, start, seconds)`` on ``perf_counter`` of the program's own
    host spans (the ring of ``ps_tpu.obs.tracer()`` that
    ``layer_metrics/host.py`` reads) that started in ``[since, until)`` on
    this thread: what the loop's ``loop.dispatch`` and ``input.next`` were
    doing inside. The producer thread's spans run beside the loop and say
    nothing of where it stood. None on a program without the ring."""
    try:
        from ps_tpu import obs
        ring = obs.tracer().spans()
    except (ImportError, AttributeError):
        return []
    here = threading.get_ident()
    return [(s.name, s.t0, 1e-6 * s.dur_us) for s in ring
            if since <= s.t0 < until and getattr(s, "_tid", here) == here]


def _live_executables() -> list:
    import jax

    return jax.devices()[0].client.live_executables()


def device_peak_bytes(alloc_peak: int, step_executables: list) -> int:
    """Peak bytes on the fullest chip. The allocator's ``peak_bytes_in_use``
    (``alloc_peak``) counts live arrays and leaves out a running program's
    temporaries (1.9 GB beside 9 GB of compiled temporaries for ResNet-50,
    PR 24), so this is the larger of it and the most a program of the step
    needs while it runs: arguments + outputs - aliased + temporaries, per
    device, from XLA's compiled memory stats. ``step_executables`` are the
    programs that step 0 loaded: the reference forward pass and the jits
    that make weights and pools never run in the window and are left out."""
    peak = alloc_peak
    for exe in step_executables:
        m = exe.get_compiled_memory_stats()
        peak = max(peak, m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return int(peak)


def run(cell, traffic: dict, seconds: float, trace: bool, compiles,
        t_start: float) -> dict:
    """Drive one cell. Returns everything the layer-metric readers and the
    result line are made from."""
    import jax

    k = int(traffic["block_steps"])
    n = int(traffic["loss_step"])
    losses: list = []
    compiled_steps: List[int] = []

    # -- set-up: reference, step 0, warm-up (steps of the same seeded run)
    stream = cell.stream
    batch0 = next(stream)
    t_built = time.perf_counter()
    reference = cell.reference_loss(batch0)
    t_reference = time.perf_counter()
    loaded_before = _live_executables()
    losses.append(cell.step(batch0))
    loss0 = float(losses[0])
    step_executables = [e for e in _live_executables()
                        if e not in loaded_before]
    more = cell.after_step0() if cell.after_step0 else {}
    t_step0 = time.perf_counter()
    for _ in range(int(traffic["warmup_steps"]) - 1):
        losses.append(cell.step(next(stream)))
    losses[-1].block_until_ready()
    setup_compile_s = compiles.seconds
    setup_compiles = compiles.count
    warm = len(losses)
    spans = Spans()
    t_window = time.perf_counter()
    setup_s = t_window - t_start

    # -- the window: a whole number of blocks, nothing compiles in it
    block_s = _blocks(
        cell, stream, k, spans, losses, compiles, compiled_steps,
        lambda done: time.perf_counter() - t_window < seconds)
    window_s = sum(block_s)
    steps = k * len(block_s)
    losses[-1].block_until_ready()
    compiles_in_window = compiles.count - setup_compiles
    alloc_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices())
    peak_bytes = device_peak_bytes(alloc_peak, step_executables)

    # -- the traced segment, in a --trace 1 run only
    reduced, traced_steps = None, 0
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            tspans, syncs = Spans(), []
            before = len(losses)
            # device tracing only: the host tracer writes an event for every
            # chunk of the host-side layout change of a 154 MB batch (30 MB
            # of trace a ResNet step) and slows that path 17x, whatever its
            # level; the Python tracer writes every call. The host spans
            # are the loop's own, tied to the trace's clock at its syncs.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            t_traced = time.perf_counter()
            jax.profiler.start_trace(tdir, profiler_options=options)
            try:
                blocks = int(traffic["trace_blocks"])
                _blocks(cell, stream, k, tspans, losses, compiles, [],
                        lambda done: done < blocks, syncs)
                losses[-1].block_until_ready()
                syncs.append((time.perf_counter(), len(losses) - 1))
            finally:
                jax.profiler.stop_trace()
            traced_steps = len(losses) - before
            loaded = tracered.load(tracered.find_xplane(tdir))
            offset = tracered.host_clock_offset(
                loaded, [(t, i - before) for t, i in syncs])
            if offset is not None:
                # the loop's three spans of the traced blocks and, inside
                # them, the program's: a gap goes to the innermost
                own = [(name, t0, dur) for name, spans_ in tspans.each.items()
                       for t0, dur in spans_]
                loaded["host"] = [
                    (name, (t0 - offset) * 1e9, dur * 1e9)
                    for name, t0, dur in own + program_spans(
                        t_traced, syncs[-1][0])]
            reduced = tracered.reduce(loaded)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # -- loss_at_n: steps past the window if the window was short of n
    while len(losses) <= n:
        losses.append(cell.step(next(stream)))
    values = [float(x) for x in jax.device_get(losses)]
    at_n = stats.loss_at_n(values, n)
    window_losses = values[warm:warm + steps]
    bad = {i for i, v in enumerate(window_losses) if not math.isfinite(v)}
    bad |= {i - warm for i in compiled_steps}
    counters = cell.counters()

    tol, why = cell.tolerance
    checks = {
        "step0_matches_reference":
            abs(loss0 - reference) <= tol * abs(reference),
        "losses_finite": stats.all_finite(values),
        "loss_fell": at_n < loss0,
        "no_compile_in_window": compiles_in_window == 0,
        "no_dropped_rows": counters.get("dropped_rows", 0) == 0,
        **more.get("checks", {}),
    }
    return {
        "correct": all(checks.values()),
        "checks": checks,
        "reference": {"step0_loss": loss0, "plain_forward_loss": reference,
                      "rel_diff": abs(loss0 - reference) / abs(reference),
                      "tolerance": tol, "why": why,
                      **more.get("detail", {})},
        "attempted": steps,
        "failed": len(bad),
        "setup_s": setup_s,
        "setup_phases_s": {"imports_and_build": t_built - t_start,
                           "reference": t_reference - t_built,
                           "step0": t_step0 - t_reference,
                           "warmup": t_window - t_step0},
        "setup_compile_s": setup_compile_s,
        "cache": {"hits": compiles.hits, "misses": compiles.misses},
        "compiles_in_window": compiles_in_window,
        "window_s": window_s,
        "block_s": block_s,
        "steps": steps,
        "throughput": stats.throughput(cell.samples_per_step_per_chip, steps,
                                       window_s),
        "loss_at_n": at_n,
        "spans": spans.durations(),
        "counters": counters,
        "peak_bytes": peak_bytes,
        "alloc_peak_bytes": alloc_peak,
        "trace": reduced,
        "traced_steps": traced_steps,
        "facts": cell.facts,
    }
