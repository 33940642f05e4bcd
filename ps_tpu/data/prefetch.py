"""Host→device input prefetch (double buffering).

The bench's device-step metric excludes host input cost by pre-placing
batches; real trainers can't. This closes the gap (VERDICT r2 item 7): keep
``depth`` batches in flight on device while the current step runs —
``jax.device_put`` is asynchronous, so placement of batch N+1/N+2 overlaps
step N's compute instead of serializing after it. Depth 2 suffices: one
buffer being consumed, one arriving.
"""

from __future__ import annotations

import collections
import itertools
from typing import Callable, Iterable, Iterator, Optional

from ps_tpu import obs
from ps_tpu.obs import phases


def device_prefetch(batches: Iterable, place: Optional[Callable] = None,
                    depth: int = 2) -> Iterator:
    """Yield device-resident batches with ``depth`` placements in flight.

    Args:
      batches: host-side batch iterable (e.g. a data generator).
      place: host→device placement, e.g. ``store.shard_batch`` (splits the
        batch over the mesh's data axis) or a plain ``jax.device_put``.
        Default: ``jax.device_put`` to the default device.
      depth: batches resident ahead of consumption (2 = double buffering).
    """
    import jax

    if place is None:
        place = jax.device_put
    if depth < 1:
        raise ValueError("prefetch depth must be >= 1")
    span = obs.tracer().program_span
    buf = collections.deque()
    for seq, item in enumerate(batches):
        nbytes = sum(getattr(x, "nbytes", 0)
                     for x in jax.tree_util.tree_leaves(item))
        with span(phases.INPUT_PLACE, seq=seq, nbytes=nbytes):
            buf.append(place(item))
        if len(buf) >= depth:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def threaded_source(batches: Iterable, capacity: int = 2) -> Iterator:
    """Run a host batch generator in a producer thread behind a bounded
    queue, overlapping generation with training. With CPU-heavy synthetic
    generators this turns ``gen + step`` per iteration into
    ``max(gen, step)``; on a single-core host the generator remains the
    floor — a real input stack spreads it over many loader processes.
    """
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=capacity)
    _END = object()

    span = obs.tracer().program_span

    def produce():
        it = iter(batches)
        try:
            for seq in itertools.count():
                with span(phases.INPUT_PRODUCE, seq=seq):
                    item = next(it, _END)
                if item is _END:
                    break
                q.put(item)
        finally:
            q.put(_END)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    for seq in itertools.count():
        with span(phases.INPUT_SOURCE_WAIT, seq=seq):
            item = q.get()
        if item is _END:
            break
        yield item
