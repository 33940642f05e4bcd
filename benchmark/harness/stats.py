"""The arithmetic from readings to end-to-end numbers. No JAX in here."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def throughput(samples_per_step_per_chip: float, steps: int,
               window_s: float) -> float:
    """Samples per second per chip over all the work and all the time of
    the window (a whole number of blocks, each ended by a block on its last
    loss)."""
    return samples_per_step_per_chip * steps / window_s


def loss_at_n(losses: Sequence[float], n: int, span: int = 8) -> float:
    """Mean loss over steps n-span+1 .. n, counted from step 0 of the run."""
    if len(losses) <= n:
        raise ValueError(f"run has {len(losses)} steps, loss_at_n needs "
                         f"step {n}")
    return statistics.fmean(losses[n - span + 1:n + 1])


def stall_share(block_s: Sequence[float], window_s: float) -> float:
    """Percent of the window by which it outlasted blocks x the median
    block: what a median-of-blocks rate would hide. Negative when most
    blocks are slower than a few fast ones."""
    return 100.0 * (window_s - len(block_s) * statistics.median(block_s)) \
        / window_s


def all_finite(values: Sequence[float]) -> bool:
    return all(math.isfinite(v) for v in values)
