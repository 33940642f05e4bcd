"""Profiling hooks — thin, dependency-free wrappers over jax.profiler.

SURVEY.md §6 "Tracing/profiling": the TPU-native mechanism is
``jax.profiler.trace`` (TensorBoard/Perfetto XPlane dumps, including ICI
collective timelines on real TPUs). The PS phases are findable in the trace
by the ``jax.named_scope`` names of ``ps_tpu/obs/phases.py``, which the
fused steps carry in every op's ``op_name``. The analytic GB/s counters in
ps_tpu/parallel/collectives.py can be cross-checked against the profiler's
ICI utilization on hardware.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Profile the enclosed block to ``log_dir`` (no-op when None).

    View with TensorBoard's profile plugin or Perfetto.
    """
    if log_dir is None:
        yield
        return
    import jax.profiler

    with jax.profiler.trace(log_dir):
        yield
