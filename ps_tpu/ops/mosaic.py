"""Whether a Mosaic call is interpreted: the one place that asks."""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True where a ``pl.pallas_call`` being traced is not for a TPU, so the
    CPU tests run the kernels' own code. The device is ``jax.default_device``'s
    or, where nothing set it (on the chip nothing does), the process's first.
    May be asked under any ``jax.jit`` or ``jax.checkpoint``: that state is in
    their caches' keys (jax declares it ``include_in_jit_key`` and
    ``include_in_trace_context``), so a trace for one device is never served
    to another, and ``with jax.default_device("tpu"): jax.make_jaxpr(f)(..)``
    is the program the chip traces, in a process that has no chip."""
    device = jax.config.jax_default_device or jax.devices()[0]
    return getattr(device, "platform", device) != "tpu"
