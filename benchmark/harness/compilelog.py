"""Counts XLA compile requests and persistent-cache hits from jax's own
monitoring events. Copied from chip_smoke.py's CompileLog (sound, PR 21) so
that a later change to the program cannot change the yardstick."""

from __future__ import annotations

# jax's monitoring event names (jax/_src/dispatch.py, jax/_src/compiler.py)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """A request answered from the persistent cache still counts: ``count``
    moving after warm-up means a step was retraced, wherever the executable
    came from."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += seconds

    def _event(self, event: str, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1
        elif event == _CACHE_MISS_EVENT:
            self.misses += 1
