"""Process-level init/shutdown and the global runtime context.

Mirrors the reference's ``ps.init(backend=...)`` entrypoint (SURVEY.md §3
row 1, verified in BASELINE.json's north star). In the reference family this
starts the ZMQ van, registers with the scheduler, and allocates
KVWorker/KVServer objects. Here:

- ``backend='local'``: no network, no mesh — a single-process in-memory
  server (the reference's "single-process local PS, CPU" mode, config 1).
- ``backend='tpu'``: optional ``jax.distributed.initialize`` (multi-host
  rendezvous — the scheduler equivalent), then a ``jax.sharding.Mesh`` over
  all devices. Worker/server roles become mesh axes, not processes.
"""

from __future__ import annotations

import threading
from typing import Optional

from ps_tpu import obs
from ps_tpu.config import Config
from ps_tpu.obs import phases


class Context:
    """The live runtime created by :func:`init`.

    Holds the config, the backend engine, and (tpu backend) the device mesh.
    """

    def __init__(self, config: Config, backend, mesh=None):
        self.config = config
        self.backend = backend
        self.mesh = mesh

    @property
    def num_workers(self) -> int:
        return self.backend.num_workers


_lock = threading.Lock()
_context: Optional[Context] = None


def init(backend: Optional[str] = None, config: Optional[Config] = None, **overrides) -> Context:
    """Initialize ps_tpu. Single-shot per process: a second call raises until
    :func:`shutdown` resets the runtime.

    Args:
      backend: 'local' or 'tpu'; overrides config.backend.
      config: full Config; default is ``Config.from_env()``.
      **overrides: any Config field, e.g. ``num_workers=4``,
        ``mesh_shape={'data': 8}``.
    """
    global _context
    with _lock, obs.tracer().program_span(phases.SETUP_INIT) as span:
        if _context is not None:
            raise RuntimeError("ps_tpu already initialized; call shutdown() first")
        if config is None:
            config = Config.from_env(**overrides)
        elif overrides:
            config = Config(**{**config.__dict__, **overrides})
        if backend is not None:
            config = Config(**{**config.__dict__, "backend": backend})

        if config.backend == "local":
            from ps_tpu.backends.local import LocalBackend

            be = LocalBackend(config)
            _context = Context(config, be, mesh=None)
        else:
            from ps_tpu.backends.tpu import TpuBackend

            be = TpuBackend(config)  # pslint: disable=PSL101 -- single-shot process init: the module lock exists to serialize exactly this construction (distributed rendezvous + detector warm-up); nothing else ever contends for it mid-job
            _context = Context(config, be, mesh=be.mesh)
        mesh = _context.mesh
        span.set(backend=config.backend,
                 devices=1 if mesh is None else mesh.size)
        return _context


def shutdown(abort: bool = False) -> None:
    """Tear down the runtime (barrier + socket close in the reference family;
    here: drop the context so a fresh init can follow).

    ``abort=True`` is the post-failure escape hatch: after a
    :class:`~ps_tpu.control.WorkerFailureError`, the normal teardown would
    hang in the ``jax.distributed`` shutdown barrier (a dead peer can never
    arrive), so abort announces a clean goodbye on the control plane and
    severs the coordination-service connection without barriers. The process
    can then exit normally."""
    global _context
    with _lock:
        if _context is not None:
            _context.backend.shutdown(abort=abort)
            _context = None


def is_initialized() -> bool:
    return _context is not None


def current_context() -> Context:
    if _context is None:
        raise RuntimeError("ps_tpu is not initialized; call ps_tpu.init() first")
    return _context
