"""ps_tpu — a TPU-native parameter-server training framework.

A from-scratch rebuild of the capabilities of ``Distributed-Deep-Learning/ps``
(a ps-lite/BytePS-family parameter server: CUDA/NCCL intra-node reduce + ZMQ
cross-node push/pull + C++ server-side optimizers), redesigned for TPU:

- Worker tensors are ``jax.Array``s.
- The NCCL-reduce + ZMQ push/pull pair collapses into XLA collectives
  (``lax.psum`` / reduce-scatter / all-gather) over the ICI mesh.
- The server's per-key optimizer apply (SGD/Adam/LAMB) is a jit-sharded
  update over a mesh-partitioned parameter pytree.
- Sparse embedding row push/pull maps to ``lax.all_to_all`` row exchange.

Capability map vs the reference (see SURVEY.md §2/§3; the reference itself was
unreadable this round — SURVEY.md §0):

==========================  =================================================
reference (GPU/PS)          ps_tpu (TPU-native)
==========================  =================================================
ps.init(backend=...)        :func:`ps_tpu.init` — 'local' | 'tpu'
KVWorker.Push/Pull (dense)  :class:`ps_tpu.KVStore` push/pull + fused
                            ``push_pull`` (one collective + sharded apply)
key→server range sharding   mesh-axis ``NamedSharding`` over the param pytree
server SGD/Adam/LAMB        optax under jit, state sharded next to params
sparse row push/pull        all_to_all row exchange + segment-sum dedupe
sync aggregation            implicit in SPMD psum
async + delay compensation  host-driven loop, DC-ASGD correction
ZMQ van / scheduler         XLA collectives (data) + host control plane
==========================  =================================================
"""

import time as _time

_T_IMPORT = _time.perf_counter()  # the start of the ``setup.import`` span

from ps_tpu.config import Config
from ps_tpu.api import init, shutdown, is_initialized, current_context
from ps_tpu.kv.store import KVStore
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.train import make_composite_step
from ps_tpu.backends.aggregator import AggregatorService, serve_aggregator
from ps_tpu.backends.remote_async import (
    ServerFailureError,
    connect_async,
    serve_async,
    shard_tree,
)
from ps_tpu.backends.remote_sparse import (
    connect_sparse,
    row_range,
    serve_sparse,
)
from ps_tpu import checkpoint
from ps_tpu import compress
from ps_tpu import optim
from ps_tpu import replica
from ps_tpu.replica import PromotionWatch
from ps_tpu.data.files import file_batches, write_dataset
from ps_tpu.ops import flash_attention

__version__ = "0.1.0"

__all__ = [
    "Config",
    "init",
    "shutdown",
    "is_initialized",
    "current_context",
    "KVStore",
    "SparseEmbedding",
    "make_composite_step",
    "serve_async",
    "connect_async",
    "shard_tree",
    "serve_aggregator",
    "AggregatorService",
    "serve_sparse",
    "connect_sparse",
    "row_range",
    "ServerFailureError",
    "checkpoint",
    "compress",
    "optim",
    "replica",
    "PromotionWatch",
    "file_batches",
    "write_dataset",
    "flash_attention",
    "__version__",
]

# this import's own span (ps_tpu/obs/phases.py, SETUP_SPANS); importing
# ps_tpu.obs has put the listener of the compiler's events in place
from ps_tpu import obs as _obs  # noqa: E402

_obs.tracer().record_program(_obs.phases.SETUP_IMPORT, _T_IMPORT,
                             _time.perf_counter() - _T_IMPORT)
