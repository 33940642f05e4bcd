"""Subprocess entries for the cross-process async PS test
(tests/test_remote_async.py).

Roles (argv[1]):
  server <port> <out_dir> <nworkers> <cycles> [<shard> <nshards>]
      owns the async KVStore + AsyncPSService; waits until every worker's
      pushes arrived, then dumps final params (exact bytes), the apply/pull
      event log, and the staleness histogram. With the optional shard args
      it owns only its shard_for_key range (multi-server partition,
      tests/test_multiserver_async.py) and suffixes its output files with
      the shard index.
  worker <ports> <out_dir> <worker_id> <cycles>
      a separate async NODE: pull -> local grad (deterministic fn of
      (worker, cycle)) -> push, with jitter so pushes interleave across
      processes and real cross-process staleness accrues. <ports> may be a
      comma-separated list naming every server of a partition.

The parity contract: replaying each server's event log through a threaded
AsyncTpuServer in the parent reproduces the final params bit-for-bit.
"""

import json
import os
import sys
import time


def _model_params():
    import jax
    import jax.numpy as jnp

    from ps_tpu.models.mlp import MLP

    model = MLP(hidden=16)
    return model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))["params"]


def make_grads(params, worker: int, cycle: int):
    """Deterministic per-(worker, cycle) gradient tree — the replay in the
    parent regenerates the same values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(params)
    rng = np.random.default_rng([worker, cycle])
    return jax.tree_util.tree_unflatten(
        treedef,
        [jnp.asarray(rng.normal(0, 0.1, x.shape).astype(np.float32))
         for x in leaves],
    )


def run_server(port: int, out_dir: str, nworkers: int, cycles: int,
               shard=None, nshards=None) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import ps_tpu as ps
    from ps_tpu.backends.remote_async import AsyncPSService, shard_tree

    params = _model_params()
    suffix = "" if shard is None else str(shard)
    if nshards is not None:
        params = shard_tree(params, shard, nshards)
    ps.init(backend="tpu", mode="async", num_workers=nworkers, dc_lambda=0.04)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
    store.init(params)
    # full history: the parent replays this server's event log bit-for-bit
    # (the logs are bounded rings by default)
    svc = AsyncPSService(store, port=port, bind="127.0.0.1",
                         shard=shard, num_shards=nshards,
                         record_full_history=True)
    # quiesce on worker SHUTDOWNs, not apply counts: a worker says goodbye
    # only after its final push's reply arrived, so at goodbyes==nworkers
    # nothing is in flight anywhere and stop() cannot race a reply
    target = nworkers * cycles
    if not svc.wait_for_goodbyes(nworkers, timeout=120):
        raise TimeoutError(
            f"only {svc.goodbyes}/{nworkers} workers said goodbye "
            f"({len(svc.apply_log)}/{target} pushes arrived)"
        )
    assert len(svc.apply_log) == target, \
        f"{len(svc.apply_log)}/{target} pushes after all goodbyes"
    final = {k: np.asarray(v)
             for k, v in store._engine.pull_tree(worker=0).items()}
    np.savez(os.path.join(out_dir, f"server_params{suffix}.npz"), **final)
    with open(os.path.join(out_dir, f"server{suffix}.json"), "w") as f:
        json.dump({
            "event_log": svc.event_log,
            "apply_log": svc.apply_log,
            "keys": svc._key_order,
            "staleness_hist": {
                str(t): n for t, n in store._engine.staleness_hist.items()
            },
            "version": store._engine.version,
        }, f)
    svc.stop()
    ps.shutdown()
    return 0


def _start_line(out_dir: str, worker: int) -> None:
    """Hold this worker until all ``$MP_ASYNC_START_LINE`` workers of the
    test have connected. Process start-up (importing jax) skews by seconds
    on a loaded host while a worker's cycles last a tenth of one, so
    without a common start the pushes of a late worker meet nobody else's
    and no staleness accrues. Unset: no line (the partition tests)."""
    n = int(os.environ.get("MP_ASYNC_START_LINE", "0"))
    if not n:
        return
    open(os.path.join(out_dir, f"ready{worker}"), "w").close()
    deadline = time.monotonic() + 120
    while sum(f.startswith("ready") for f in os.listdir(out_dir)) < n:
        if time.monotonic() > deadline:
            raise TimeoutError(f"worker {worker}: fewer than {n} workers "
                               f"reached the start line in 120 s")
        time.sleep(0.001)


def run_worker(ports: str, out_dir: str, worker: int, cycles: int) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from ps_tpu.backends.remote_async import connect_async

    params = _model_params()
    uri = ",".join(f"127.0.0.1:{p}" for p in ports.split(","))
    w = connect_async(uri, worker, params)
    versions = []
    _start_line(out_dir, worker)
    w.pull_all()
    for c in range(cycles):
        # jitter so the three workers' pushes interleave (staleness > 0)
        time.sleep(0.003 * ((worker * 7 + c * 3) % 5))
        w.push_pull(make_grads(params, worker, c))
        versions.append(w.version)
    with open(os.path.join(out_dir, f"worker{worker}.json"), "w") as f:
        json.dump({"worker": worker, "versions": versions,
                   "per_server_versions": w.versions}, f)
    w.close()
    return 0


def main() -> int:
    role = sys.argv[1]
    out_dir = sys.argv[3]
    a, b = int(sys.argv[4]), int(sys.argv[5])
    os.environ["JAX_PLATFORMS"] = "cpu"
    if role == "server":
        shard = int(sys.argv[6]) if len(sys.argv) > 6 else None
        nshards = int(sys.argv[7]) if len(sys.argv) > 7 else None
        return run_server(int(sys.argv[2]), out_dir, a, b, shard, nshards)
    return run_worker(sys.argv[2], out_dir, a, b)


if __name__ == "__main__":
    sys.exit(main())
