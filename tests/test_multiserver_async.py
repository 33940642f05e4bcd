"""Multi-server cross-process async PS — VERDICT r3 item 1, SURVEY.md §3
row 4 / §4d.

The reference's async topology is N server PROCESSES each owning a key
range, not one process owning the tree. Here two real server processes each
own the subtree ``shard_for_key`` assigns them, three real worker processes
route per-subtree pushes/pulls to the owners over the van, and:

- the key partition is validated end to end (disjoint, complete, matching
  the hash assignment);
- each server sees every worker's pushes, with per-server staleness;
- replaying each server's event log through an in-process AsyncTpuServer
  engine restricted to its key range reproduces the merged final parameters
  bit-for-bit — the wire AND the partition change nothing about the math;
- killing one server process surfaces a typed ServerFailureError at a live
  worker (the fault story of the sharded topology).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.backends.remote_async import ServerFailureError, shard_tree
from ps_tpu.kv import keys as keymod

_WORKER = os.path.join(os.path.dirname(__file__), "mp_async_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NSHARDS, NWORKERS, CYCLES = 2, 3, 6


def _free_ports(n: int) -> list:
    """n DISTINCT free ports: every socket stays bound until all are picked
    (closing each before binding the next lets the kernel hand the same
    port out twice — seen once in PR 21's tier-1 runs)."""
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("localhost", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _spawn(role, ports, out_dir, a, b, extra=()):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, _WORKER, role, str(ports), str(out_dir),
         str(a), str(b), *map(str, extra)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


@pytest.fixture(scope="module")
def mp_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("multiserver_async")
    ports = _free_ports(NSHARDS)
    servers = [_spawn("server", ports[s], out, NWORKERS, CYCLES,
                      extra=(s, NSHARDS))
               for s in range(NSHARDS)]
    port_list = ",".join(map(str, ports))
    workers = [_spawn("worker", port_list, out, w, CYCLES)
               for w in range(NWORKERS)]
    outs = [p.communicate(timeout=240)[0] for p in servers + workers]
    for p, o in zip(servers + workers, outs):
        assert p.returncode == 0, f"{p.args}:\n{o}"
    infos = []
    for s in range(NSHARDS):
        with open(out / f"server{s}.json") as f:
            infos.append(json.load(f))
    finals = [dict(np.load(out / f"server_params{s}.npz"))
              for s in range(NSHARDS)]
    return out, infos, finals


def test_key_partition_is_disjoint_and_complete(mp_run):
    from tests.mp_async_worker import _model_params

    _, infos, _ = mp_run
    kv, _ = keymod.flatten_with_keys(_model_params())
    seen = {}
    for s, info in enumerate(infos):
        assert info["keys"], f"shard {s} owns no keys (degenerate test)"
        for k in info["keys"]:
            assert k not in seen, f"key {k} owned by shards {seen[k]} and {s}"
            assert keymod.shard_for_key(k, NSHARDS) == s
            seen[k] = s
    assert sorted(seen) == sorted(kv)


def test_every_server_sees_every_worker(mp_run):
    out, infos, _ = mp_run
    for s, info in enumerate(infos):
        assert len(info["apply_log"]) == NWORKERS * CYCLES
        assert sorted(set(info["apply_log"])) == list(range(NWORKERS))
        assert info["version"] == NWORKERS * CYCLES
        hist = {int(t): n for t, n in info["staleness_hist"].items()}
        assert sum(hist.values()) == NWORKERS * CYCLES
    # worker-side: total version = sum over servers
    for w in range(NWORKERS):
        with open(out / f"worker{w}.json") as f:
            r = json.load(f)
        assert len(r["versions"]) == CYCLES
        assert len(r["per_server_versions"]) == NSHARDS
        assert r["versions"][-1] == sum(r["per_server_versions"])


def test_replay_per_shard_engines_bit_identical(mp_run):
    """The partition parity contract: replay each server's event log through
    an in-process engine owning only that key range; the merged result is
    byte-equal to the merged server dumps."""
    from tests.mp_async_worker import _model_params, make_grads

    _, infos, finals = mp_run
    params = _model_params()
    ps.init(backend="tpu", mode="async", num_workers=NWORKERS, dc_lambda=0.04)
    merged_final, merged_replay = {}, {}
    for s, (info, final) in enumerate(zip(infos, finals)):
        owned = shard_tree(params, s, NSHARDS)
        assert sorted(owned) == sorted(info["keys"])
        store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
        store.init(owned)
        eng = store._engine
        pushes = {w: 0 for w in range(NWORKERS)}
        for op, w in info["event_log"]:
            if op == "pull":
                eng.pull_tree(worker=w)
            else:
                kv, _ = keymod.flatten_with_keys(make_grads(params, w, pushes[w]))
                eng.push_tree(
                    {k: np.asarray(v) for k, v in kv.items() if k in owned},
                    worker=w,
                )
                pushes[w] += 1
        replayed = eng.pull_tree(worker=0)
        assert dict(eng.staleness_hist) == {
            int(t): n for t, n in info["staleness_hist"].items()
        }
        merged_final.update(final)
        merged_replay.update({k: np.asarray(v) for k, v in replayed.items()})
    ps.shutdown()
    kv, _ = keymod.flatten_with_keys(params)
    assert sorted(merged_final) == sorted(kv)
    for k in merged_final:
        np.testing.assert_array_equal(merged_final[k], merged_replay[k],
                                      err_msg=k)


def test_kill_one_server_raises_typed_error(tmp_path):
    """SIGKILL one server of the partition mid-job: a live worker's next
    cycle must surface ServerFailureError naming the dead server — not hang,
    not a bare socket error."""
    from tests.mp_async_worker import _model_params, make_grads

    ports = _free_ports(NSHARDS)
    # cycles huge: servers wait for pushes that never all arrive; the test
    # kills them instead
    servers = [_spawn("server", ports[s], tmp_path, NWORKERS, 10_000,
                      extra=(s, NSHARDS))
               for s in range(NSHARDS)]
    try:
        # jax import + store init in the server subprocesses takes longer
        # than the worker's connect retry budget: wait for the listeners
        deadline = time.monotonic() + 120
        for p in ports:
            while time.monotonic() < deadline:
                try:
                    socket.create_connection(("127.0.0.1", p),
                                             timeout=1).close()
                    break
                except OSError:
                    time.sleep(0.2)
            else:
                pytest.fail(f"server on port {p} never came up")
        params = _model_params()
        uri = ",".join(f"127.0.0.1:{p}" for p in ports)
        w = ps.connect_async(uri, 0, params)
        w.pull_all()
        w.push_pull(make_grads(params, 0, 0))
        assert w.version >= 1

        servers[0].send_signal(signal.SIGKILL)
        servers[0].wait(timeout=10)
        with pytest.raises(ServerFailureError, match=r"server 0"):
            for c in range(1, 20):  # first push may land in dead buffers
                w.push_pull(make_grads(params, 0, c))
                time.sleep(0.05)
        # the surviving server is still serving: direct single-server
        # connect to shard 1 works
        for ch in w._chs:
            ch.close()
    finally:
        for p in servers:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


def test_misconfigured_topology_fails_loudly():
    """Dialing only one server of a 2-shard partition is a connect-time
    error (missing keys), as is a shard-count mismatch."""
    from tests.mp_async_worker import _model_params

    params = _model_params()
    ps.init(backend="tpu", mode="async", num_workers=1)
    owned = shard_tree(params, 0, NSHARDS)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
    store.init(owned)
    from ps_tpu.backends.remote_async import AsyncPSService

    svc = AsyncPSService(store, bind="127.0.0.1", shard=0,
                         num_shards=NSHARDS)
    try:
        with pytest.raises(ValueError, match="dialed 1 server"):
            ps.connect_async(f"127.0.0.1:{svc.port}", 0, params)
    finally:
        svc.stop()
        ps.shutdown()


def test_service_rejects_misplaced_keys():
    """A store holding keys outside its declared shard is refused at
    service construction."""
    from tests.mp_async_worker import _model_params

    params = _model_params()
    ps.init(backend="tpu", mode="async", num_workers=1)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
    store.init(params)  # FULL tree, but claims to be shard 0 of 2
    from ps_tpu.backends.remote_async import AsyncPSService

    with pytest.raises(ValueError, match="not owned by shard"):
        AsyncPSService(store, bind="127.0.0.1", shard=0, num_shards=NSHARDS)
    ps.shutdown()
