"""Cross-process async PS — VERDICT r2 item 2, SURVEY.md §4d / §8 P4.

The one PS capability that previously existed only in single-controller
miniature: async workers as separate OS processes pushing stale gradients
to server state owned by another process. Three real worker processes drive
async training against one server process over the native van's TCP layer;
the staleness histogram shows REAL cross-process staleness; and replaying
the server's observed (pull/push, worker) event log through the threaded
AsyncTpuServer engine reproduces the final parameters bit-for-bit — the
wire changes nothing about the DC-ASGD math.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import ps_tpu as ps

_WORKER = os.path.join(os.path.dirname(__file__), "mp_async_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NWORKERS, CYCLES = 3, 8


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn(role, port, out_dir, a, b):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # the workers start their cycles together (mp_async_worker._start_line)
    env["MP_ASYNC_START_LINE"] = str(NWORKERS)
    return subprocess.Popen(
        [sys.executable, _WORKER, role, str(port), str(out_dir),
         str(a), str(b)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


@pytest.fixture(scope="module")
def mp_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("remote_async")
    port = _free_port()
    server = _spawn("server", port, out, NWORKERS, CYCLES)
    workers = [_spawn("worker", port, out, w, CYCLES)
               for w in range(NWORKERS)]
    outs = [p.communicate(timeout=240)[0] for p in [server] + workers]
    for p, o in zip([server] + workers, outs):
        assert p.returncode == 0, f"{p.args}:\n{o}"
    with open(out / "server.json") as f:
        server_info = json.load(f)
    final = dict(np.load(out / "server_params.npz"))
    return out, server_info, final


def test_three_processes_drive_one_server(mp_run):
    out, info, _ = mp_run
    assert len(info["apply_log"]) == NWORKERS * CYCLES
    assert sorted(set(info["apply_log"])) == list(range(NWORKERS))
    assert info["version"] == NWORKERS * CYCLES
    for w in range(NWORKERS):
        with open(out / f"worker{w}.json") as f:
            r = json.load(f)
        assert len(r["versions"]) == CYCLES
        assert r["versions"][-1] <= NWORKERS * CYCLES


def test_cross_process_staleness_is_real(mp_run):
    _, info, _ = mp_run
    hist = {int(t): n for t, n in info["staleness_hist"].items()}
    assert sum(hist.values()) == NWORKERS * CYCLES
    # with 3 jittered workers interleaving, some pushes MUST land stale
    assert sum(n for t, n in hist.items() if t > 0) > 0, hist


def test_replay_through_threaded_engine_is_bit_identical(mp_run):
    """The parity contract: the wire is transparent. Replaying the server's
    event log through a threaded AsyncTpuServer yields the same bytes."""
    from ps_tpu.kv import keys as keymod
    from tests.mp_async_worker import _model_params, make_grads

    _, info, final = mp_run
    params = _model_params()
    ps.init(backend="tpu", mode="async", num_workers=NWORKERS, dc_lambda=0.04)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.05, mode="async")
    store.init(params)
    eng = store._engine
    pushes = {w: 0 for w in range(NWORKERS)}
    for op, w in info["event_log"]:
        if op == "pull":
            eng.pull_tree(worker=w)
        else:
            kv, _ = keymod.flatten_with_keys(make_grads(params, w, pushes[w]))
            eng.push_tree(
                {k: np.asarray(v) for k, v in kv.items()}, worker=w
            )
            pushes[w] += 1
    replayed = eng.pull_tree(worker=0)
    assert sorted(replayed) == sorted(final)
    for k in final:
        np.testing.assert_array_equal(final[k], np.asarray(replayed[k]), err_msg=k)
    # and the histogram matches: staleness is a pure function of the order
    hist = {int(t): n for t, n in info["staleness_hist"].items()}
    assert dict(eng.staleness_hist) == hist
    ps.shutdown()


def test_coordinated_checkpoint_restart_roundtrip(tmp_path):
    """The multi-server checkpoint/restart story (SURVEY.md §6, VERDICT r4
    missing 7): a worker triggers a coordinated checkpoint across the key
    partition, the servers keep training past it, die, restart from their
    shard checkpoints on NEW ports, the worker reconnects — and observes
    exactly the checkpoint-time parameters and versions."""
    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import (
        AsyncPSService,
        connect_async,
        shard_tree,
    )
    from ps_tpu.kv import keys as keymod

    rng = np.random.default_rng(7)
    params = {f"p{i}/w": jnp.asarray(rng.normal(0, 1, (4, 3)).astype(np.float32))
              for i in range(6)}
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.04)

    def launch(restore_from=None):
        svcs = []
        for s in range(2):
            st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
            st.init(shard_tree(params, s, 2))
            if restore_from is not None:
                st.restore(f"{restore_from}/shard{s}")
            svcs.append(AsyncPSService(st, bind="127.0.0.1",
                                       shard=s, num_shards=2))
        return svcs

    svcs = launch()
    assert all(len(s._key_order) > 0 for s in svcs), "degenerate partition"
    w = connect_async(
        ",".join(f"127.0.0.1:{s.port}" for s in svcs), 0, params
    )
    w.pull_all()
    grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
    w.push_pull(grads)

    ck = str(tmp_path / "ck")
    versions = w.checkpoint_all(ck)
    assert sum(versions) == w.version == 2  # one tree apply per shard
    ref = {k: np.asarray(v)
           for k, v in keymod.flatten_with_keys(w._params)[0].items()}

    w.push_pull(grads)  # state diverges PAST the checkpoint
    for s in svcs:
        s.stop()

    svcs2 = launch(restore_from=ck)  # restart smaller world, new ports
    try:
        w.reconnect([("127.0.0.1", s.port) for s in svcs2])
        assert w.versions == versions  # version stream resumes, not resets
        pulled = keymod.flatten_with_keys(w.pull_all())[0]
        for k, v in ref.items():
            np.testing.assert_array_equal(v, np.asarray(pulled[k]), err_msg=k)
        w.push_pull(grads)  # and training continues on the restored state
        assert w.version == sum(versions) + 2
        w.close()
    finally:
        for s in svcs2:
            s.stop()
    ps.shutdown()


def test_checkpoint_is_cross_shard_atomic_under_concurrent_pushes(tmp_path):
    """The pause phase's reason to exist: every push_pull applies one
    subtree to EACH shard, so in any cross-shard-atomic snapshot the two
    shard versions are EQUAL. A snapshot torn by a concurrent push would
    capture (v, v+1). Hammer checkpoints while another worker pushes
    continuously and assert every snapshot is untorn."""
    import threading

    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import (
        AsyncPSService,
        connect_async,
        shard_tree,
    )

    rng = np.random.default_rng(3)
    params = {f"p{i}/w": jnp.asarray(rng.normal(0, 1, (4, 3)).astype(np.float32))
              for i in range(6)}
    ps.init(backend="tpu", mode="async", num_workers=2, dc_lambda=0.0)
    svcs = []
    for s in range(2):
        st = ps.KVStore(optimizer="sgd", learning_rate=0.01, mode="async")
        st.init(shard_tree(params, s, 2))
        svcs.append(AsyncPSService(st, bind="127.0.0.1",
                                   shard=s, num_shards=2))
    uri = ",".join(f"127.0.0.1:{s.port}" for s in svcs)
    pusher = connect_async(uri, 0, params)
    ckpter = connect_async(uri, 1, params)
    grads = {k: jnp.full_like(v, 0.01) for k, v in params.items()}
    stop = threading.Event()

    def push_loop():
        pusher.pull_all()
        while not stop.is_set():
            pusher.push_pull(grads)

    t = threading.Thread(target=push_loop)
    t.start()
    try:
        for i in range(5):
            versions = ckpter.checkpoint_all(str(tmp_path / f"ck{i}"))
            assert versions[0] == versions[1], \
                f"torn snapshot at checkpoint {i}: {versions}"
    finally:
        stop.set()
        t.join(timeout=30)
    assert not t.is_alive()
    pusher.close()
    ckpter.close()
    for s in svcs:
        s.stop()
    ps.shutdown()


def test_stop_drains_inflight_reply():
    """Regression (the r4 flake): ``stop()`` used to sever every channel
    immediately, tearing the reply of a PUSH_PULL whose apply was still in
    flight — the worker died with 'recv failed mid-frame: peer closed'.
    The drain contract (van_service.py): a request RECEIVED before stop()
    completes — its push applies and its full reply reaches the worker,
    even when stop() is called mid-apply."""
    import threading
    import time

    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import AsyncPSService, RemoteAsyncWorker

    params = {"w": jnp.zeros((256, 256))}
    ps.init(backend="tpu", mode="async", num_workers=1)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    store.init(params)
    svc = AsyncPSService(store, bind="127.0.0.1")
    w = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params)
    w.pull_all()

    eng = store._engine
    orig_push = eng.push_tree
    in_apply = threading.Event()
    release = threading.Event()

    def slow_push(grads, worker=0):
        in_apply.set()  # request received, apply started …
        release.wait(timeout=30)  # … and held open while stop() runs
        return orig_push(grads, worker=worker)

    eng.push_tree = slow_push
    result = {}

    def do_push_pull():
        try:
            result["params"] = w.push_pull({"w": jnp.ones((256, 256))})
        except Exception as e:  # noqa: BLE001 — recorded for the assert
            result["error"] = e

    pusher = threading.Thread(target=do_push_pull)
    pusher.start()
    assert in_apply.wait(timeout=30)
    stopper = threading.Thread(target=svc.stop)
    stopper.start()
    time.sleep(0.3)  # let stop() reach its in-flight drain wait
    assert pusher.is_alive(), "reply path torn while the apply was in flight"
    release.set()
    pusher.join(timeout=30)
    stopper.join(timeout=30)
    assert not pusher.is_alive() and not stopper.is_alive()
    assert "error" not in result, f"reply torn by stop(): {result.get('error')!r}"
    # the racing push COMMITTED and the worker saw the post-apply params
    assert eng.version == 1
    np.testing.assert_array_equal(
        np.asarray(result["params"]["w"]),
        np.asarray(eng.pull_tree(worker=0)["w"]),
    )
    w.close()
    ps.shutdown()


def test_wait_for_goodbyes_times_out_false():
    """The quiescence wait reports timeout as False (not an exception),
    and counts goodbyes exactly once per worker SHUTDOWN."""
    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import AsyncPSService, RemoteAsyncWorker

    params = {"w": jnp.zeros((4, 4))}
    ps.init(backend="tpu", mode="async", num_workers=2)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    store.init(params)
    svc = AsyncPSService(store, bind="127.0.0.1")
    w0 = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params)
    w0.pull_all()
    assert svc.wait_for_goodbyes(1, timeout=0.2) is False  # nobody left yet
    w0.close()
    assert svc.wait_for_goodbyes(1, timeout=10) is True
    assert svc.goodbyes == 1
    assert svc.wait_for_goodbyes(2, timeout=0.2) is False  # worker 1 never came
    svc.stop()
    ps.shutdown()


def test_idle_client_survives_slow_cadence():
    """Regression (r3): the accepted fd inherited the listener's 200ms
    accept-poll SO_RCVTIMEO on Linux, so any client thinking for longer
    than that (a jit compile, a slow batch) was cut off as 'peer closed'.
    A worker that idles >1s between requests must keep its connection."""
    import time

    import jax.numpy as jnp

    from ps_tpu.backends.remote_async import AsyncPSService, RemoteAsyncWorker

    params = {"w": jnp.zeros((64, 64))}
    ps.init(backend="tpu", mode="async", num_workers=1)
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    store.init(params)
    svc = AsyncPSService(store, bind="127.0.0.1")
    w = RemoteAsyncWorker("127.0.0.1", svc.port, 0, params)
    w.pull_all()
    for i in range(2):
        time.sleep(1.1)  # well past any accept-poll cadence
        w.push_pull({"w": jnp.ones((64, 64))})
    assert w.version == 2
    # drain contract: stop() severs live connections; a push after stop is
    # REFUSED (never silently applied post-drain) and the version is frozen
    svc.stop()
    with pytest.raises(Exception):
        w.push_pull({"w": jnp.ones((64, 64))})
    assert store._engine.version == 2
    w.close()
    ps.shutdown()
