"""Failure detection + fault injection — SURVEY.md §6, VERDICT r1 item 4.

Layer 1: the native heartbeat van primitives (C++ UDP beat/monitor threads)
in one process. Layer 2: a real multi-process run where one process is
SIGKILL-hard-killed mid-training and the survivors must surface a timely,
typed WorkerFailureError naming it — not hang in the next collective.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from ps_tpu.control import (
    FailureDetector,
    HeartbeatClient,
    HeartbeatServer,
    WorkerFailureError,
)

_WORKER = os.path.join(os.path.dirname(__file__), "mp_worker.py")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _free_udp_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _wait_until(cond, timeout=5.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


# -- layer 1: native van primitives ------------------------------------------


def test_heartbeat_alive_then_dead():
    with HeartbeatServer(timeout_ms=300) as srv:
        c1 = HeartbeatClient("127.0.0.1", srv.port, node_id=1, interval_ms=40)
        c2 = HeartbeatClient("127.0.0.1", srv.port, node_id=2, interval_ms=40)
        assert _wait_until(lambda: srv.alive() == [1, 2])
        assert srv.dead() == []
        assert srv.seq(1) > 0 and srv.seq(2) > 0
        c1.close()  # node 1 stops beating = death, from the monitor's view
        assert _wait_until(lambda: srv.dead() == [1], timeout=2.0)
        assert srv.alive() == [2]
        c2.close()


def test_heartbeat_seq_monotonic():
    with HeartbeatServer(timeout_ms=500) as srv:
        with HeartbeatClient("127.0.0.1", srv.port, node_id=7, interval_ms=20):
            assert _wait_until(lambda: srv.seq(7) >= 3, timeout=2.0)
            a = srv.seq(7)
            assert _wait_until(lambda: srv.seq(7) > a, timeout=2.0)
    with pytest.raises(RuntimeError, match="closed"):
        srv.seq(7)


def test_failure_detector_pairwise():
    """Two in-process detectors watching each other; one closes, the other
    raises a typed error."""
    pa, pb = _free_udp_port(), _free_udp_port()
    a = FailureDetector(0, peers={1: ("127.0.0.1", pb)}, port=pa,
                        interval_ms=40, timeout_ms=300)
    b = FailureDetector(1, peers={0: ("127.0.0.1", pa)}, port=pb,
                        interval_ms=40, timeout_ms=300)
    a.wait_for_peers(timeout_s=5)
    b.wait_for_peers(timeout_s=5)
    a.check()
    b.check()
    b.close()  # b dies
    assert _wait_until(
        lambda: bool(a.server.dead()), timeout=2.0
    ), "b's death was never detected"
    with pytest.raises(WorkerFailureError) as ei:
        a.check()
    assert ei.value.dead == [1]
    a.close()


def test_detector_wait_for_peers_timeout():
    p = _free_udp_port()
    d = FailureDetector(0, peers={9: ("127.0.0.1", p)}, port=0,
                        interval_ms=50, timeout_ms=300)
    with pytest.raises(TimeoutError, match="9"):
        d.wait_for_peers(timeout_s=0.3)
    d.close()


def test_clean_leave_is_not_death():
    """A client that closes with goodbye=True becomes *left*, never *dead*:
    the surviving detector's check() stays silent past the death horizon."""
    pa, pb = _free_udp_port(), _free_udp_port()
    a = FailureDetector(0, peers={1: ("127.0.0.1", pb)}, port=pa,
                        interval_ms=40, timeout_ms=300)
    b = FailureDetector(1, peers={0: ("127.0.0.1", pa)}, port=pb,
                        interval_ms=40, timeout_ms=300)
    a.wait_for_peers(timeout_s=5)
    b.wait_for_peers(timeout_s=5)
    b.close(goodbye=True)  # clean leave
    assert _wait_until(lambda: a.left() == [1], timeout=2.0)
    time.sleep(0.5)  # well past timeout_ms: silence after goodbye stays clean
    a.check()  # must not raise
    assert a.server.dead() == []
    assert a.left() == [1]
    a.close()


def test_forged_goodbye_is_ignored():
    """A goodbye is only honored from the exact source address the node's
    beats come from: a datagram forged from any other socket must not
    silence death detection (code-review r3 finding on the 'left' state)."""
    import struct

    with HeartbeatServer(timeout_ms=400, bind="127.0.0.1") as srv:
        c = HeartbeatClient("127.0.0.1", srv.port, node_id=5, interval_ms=40)
        assert _wait_until(lambda: srv.alive() == [5])
        # forge a goodbye for node 5 from a different socket (source port
        # differs from the beating client's fd)
        forged = struct.pack("<IIQ", 0x50534742, 5, 2**64 - 1)
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            for _ in range(3):
                s.sendto(forged, ("127.0.0.1", srv.port))
        time.sleep(0.2)
        assert srv.left() == []  # forgery rejected
        assert srv.alive() == [5]
        c.close()  # silent stop: a real death must still be detected
        assert _wait_until(lambda: srv.dead() == [5], timeout=2.0)


def test_bind_loopback_and_any():
    """Both bind modes produce a working monitor (the pod-real default is
    0.0.0.0; tests may confine to loopback)."""
    for bind in ("0.0.0.0", "127.0.0.1"):
        with HeartbeatServer(timeout_ms=300, bind=bind) as srv:
            with HeartbeatClient("127.0.0.1", srv.port, node_id=3,
                                 interval_ms=30):
                assert _wait_until(lambda: srv.alive() == [3]), bind


@pytest.mark.slow
def test_tsan_van_clean():
    """SURVEY.md §6: the native van runs its full concurrent surface under
    ThreadSanitizer (tools/tsan_van.cpp driver) with zero reports."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    script = os.path.join(_REPO, "tools", "tsan_van.sh")
    proc = subprocess.run([script], capture_output=True, text=True,
                          timeout=300)
    if "libtsan" in proc.stderr and proc.returncode != 0 and (
            "cannot find" in proc.stderr or "No such file" in proc.stderr):
        pytest.skip("libtsan unavailable")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "TSAN: clean" in proc.stdout


@pytest.mark.slow
def test_asan_van_clean():
    """The memory-safety sibling: the same native driver under
    AddressSanitizer (leaks included) + UndefinedBehaviorSanitizer
    (tools/asan_van.sh) with zero reports."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    script = os.path.join(_REPO, "tools", "asan_van.sh")
    proc = subprocess.run([script], capture_output=True, text=True,
                          timeout=300)
    if "libasan" in proc.stderr and proc.returncode != 0 and (
            "cannot find" in proc.stderr or "No such file" in proc.stderr):
        pytest.skip("libasan unavailable")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ASAN/UBSAN: clean" in proc.stdout


# -- the jax coordination seam the clean-abort path rides ---------------------


def test_coordination_seam_accepts_recoverable_kwargs():
    """Pin the private jax API `_coordination_client_options` patches
    (ps_tpu/backends/tpu.py): the resolved coordination seam must accept
    ``recoverable``/``shutdown_on_destruction``. If jax moves the seam or
    drops the kwargs, the abort path silently degrades to
    LOG(FATAL)-on-peer-death — this test turns that into a loud CI failure
    (VERDICT r3 item 9 / r4 item 4)."""
    from ps_tpu.backends.tpu import _coordination_seam

    _, factory = _coordination_seam()  # AttributeError = seam moved
    # constructing (without connect()) exercises kwarg acceptance; a
    # TypeError here is exactly the degradation the runtime warning masks
    client = factory("127.0.0.1:1", 0, init_timeout=1,
                     recoverable=True, shutdown_on_destruction=False)
    assert client is not None


def test_coordination_client_options_inject_without_degrading():
    """The context manager swaps the factory in (at the seam) and restores
    it, and the patched factory builds a client WITHOUT tripping its
    TypeError fallback (which would warn and strip the recoverable
    semantics)."""
    import warnings

    from ps_tpu.backends.tpu import (
        _coordination_client_options,
        _coordination_seam,
    )

    owner, orig = _coordination_seam()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with _coordination_client_options():
            patched = owner.get_distributed_runtime_client
            assert patched is not orig
            client = patched("127.0.0.1:1", 0, init_timeout=1)
            assert client is not None
    assert owner.get_distributed_runtime_client is orig
    degraded = [w for w in caught
                if "no longer accepts" in str(w.message)
                or "seam moved" in str(w.message)]
    assert not degraded, [str(w.message) for w in degraded]


# -- layer 2: kill a process mid-run -----------------------------------------


@pytest.mark.slow
def test_kill_process_mid_run_surfaces_typed_error(tmp_path):
    """3 processes train together with heartbeats on; process 2 hard-dies
    after step 0; processes 0 and 1 must detect it and exit cleanly with a
    WorkerFailureError naming process 2 — within seconds, not hanging."""
    nproc, victim = 3, 2
    port = _free_port()
    hb_base = _free_udp_port()
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env_base["PYTHONPATH"] = _REPO + os.pathsep + env_base.get("PYTHONPATH", "")
    env_base["PS_TEST_FAULT_VICTIM"] = str(victim)
    env_base["PS_HEARTBEAT_BASE_PORT"] = str(hb_base)
    env_base["PS_HEARTBEAT_TIMEOUT_MS"] = "500"
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), str(nproc), str(port),
             str(tmp_path), "1", "10"],
            env=dict(env_base),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(nproc)
    ]
    t0 = time.monotonic()
    outs = [p.communicate(timeout=180)[0] for p in procs]
    elapsed = time.monotonic() - t0

    assert procs[victim].returncode == 17, outs[victim]  # died as injected
    for pid in (0, 1):
        assert procs[pid].returncode == 0, f"survivor {pid}:\n{outs[pid]}"
        with open(os.path.join(tmp_path, f"proc{pid}.json")) as f:
            r = json.load(f)
        assert r["failure_detected"] == [victim], r
        assert len(r["losses"]) >= 1  # it really was mid-run
    # timely: well under the 10-step runtime, nowhere near a hang
    assert elapsed < 120, f"detection took {elapsed:.1f}s"


@pytest.mark.slow
def test_clean_leave_mid_run_no_error(tmp_path):
    """3 processes with heartbeats on; process 2 leaves CLEANLY after step 0
    (goodbye + barrier-free teardown). Survivors must observe *left* — not
    raise WorkerFailureError — and exit 0 through ps.shutdown(abort=True)."""
    nproc, leaver = 3, 2
    port = _free_port()
    hb_base = _free_udp_port()
    env_base = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env_base["PYTHONPATH"] = _REPO + os.pathsep + env_base.get("PYTHONPATH", "")
    env_base["PS_TEST_LEAVER"] = str(leaver)
    env_base["PS_HEARTBEAT_BASE_PORT"] = str(hb_base)
    env_base["PS_HEARTBEAT_TIMEOUT_MS"] = "500"
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), str(nproc), str(port),
             str(tmp_path), "1", "10"],
            env=dict(env_base),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for pid in range(nproc)
    ]
    outs = [p.communicate(timeout=180)[0] for p in procs]
    for pid in range(nproc):
        assert procs[pid].returncode == 0, f"proc {pid}:\n{outs[pid]}"
    with open(os.path.join(tmp_path, f"proc{leaver}.json")) as f:
        assert json.load(f)["left"] is True
    for pid in (0, 1):
        with open(os.path.join(tmp_path, f"proc{pid}.json")) as f:
            r = json.load(f)
        # the other survivor's own clean goodbye may race into the snapshot;
        # what matters is the leaver was seen as LEFT and nobody saw a death
        assert leaver in r["left_detected"], r
        assert "failure_detected" not in r
