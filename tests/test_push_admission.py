"""Zero-upcall push admission (README "Push path") — the native epoll
loop classifies dedup-tagged PUSH frames against a per-worker ledger
mirror and answers pure replays / role refusals without waking Python.

Drills:

- byte parity: the native replay ack and typed backup refusal are
  bit-identical to the pump oracle's replies (dense and sparse);
- exactly-once across the tiers: a natively-acked replay never re-applies
  (engine version pinned), and a fresh push after the mirror is seeded
  still applies exactly once;
- failover reseed: a promoted backup's re-seeded mirror suppresses the
  dead primary's in-flight replay natively, with the same bytes;
- PS_PUSH_NATIVE_ADMIT knob: Config roundtrip + service arming, and the
  four-surface sync pin (field / env / README / docstrings).
"""

import time

import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.backends.remote_async import AsyncPSService
from ps_tpu.backends.remote_sparse import SparsePSService
from ps_tpu.control import tensor_van as tv
from ps_tpu.kv.sparse import SparseEmbedding

import jax
import jax.numpy as jnp


def _params(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return {f"p{i}/w": jnp.asarray(rng.normal(0, 1, (4, 3)).astype(np.float32))
            for i in range(n)}


def _store(params, lr=0.1):
    st = ps.KVStore(optimizer="sgd", learning_rate=lr, mode="async")
    st.init(params)
    return st


def _grads(params, fill=0.1):
    return {k: np.full(np.asarray(v).shape, fill, np.float32)
            for k, v in params.items()}


def _push(port, payload):
    ch = tv.Channel.connect("127.0.0.1", port)
    try:
        return bytes(ch.request(bytes(payload)))
    finally:
        ch.close()


def _counted(svc, counter, want, deadline_s=10.0):
    """``admit_stats()[counter]`` once it has reached ``want``, or as it
    stands when ``deadline_s`` ran out. The native loop counts an ack or a
    refusal after it has written the reply (``van.cpp::nl_admit``), so the
    client can hold the bytes before the counter has moved: wait on the
    counter, do not read it once."""
    end = time.monotonic() + deadline_s
    while (got := svc._nloop.admit_stats()[counter]) < want \
            and time.monotonic() < end:
        time.sleep(0.005)
    return got


def _sparse_emb():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    emb = SparseEmbedding(64, 8, optimizer="sgd", learning_rate=0.5,
                          mesh=mesh)
    emb.init(np.random.default_rng(0)
             .normal(0, 0.01, (64, 8)).astype(np.float32))
    return emb


# -- byte parity: native vs pump ---------------------------------------------


def test_dense_replay_ack_byte_parity(request, monkeypatch):
    """The same tagged push + replay against a pump-only service and a
    native-admission service: replay replies are byte-identical, the
    native one is served from the loop (acks counter moves, version
    pinned), and a fresh follow-up still applies."""
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    params = _params()
    sub = _grads(params)
    first = tv.encode(tv.PUSH, 0, sub, extra={"pseq": 1, "pnonce": "inc"})
    replay = bytes(first)

    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "off")
    pump = AsyncPSService(_store(params), bind="127.0.0.1",
                          native_loop=True)
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "on")
    native = AsyncPSService(_store(params), bind="127.0.0.1",
                            native_loop=True)
    try:
        assert pump._native_admit is False
        assert native._native_admit is True
        for svc in (pump, native):
            kind, _, _, extra = tv.decode(_push(svc.port, first))
            assert kind == tv.OK and extra["dedup"] is False
        vpump, vnat = pump._engine.version, native._engine.version
        base = native._nloop.admit_stats()["acks"]
        raw_pump = _push(pump.port, replay)
        raw_native = _push(native.port, replay)
        assert raw_pump == raw_native
        kind, _, _, extra = tv.decode(raw_native)
        assert kind == tv.OK and extra["dedup"] is True
        # served natively, and never re-applied on either side
        assert _counted(native, "acks", base + 1) == base + 1
        assert pump._engine.version == vpump
        assert native._engine.version == vnat
        # a strictly-fresh seq still applies exactly once through Python
        fresh = tv.encode(tv.PUSH, 0, sub, extra={"pseq": 2, "pnonce": "inc"})
        kind, _, _, extra = tv.decode(_push(native.port, fresh))
        assert kind == tv.OK and extra["dedup"] is False
        assert native._engine.version == vnat + 1
        assert native._nloop.admit_stats()["fresh"] >= 1
    finally:
        pump.stop()
        native.stop()


def test_sparse_replay_ack_byte_parity(request, monkeypatch):
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    ids = np.array([1, 5, 9], np.int32)
    grads = np.full((3, 8), 0.25, np.float32)
    first = tv.encode(tv.ROW_PUSH, 0,
                      {"deep/ids": ids, "deep/grads": grads},
                      extra={"pseq": 3, "pnonce": "inc"})
    replay = bytes(first)

    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "off")
    pump = SparsePSService({"deep": _sparse_emb()}, bind="127.0.0.1",
                           native_loop=True)
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "auto")
    native = SparsePSService({"deep": _sparse_emb()}, bind="127.0.0.1",
                             native_loop=True)
    try:
        assert pump._native_admit is False
        assert native._native_admit is True
        for svc in (pump, native):
            kind, _, _, extra = tv.decode(_push(svc.port, first))
            assert kind == tv.OK and extra["dedup"] is False
        base = native._nloop.admit_stats()["acks"]
        vers = dict(native.versions)
        raw_pump = _push(pump.port, replay)
        raw_native = _push(native.port, replay)
        assert raw_pump == raw_native
        kind, _, _, extra = tv.decode(raw_native)
        assert kind == tv.OK and extra["dedup"] is True
        assert _counted(native, "acks", base + 1) == base + 1
        assert dict(native.versions) == vers  # exactly once
    finally:
        pump.stop()
        native.stop()


def test_backup_refusal_byte_parity(request, monkeypatch):
    """A tagged push at a backup: the native typed-ERR refusal is
    byte-identical to the pump's, and the push is never applied."""
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    params = _params()
    payload = tv.encode(tv.PUSH, 0, _grads(params),
                        extra={"pseq": 1, "pnonce": "inc"})

    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "off")
    pump = AsyncPSService(_store(params), bind="127.0.0.1", backup=True,
                          native_loop=True)
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "on")
    native = AsyncPSService(_store(params), bind="127.0.0.1", backup=True,
                            native_loop=True)
    try:
        base = native._nloop.admit_stats()["refusals"]
        raw_pump = _push(pump.port, bytes(payload))
        raw_native = _push(native.port, bytes(payload))
        assert raw_pump == raw_native
        kind, _, _, extra = tv.decode(raw_native)
        assert kind == tv.ERR and extra["backup"] is True
        assert "retry after promotion" in extra["error"]
        assert _counted(native, "refusals", base + 1) == base + 1
        assert native._engine.version == 0  # refused, not applied
    finally:
        pump.stop()
        native.stop()


# -- failover: the promoted mirror -------------------------------------------


def test_failover_reseeds_mirror_and_acks_natively(request, monkeypatch):
    """A push applied + replicated whose reply died with the primary is
    replayed at the promoted backup: the promote-time reseed lets the
    NATIVE tier suppress it — exactly once, pump-identical extra."""
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "on")
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    params = _params()
    prim = AsyncPSService(_store(params), bind="127.0.0.1",
                          native_loop=True)
    back = AsyncPSService(_store(params), bind="127.0.0.1", backup=True,
                          native_loop=True)
    prim.attach_backup("127.0.0.1", back.port, ack="sync")
    payload = tv.encode(tv.PUSH, 0, _grads(params),
                        extra={"pseq": 4, "pnonce": "inc"})
    try:
        kind, _, _, _ = tv.decode(_push(prim.port, bytes(payload)))
        assert kind == tv.OK
        assert back._engine.version == 1  # replicated (sync ack)
        prim.kill()
        back.promote(reason="test")
        base = back._nloop.admit_stats()["acks"]
        raw = _push(back.port, bytes(payload))
        kind, _, _, extra = tv.decode(raw)
        assert kind == tv.OK and extra["dedup"] is True
        assert extra["version"] == 1
        assert _counted(back, "acks", base + 1) == base + 1
        assert back._engine.version == 1  # exactly once across failover
    finally:
        back.stop()
        prim.stop()


# -- the knob -----------------------------------------------------------------


def test_push_admit_knob_roundtrip(request, monkeypatch):
    from ps_tpu.config import Config

    cfg = Config()
    assert cfg.push_native_admit == "auto"
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "on")
    assert Config.from_env().push_native_admit == "on"
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "OFF")  # case-folded
    assert Config.from_env().push_native_admit == "off"
    with pytest.raises(ValueError):
        Config(push_native_admit="always")

    # service arming: off disarms even with the loop up; an unknown
    # token warns and keeps the auto default (armed)
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    params = _params(n=1)
    for token, armed in (("off", False), ("on", True), ("bogus", True)):
        monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", token)
        svc = AsyncPSService(_store(params), bind="127.0.0.1",
                             native_loop=True)
        try:
            assert svc._native_admit is armed, token
        finally:
            svc.stop()
    # without the native loop there is no admission tier to arm
    monkeypatch.setenv("PS_PUSH_NATIVE_ADMIT", "on")
    svc = AsyncPSService(_store(params), bind="127.0.0.1")
    try:
        assert svc._native_admit is False
    finally:
        svc.stop()


def test_push_admit_knob_four_way_synced():
    """Pins the admission knob's four surfaces — Config field, PS_* env
    mirror, README, docstrings — by name (the PSL4xx gate flags drift
    repo-wide; this names the contract so a rename can't slip through a
    lint-rule change unnoticed)."""
    import dataclasses
    import os

    from ps_tpu import config as cfgmod

    fields = {f.name for f in dataclasses.fields(cfgmod.Config)}
    assert "push_native_admit" in fields
    assert "PS_PUSH_NATIVE_ADMIT" in cfgmod.__doc__
    assert "push_native_admit:" in cfgmod.Config.__doc__
    readme = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "README.md")
    with open(readme) as f:
        text = f.read()
    for name in ("PS_PUSH_NATIVE_ADMIT", "push_native_admit"):
        assert name in text, f"README lost the {name} row"
