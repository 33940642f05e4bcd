"""Observability layer (ps_tpu/obs): histograms, tracing, flight
recorder, /metrics endpoint, clock sync, ps_top.

- histogram quantile estimates hold to their sub-bucket resolution
  against numpy on random samples;
- a trace context round-trips through a REAL in-process push/pull/replica
  cycle: the worker op span parents the server's apply span, which
  parents the backup's replica_append and the primary's ack-wait span;
- the flight recorder dumps JSONL on an induced unhandled VanError (the
  threading excepthook path — what a dead pump thread would trigger);
- the /metrics endpoint serves parseable Prometheus text with live
  counters and nonzero histogram counts;
- StepLogger.event mirrors into the flight recorder (step log and black
  box agree);
- tools/ps_top.py --once --json renders a live pair machine-readably.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu import obs
from ps_tpu.backends.remote_async import AsyncPSService, connect_async
from ps_tpu.control import tensor_van as tv
from ps_tpu.obs.clock import ClockSync
from ps_tpu.obs.flight import FlightRecorder
from ps_tpu.obs.http import MetricsServer
from ps_tpu.obs.metrics import Counter, Histogram, MetricsRegistry
from ps_tpu.obs.trace import Tracer, merge_chrome
from ps_tpu.utils.metrics import TransportStats
from ps_tpu.utils.step_log import StepLogger


@pytest.fixture
def sampled_tracer():
    """Flip the PROCESS tracer to always-sample for one test, restore
    after (other tests must keep the zero-cost off path)."""
    t = obs.tracer()
    old_sample = t.sample
    t.clear()
    t.sample = 1.0
    yield t
    t.sample = old_sample
    t.clear()


# -- histograms ---------------------------------------------------------------


@pytest.mark.parametrize("sigma", [0.5, 1.5])
def test_histogram_quantiles_match_numpy(sigma):
    rng = np.random.default_rng(7)
    xs = rng.lognormal(mean=-7, sigma=sigma, size=30_000)
    h = Histogram("t_seconds")
    for x in xs:
        h.record(x)
    # resolution is one sub-bucket: 2^(1/4) ≈ 1.19x; allow a hair more
    # for interpolation at the distribution's knees
    for q in (0.5, 0.9, 0.99, 0.999):
        est = h.quantile(q)
        true = float(np.quantile(xs, q))
        assert true / 1.25 <= est <= true * 1.25, (q, est, true)
    s = h.summary()
    assert s["count"] == len(xs)
    # summary rounds to 6 decimals for the STATS frame
    assert s["max"] == pytest.approx(float(xs.max()), abs=1e-6)
    assert s["mean"] == pytest.approx(float(xs.mean()), rel=1e-3, abs=1e-6)


def test_histogram_range_edges():
    h = Histogram("t", lo=1e-6, hi=10.0)
    h.record(1e-9)   # underflow
    h.record(100.0)  # overflow
    assert h.total == 2
    assert h.quantile(0.999) == pytest.approx(100.0)  # overflow = max seen
    assert h.counts[0] == 1 and h.counts[-1] == 1


def test_registry_merges_same_name_and_renders_prometheus():
    reg = MetricsRegistry()
    c1 = reg.counter("ps_things_total", "things")
    c2 = reg.counter("ps_things_total")
    c1.inc(3)
    c2.inc(4)
    h1 = reg.histogram("ps_lat_seconds", "lat")
    h2 = reg.histogram("ps_lat_seconds")
    h1.record(0.001)
    h2.record(0.004)
    g = reg.gauge("ps_lag", "lag")
    g.set(7)
    snap = reg.snapshot()
    assert snap["ps_things_total"] == 7
    assert snap["ps_lat_seconds"]["count"] == 2
    assert snap["ps_lag"] == 7
    text = reg.render_prometheus()
    assert "# TYPE ps_things_total counter" in text
    assert "ps_things_total 7" in text
    assert "ps_lat_seconds_count 2" in text
    # cumulative buckets are monotone and end at +Inf == count
    cum = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
           if line.startswith("ps_lat_seconds_bucket")]
    assert cum == sorted(cum) and cum[-1] == 2
    assert '+Inf' in text


def test_counter_name_sanitized():
    c = Counter("bad name-with.chars")
    assert " " not in c.name and "-" not in c.name and "." not in c.name


def test_transport_stats_feed_histograms_and_summary_quantiles():
    ts = TransportStats()
    for ms in (1, 2, 50):
        ts.record_repl_ack_wait(ms / 1e3)
    ts.record_failover(0.6)
    ts.record_op("push", 0.01)
    lat = ts.latency_quantiles()
    assert lat["repl_ack_wait_s"]["count"] == 3
    assert lat["failover_s"]["p99"] == pytest.approx(0.6, rel=0.3)
    assert lat["push_s"]["count"] == 1
    out = ts.summary()
    assert "lat" in out and "repl_ack_wait_s" in out["lat"]
    snap = ts.metrics_snapshot()
    assert snap["lat"]["repl_ack_wait_s"]["p999"] >= \
        snap["lat"]["repl_ack_wait_s"]["p50"]


# -- tracing ------------------------------------------------------------------


def test_tracer_off_path_is_noop_and_free():
    t = Tracer(sample=0.0)
    sp = t.span("push")
    assert not sp and sp.wire() is None and sp.ctx() is None
    with sp:
        assert t.current() is None
        assert not t.child("inner")
    assert t.spans() == []


def test_tracer_parentage_and_ring_bound():
    t = Tracer(sample=1.0, capacity=4)
    with t.span("root") as root:
        with t.child("inner") as inner:
            assert inner.parent_id == root.span_id
            assert inner.trace_id == root.trace_id
    follow = t.span("srv", parent=root.ctx())
    with follow:
        pass
    assert follow.parent_id == root.span_id
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t.spans()) == 4 and t.dropped > 0


def test_chrome_export_and_merge(tmp_path):
    t = Tracer(service="w0", sample=1.0)
    with t.span("push"):
        time.sleep(0.001)
    t2 = Tracer(service="srv", sample=1.0)
    t2.clock_offset_us = 500.0
    with t2.span("apply"):
        pass
    p1 = t.export_chrome(str(tmp_path / "w0.json"))
    p2 = t2.export_chrome(str(tmp_path / "srv.json"))
    merged = merge_chrome([p1, p2], str(tmp_path / "all.json"))
    events = json.load(open(merged))["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 2
    assert {e["name"] for e in xs} == {"push", "apply"}
    for e in xs:
        assert e["ts"] > 0 and e["dur"] > 0
        assert "span_id" in e["args"]
    # both processes named on the merged timeline
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {"w0", "srv"}


def _params(n=4, seed=0):
    rng = np.random.default_rng(seed)
    return {f"p{i}/w": jnp.asarray(rng.normal(0, 1, (4, 3))
                                   .astype(np.float32))
            for i in range(n)}


def _mkstore(params):
    st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    st.init(params)
    return st


def test_trace_roundtrip_through_push_pull_replica(request, sampled_tracer):
    """The acceptance chain on a real in-process cycle: worker op span ->
    primary apply span -> backup replica_append span + primary
    replica_ack_wait span, all one trace."""
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    prim = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    back = AsyncPSService(_mkstore(params), bind="127.0.0.1", backup=True)
    prim.attach_backup("127.0.0.1", back.port, ack="sync")
    w = connect_async(f"127.0.0.1:{prim.port}|127.0.0.1:{back.port}", 0,
                      params, failover_timeout=10.0)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_pull(grads)
    finally:
        w.close()
        back.stop()
        prim.stop()
    spans = sampled_tracer.spans()
    wk = [s for s in spans if s.cat == "worker" and s.name == "push_pull"]
    assert len(wk) == 1
    srv = [s for s in spans if s.cat == "server" and s.name == "push_pull"
           and s.parent_id == wk[0].span_id]
    assert len(srv) == 1, [(s.name, s.cat) for s in spans]
    assert srv[0].trace_id == wk[0].trace_id
    # the engine apply is its own child hop (fleet-telemetry PR's
    # span-phase tagging): the push-record append parents to it, the
    # pull-record append to the dispatch span — one linked chain
    applies = [s for s in spans if s.name == "server_apply"
               and s.parent_id == srv[0].span_id]
    assert len(applies) == 1 and applies[0].trace_id == wk[0].trace_id
    chain_ids = {srv[0].span_id, applies[0].span_id}
    appends = [s for s in spans if s.name == "replica_append"
               and s.parent_id in chain_ids]
    # the push_pull commit replicates a push AND a pull record
    assert len(appends) >= 2
    assert all(s.trace_id == wk[0].trace_id for s in appends)
    acks = [s for s in spans if s.name == "replica_ack_wait"
            and s.parent_id == srv[0].span_id]
    assert acks and all(s.trace_id == wk[0].trace_id for s in acks)
    # pull_all was traced too, as its own trace
    pulls = [s for s in spans if s.cat == "worker" and s.name == "pull"]
    assert pulls and pulls[0].trace_id != wk[0].trace_id


def test_bucketed_trace_spans_buckets(request, sampled_tracer):
    params = _params(6)
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    svc = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    w = connect_async(f"127.0.0.1:{svc.port}", 0, params,
                      bucket_bytes=64, pool_size=2)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_all(grads)
    finally:
        w.close()
        svc.stop()
    spans = sampled_tracer.spans()
    wk = [s for s in spans if s.cat == "worker" and s.name == "push"]
    assert len(wk) == 1
    buckets = [s for s in spans if s.name == "bucket_push"
               and s.parent_id == wk[0].span_id]
    # every bucket of the push parents to the ONE worker op span
    assert len(buckets) > 1


def test_untraced_frames_carry_no_tc(request):
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    assert obs.tracer().sample == 0.0  # the suite default
    svc = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    w = connect_async(f"127.0.0.1:{svc.port}", 0, params)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_pull(grads)
        # but for what the program records of its own set-up and compiles
        # (ps_tpu/obs/phases.py), with no sampling decision
        assert [s for s in obs.tracer().spans()
                if s.cat != "program"] == []
    finally:
        w.close()
        svc.stop()


# -- clock sync ---------------------------------------------------------------


def test_clock_sync_probe_same_host(request):
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    svc = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    ch = tv.Channel.connect("127.0.0.1", svc.port)
    try:
        cs = ClockSync()
        off = cs.probe(ch, n=5)
        # same process, same clock: the estimate is bounded by the RTT
        assert cs.rtt_us is not None and cs.rtt_us > 0
        assert abs(off) <= max(cs.rtt_us, 5e4)
        assert cs.probes == 5
    finally:
        ch.close()
        svc.stop()


# -- flight recorder ----------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4, dir=str(tmp_path), service="t")
    for i in range(9):
        fr.record("failover", shard=i)
    assert fr.total == 9 and len(fr.events()) == 4
    assert fr.events()[-1]["shard"] == 8
    path = fr.dump("unit test")
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["flight_dump"] == "unit test"
    assert lines[0]["events"] == 4 and lines[0]["events_total"] == 9
    assert [x["kind"] for x in lines[1:]] == ["failover"] * 4
    assert all("t" in x and "mono" in x for x in lines[1:])


def test_flight_recorder_empty_dump_is_none(tmp_path):
    fr = FlightRecorder(dir=str(tmp_path))
    assert fr.dump("nothing") is None


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_flight_dump_on_unhandled_vanerror_in_thread(tmp_path):
    fr = FlightRecorder(capacity=16, dir=str(tmp_path), service="boom")
    old_sys, old_thread = sys.excepthook, threading.excepthook
    # the PROCESS recorder's hooks (installed lazily by earlier tests)
    # also fire on the intentional VanError below — keep its dump in
    # tmp_path too, not the repo root
    proc = obs.flight()
    old_dir, proc.dir = proc.dir, str(tmp_path)
    try:
        fr.install()
        fr.record("stale_epoch", worker=1)
        done = threading.Event()
        inner = threading.excepthook

        def hook(args):
            inner(args)
            done.set()

        threading.excepthook = hook

        def die():
            raise tv.VanError("pump thread lost its peer")

        t = threading.Thread(target=die, name="doomed")
        t.start()
        t.join(5)
        assert done.wait(5)
        dumps = sorted(tmp_path.glob("flight-boom-*.jsonl"))
        assert dumps, "no flight dump after an unhandled VanError"
        lines = [json.loads(x) for x in open(dumps[-1])]
        assert "VanError" in lines[0]["flight_dump"]
        assert lines[1]["kind"] == "stale_epoch"
    finally:
        sys.excepthook, threading.excepthook = old_sys, old_thread
        proc.dir = old_dir


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_flight_hooks_ignore_other_exceptions(tmp_path):
    fr = FlightRecorder(capacity=4, dir=str(tmp_path))
    old_sys, old_thread = sys.excepthook, threading.excepthook
    try:
        fr.install()
        fr.record("reconnect")
        t = threading.Thread(target=lambda: 1 / 0)
        t.start()
        t.join(5)
        assert not list(tmp_path.glob("flight-*.jsonl"))
    finally:
        sys.excepthook, threading.excepthook = old_sys, old_thread


def test_steplogger_event_bridges_to_flight(tmp_path):
    fr = obs.flight()
    before = fr.total
    log = StepLogger(every=1, jsonl=str(tmp_path / "run.jsonl"))
    with log:
        log.event("failover", shard=2, seconds=0.5)
    assert fr.total == before + 1
    evt = fr.events()[-1]
    assert evt["kind"] == "failover" and evt["shard"] == 2
    # ...and the JSONL stream got the same record (close() flushed it)
    rec = json.loads(open(tmp_path / "run.jsonl").read().splitlines()[-1])
    assert rec["event"] == "failover" and rec["shard"] == 2


def test_failover_paths_record_flight_events(request):
    """The kill→promote→re-route cycle leaves a readable black box."""
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    fr = obs.flight()
    n0 = fr.total
    prim = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    back = AsyncPSService(_mkstore(params), bind="127.0.0.1", backup=True)
    prim.attach_backup("127.0.0.1", back.port, ack="sync")
    w = connect_async(f"127.0.0.1:{prim.port}|127.0.0.1:{back.port}", 0,
                      params, failover_timeout=10.0)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_pull(grads)
        prim.kill()
        back.promote(reason="drill")
        w.push_pull(grads)
    finally:
        w.close()
        back.stop()
    assert fr.total > n0
    kinds = [e["kind"] for e in fr.events()]
    assert "promotion" in kinds
    assert "failover" in kinds


def test_dead_backup_degrade_records_flight_event(request):
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    fr = obs.flight()
    prim = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    back = AsyncPSService(_mkstore(params), bind="127.0.0.1", backup=True)
    sess = prim.attach_backup("127.0.0.1", back.port, ack="sync")
    w = connect_async(f"127.0.0.1:{prim.port}", 0, params)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_pull(grads)
        back.kill()  # the BACKUP dies: primary degrades, never wedges
        deadline = time.monotonic() + 10
        while not sess.degraded and time.monotonic() < deadline:
            w.push_pull(grads)
        assert sess.degraded
    finally:
        w.close()
        prim.stop()
        back.stop()
    assert "repl_degraded" in [e["kind"] for e in fr.events()]


# -- /metrics endpoint --------------------------------------------------------


def _parse_prometheus(text):
    """name{labels} -> float for every sample line; validates the basic
    exposition grammar (comments start with #, samples split on the last
    space)."""
    out = {}
    for line in text.splitlines():
        if not line.strip() or line.startswith("#"):
            continue
        name, val = line.rsplit(" ", 1)
        out[name.strip()] = float(val)
    return out


def test_metrics_endpoint_serves_parseable_prometheus(request):
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    srv = MetricsServer(port=0)  # private server, same process registry
    request.addfinalizer(srv.close)
    svc = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    w = connect_async(f"127.0.0.1:{svc.port}", 0, params)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        for _ in range(3):
            w.push_pull(grads)
        url = f"http://127.0.0.1:{srv.port}/metrics"
        resp = urllib.request.urlopen(url, timeout=5)
        assert resp.headers["Content-Type"].startswith("text/plain")
        samples = _parse_prometheus(resp.read().decode())
        assert samples["ps_server_requests_total"] >= 4  # hello+pull+pushes
        # at least one histogram with nonzero counts (the acceptance bar)
        assert samples["ps_push_pull_seconds_count"] >= 3
        buckets = [v for k, v in samples.items()
                   if k.startswith("ps_push_pull_seconds_bucket")]
        assert buckets and max(buckets) >= 3
        # 404 for anything else
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        w.close()
        svc.stop()


def test_start_metrics_server_env_gate(monkeypatch):
    from ps_tpu.obs import http as obs_http

    monkeypatch.setattr(obs_http, "_server", None)
    monkeypatch.delenv("PS_METRICS_PORT", raising=False)
    assert obs_http.start_metrics_server() is None  # unset = no endpoint
    monkeypatch.setenv("PS_METRICS_PORT", "0")
    srv = obs_http.start_metrics_server()
    try:
        assert srv is not None and srv.port > 0
        # idempotent: second start returns the same server
        assert obs_http.start_metrics_server(0) is srv
    finally:
        srv.close()
        monkeypatch.setattr(obs_http, "_server", None)


# -- config knobs -------------------------------------------------------------


def test_config_obs_knobs_from_env(monkeypatch):
    monkeypatch.setenv("PS_TRACE_SAMPLE", "0.25")
    monkeypatch.setenv("PS_TRACE_DIR", "/tmp/traces")
    monkeypatch.setenv("PS_METRICS_PORT", "9091")
    monkeypatch.setenv("PS_FLIGHT_EVENTS", "128")
    cfg = ps.Config.from_env()
    assert cfg.trace_sample == 0.25
    assert cfg.trace_dir == "/tmp/traces"
    assert cfg.metrics_port == 9091
    assert cfg.flight_events == 128
    monkeypatch.setenv("PS_METRICS_PORT", "")
    assert ps.Config.from_env().metrics_port is None


def test_config_obs_knob_validation():
    with pytest.raises(ValueError):
        ps.Config(trace_sample=1.5)
    with pytest.raises(ValueError):
        ps.Config(metrics_port=-1)
    with pytest.raises(ValueError):
        ps.Config(flight_events=0)


# -- ps_top -------------------------------------------------------------------


def test_ps_top_once_json_against_live_pair(request):
    params = _params()
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)
    prim = AsyncPSService(_mkstore(params), bind="127.0.0.1")
    back = AsyncPSService(_mkstore(params), bind="127.0.0.1", backup=True)
    prim.attach_backup("127.0.0.1", back.port, ack="sync")
    uri = f"127.0.0.1:{prim.port}|127.0.0.1:{back.port}"
    w = connect_async(uri, 0, params, failover_timeout=10.0)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.1) for k, v in params.items()}
        w.push_pull(grads)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "tools/ps_top.py", "--servers", uri,
             "--once", "--json"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr
        rows = json.loads(out.stdout)
        assert len(rows) == 2
        assert sorted(r["role"] for r in rows) == ["backup", "primary"]
        primary = next(r for r in rows if r["role"] == "primary")
        assert primary["apply_log_total"] >= 1
        assert "lat" in primary["metrics"]
        # the table renderer accepts both roles without crashing
        import importlib.util
        import io

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "ps_top", os.path.join(root, "tools", "ps_top.py"))
        ps_top = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ps_top)
        buf = io.StringIO()
        ps_top.print_table(rows, stream=buf)
        assert "primary" in buf.getvalue() and "backup" in buf.getvalue()
    finally:
        w.close()
        back.stop()
        prim.stop()
