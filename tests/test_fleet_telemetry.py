"""Fleet telemetry (ps_tpu/obs tsdb/collector/breakdown/straggler/slo +
the coordinator pipeline) and the ClockSync hardening.

The contract under test, per layer:

- raw log2 histogram buckets merge LOSSLESSLY: the fleet quantile of N
  members' merged buckets matches numpy over the concatenated samples
  within the documented ~19% bound (under/overflow included) — and is
  NOT the average of per-member percentiles;
- the delta wire encoding reconstructs exact cumulative state, survives
  metrics appearing mid-stream, and self-heals a seq gap via resync;
- the tsdb's windows, ring bounds, and member pruning;
- the per-step breakdown table (always-on form) and the span-chain
  decomposition (TraceBreakdown);
- straggler detection: a slowed member is localized by the leave-one-out
  z-score, an un-slowed fleet stays quiet across multiple windows
  (ISSUE acceptance: zero false positives in the control run);
- SLO rules: parse errors are loud, breaches fire events + the counter,
  recovery clears;
- ClockSync: min-RTT-tie median guard, TTL re-probe, skewed fake clock;
- the 3-member in-process DRILL: one member's apply path artificially
  slowed → straggler_suspect flight event + counter + coordinator hint
  name the right member; COORD_TELEMETRY serves fleet quantiles and a
  breakdown; a dead coordinator leaves the data plane serving.
"""

import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu import obs
from ps_tpu.backends.remote_async import AsyncPSService, connect_async
from ps_tpu.config import Config
from ps_tpu.elastic import Coordinator, fetch_telemetry
from ps_tpu.obs.breakdown import TraceBreakdown, breakdown
from ps_tpu.obs.clock import ClockSync
from ps_tpu.obs.collector import (
    DeltaDecoder,
    DeltaEncoder,
    collect_telemetry,
)
from ps_tpu.obs.metrics import Histogram, state_add, state_sub
from ps_tpu.obs.slo import SloEvaluator, parse_rule, parse_rules
from ps_tpu.obs.straggler import StragglerDetector
from ps_tpu.obs.tsdb import FleetTSDB
from ps_tpu.utils.metrics import TransportStats


# -- raw-bucket states: roundtrip, merge, exact fleet quantiles ---------------


def test_hist_state_roundtrip_and_delta():
    h = Histogram("ps_t_seconds")
    for v in (0.001, 0.004, 0.1):
        h.record(v)
    st = json.loads(json.dumps(h.state()))  # must survive the wire
    h2 = Histogram.from_state("ps_t_seconds", st)
    assert h2.total == 3 and h2.counts == h.counts
    assert h2.quantile(0.5) == h.quantile(0.5)
    base = dict(st)
    h.record(0.02)
    delta = state_sub(h.state(), base)
    assert delta["n"] == 1 and sum(delta["c"]) == 1
    # add(base, delta) reconstitutes the cumulative counts
    back = state_add(base, delta)
    assert back["c"] == h.state()["c"] and back["n"] == h.total


def test_exact_fleet_quantiles_from_merged_buckets():
    """Satellite: merge N members' raw buckets vs numpy quantiles over
    the concatenated samples, within the documented ~19% log2 bound —
    under/overflow buckets included."""
    rng = np.random.default_rng(3)
    members = [
        rng.lognormal(mean=-7, sigma=0.8, size=12_000),   # fast member
        rng.lognormal(mean=-6, sigma=0.4, size=12_000),
        rng.lognormal(mean=-4.5, sigma=0.9, size=12_000),  # slow member
    ]
    merged = None
    for xs in members:
        h = Histogram("ps_op_seconds")  # default lo=1e-6, hi=3600
        for x in xs:
            h.record(x)
        merged = state_add(merged, h.state())
    allx = np.concatenate(members)
    hm = Histogram.from_state("ps_op_seconds", merged)
    assert hm.total == len(allx)
    for q in (0.5, 0.9, 0.99, 0.999):
        est = hm.quantile(q)
        true = float(np.quantile(allx, q))
        assert true / 1.25 <= est <= true * 1.25, (q, est, true)
    # under/overflow: samples outside [lo, hi) land in the edge buckets
    # and the merged estimate clamps to the observed range
    hu = Histogram("ps_op_seconds")
    hu.record(1e-9)     # underflow
    hu.record(7200.0)   # overflow
    merged2 = state_add(merged, hu.state())
    hm2 = Histogram.from_state("ps_op_seconds", merged2)
    assert hm2.counts[0] >= 1 and hm2.counts[-1] >= 1
    assert hm2.quantile(0.99999) == pytest.approx(7200.0)
    assert hm2.vmin == pytest.approx(1e-9)


def test_fleet_quantile_is_not_average_of_percentiles():
    """The failure mode the design note forbids: a bimodal fleet's true
    p50 is NOT the mean of per-member p50s; merged buckets get it right."""
    fast = np.full(9000, 0.001)
    slow = np.full(1000, 1.0)
    merged = None
    p50s = []
    for xs in (fast, slow):
        h = Histogram("ps_m_seconds")
        for x in xs:
            h.record(float(x))
        p50s.append(h.quantile(0.5))
        merged = state_add(merged, h.state())
    avg_of_p50 = sum(p50s) / 2          # ≈ 0.5 — meaningless
    true_p50 = float(np.quantile(np.concatenate([fast, slow]), 0.5))
    est = Histogram.from_state("ps_m_seconds", merged).quantile(0.5)
    assert est == pytest.approx(true_p50, rel=0.25)
    assert avg_of_p50 > 100 * est       # the averaged version is garbage


# -- delta encoder / decoder ---------------------------------------------------


class _FakeTransport:
    """The duck-typed face collect_telemetry needs."""

    def __init__(self):
        self.hist = {"op_s": Histogram("ps_op_seconds")}
        self.stale_epochs = 0
        self.dedup_hits = 0
        self.failovers = 0
        self.table_reroutes = 0


def _wire(payload):
    return json.loads(json.dumps(payload))  # the van's json round trip


def test_delta_roundtrip_new_metric_and_silence():
    t = _FakeTransport()
    t.hist["op_s"].record(0.01)
    enc = DeltaEncoder(lambda: collect_telemetry(t))
    dec = DeltaDecoder()
    cum = dec.ingest(_wire(enc.snapshot()))
    assert cum["ps_op_seconds"]["n"] == 1
    # nothing moved -> no payload at all (reports travel telemetry-free)
    assert enc.snapshot() is None
    # a counter appearing mid-stream rides its first payload in full form
    t.stale_epochs = 4
    t.hist["op_s"].record(0.02)
    cum = dec.ingest(_wire(enc.snapshot()))
    assert cum["ps_stale_epochs_total"]["v"] == 4
    assert cum["ps_op_seconds"]["n"] == 2
    assert cum["ps_op_seconds"]["s"] == pytest.approx(0.03)
    # sparse histogram delta: exactly the buckets that moved traveled
    h = t.hist["op_s"]
    t.stale_epochs = 4  # unchanged: no counter entry this time
    h.record(0.02)
    payload = _wire(enc.snapshot())
    entry = payload["m"]["ps_op_seconds"]
    assert "dc" in entry and len(entry["dc"]) == 1
    assert "ps_stale_epochs_total" not in payload["m"]
    cum = dec.ingest(payload)
    assert cum["ps_op_seconds"]["n"] == 3


def test_delta_gap_forces_resync_then_full_recovers():
    t = _FakeTransport()
    t.hist["op_s"].record(0.01)
    enc = DeltaEncoder(lambda: collect_telemetry(t))
    dec = DeltaDecoder()
    assert dec.ingest(_wire(enc.snapshot())) is not None
    t.hist["op_s"].record(0.01)
    enc.snapshot()                      # LOST on the wire
    t.hist["op_s"].record(0.01)
    assert dec.ingest(_wire(enc.snapshot())) is None  # gap -> resync ask
    enc.force_full()                    # what the member does on resync
    t.hist["op_s"].record(0.01)
    cum = dec.ingest(_wire(enc.snapshot()))
    assert cum is not None and cum["ps_op_seconds"]["n"] == 4
    # a delta for a metric the decoder never baselined also resyncs
    dec2 = DeltaDecoder()
    t.stale_epochs = 1
    assert dec2.ingest(_wire(enc.snapshot())) is None


def test_collect_telemetry_scopes_to_one_transport():
    """Two in-process endpoints must report their OWN numbers — the
    in-process-fleet property the straggler drill depends on."""
    a, b = TransportStats(), TransportStats()
    a.record_apply(0.5)
    b.record_apply(0.001)
    sa = collect_telemetry(a)
    sb = collect_telemetry(b)
    assert sa["ps_server_apply_seconds"]["n"] == 1
    assert sa["ps_server_apply_seconds"]["s"] == pytest.approx(0.5)
    assert sb["ps_server_apply_seconds"]["s"] == pytest.approx(0.001)
    extra = collect_telemetry(a, counters={"ps_applies_total": lambda: 7})
    assert extra["ps_applies_total"] == {"k": "counter", "v": 7}


# -- tsdb ----------------------------------------------------------------------


def _hist_state(samples, name="ps_op_seconds"):
    h = Histogram(name)
    for s in samples:
        h.record(s)
    return {"k": "hist", **h.state()}


def test_tsdb_windows_rates_and_ring_bound():
    db = FleetTSDB(window_s=10.0, ring=4)
    now = time.monotonic()
    # cumulative counter samples 1s apart
    for i, v in enumerate((10, 20, 40, 80, 160, 320)):
        db.ingest("m0", {"c": {"k": "counter", "v": v}}, t=now - 5 + i)
    ring = db._series[("m0", "c")]
    assert len(ring) == 4  # bounded: oldest evicted
    win = db.window("m0", "c", window_s=2.5)
    assert win["k"] == "counter" and win["delta"] > 0
    assert win["rate"] == pytest.approx(win["delta"] / 2.0, rel=0.6)
    # a SINGLE-sample counter series has no window movement: a member's
    # first full snapshot after a coordinator restart carries its
    # lifetime total, and reporting that as the window delta would show
    # a bogus fleet-wide burst
    db.ingest("mr", {"c2": {"k": "counter", "v": 50_000}}, t=now)
    win = db.window("mr", "c2", window_s=2.5)
    assert win["delta"] == 0.0 and win["rate"] == 0.0
    assert win["value"] == 50_000
    # hist windows: delta of cumulative states
    db.ingest("m0", {"h": _hist_state([0.001] * 5)}, t=now - 3)
    db.ingest("m0", {"h": _hist_state([0.001] * 5 + [0.1] * 5)}, t=now)
    win = db.window("m0", "h", window_s=10.0)
    assert win["state"]["n"] == 5          # only the window's samples
    assert win["summary"]["p50"] == pytest.approx(0.1, rel=0.3)
    # a member that stopped reporting 3x the window ago drops out
    db.ingest("m1", {"h": _hist_state([0.5])}, t=now - 100)
    assert db.window("m1", "h", window_s=10.0) is None
    assert db.fleet_window("h", window_s=10.0)["members"] == ["m0"]
    db.drop_member("m0")
    assert ("m0", "h") not in db._series
    assert db.members() == ["m1", "mr"]


def test_tsdb_fleet_merge_and_prometheus_render():
    db = FleetTSDB(window_s=30.0, ring=8)
    now = time.monotonic()
    db.ingest("a", {"op": _hist_state([0.001] * 100, "ps_x_seconds")},
              t=now - 1)
    db.ingest("b", {"op": _hist_state([1.0] * 100, "ps_x_seconds")},
              t=now)
    q = db.quantile("op", 0.99)
    assert q == pytest.approx(1.0, rel=0.3)  # the slow member's tail
    assert db.quantile("op", 0.25) == pytest.approx(0.001, rel=0.3)
    text = db.render_prometheus()
    assert "ps_fleet_op_bucket" in text or "ps_fleet_op" in text
    assert 'member="a"' in text and 'member="b"' in text
    assert 'q="p99"' in text


# -- breakdown -----------------------------------------------------------------


def test_breakdown_table_phases_shares_and_derived_rows():
    sums = {
        "ps_cycle_seconds": {"count": 100, "mean": 0.010, "p50": 0.009,
                             "p99": 0.03, "p999": 0.04, "max": 0.05},
        "ps_blocked_seconds": {"count": 100, "mean": 0.002, "p50": 0.001,
                               "p99": 0.01, "p999": 0.01, "max": 0.02},
        "ps_bucket_seconds": {"count": 400, "mean": 0.0015, "p50": 0.001,
                              "p99": 0.004, "p999": 0.005, "max": 0.01},
        "ps_server_apply_seconds": {"count": 100, "mean": 0.003,
                                    "p50": 0.003, "p99": 0.005,
                                    "p999": 0.006, "max": 0.01},
    }
    out = breakdown(lambda m: sums.get(m))
    assert out["total"]["metric"] == "ps_cycle_seconds"
    assert out["flush_wait"]["share"] == pytest.approx(0.2, rel=0.01)
    # wire = wire_round - server_apply at the seconds level
    assert out["wire"]["seconds"] == pytest.approx(
        400 * 0.0015 - 100 * 0.003, rel=0.01)
    # client = total - (flush + wire_round): the worker-side remainder
    assert out["client"]["seconds"] == pytest.approx(
        1.0 - 0.2 - 0.6, rel=0.05)
    for phase, row in out.items():
        if phase != "total":
            assert 0.0 <= row["share"] <= 1.0
    assert breakdown(lambda m: None) == {}


def test_trace_breakdown_span_chain():
    def ev(name, cat, tid, dur_us, parent=None):
        return {"ph": "X", "name": name, "cat": cat, "dur": dur_us,
                "args": {"trace_id": tid, "parent_id": parent,
                         "span_id": name}}

    events = []
    for tid in ("t1", "t2"):
        events += [
            ev("push_pull", "worker", tid, 10_000),
            ev("flush_wait", "worker", tid, 1_000, parent="push_pull"),
            ev("bucket_push", "server", tid, 3_000, parent="push_pull"),
            ev("server_apply", "server", tid, 2_000, parent="bucket_push"),
            ev("replica_ack_wait", "server", tid, 500,
               parent="bucket_push"),
        ]
    tb = TraceBreakdown()
    assert tb.feed(events) == 2
    s = tb.summary()
    assert s["total"]["count"] == 2
    assert s["total"]["mean"] == pytest.approx(0.010, rel=0.01)
    assert s["server"]["mean"] == pytest.approx(0.003, rel=0.01)
    assert s["server_apply"]["mean"] == pytest.approx(0.002, rel=0.01)
    assert s["ack_wait"]["mean"] == pytest.approx(0.0005, rel=0.01)
    # wire = total - server - flush_wait
    assert s["wire"]["mean"] == pytest.approx(0.006, rel=0.01)
    assert s["server"]["share"] == pytest.approx(0.3, rel=0.01)
    # live Span objects feed the same way
    tracer = obs.trace.Tracer(sample=1.0)
    with tracer.span("push", cat="worker"):
        pass
    assert TraceBreakdown().feed(tracer.spans()) == 1


# -- straggler detection -------------------------------------------------------


def _seed_members(db, means, t, n=20, prev=None):
    """Ingest cumulative states so each member's WINDOW mean is means[i];
    returns the cumulative histograms for the next round."""
    prev = prev or {}
    for i, mean in enumerate(means):
        h = prev.get(i)
        if h is None:
            h = Histogram("ps_server_apply_seconds")
            prev[i] = h
        for _ in range(n):
            h.record(mean)
        db.ingest(f"m{i}", {"ps_server_apply_seconds":
                            {"k": "hist", **h.state()}}, t=t)
    return prev


def test_straggler_leave_one_out_z_flags_outlier_and_control_quiet():
    db = FleetTSDB(window_s=10.0, ring=32)
    det = StragglerDetector(db, z=3.0, min_members=3, min_count=3)
    before = det._m_suspects.value
    now = time.monotonic()
    # control: three statistically-equal members over several windows —
    # zero false positives (the ISSUE acceptance's control run)
    prev = _seed_members(db, (0.0010, 0.0012, 0.0011), now - 2)
    for k in range(4):
        prev = _seed_members(db, (0.0010, 0.0012, 0.0011),
                             now - 1.5 + k * 0.5, prev=prev)
        assert det.evaluate({f"m{i}": i for i in range(3)}) == []
    assert det._m_suspects.value == before
    # one member 20x slower: flagged, once (onset), with the right id
    prev = _seed_members(db, (0.001, 0.022, 0.001), now, prev=prev)
    suspects = det.evaluate({f"m{i}": i for i in range(3)})
    assert len(suspects) == 1
    assert suspects[0]["uri"] == "m1" and suspects[0]["shard"] == 1
    assert suspects[0]["z"] >= 3.0
    assert det._m_suspects.value == before + 1
    # still suspected on the next pass (hysteresis) but no second onset
    det.evaluate({f"m{i}": i for i in range(3)})
    assert det._m_suspects.value == before + 1
    hints = det.hints()
    assert hints and hints[0]["kind"] == "straggler"
    assert "shard 1" in hints[0]["action"]


def test_straggler_needs_min_members_and_counts():
    db = FleetTSDB(window_s=10.0, ring=8)
    det = StragglerDetector(db, z=3.0, min_members=3, min_count=3)
    now = time.monotonic()
    _seed_members(db, (0.001, 0.1), now)          # only two members
    assert det.evaluate({"m0": 0, "m1": 1}) == []
    db2 = FleetTSDB(window_s=10.0, ring=8)
    det2 = StragglerDetector(db2, z=3.0, min_members=3, min_count=5)
    _seed_members(db2, (0.001, 0.001, 0.1), now, n=2)  # too few samples
    assert det2.evaluate({f"m{i}": i for i in range(3)}) == []


# -- SLO rules -----------------------------------------------------------------


def test_slo_rule_parsing():
    r = parse_rule("push p99 < 10ms over 30s")
    assert (r.metric, r.q, r.qlabel) == ("ps_push_seconds", 0.99, "p99")
    assert r.threshold_s == pytest.approx(0.010)
    assert r.window_s == pytest.approx(30.0)
    r = parse_rule("apply p999 <= 50us over 2m")
    assert r.metric == "ps_server_apply_seconds"
    assert r.q == 0.999 and r.threshold_s == pytest.approx(50e-6)
    assert r.window_s == pytest.approx(120.0)
    r = parse_rule("ps_custom_seconds p50 < 1s over 500ms")
    assert r.metric == "ps_custom_seconds"
    rules = parse_rules("push p99 < 10ms over 30s; pull p50 < 1ms over 5s")
    assert len(rules) == 2
    assert parse_rules(None) == [] and parse_rules("  ") == []
    with pytest.raises(ValueError, match="unparseable"):
        parse_rule("push faster please")
    with pytest.raises(ValueError, match="unknown SLO metric"):
        parse_rule("warp p99 < 1ms over 5s")


def test_slo_evaluator_breach_event_counter_and_recovery():
    db = FleetTSDB(window_s=30.0, ring=8)
    rules = parse_rules("apply p99 < 5ms over 10s; push p99 < 1s over 10s")
    ev = SloEvaluator(db, rules)
    before = ev._m_breach.value
    flight_before = len([e for e in obs.flight().events()
                         if e["kind"] == "slo_breach"])
    now = time.monotonic()
    db.ingest("m0", {"ps_server_apply_seconds": _hist_state(
        [0.050] * 50, "ps_server_apply_seconds")}, t=now)
    states = ev.evaluate()
    by_rule = {s["rule"]: s for s in states}
    breach = by_rule["apply p99 < 5ms over 10s"]
    assert breach["breached"] and breach["value_ms"] > 5.0
    # the push rule has NO data: not a breach
    assert not by_rule["push p99 < 1s over 10s"]["breached"]
    assert by_rule["push p99 < 1s over 10s"]["value_ms"] is None
    assert ev._m_breach.value == before + 1
    assert len([e for e in obs.flight().events()
                if e["kind"] == "slo_breach"]) == flight_before + 1
    # still breached: counter keeps burning, no second transition event
    ev.evaluate()
    assert ev._m_breach.value == before + 2
    assert len([e for e in obs.flight().events()
                if e["kind"] == "slo_breach"]) == flight_before + 1
    # recovery: fast applies flood the window
    db.ingest("m0", {"ps_server_apply_seconds": _hist_state(
        [0.050] * 50 + [0.0001] * 10_000, "ps_server_apply_seconds")},
        t=now + 0.5)
    states = ev.evaluate()
    assert not {s["rule"]: s for s in states}[
        "apply p99 < 5ms over 10s"]["breached"]
    assert any(e["kind"] == "slo_recover" for e in obs.flight().events())
    assert ev.breached() == []


def test_config_slo_rules_validated_at_config_time():
    Config(slo_rules="push p99 < 10ms over 30s")  # parses fine
    with pytest.raises(ValueError, match="unparseable"):
        Config(slo_rules="nonsense here")
    with pytest.raises(ValueError, match="telemetry_ring"):
        Config(telemetry_ring=1)
    with pytest.raises(ValueError, match="telemetry_window_s"):
        Config(telemetry_window_s=0)
    with pytest.raises(ValueError, match="straggler_z"):
        Config(telemetry_straggler_z=0)


def test_config_telemetry_env_mirrors(monkeypatch):
    monkeypatch.setenv("PS_TELEMETRY", "0")
    monkeypatch.setenv("PS_TELEMETRY_WINDOW_S", "12.5")
    monkeypatch.setenv("PS_TELEMETRY_RING", "64")
    monkeypatch.setenv("PS_TELEMETRY_STRAGGLER_Z", "4.5")
    monkeypatch.setenv("PS_SLO_RULES", "push p99 < 10ms over 30s")
    cfg = Config.from_env()
    assert cfg.telemetry is False
    assert cfg.telemetry_window_s == 12.5
    assert cfg.telemetry_ring == 64
    assert cfg.telemetry_straggler_z == 4.5
    assert cfg.slo_rules == "push p99 < 10ms over 30s"
    monkeypatch.setenv("PS_SLO_RULES", "")
    assert Config.from_env().slo_rules is None


# -- ClockSync hardening -------------------------------------------------------


def test_clock_sync_min_rtt_tie_median_guard():
    """All-min-RTT ties (coarse clocks) must not apply one arbitrary
    probe's jitter: the offset is the median over the tie set."""
    cs = ClockSync(tie_us=50.0)
    skew = 5.0  # server is 5s ahead
    # three probes with IDENTICAL rtt but jittered midpoints
    for jitter in (-0.4e-3, 0.0, +0.4e-3):
        t0 = 100.0
        t1 = t0 + 2e-3
        cs.observe(t0, t1, (t0 + t1) / 2 + skew + jitter)
    assert cs.offset_us == pytest.approx(skew * 1e6, abs=1.0)
    # a genuinely-smaller-RTT probe outside the tie band wins alone
    cs.observe(200.0, 200.0 + 1e-4, 200.00005 + skew + 0.9)
    assert cs.offset_us == pytest.approx((skew + 0.9) * 1e6, abs=1.0)


def test_clock_sync_skewed_fake_clock_and_ttl_reprobe():
    """Satellite regression: a fake peer whose clock drifts mid-run —
    the TTL re-probe tracks the NEW offset; a never-expiring sync keeps
    the stale one."""
    from ps_tpu.control import tensor_van as tv

    class FakeChannel:
        def __init__(self):
            self.skew = 2.0

        def request(self, frame):
            kind, worker, _, _ = tv.decode(memoryview(bytes(frame)))
            assert kind == tv.REPLICA_STATE
            return memoryview(bytes(tv.encode(
                tv.OK, worker, None,
                extra={"now": time.time() + self.skew})))

    ch = FakeChannel()
    cs = ClockSync(ttl_s=0.2)
    off = cs.probe(ch, n=4)
    assert off == pytest.approx(2.0e6, abs=5e3)
    assert cs.fresh()
    ch.skew = 7.0                      # the clock drifted
    assert cs.ensure_fresh(ch) == pytest.approx(2.0e6, abs=5e3)  # cached
    time.sleep(0.25)
    assert not cs.fresh()
    off = cs.ensure_fresh(ch, n=4)     # TTL expired: re-probes
    assert off == pytest.approx(7.0e6, abs=5e3)
    assert cs.reprobes == 1
    # no TTL = the old one-shot behavior: never re-probes on its own
    cs2 = ClockSync()
    cs2.probe(ch, n=2)
    ch.skew = 1.0
    assert cs2.fresh() and cs2.ensure_fresh(ch) == pytest.approx(
        7.0e6, abs=5e3)


# -- the in-process fleet drill ------------------------------------------------


@pytest.fixture
def tpu_async(request):
    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    request.addfinalizer(ps.shutdown)


def _fleet(coord_addr, params, nshards=3):
    keys = sorted(params)
    per = len(keys) // nshards
    svcs = []
    for s in range(nshards):
        st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
        st.init({k: params[k] for k in keys[s * per:(s + 1) * per]})
        svcs.append(AsyncPSService(st, bind="127.0.0.1",
                                   coordinator=coord_addr))
    return svcs


def _straggler_events():
    return [e for e in obs.flight().events()
            if e["kind"] == "straggler_suspect"]


def _traffic_until(w, grads, done, deadline_s=60.0):
    """``push_pull`` in a loop until ``done()`` holds; fails at the
    deadline. In place of "for 2 s": what a window holds after 2 s depends
    on what else the host runs."""
    end = time.monotonic() + deadline_s
    while not done():
        assert time.monotonic() < end, "deadline"
        w.push_pull(grads)


def test_straggler_drill_localizes_slowed_member(tpu_async):
    """ISSUE acceptance: 3-member fleet, one member's apply artificially
    slowed → straggler_suspect flight event + counter + coordinator hint
    identify the right member; the un-slowed control phase stays quiet
    over multiple evaluation windows."""
    coord = Coordinator(port=0, report_ms=100, telemetry_window_s=2.0)
    caddr = f"127.0.0.1:{coord.port}"
    params = {f"p{i}/w": jnp.asarray(np.full((64, 8), 0.5, np.float32))
              for i in range(6)}
    svcs = _fleet(caddr, params)
    w = connect_async(None, 0, params, coordinator=caddr)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.01) for k, v in params.items()}
        # warm-up: each member's first applies compile, and beside other
        # processes one member's compile can stand out by itself. Traffic
        # until those samples have left the window and nobody is suspected
        warm = time.monotonic() + 1.25 * coord.tsdb.window_s
        _traffic_until(w, grads, lambda: time.monotonic() > warm
                       and not coord.straggler.suspects())
        events0 = len(_straggler_events())
        evals0 = coord.straggler.evaluations

        # control: equal members — no false positive over M windows. The
        # windows are counted, not timed: traffic until every member's
        # window holds what the detector scores, then through two more
        # evaluations (a busy host runs fewer of them in 2 s than an idle
        # one)
        scorer, uris = coord.straggler, [f"127.0.0.1:{s.port}" for s in svcs]

        def scored():
            means = [coord.tsdb.member_mean(u, scorer.metrics[0])
                     for u in uris]
            return all(m and m[1] >= scorer.min_count for m in means)

        _traffic_until(w, grads, scored)
        seen = scorer.evaluations
        _traffic_until(w, grads, lambda: scorer.evaluations - seen >= 2)
        assert scorer.evaluations - evals0 >= 2  # windows ran
        assert len(_straggler_events()) == events0
        assert coord.straggler.suspects() == []

        # slow shard 1's apply path
        slow = svcs[1]
        orig = slow._engine.push_tree

        def crawling(*a, **kw):
            time.sleep(0.025)
            return orig(*a, **kw)

        slow._engine.push_tree = crawling
        # until the onset's flight event: the evaluation that flags it sets
        # the suspect, moves the counter and records the event, in that order
        _traffic_until(w, grads,
                       lambda: len(_straggler_events()) > events0)

        suspects = coord.straggler.suspects()
        assert len(suspects) == 1, suspects
        assert suspects[0]["uri"] == f"127.0.0.1:{slow.port}"
        assert suspects[0]["metric"] == "ps_server_apply_seconds"
        new_events = _straggler_events()[events0:]
        assert new_events and new_events[-1]["uri"] == \
            f"127.0.0.1:{slow.port}"
        hints = coord.hints()
        straggler_hints = [h for h in hints if h["kind"] == "straggler"]
        assert straggler_hints and straggler_hints[0]["shard"] == 1
        assert coord.straggler._m_suspects.value >= 1

        # the query shape ps_top --fleet / ps_doctor consume
        tel = fetch_telemetry(caddr)
        assert f"127.0.0.1:{slow.port}" in tel["members"]
        assert "ps_server_apply_seconds" in tel["fleet"]
        assert tel["fleet"]["ps_server_apply_seconds"]["count"] > 0
        assert tel["breakdown"]["total"]["count"] > 0
        assert tel["stragglers"][0]["shard"] == 1
        assert any(h["kind"] == "straggler" for h in tel["hints"])
        # fleet-labeled series on the process /metrics render
        text = obs.default_registry().render_prometheus()
        assert "ps_fleet_server_apply_seconds_bucket" in text
    finally:
        w.close()
        for s in svcs:
            s.stop()
        coord.stop()
    # a stopped coordinator's fleet series leave the scrape
    assert "ps_fleet_server_apply_seconds_bucket" not in \
        obs.default_registry().render_prometheus()


def test_dead_coordinator_degrades_to_local_observability(tpu_async):
    """ISSUE acceptance: a dead coordinator leaves the data plane (and
    the members' local observability) untouched — reporters go quiet,
    pushes keep landing, local histograms keep recording."""
    coord = Coordinator(port=0, report_ms=100)
    caddr = f"127.0.0.1:{coord.port}"
    params = {f"p{i}/w": jnp.asarray(np.full((16, 4), 0.5, np.float32))
              for i in range(3)}
    svcs = _fleet(caddr, params, nshards=3)
    w = connect_async(None, 0, params, coordinator=caddr)
    try:
        w.pull_all()
        grads = {k: jnp.full_like(v, 0.01) for k, v in params.items()}
        w.push_pull(grads)
        coord.kill()                     # coordinator dies mid-run
        time.sleep(0.35)                 # a few report cadences fail
        before = svcs[0].transport.hist["apply_s"].total
        for _ in range(5):
            w.push_pull(grads)           # data plane unaffected
        assert svcs[0].transport.hist["apply_s"].total > before
        assert svcs[0].transport.latency_quantiles()[
            "apply_s"]["count"] > 0      # local obs still live
    finally:
        w.close()
        for s in svcs:
            s.stop()


@pytest.fixture
def sparse_mesh(request):
    # in-process sparse services need a 1-device mesh under the 8-virtual-
    # device test env (see test_replica.py's gotcha)
    ps.init(backend="tpu", mode="async", num_workers=1,
            mesh_shape={"data": 1})
    request.addfinalizer(ps.shutdown)


def test_sparse_member_ships_telemetry(sparse_mesh):
    """Sparse shards join the same pipeline: their apply histogram
    reaches the coordinator's tsdb under their uri."""
    from ps_tpu.backends.remote_sparse import (
        SparsePSService,
        connect_sparse,
    )
    from ps_tpu.kv.sparse import SparseEmbedding

    coord = Coordinator(port=0, report_ms=100, telemetry_window_s=5.0)
    caddr = f"127.0.0.1:{coord.port}"
    emb = SparseEmbedding(32, 4, optimizer="sgd", learning_rate=0.1)
    rng = np.random.default_rng(5)
    emb.init(rng.normal(0, 0.01, (32, 4)).astype(np.float32))
    svc = SparsePSService({"t": emb}, bind="127.0.0.1",
                          coordinator=caddr)
    try:
        wk = connect_sparse(None, 0, {"t": (32, 4)}, coordinator=caddr)
        try:
            ids = np.arange(8, dtype=np.int32)
            grads = np.full((8, 4), 0.01, np.float32)
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.5:
                wk.push({"t": (ids, grads)})
            time.sleep(0.3)
            uri = f"127.0.0.1:{svc.port}"
            assert uri in coord.tsdb.members()
            win = coord.tsdb.window(uri, "ps_server_apply_seconds")
            assert win is not None and win["state"]["n"] > 0
        finally:
            wk.close()
    finally:
        svc.stop()
        coord.stop()
