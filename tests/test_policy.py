"""Autopilot policy engine (ps_tpu/elastic/policy.py, README "Autopilot
& chaos"): the declarative rules over synthetic views, the storm brakes
(burn windows, hysteresis re-arm, per-action-class cooldown, one action
in flight), dry-run semantics, the coordinator knob plumbing + wire
surface, and the ISSUE's small fix — ``Coordinator.hints()`` stamping
and expiry.

Rules are tested on PLAIN-DATA views (the ``_policy_view`` shape) with
injected clocks — no sleeps, no fleets — exactly the seam the engine
documents for tests. The byte-identical policy-off check and the knob
plumbing boot real coordinators.
"""

import numpy as np
import pytest

import ps_tpu as ps
from ps_tpu.backends.remote_async import AsyncPSService, connect_async
from ps_tpu.elastic import Coordinator
from ps_tpu.elastic.member import fetch_policy
from ps_tpu.elastic.policy import (
    ELEVATED,
    FIRING,
    QUIET,
    HotspotRebalance,
    PolicyEngine,
    PolicyRule,
    ReplicaReseed,
    ShardAdd,
    ShardDrain,
)


def member(shard, uri=None, kind="dense", keys=3, nbytes=3000,
           hb="alive", report=None, handled=False):
    return {"shard": shard, "uri": uri or f"127.0.0.1:{9000 + shard}",
            "kind": kind, "node": shard, "hb_state": hb, "hb_age_ms": 10,
            "keys": keys, "nbytes": nbytes, "report": report or {},
            "handled": handled}


def view(members, **kw):
    v = {"now": 0.0, "members": members, "spares": [],
         "rebalancing": False, "hints": [], "slo": [], "skew": None,
         "max_skew": 2.0}
    v.update(kw)
    return v


def straggler_hint(shard):
    return {"kind": "straggler", "shard": shard, "t": 0.0, "window_s": 2.0}


def slo_state(breached=True, value_ms=500.0, threshold_ms=400.0):
    return {"rule": "push_pull p99 < 400ms over 2s",
            "metric": "ps_push_pull_seconds", "q": 0.99, "window_s": 2.0,
            "threshold_ms": threshold_ms, "value_ms": value_ms,
            "breached": breached}


# -- rule signals + plans -----------------------------------------------------


def test_hotspot_signal_levels_and_plans():
    r = HotspotRebalance()
    fleet = [member(i) for i in range(4)]
    # straggler suspect: FIRING, and the plan drains it toward the rest
    v = view(fleet, hints=[straggler_hint(1)])
    assert r.signal(v) == FIRING
    assert r.plan(v) == {"targets": [0, 2, 3], "suspects": [1]}
    # SLO: breach fires, the recover band holds ELEVATED, quiet below
    assert r.signal(view(fleet, slo=[slo_state()])) == FIRING
    assert r.signal(view(fleet, slo=[slo_state(
        breached=False, value_ms=350.0)])) == ELEVATED
    assert r.signal(view(fleet, slo=[slo_state(
        breached=False, value_ms=100.0)])) == QUIET
    # byte skew past the threshold fires; the plan is a leveling pass
    v = view(fleet, skew=3.0, max_skew=2.0)
    assert r.signal(v) == FIRING
    assert r.plan(v) == {"targets": [0, 1, 2, 3]}
    assert r.signal(view(fleet, skew=1.9, max_skew=2.0)) == ELEVATED
    # inf skew = an EMPTY dense shard (a standby) — not a hotspot; the
    # guard keeps the rule from latching FIRING forever after its own
    # suspect drain emptied a member
    assert r.signal(view(fleet, skew=float("inf"),
                         max_skew=2.0)) == QUIET
    # a dead member never receives drained keys
    fleet_dead = [member(0), member(1), member(2, hb="dead")]
    v = view(fleet_dead, hints=[straggler_hint(1)])
    assert r.plan(v) == {"targets": [0], "suspects": [1]}


def test_replica_reseed_candidates_and_plan():
    r = ReplicaReseed()
    pair = "127.0.0.1:9000|127.0.0.1:9001"
    consumed = member(0, uri=pair, report={
        "repl": {"attached": False, "degraded": False, "promoted": True}})
    assert r.signal(view([consumed])) == FIRING
    # no spare: the plan is None with the reason the audit records
    assert r.plan(view([consumed])) is None and r.why == "no_spare"
    v = view([consumed], spares=["127.0.0.1:9002"])
    assert r.plan(v) == {"shard": 0, "uri": pair,
                        "spare": "127.0.0.1:9002"}
    # a degraded stream and a dead PAIR member are candidates too; a
    # dead singleton (no "|") is a plain failover matter, not a re-seed
    assert r.signal(view([member(0, uri=pair, report={
        "repl": {"attached": True, "degraded": True,
                 "promoted": False}})])) == FIRING
    assert r.signal(view([member(0, uri=pair, hb="dead")])) == FIRING
    assert r.signal(view([member(0, hb="dead")])) == QUIET
    # the executor's handled mark stops the re-fire loop
    assert r.signal(view([member(0, uri=pair, hb="dead",
                                 handled=True)])) == QUIET
    # healthy pair: quiet
    assert r.signal(view([member(0, uri=pair, report={
        "repl": {"attached": True, "degraded": False,
                 "promoted": False}})])) == QUIET


def test_shard_add_needs_standby_and_breach():
    r = ShardAdd()
    loaded = [member(0), member(1)]
    standby = loaded + [member(2, keys=0, nbytes=0)]
    # overload without a standby: nothing to add
    assert r.signal(view(loaded, slo=[slo_state()])) == QUIET
    # standby without overload: leave it parked
    assert r.signal(view(standby)) == QUIET
    assert r.signal(view(standby, slo=[slo_state()])) == FIRING
    assert r.signal(view(standby, slo=[slo_state(
        breached=False, value_ms=350.0)])) == ELEVATED
    # the split spreads over EVERY dense shard, standby included
    assert r.plan(view(standby, slo=[slo_state()])) == {
        "targets": [0, 1, 2]}


def test_shard_drain_underload_and_emptiest_leave_first():
    r = ShardDrain(qps_floor=1.0, min_shards=2)
    fleet = [member(0, nbytes=9000, report={"push_qps": 0.1}),
             member(1, nbytes=8000, report={"push_qps": 0.1}),
             member(2, nbytes=100, report={"push_qps": 0.0}),
             member(3, nbytes=100, report={"push_qps": 0.0})]
    assert r.signal(view(fleet)) == FIRING
    # emptiest leave first, ties toward the latest joiner
    assert r.plan(view(fleet)) == {"drain": [2, 3]}
    # at the floor: never drain below min_shards
    assert r.signal(view(fleet[:2])) == QUIET
    # no load data AT ALL: never drain blind
    blind = [member(i) for i in range(4)]
    assert r.signal(view(blind)) == QUIET
    # busy fleet: quiet; the 2x band holds ELEVATED
    busy = [member(i, report={"push_qps": 5.0}) for i in range(4)]
    assert r.signal(view(busy)) == QUIET
    low = [member(i, report={"push_qps": 0.4}) for i in range(4)]
    assert r.signal(view(low)) == ELEVATED


# -- the engine: burn windows, hysteresis, cooldown, dry-run ------------------


def _dry_engine(rules, burn=2, cooldown=100.0):
    return PolicyEngine(mode="dry", cooldown_s=cooldown,
                        burn_windows=burn, tick_s=0.0, rules=rules)


def test_fire_needs_full_burn_and_one_window_shorter_does_not():
    fire_v = view([member(i) for i in range(4)],
                  hints=[straggler_hint(1)])
    eng = _dry_engine([HotspotRebalance()], burn=3)
    # one window SHORT of the burn: no audit entry, no action
    assert eng.tick(fire_v, now=1.0) == []
    assert eng.tick(fire_v, now=2.0) == []
    assert eng.actions_total == {}
    # the third consecutive window fires
    [entry] = eng.tick(fire_v, now=3.0)
    assert entry["outcome"] == "dry" and entry["rule"] == "hotspot_rebalance"
    assert entry["detail"] == {"targets": [0, 2, 3], "suspects": [1]}
    assert eng.actions_total == {("rebalance", "dry"): 1}
    # an intervening recovery resets the streak: 2 FIRING + QUIET + 2
    # FIRING never fires at burn=3
    eng2 = _dry_engine([HotspotRebalance()], burn=3)
    quiet_v = view([member(i) for i in range(4)])
    for i, v in enumerate([fire_v, fire_v, quiet_v, fire_v, fire_v]):
        assert eng2.tick(v, now=float(i)) == []
    assert eng2.actions_total == {}


def test_flapping_fires_exactly_once_cooldown_and_hysteresis():
    """ISSUE acceptance: a flapping signal (alternating burn/recover)
    produces exactly ONE action inside the cooldown window, with the
    suppressions counted."""
    fire_v = view([member(i) for i in range(4)],
                  hints=[straggler_hint(1)])
    quiet_v = view([member(i) for i in range(4)])
    eng = _dry_engine([HotspotRebalance()], burn=2, cooldown=1000.0)
    now = [0.0]

    def tick(v):
        now[0] += 1.0
        return eng.tick(v, now=now[0])

    tick(fire_v)
    [fired] = tick(fire_v)
    assert fired["outcome"] == "dry"
    # flap: recover long enough to re-arm, burn again — cooldown holds
    suppressed = []
    for _ in range(5):
        tick(quiet_v), tick(quiet_v)          # re-arms (quiet >= burn)
        tick(fire_v)
        suppressed += [e for e in tick(fire_v)
                       if e["outcome"] == "suppressed"]
    assert eng.actions_total == {("rebalance", "dry"): 1}
    assert eng.suppressed_total.get("cooldown", 0) >= 5
    assert all(e["detail"]["reason"] == "cooldown" for e in suppressed)
    # hysteresis: after the fire, ELEVATED windows sustain NEITHER the
    # streak nor the re-arm — a signal hovering in the recover band
    # cannot re-fire even after the cooldown expires
    eng2 = _dry_engine([HotspotRebalance()], burn=2, cooldown=1.0)
    elev_v = view([member(i) for i in range(4)],
                  slo=[slo_state(breached=False, value_ms=350.0)])
    eng2.tick(fire_v, now=1.0)
    eng2.tick(fire_v, now=2.0)              # fires, disarms
    for i in range(10):                     # cooldown long since expired
        out = eng2.tick(elev_v if i % 2 else fire_v, now=10.0 + i)
        assert out == []                    # disarmed: skipped silently
    assert eng2.actions_total == {("rebalance", "dry"): 1}


class _Always(PolicyRule):
    def __init__(self, name, action):
        super().__init__()
        self.name, self.action = name, action

    def signal(self, view):
        return FIRING

    def plan(self, view):
        return {"from": self.name}


def test_one_action_per_tick_and_inflight_suppression():
    eng = _dry_engine([_Always("a", "act_a"), _Always("b", "act_b")],
                      burn=1)
    entries = eng.tick(view([member(0)]), now=1.0)
    assert [e["outcome"] for e in entries] == ["dry", "suppressed"]
    assert entries[1]["detail"]["reason"] == "inflight"
    assert eng.suppressed_total == {"inflight": 1}
    # an externally in-flight rebalance (operator-driven) gates too
    eng2 = _dry_engine([_Always("a", "act_a")], burn=1)
    [e] = eng2.tick(view([member(0)], rebalancing=True), now=1.0)
    assert e["outcome"] == "suppressed"
    assert e["detail"]["reason"] == "inflight"


def test_dry_run_records_but_never_executes():
    import time as _time

    calls = []
    eng = PolicyEngine(
        mode="dry", actions={"rebalance": lambda d: calls.append(d)},
        cooldown_s=100.0, burn_windows=1, tick_s=0.0,
        rules=[HotspotRebalance()])
    v = view([member(i) for i in range(4)], hints=[straggler_hint(2)])
    # a real-clock now: state()'s cooldown view compares against
    # time.monotonic(), so the charged window must be anchored to it
    [entry] = eng.tick(v, now=_time.monotonic())
    assert entry["outcome"] == "dry" and calls == []
    assert eng.last_action()["outcome"] == "dry"
    st = eng.state()
    assert st["mode"] == "dry"
    assert st["actions_total"] == {"rebalance:dry": 1}
    assert st["rules"]["hotspot_rebalance"]["fired_total"] == 1
    assert not st["rules"]["hotspot_rebalance"]["armed"]
    assert "rebalance" in st["cooldown"]  # cooldown charged even dry
    # the prometheus exporter renders the labeled counters
    text = eng.render_prometheus()
    assert ('ps_policy_actions_total{action="rebalance",outcome="dry"} 1'
            in text)


def test_engine_executes_and_audit_mutates_in_place():
    import time as _time

    done = []
    eng = PolicyEngine(
        mode="on", actions={"rebalance": lambda d: done.append(d)
                            or {"moves": 1}},
        cooldown_s=100.0, burn_windows=1, tick_s=0.0,
        rules=[HotspotRebalance()])
    v = view([member(i) for i in range(4)], hints=[straggler_hint(1)])
    [entry] = eng.tick(v, now=1.0)
    # the executor runs on its own thread; the tick's entry starts as
    # "started" and MUTATES in place — it may already be final here
    assert entry["outcome"] in ("started", "ok")
    deadline = _time.monotonic() + 5.0
    while entry["outcome"] == "started" and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert entry["outcome"] == "ok" and entry["result"] == {"moves": 1}
    assert done == [{"targets": [0, 2, 3], "suspects": [1]}]
    assert eng.actions_total == {("rebalance", "ok"): 1}
    # a failing executor audits as failed, never raises into the tick
    eng2 = PolicyEngine(
        mode="on", actions={"rebalance": lambda d: 1 / 0},
        cooldown_s=100.0, burn_windows=1, tick_s=0.0,
        rules=[HotspotRebalance()])
    [e2] = eng2.tick(v, now=1.0)
    deadline = _time.monotonic() + 5.0
    while e2["outcome"] == "started" and _time.monotonic() < deadline:
        _time.sleep(0.01)
    assert e2["outcome"] == "failed"
    assert "ZeroDivisionError" in e2["result"]["error"]


# -- coordinator plumbing + wire surface --------------------------------------


def test_coordinator_policy_knobs_and_wire_surface():
    coord = Coordinator(bind="127.0.0.1", policy="dry",
                        policy_cooldown_s=5.0, policy_burn_windows=2)
    try:
        assert coord.policy is not None
        assert coord.policy.mode == "dry"
        assert coord.policy.cooldown_s == 5.0
        assert coord.policy.burn_windows == 2
        out = fetch_policy(f"127.0.0.1:{coord.port}")
        assert out["mode"] == "dry"
        assert set(out["rules"]) == {"hotspot_rebalance", "replica_reseed",
                                     "shard_add", "shard_drain"}
        assert out["actions"] == []
    finally:
        coord.stop()
    # default (Config policy="off"): no engine, and the wire says so
    coord2 = Coordinator(bind="127.0.0.1")
    try:
        assert coord2.policy is None
        assert fetch_policy(f"127.0.0.1:{coord2.port}")["mode"] == "off"
    finally:
        coord2.stop()


def test_policy_bad_mode_is_loud():
    with pytest.raises(ValueError, match="dry/on"):
        PolicyEngine(mode="sometimes")


def test_policy_off_is_byte_identical():
    """ISSUE acceptance: PS_POLICY=off (the default) changes NOTHING —
    the same seeded push sequence lands bitwise-identical params whether
    the coordinator runs no engine or an armed-but-quiet one."""
    rng = np.random.default_rng(11)
    tree = {f"k{i}": rng.standard_normal((256,)).astype(np.float32)
            for i in range(4)}
    grads = {k: np.full((256,), 1e-3, np.float32) for k in tree}

    def run(policy):
        ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
        try:
            st = ps.KVStore(optimizer="sgd", learning_rate=0.5,
                            mode="async")
            st.init({k: np.array(v) for k, v in tree.items()})
            coord = Coordinator(bind="127.0.0.1", policy=policy,
                                telemetry_window_s=2.0)
            svc = AsyncPSService(st, bind="127.0.0.1",
                                 coordinator=f"127.0.0.1:{coord.port}")
            w = connect_async(None, 0, tree,
                              coordinator=f"127.0.0.1:{coord.port}")
            try:
                w.pull_all()
                for _ in range(10):
                    w.push_pull(grads)
                params = {k: np.array(st._engine._params[k])
                          for k in tree}
                audit = (list(coord.policy.audit())
                         if coord.policy else [])
                return params, audit
            finally:
                w.close()
                svc.stop()
                coord.stop()
        finally:
            ps.shutdown()

    p_off, audit_off = run("off")
    p_on, audit_on = run("on")
    assert audit_off == [] and audit_on == []  # quiet fleet: no actions
    for k in tree:
        assert np.array_equal(p_off[k], p_on[k]), k


def test_hints_stamping_and_expiry():
    """ISSUE small fix: every hint carries the coordinator-clock stamp
    (``t``) and the window it covers (``window_s``), and expires out of
    the reply once the stamp ages past 3x the window."""
    import time as _time

    from ps_tpu.elastic.member import CoordinatorMember

    coord = Coordinator(bind="127.0.0.1", max_skew=2.0)
    members = []
    try:
        members.append(CoordinatorMember(
            f"127.0.0.1:{coord.port}", "127.0.0.1:9100",
            {"a": 100_000}))
        members.append(CoordinatorMember(
            f"127.0.0.1:{coord.port}", "127.0.0.1:9101", {"b": 100}))
        now = _time.monotonic()
        hints = coord.hints(now=now)
        assert len(hints) == 1 and hints[0]["kind"] == "byte_skew"
        # stamped to the millisecond, so it can round up past `now`
        assert hints[0]["t"] <= now + 5e-4
        assert hints[0]["window_s"] > 0
        # within the freshness horizon the hint survives...
        assert coord.hints(now=now + 2.0 * hints[0]["window_s"])
        # ...past 3x its window it expires instead of lying forever
        assert coord.hints(
            now=now + 3.0 * hints[0]["window_s"] + 1.0) == []
    finally:
        for m in members:
            m.close()
        coord.stop()
