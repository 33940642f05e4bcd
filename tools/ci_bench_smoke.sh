#!/usr/bin/env bash
# Bench smoke (<60 s per leg), referenced from the README next to
# tools/ci_tier1.sh:
#   1. transport: `bench.py --model transport --quick` — asserts BOTH the
#      bucketed-TCP lane and the same-host shared-memory lane move data,
#      printing the per-lane GB/s — and the zero-upcall push-admission
#      A/B: byte-identical final params and a pushes/s win at N=8
#      replaying workers with native admission on vs off.
#   2. failover: `bench.py --model failover --quick` — spawns a
#      primary+backup pair, severs the primary (SIGKILL-equivalent),
#      asserts the heartbeat-triggered promotion completed and the worker's
#      next push landed, printing the kill-to-recovery latency — and that
#      the traced 2-shard drill produced a linked Perfetto trace.
#   3. obs (<30 s): spawns a replicated pair with the /metrics endpoint
#      on, pushes traffic, scrapes /metrics mid-run and asserts the
#      counters moved, then runs `tools/ps_top.py --once` against the
#      pair and checks both roles render.
#   4. rebalance (<60 s): spawns 2 shards + a coordinator, splits to 4
#      shards mid-traffic over the live migration stream (then drains
#      back to 2), and asserts zero lost pushes (the per-key exactly-once
#      ledger), a committed table epoch, and that the worker re-routed
#      without restarting.
#   5. fleet telemetry (<45 s): 3 members + a coordinator + an elastic
#      worker pushing; asserts the coordinator's /metrics serves fleet
#      p99 series (merged raw buckets), and that `tools/ps_doctor.py
#      --coord` exits 0 with a non-empty per-step breakdown (and
#      `ps_top --fleet` renders).
#
# Usage: tools/ci_bench_smoke.sh   (from the repo root)
#
# Leg 0 (< 30 s): tools/ci_lint.sh — pslint static analysis + the
# TSan and ASan/UBSan native-van legs; a lint finding or sanitizer
# report fails the smoke before any bench runs.
set -euo pipefail
bash "$(dirname "$0")/ci_lint.sh"
out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model transport --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

det = json.loads(sys.argv[1])["detail"]
lanes = {
    "serial (writev)": det["serial_gbps"],
    "serial (staged)": det["serial_staged_gbps"],
    "bucketed tcp": det["bucketed_gbps"],
    "shm (full cycle)": det["shm_gbps"],
    "wire bucketed tcp": det["wire_bucketed_tcp_gbps"],
    "wire shm": det["wire_shm_gbps"],
}
for name, gbps in lanes.items():
    print(f"  {name:18s} {gbps:8.3f} GB/s")
assert det["bucketed_gbps"] and det["bucketed_gbps"] > 0, \
    "bucketed-TCP lane moved no data"
assert det["shm_gbps"] and det["shm_gbps"] > 0, "shm lane moved no data"
assert det["shm_lane_stats"]["negotiated"], "shm lane failed to negotiate"
assert det["shm_lane_stats"]["shm_frames"] > 0, \
    "shm lane negotiated but no frames rode the rings"
print(f"  shm/tcp wire speedup: {det['shm_speedup_vs_bucketed_tcp']}x")
# fleet-telemetry overhead: reports-on vs reports-off, back to back.
# The real cost is one snapshot+delta per second (< 2% on a quiet
# machine); the CI bound is loose because best-of-2 windows on a
# 2-core host carry ±10% scheduler noise either direction.
assert det["telemetry_on_gbps"] and det["telemetry_on_gbps"] > 0, \
    "telemetry leg moved no data"
assert det["telemetry_overhead_pct"] < 20.0, \
    f"telemetry overhead way over budget: {det['telemetry_overhead_pct']}%"
print(f"  telemetry overhead: {det['telemetry_overhead_pct']}% "
      f"({det['telemetry_off_gbps']} -> {det['telemetry_on_gbps']} GB/s)")
# two-tier aggregation drill (2-host-emulated, process-grouped): fan_in
# workers pre-reduce through one aggregator over an emulated shared
# uplink. The headline claim is MEASURED: cross-host bytes/step must be
# the flat group's bytes divided by the fan-in (+ per-bucket header
# overhead), and the ByteScheduler-side effects must point the right
# way — overlap efficiency up, flush-wait share down — vs the flat
# group under the identical uplink.
ag = det["agg"]
F = ag["fan_in"]
assert F >= 2, f"aggregation drill ran with fan_in {F} < 2"
header_allowance = 256 * 1024  # json meta per bucket + members tokens
assert ag["cross_host_bytes_per_step"] <= \
    ag["flat_bytes_per_step"] / F + header_allowance, \
    (f"cross-host bytes/step {ag['cross_host_bytes_per_step']} not cut "
     f"by the fan-in (flat {ag['flat_bytes_per_step']} / F={F})")
assert ag["reduction_ratio"] and ag["reduction_ratio"] > 1.8, \
    f"cross-host byte reduction {ag['reduction_ratio']}x < 1.8x"
assert ag["realized_fan_in"] == F, \
    f"rounds merged {ag['realized_fan_in']} members, expected {F}"
assert ag["overlap_efficiency"] > ag["flat_overlap_efficiency"], \
    (f"overlap efficiency did not improve: agg "
     f"{ag['overlap_efficiency']} vs flat {ag['flat_overlap_efficiency']}")
assert ag["flush_wait_share"] < ag["flat_flush_wait_share"], \
    (f"flush-wait share did not shrink: agg {ag['flush_wait_share']} vs "
     f"flat {ag['flat_flush_wait_share']}")
print(f"  agg drill: bytes/step {ag['flat_bytes_per_step']} -> "
      f"{ag['cross_host_bytes_per_step']} ({ag['reduction_ratio']}x, "
      f"fan-in {F}); overlap {ag['flat_overlap_efficiency']} -> "
      f"{ag['overlap_efficiency']}; flush-wait share "
      f"{ag['flat_flush_wait_share']} -> {ag['flush_wait_share']}; "
      f"wall {ag['flat_wall_s']}s -> {ag['wall_s']}s")
# zero-upcall push admission A/B (README "Push path"): byte-identical
# applied state is a HARD gate — the native tier must ack replays and
# refuse roles without ever changing what applies; the pushes/s win at
# N=8 replaying workers is the perf acceptance (the CI bar leaves
# 2-core scheduler-noise room under the measured ~1.8x)
pp = det["push_plane"]
assert pp["params_match"], \
    (f"admission on/off final params diverged: {pp['digest_off']} vs "
     f"{pp['digest_on']}")
assert pp["replay_acked"]["on"] == pp["replay_acked"]["off"], \
    f"replay acks diverged across the A/B: {pp['replay_acked']}"
assert pp["native_admit_share"] and pp["native_admit_share"] > 0.5, \
    f"native admission barely classifying: {pp['native_admit_share']}"
assert pp["speedup"] and pp["speedup"] > 1.05, \
    f"no pushes/s win from native admission: {pp['speedup']}x"
print(f"  push plane (N={pp['workers']}): "
      f"{pp['pushes_per_s']['off']} -> {pp['pushes_per_s']['on']} "
      f"pushes/s ({pp['speedup']}x), p99 "
      f"{pp['push_p99_us']['off']} -> {pp['push_p99_us']['on']} us, "
      f"native share {pp['native_admit_share']}, params bitwise-equal")
print("transport smoke OK")
EOF

out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model failover --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
det = rec["detail"]
assert det["promote_reason"] == "timeout", \
    f"backup never promoted on the heartbeat timeout: {det['promote_reason']}"
assert rec["value"] and rec["value"] > 0, "no post-failover push landed"
assert det["baseline_cycles_per_s"] > 0 and det["sync_repl_cycles_per_s"] > 0
assert det["trace_linked"], \
    "failover drill trace: worker->primary->backup span chain is broken"
assert det["trace_spans"] > 0 and det["flight_events"] > 0
print(f"  trace: {det['trace_spans']} spans -> {det['trace_file']} "
      f"(linked={det['trace_linked']}); "
      f"{det['flight_events']} flight event(s)")
print(f"  baseline          {det['baseline_cycles_per_s']:8.1f} cycles/s")
print(f"  sync-ack pair     {det['sync_repl_cycles_per_s']:8.1f} cycles/s "
      f"({det['sync_overhead_x']}x overhead)")
print(f"  async-ack pair    {det['async_repl_cycles_per_s']:8.1f} cycles/s "
      f"({det['async_overhead_x']}x overhead)")
print(f"  kill -> first successful push: {rec['value']}s "
      f"(heartbeat horizon {det['heartbeat_timeout_ms']}ms)")
print("failover smoke OK")
EOF

# obs leg (<30 s): live /metrics scrape mid-traffic + ps_top --once
timeout -k 10 60 env JAX_PLATFORMS=cpu python - <<'EOF'
import json
import subprocess
import sys
import urllib.request

import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp

import ps_tpu as ps
from ps_tpu import obs
from ps_tpu.backends.remote_async import AsyncPSService, connect_async

srv = obs.start_metrics_server(0)  # ephemeral port, this process
params = {f"p{i}/w": jnp.asarray(np.full((64, 8), 0.5, np.float32))
          for i in range(4)}
ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
st.init(params)
prim = AsyncPSService(st, bind="127.0.0.1")
st2 = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
st2.init(params)
back = AsyncPSService(st2, bind="127.0.0.1", backup=True)
prim.attach_backup("127.0.0.1", back.port, ack="sync")
uri = f"127.0.0.1:{prim.port}|127.0.0.1:{back.port}"
w = connect_async(uri, 0, params)
w.pull_all()
grads = {k: jnp.full_like(v, 0.01) for k, v in params.items()}

def scrape():
    url = f"http://127.0.0.1:{srv.port}/metrics"
    text = urllib.request.urlopen(url, timeout=5).read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        name, val = line.rsplit(" ", 1)
        out[name] = float(val)
    return out

before = scrape()["ps_server_requests_total"]
for _ in range(5):
    w.push_pull(grads)
mid = scrape()  # mid-bench: the pair is still serving
assert mid["ps_server_requests_total"] > before, \
    "/metrics counters did not move under traffic"
assert mid.get("ps_replica_ack_wait_seconds_count", 0) > 0, \
    "replica-ack histogram empty under sync replication"
print(f"  /metrics: requests {before:.0f} -> "
      f"{mid['ps_server_requests_total']:.0f}, ack-hist count "
      f"{mid['ps_replica_ack_wait_seconds_count']:.0f}")

top = subprocess.run(
    [sys.executable, "tools/ps_top.py", "--servers", uri,
     "--once", "--json"],
    capture_output=True, text=True, timeout=30)
assert top.returncode == 0, top.stderr
rows = json.loads(top.stdout)
roles = sorted(r.get("role") for r in rows)
assert roles == ["backup", "primary"], roles
assert all("lat" in (r.get("metrics") or {}) for r in rows
           if r.get("role") == "primary"), "primary STATS carries no lat"
print(f"  ps_top --once: {len(rows)} endpoint(s), roles {roles}")

w.close(); back.stop(); prim.stop(); ps.shutdown()
print("obs smoke OK")
EOF

# rebalance leg (<60 s): 2 shards + coordinator, split mid-traffic over
# the live migration stream, drain back — zero lost pushes (the per-key
# exactly-once ledger is asserted INSIDE the bench), a committed table
# epoch, and the worker re-routed live instead of restarting.
out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model rebalance --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
det = rec["detail"]
assert rec["metric"] == "rebalance_move_gbps" and rec["value"] > 0, rec
assert det["exactly_once"], "the per-key apply ledger did not balance"
assert det["pushes"] > 0, "the hammer never pushed during the drill"
assert det["table_epoch"] >= 4, \
    f"too few committed epochs for a split+drain: {det['table_epoch']}"
assert det["table_reroutes"] >= 1, \
    "the worker never re-routed — the moves cannot have been live"
assert det["split_moves"] and det["drain_moves"], det
print(f"  move throughput   {rec['value']:8.3f} GB/s "
      f"({det['moved_bytes'] / 1e6:.1f} MB in {det['move_seconds']}s)")
base, split = det["cycle_p_baseline"], det["cycle_p_during_split"]
if base and split:
    print(f"  cycle p99: baseline {base['p99_ms']}ms, during split "
          f"{split['p99_ms']}ms (disturbance {det['p99_disturbance_x']}x)")
print(f"  {det['pushes']} pushes, {det['table_reroutes']} live "
      f"re-route(s), table epoch {det['table_epoch']}; "
      f"exactly-once ledger balanced")
print("rebalance smoke OK")
EOF

# fleet-telemetry leg (<45 s): 3 members + coordinator + elastic worker;
# fleet p99 series on the coordinator's /metrics (merged raw buckets),
# ps_doctor exits 0 with a non-empty breakdown, ps_top --fleet renders.
timeout -k 10 90 env JAX_PLATFORMS=cpu PS_SLO_RULES='push_pull p99 < 30s over 10s' python - <<'EOF'
import json
import subprocess
import sys
import time
import urllib.request

import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp

import ps_tpu as ps
from ps_tpu import obs
from ps_tpu.backends.remote_async import AsyncPSService, connect_async
from ps_tpu.elastic import Coordinator

srv = obs.start_metrics_server(0)  # the coordinator process's scrape
ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
coord = Coordinator(port=0, report_ms=150, telemetry_window_s=5.0)
caddr = f"127.0.0.1:{coord.port}"
params = {f"p{i}/w": jnp.asarray(np.full((64, 8), 0.5, np.float32))
          for i in range(6)}
keys = sorted(params)
svcs = []
for s in range(3):
    st = ps.KVStore(optimizer="sgd", learning_rate=0.1, mode="async")
    st.init({k: params[k] for k in keys[s * 2:(s + 1) * 2]})
    svcs.append(AsyncPSService(st, bind="127.0.0.1", coordinator=caddr))
w = connect_async(None, 0, params, coordinator=caddr)
w.pull_all()
grads = {k: jnp.full_like(v, 0.01) for k, v in params.items()}
t0 = time.time()
pushes = 0
while time.time() - t0 < 4.0:
    w.push_pull(grads)
    pushes += 1
time.sleep(0.4)  # one more report cadence lands

text = urllib.request.urlopen(
    f"http://127.0.0.1:{srv.port}/metrics", timeout=5).read().decode()
assert "ps_fleet_server_apply_seconds_bucket" in text, \
    "coordinator /metrics serves no fleet histogram series"
p99 = [ln for ln in text.splitlines()
       if "quantile_seconds" in ln and 'q="p99"' in ln]
assert p99, "no per-member fleet p99 gauges on /metrics"
print(f"  /metrics: fleet series present ({len(p99)} p99 gauge(s))")

doc = subprocess.run(
    [sys.executable, "tools/ps_doctor.py", "--coord", caddr, "--json"],
    capture_output=True, text=True, timeout=30)
assert doc.returncode == 0, doc.stderr or doc.stdout
rep = json.loads(doc.stdout)
bd = rep["telemetry"]["breakdown"]
assert bd and bd.get("total", {}).get("count", 0) > 0, \
    f"ps_doctor breakdown is empty: {bd}"
assert rep["telemetry"]["fleet"], "ps_doctor saw no fleet quantiles"
assert any(r["rule"] for r in rep["telemetry"]["slo"]), \
    "PS_SLO_RULES rule did not reach the coordinator"
print(f"  ps_doctor: breakdown phases {sorted(bd)} over "
      f"{bd['total']['count']} step(s)")

top = subprocess.run(
    [sys.executable, "tools/ps_top.py", "--fleet", "--coord", caddr,
     "--once"],
    capture_output=True, text=True, timeout=30)
assert top.returncode == 0, top.stderr
assert "fleet window" in top.stdout and "primary" in top.stdout, \
    top.stdout
print("  ps_top --fleet: header + member table render")

w.close()
for s in svcs:
    s.stop()
coord.stop()
ps.shutdown()
print(f"fleet-telemetry smoke OK ({pushes} pushes)")
EOF

# 6. native event loop fleet curve (<45 s): per-connection overhead at
# N=8 simulated workers, native epoll loop vs thread-per-connection
# (README "Native event loop") — asserts the native curve exists, stays
# within the flatness bar, and that a quick native push/pull round trip
# works end to end (drain included).
out=$(timeout -k 10 100 env JAX_PLATFORMS=cpu python bench.py --model transport --fleet 8 --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "fleet_overhead_us_per_conn", rec["metric"]
det = rec["detail"]
nat, thr = det["native_us_per_conn"], det["threaded_us_per_conn"]
assert nat and thr, "fleet curve missing a mode"
for n, us in sorted(nat.items(), key=lambda kv: int(kv[0])):
    print(f"  N={n:>3}: native {us:8.2f} us/conn   "
          f"threaded {thr[n]:8.2f} us/conn")
# the acceptance bar (flat within 2x of the smallest-N value) with CI
# headroom: quick windows on a noisy 2-core host
assert det["native_flatness"] < 3.0, \
    f"native per-conn overhead not flat: {det['native_flatness']}x"
print(f"  flatness: native {det['native_flatness']}x, "
      f"threaded {det['threaded_flatness']}x; "
      f"threaded/native at N={det['fleet']}: "
      f"{det['threaded_vs_native_at_max']}x")
print("native-loop fleet smoke OK")
EOF

# 7. serve / read path (<60 s): N concurrent readers against a
# replicated shard (README "Read path") — layered serving (native
# zero-upcall cache + replica reads) vs the primary-only pump path,
# under a concurrent pusher. Asserts the native-hit curve stays flat as
# readers grow, read scaling clears its CI bar (quiet-hardware target
# >= 5x, measured 5.3x), the read_all p99 is sane, reads spread across
# the replica set, the bounded-staleness drill saw ZERO violations, and
# the conditional-read leg ships >= 5x fewer bytes per warm read at
# bitwise parity with the full pull.
out=$(timeout -k 10 150 env JAX_PLATFORMS=cpu python bench.py --model serve --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "serve_read_qps", rec["metric"]
det = rec["detail"]
counts = [str(n) for n in det["reader_counts"]]  # json stringifies keys
for n in counts:
    print(f"  N={n}: layered {det['layered_qps'][n]:>9} reads/s   "
          f"primary-only {det['primary_only_qps'][n]:>8} reads/s   "
          f"native-hit {det['native_hit_rate'][n]:.4f}")
# native-hit curve flat-or-rising as readers grow (small tolerance:
# every invalidation by the pusher costs one miss per cache)
hr = [det["native_hit_rate"][n] for n in counts]
assert hr[-1] >= hr[0] - 0.05, f"native-hit rate degraded with readers: {hr}"
assert min(hr) > 0.5, f"native cache barely hitting: {hr}"
# read scaling vs primary-only at equal reader count: quiet-hardware
# target >= 5x; the CI bar leaves room for 2-core scheduler noise
assert det["read_scaling"] > 3.0, \
    f"read scaling {det['read_scaling']}x under the CI bar (3x)"
# end-to-end read_all p99 (quiet-hardware bar: < 10 ms; CI headroom)
assert det["read_p99_ms"] is not None and det["read_p99_ms"] < 50.0, \
    f"read p99 {det['read_p99_ms']}ms way over budget"
assert det["replica_read_share"] > 0.2, \
    f"reads not spreading over the replica set: {det['replica_read_share']}"
assert det["staleness_drill"]["violations"] == 0, \
    f"staleness bound violated: {det['staleness_drill']}"
# conditional & delta reads: a warm zipfian reader revalidating its
# id-set ships a NOT_MODIFIED handshake or a row delta, never the full
# payload — >= 5x fewer bytes per warm read (measured ~97x) at
# unchanged-or-better QPS, and the merged view stays bitwise the full
# pull (the loose QPS bar absorbs 2-core scheduler noise)
cr = det["conditional_read"]
assert cr["parity"], "conditional-read merged view != full pull"
assert cr["warm_bytes_ratio"] >= 5.0, \
    f"warm bytes/read only {cr['warm_bytes_ratio']}x smaller " \
    f"with conditional reads on: {cr}"
assert cr["on"]["reads_per_s"] > 0.5 * cr["off"]["reads_per_s"], \
    f"conditional reads cost QPS: {cr}"
assert cr["not_modified"] > 0, f"no NOT_MODIFIED served under churn: {cr}"
# in-loop telemetry (README "Native observability"): the zero-upcall
# READ-hit latency must be visible END TO END — native striped buckets
# -> pump sync -> /metrics — with a sane p99 (a native hit is a memcmp
# + a writev: microseconds, never approaching a second)
nl = det["nl_read_hit_metrics"]
assert nl["on_metrics"] and nl["count"] > 0, \
    f"ps_nl_read_hit_seconds missing from /metrics: {nl}"
assert nl["p99_ms"] is not None and 0 < nl["p99_ms"] < 1000.0, \
    f"native read-hit p99 insane: {nl}"
assert det["native_hit_p99_us"] and det["native_hit_p99_us"] > 0, det
# instrumentation must not tax the path it measures: stats-on vs
# stats-off read QPS (quiet-hardware bar < 2%; the CI bound is loose
# because best-of-2 windows on a 2-core host carry scheduler noise)
assert det["telemetry_overhead_pct"] < 25.0, \
    f"in-loop telemetry overhead way over budget: " \
    f"{det['telemetry_overhead_pct']}%"
print(f"  scaling {det['read_scaling']}x, read_all p99 "
      f"{det['read_p99_ms']}ms, replica share "
      f"{det['replica_read_share']}, staleness violations 0")
print(f"  conditional: warm {cr['off']['warm_bytes_per_read']} -> "
      f"{cr['on']['warm_bytes_per_read']} B/read "
      f"({cr['warm_bytes_ratio']}x), "
      f"{cr['not_modified']} not-modified, "
      f"{cr['delta_rows']} delta rows, parity {cr['parity']}")
print(f"  native hit p99 {det['native_hit_p99_us']}us "
      f"(/metrics count {nl['count']}, p99 {nl['p99_ms']}ms); "
      f"nl-stats overhead {det['telemetry_overhead_pct']}% "
      f"({det['nl_stats_off_qps']} -> {det['nl_stats_on_qps']} reads/s)")
print("serve read-path smoke OK")
EOF

# 8. sparse fused apply (<45 s): the fused gather->apply->scatter vs the
# masked full-table baseline (README "Sparse apply"), identical push
# streams on the CPU — asserts numerical parity held (bitwise expected
# for adagrad's fixed reduction order), the >=2x rows-applied/s
# acceptance bar at a table >=100x the batch id-set, and that the HBM
# model + tier landed in the BENCH json. The parity drill itself runs
# in tier-1 (tests/test_sparse_apply.py); this leg is the
# measured-throughput half.
out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model sparse_apply --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "sparse_rows_applied_per_s", rec["metric"]
det = rec["detail"]
assert det["parity_allclose"], \
    f"fused vs full-table parity broke: max abs {det['parity_max_abs']}"
assert det["parity_bitwise"], \
    "adagrad fused apply should be BITWISE vs the masked path " \
    f"(fixed reduction order); max abs {det['parity_max_abs']}"
assert det["table_to_batch_x"] >= 100, det["table_to_batch_x"]
# the acceptance bar: >=2x rows/s vs the masked full-table baseline
# (measured ~14x on the 2-core host — donation makes the fused scatter
# a true in-place update; the bar leaves room for scheduler noise)
assert det["speedup_x"] >= 2.0, \
    f"fused speedup {det['speedup_x']}x under the 2x acceptance bar"
assert rec["value"] and rec["value"] > 0, "no rows applied"
m = det["hbm_bytes_per_apply"]
assert m["fused_bytes_per_apply"] < m["full_table_bytes_per_apply"]
for tier, rps in det["rows_applied_per_s"].items():
    print(f"  {tier:>6}: {rps:>12,.0f} rows/s")
print(f"  speedup {det['speedup_x']}x at table/batch "
      f"{det['table_to_batch_x']}x (tier {det['tier']}); parity "
      f"bitwise={det['parity_bitwise']}; HBM model "
      f"{m['fused_bytes_per_apply']:,} vs "
      f"{m['full_table_bytes_per_apply']:,} bytes/apply "
      f"({m['ratio']}x)")
print("sparse fused-apply smoke OK")
EOF

# 9. tiered embedding storage (<60 s): one Wide-&-Deep-shaped zipf
# push/read stream against a TieredTable 4x its device budget vs the
# identical stream untiered (README "Tiered embedding storage") —
# asserts the two non-negotiables (all-hot-path bitwise parity, zero
# rows lost across admission/eviction churn) plus a host-scaled
# throughput floor. ROADMAP's >=70% is the TPU hardware acceptance;
# the CI bar is looser because the 2-core host pays python directory
# overhead per push that HBM/DRAM bandwidth asymmetry dwarfs on metal.
out=$(timeout -k 10 180 env JAX_PLATFORMS=cpu python bench.py --model tiered --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "tiered_rows_applied_per_s", rec["metric"]
det = rec["detail"]
# the non-negotiable: a stream confined to the resident hot set must
# leave the device tier bitwise-equal to an untiered table
assert det["allhot_parity_bitwise"], \
    "tiered all-hot path diverged bitwise from the untiered table"
# zero rows lost across promotion/demotion churn: every logical row
# must match the untiered oracle's value (f64 row-sum audit)
assert det["rowsum_conserved"], \
    f"rows lost/corrupted across tier churn: rel err {det['rowsum_rel_err']}"
assert det["table_to_budget_x"] == 4, det["table_to_budget_x"]
# the host-scaled CI floor: measured ~1.3x on the 2-core host (the
# tiered device table is 4x smaller, which CPU likes); 0.5 leaves
# room for scheduler noise while still catching a serialized cold path
assert det["throughput_ratio"] >= 0.5, \
    f"tiered throughput {det['throughput_ratio']}x under the CI floor"
assert det["hot_hit_rate"] and det["hot_hit_rate"] > 0.5, \
    f"zipf stream should mostly hit the hot set: {det['hot_hit_rate']}"
assert det["promotions_per_1k"] > 0, "admission never fired"
assert det["evictions_per_1k"] > 0, "eviction never fired"
for kind, rps in det["rows_applied_per_s"].items():
    print(f"  {kind:>6}: {rps:>12,.0f} rows/s")
print(f"  ratio {det['throughput_ratio']}x at table/budget "
      f"{det['table_to_budget_x']}x; hot-hit {det['hot_hit_rate']}; "
      f"promotions/1k {det['promotions_per_1k']}, evictions/1k "
      f"{det['evictions_per_1k']}; all-hot bitwise="
      f"{det['allhot_parity_bitwise']}, rows conserved="
      f"{det['rowsum_conserved']}")
print("tiered embedding smoke OK")
EOF

# 10. autopilot chaos soak (<60 s): `bench.py --model chaos --quick` —
# the policy-driven self-heal loop under scheduled faults (README
# "Autopilot & chaos"). Asserts every injected fault class healed
# inside its SLO bound, the per-key exactly-once ledger balanced across
# the whole soak, at least one policy action EXECUTED (outcome ok), and
# zero operator interventions inside the soak window.
out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model chaos --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "chaos_self_heal_p99_s", rec["metric"]
det = rec["detail"]
assert det["exactly_once"], \
    "the per-key apply ledger did not balance across the soak"
assert det["operator_actions_in_soak"] == 0, \
    f"soak needed operator help: {det['operator_actions_in_soak']}"
assert det["faults"], "no fault classes were drilled"
for cls, row in sorted(det["faults"].items()):
    assert row["heal_p99_s"] <= row["slo_bound_s"], \
        (f"{cls} healed in {row['heal_p99_s']}s, over its "
         f"{row['slo_bound_s']}s bound")
    print(f"  {cls:>15}: healed p99 {row['heal_p99_s']:6.2f}s "
          f"(bound {row['slo_bound_s']}s) via {row['resolved_by']}")
acted = {k: n for k, n in det["policy_actions_total"].items()
         if k.endswith(":ok")}
assert acted, \
    f"no policy action executed: {det['policy_actions_total']}"
assert rec["value"] is not None and rec["value"] >= 0, rec
print(f"  policy actions {det['policy_actions_total']} "
      f"(suppressed {det['policy_suppressed_total']}); "
      f"{det['pushes']} pushes exactly-once; seed {det['chaos_seed']}")
print("chaos autopilot smoke OK")
EOF

# 11. online serving freshness (<60 s): `bench.py --model online --quick`
# — the closed-loop train-and-serve drill (README "Online serving &
# freshness"): zipfian readers at bounded staleness against dense+sparse
# shards while trainers keep pushing through an aggregator, swept through
# diurnal load, a 10x flash crowd on a hot id-set, and a reader:writer
# ratio shift. Asserts BOTH headline SLOs held through the flash crowd
# with training running (read p99 AND push->servable freshness p99,
# judged by the same rule grammar the coordinator parses), NM
# revalidations actually fired, and the bounded-staleness contract saw
# zero violations.
out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu python bench.py --model online --quick 2>/dev/null | tail -1)
python - "$out" <<'EOF'
import json
import sys

rec = json.loads(sys.argv[1])
assert rec["metric"] == "online_read_p99_ms", rec["metric"]
det = rec["detail"]
for s in det["slo"]:
    mark = "BREACH" if s["breached"] else "ok"
    print(f"  [{mark:6s}] {s['rule']}  value={s['value_ms']}ms")
assert det["slo_compliant"], \
    f"online SLOs breached through the flash crowd: {det['slo']}"
assert det["read_p99_ms"] is not None and det["lag_p99_ms"] is not None
assert det["nm_hits"] > 0, \
    f"no NOT_MODIFIED revalidations under the warm readers: {det['nm_hits']}"
assert det["staleness_violations"] == 0, \
    f"bounded-staleness contract violated: {det['staleness_violations']}"
assert det["reads_aged"] > 0, "no served read carried a birth stamp"
assert det["clock_clamped"] == 0, \
    f"negative ages clamped: {det['clock_clamped']}"
tiers = det["age_tiers"]
print(f"  read p99 {det['read_p99_ms']}ms, freshness lag p99 "
      f"{det['lag_p99_ms']}ms, age p95 {det['age_p95_ms']}ms; "
      f"fresh share {det['fresh_share']} over {det['reads_aged']} "
      f"aged reads")
print(f"  nm hits {det['nm_hits']} (rate {det['nm_hit_rate']}), "
      f"delta rows {det['delta_rows']}; tiers "
      + " ".join(f"{t}:{v['n']}" for t, v in sorted(tiers.items())))
print("  phases: " + "  ".join(
    f"{name} read_p99={row['read_p99_ms']}ms"
    for name, row in det["phases"].items()))
print("online freshness smoke OK")
EOF
