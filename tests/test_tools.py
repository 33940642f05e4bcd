"""The measurement tools run end to end at toy scale.

BASELINE.md's numbers come from tools/ scripts; a refactor that breaks one
should fail here, not when someone tries to reproduce a measurement.
Each runs as a subprocess at the smallest meaningful scale and must emit
its one-line JSON.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", script), *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, f"{script}:\n{proc.stdout}\n{proc.stderr}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_bench_van_smoke():
    out = _run("bench_van.py", "--mb", "2", "--cycles", "1", "--workers", "2")
    assert out["tree_mb"] > 1 and out["pull_gbps"] > 0
    assert "concurrent_pull_2w_gbps" in out


def test_bench_transport_smoke():
    """bench.py --model transport: the tentpole's win condition probe —
    must emit serial vs bucketed GB/s and an overlap-efficiency figure.
    (Not marked slow: it is the acceptance gauge for the bucketed
    transport and runs in seconds at this scale.)"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--model", "transport", "--steps", "2", "--transport-mb", "8"],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "van_push_pull_gbps_bucketed"
    d = out["detail"]
    assert d["serial_gbps"] > 0 and d["bucketed_gbps"] > 0
    assert d["overlap_efficiency"] is None or 0 <= d["overlap_efficiency"] <= 1
    assert d["transport"]["transport_buckets"] > 0


def test_bench_failover_smoke():
    """bench.py --model failover: the replication PR's acceptance gauge —
    must report steady-state replication overhead (sync + async legs) and
    a kill-to-first-successful-push latency with the backup promoted on
    the heartbeat timeout. (Not marked slow: ~6 s at --quick scale.)"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--model", "failover", "--quick"],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "failover_kill_to_first_push_s"
    assert out["value"] > 0
    d = out["detail"]
    assert d["baseline_cycles_per_s"] > 0
    assert d["sync_repl_cycles_per_s"] > 0
    assert d["async_repl_cycles_per_s"] > 0
    assert d["promote_reason"] == "timeout"


def test_bench_rebalance_smoke():
    """bench.py --model rebalance: the elastic-membership acceptance
    gauge — a 2→4→2 live rebalance under traffic must report move GB/s,
    the per-phase p99 disturbance, and a balanced per-key exactly-once
    ledger (asserted inside the bench). (Not marked slow: a few seconds
    of hammer windows at --quick scale.)"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"),
         "--model", "rebalance", "--quick"],
        env=env, capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["metric"] == "rebalance_move_gbps"
    assert out["value"] > 0
    d = out["detail"]
    assert d["exactly_once"] is True
    assert d["pushes"] > 0
    assert d["table_reroutes"] >= 1
    assert d["split_moves"] and d["drain_moves"]
    assert d["table_epoch"] >= 4  # 2 joins + >=1 split move + drain


def test_ps_top_fleet_and_ps_doctor_smoke():
    """Satellite: `ps_top --fleet` discovers the member list FROM the
    coordinator (no hand-listed endpoints) and `ps_doctor` produces a
    one-shot report with a non-empty breakdown; a dead coordinator makes
    --fleet fall back to the CLI --servers list (the old path)."""
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import ps_tpu as ps
    from ps_tpu.backends.remote_async import AsyncPSService, connect_async
    from ps_tpu.elastic import Coordinator, fetch_telemetry

    ps.init(backend="tpu", mode="async", num_workers=1, dc_lambda=0.0)
    # a window that still holds the samples when the last tool asks, however
    # long four interpreters take to start beside other processes
    coord = Coordinator(port=0, report_ms=100, telemetry_window_s=600.0)
    caddr = f"127.0.0.1:{coord.port}"
    params = {f"p{i}/w": jnp.asarray(np.full((32, 4), 0.5, np.float32))
              for i in range(4)}
    keys = sorted(params)
    svcs = []
    try:
        for s in range(2):
            st = ps.KVStore(optimizer="sgd", learning_rate=0.1,
                            mode="async")
            st.init({k: params[k] for k in keys[s * 2:(s + 1) * 2]})
            svcs.append(AsyncPSService(st, bind="127.0.0.1",
                                       coordinator=caddr))
        w = connect_async(None, 0, params, coordinator=caddr)
        try:
            w.pull_all()
            grads = {k: jnp.full_like(v, 0.01)
                     for k, v in params.items()}
            # traffic until the coordinator holds what the tools read: both
            # members' samples and the worker's breakdown (not "for 1.5 s",
            # which on a busy host can end before the first report)
            members = {f"127.0.0.1:{svc.port}" for svc in svcs}
            tel, end = {}, time.monotonic() + 60.0
            while not (members <= set(tel.get("members", ()))
                       and tel.get("fleet")
                       and tel["breakdown"].get("total", {}).get("count", 0)
                       > 0):
                assert time.monotonic() < end, tel
                for _ in range(20):
                    w.push_pull(grads)
                tel = fetch_telemetry(caddr)

            env = {k: v for k, v in os.environ.items()
                   if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
            env["JAX_PLATFORMS"] = "cpu"
            top = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "ps_top.py"),
                 "--fleet", "--coord", caddr, "--once"],
                env=env, capture_output=True, text=True, timeout=120)
            assert top.returncode == 0, top.stderr
            assert "fleet window" in top.stdout
            for svc in svcs:  # discovered, not hand-listed
                assert f"127.0.0.1:{svc.port}" in top.stdout
            assert "primary" in top.stdout

            doc = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "ps_doctor.py"),
                 "--coord", caddr, "--json"],
                env=env, capture_output=True, text=True, timeout=120)
            assert doc.returncode == 0, doc.stderr or doc.stdout
            rep = json.loads(doc.stdout)
            assert rep["telemetry"]["breakdown"].get("total", {}) \
                .get("count", 0) > 0
            assert rep["telemetry"]["fleet"]

            # dead coordinator: --fleet falls back to --servers
            servers_uri = ",".join(f"127.0.0.1:{s.port}" for s in svcs)
            coord.kill()
            top = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "ps_top.py"),
                 "--fleet", "--coord", caddr,
                 "--servers", servers_uri, "--once"],
                env=env, capture_output=True, text=True, timeout=120)
            assert top.returncode == 0, top.stderr
            assert "falling back to --servers" in top.stdout
            assert "primary" in top.stdout

            doc = subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "ps_doctor.py"),
                 "--coord", caddr],
                env=env, capture_output=True, text=True, timeout=120)
            assert doc.returncode == 2  # unreachable is a typed exit
        finally:
            w.close()
    finally:
        for s in svcs:
            s.stop()
        coord.stop()
        ps.shutdown()


@pytest.mark.slow
def test_bench_dc_asgd_smoke():
    out = _run("bench_dc_asgd.py", "--applies", "12", "--eval-every", "6",
               "--hidden", "8", "--batch", "16")
    assert len(out["sync_curve"]) == 2
    # 3 tau values x 2 lambdas
    assert len(out["configs"]) == 6
    for cfg in out["configs"]:
        assert len(cfg["curve"]) == 2
        assert sum(cfg["staleness_hist"].values()) == 12


@pytest.mark.slow
def test_measure_flops_smoke():
    out = _run("measure_flops.py", "widedeep")
    assert out["model"] == "widedeep"
    assert out["slope_per_example"] > 0 and out["const_per_step"] > 0


def test_olmoe_grad_check_rehearses():
    """tools/olmoe_grad_check.py at the configuration's tiny sizes: the
    system's gradients are the reference's, and every knocked-out piece
    moves the reference's loss or turns a witness's gradient."""
    out = _run("olmoe_grad_check.py", "--rehearse")
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    assert out["pairs_on_another_expert"] == 0
    assert set(out["knocked_out"]) == {
        "renormalised_top_k", "no_qk_norm", "no_load_balance_term",
        "no_z_loss_term", "reference_on_e4m3_weights"}
    for name, found in out["knocked_out"].items():
        cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
        assert len(cosines) == 2
        assert found["loss_rel_diff"] > 5e-5 or min(cosines) < 0.999, name


def test_lfm2_grad_check_rehearses():
    """tools/lfm2_grad_check.py at the configuration's tiny sizes: the
    system's gradients are the reference's, and every changed piece moves
    the reference's loss or turns or stretches a witness's gradient."""
    out = _run("lfm2_grad_check.py", "--rehearse")
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    assert out["pairs_on_another_expert"] == [0, 0, 0, 0]
    assert set(out["knocked_out"]) == {
        "reference_on_e4m3_weights", "picks_not_renormalised", "no_qk_norm",
        "conv_without_output_gate"}
    for name, found in out["knocked_out"].items():
        cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
        ratios = [v for k, v in found.items()
                  if k.startswith("grad_norm_ratio")]
        assert len(cosines) == len(ratios) == 4
        assert found["loss_rel_diff"] > 5e-5 or min(cosines) < 0.999 \
            or max(abs(r - 1) for r in ratios) > 0.01, name


def test_kimi_grad_check_rehearses():
    """tools/kimi_grad_check.py at the configuration's tiny sizes: the
    system's gradients are the reference's, and the reference on 8-bit
    weights turns every witness's gradient."""
    out = _run("kimi_grad_check.py", "--rehearse")
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    assert out["pairs_on_another_expert"] == [0, 0, 0, 0]
    found = out["reference_on_e4m3_weights"]
    cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
    assert len(cosines) == 6 and max(cosines) < 0.999
    assert found["loss_rel_diff"] > 5e-5


def test_qwen3_next_grad_check_rehearses():
    """tools/qwen3_next_grad_check.py at the configuration's tiny sizes: the
    system's gradients are the reference's on all 70 tensors and the system
    comes out ``correct`` through the family's own ``step0_checks``; the
    reference on 8-bit weights and every planted fault but one do not. The
    one: the gate in front of the head norm, which at a tiny width moves no
    witness past its limit (on the chip neither: ``PERF.md`` section 6, PR
    60)."""
    out = _run("qwen3_next_grad_check.py", "--rehearse")
    assert out["tensors_read"] == 70
    assert out["worst"]["cosine"] > 1 - 1e-6
    assert out["system"]["correct"] and out["system"]["failed"] == []
    assert out["system"]["pairs_on_another_expert"] == [0, 0, 0, 0]
    cases = {k: v for k, v in out.items() if k.startswith("reference_")}
    assert len(cases) == 6
    assert not cases["reference_on_e4m3_weights"]["correct"]
    for name in ("a_norm_whose_scale_ignores_w",
                 "key_heads_tiled_not_repeated", "every_channel_rotated",
                 "the_picks_not_renormalised"):
        assert "gradient_matches_reference" in \
            cases[f"reference_with_{name}"]["failed"], name


@pytest.mark.parametrize("model", ["nemotron_h", "granite_h"])
def test_nemotron_grad_check_rehearses(model):
    """tools/nemotron_grad_check.py at a configuration's tiny sizes, the
    model as data (Nemotron-H's share; Granite-4.0-H, whose loss has no
    selection bias and no counts): the system's gradients are the
    reference's, the reference on 8-bit weights turns every witness's
    gradient, and each fault planted in the reference fails a limit of the
    family's step-0 checks (Granite's through ``step0_checks`` itself)."""
    import importlib

    family = importlib.import_module(f"benchmark.families.{model}_step")
    out = _run("nemotron_grad_check.py", "--rehearse", "--table",
               "--model", model)
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    found = out["reference_on_e4m3_weights"]
    cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
    assert len(cosines) == len(family.GRAD_COSINE)
    limit = family.GRAD_NORM_TOLERANCE
    if model == "nemotron_h":
        assert max(cosines) < 0.999
        assert out["pairs_on_another_expert"] == [0, 0, 0, 0, 0]
        assert sum(found["pairs_on_another_expert"]) > 0
        # the fault the limit on the lengths is there for, and only it
        for fault in ("picks_not_scaled", "picks_not_renormalised"):
            assert out[f"reference_with_{fault}"]["lengths_apart"] > 2 * limit
        return
    # at the tiny sizes the tied embedding turns least (.99953; its gradient
    # is a sum over every position of both uses), the eight others read
    # .9842-.9973; the lengths part 0.086
    assert max(cosines) < 0.9997 and sorted(cosines)[-2] < 0.998
    assert found["lengths_apart"] > 1.5 * limit
    assert out["pairs_on_another_expert"] is None
    # through the family's own step0_checks and the loss's tolerance, as if
    # each were the system: the system is correct, no other case is
    assert out["system"] == {"correct": True, "failed": []}
    assert not found["correct"] \
        and "gradient_matches_reference" in found["failed"]
    faults = {k: v for k, v in out.items() if k.startswith("reference_with")}
    assert len(faults) == 5
    for name, read in faults.items():
        assert not read["correct"] and read["failed"], name
        # the logits' divisor is common to every gradient: the loss holds it
        assert read["lengths_apart"] > 2 * limit \
            or read["loss_rel_diff"] > 2 * family.TOLERANCE[0], name


def test_nemotron_grad_check_rehearses_ouro():
    """``tools/nemotron_grad_check.py --model ouro``: a loss with an ``aux``
    and no routing. The system's gradients are the reference's and it comes
    out correct through ``ouro_step.step0_checks`` and the loss's tolerance;
    the reference on 8-bit weights and each of the six faults the limits are
    there for do not: four by the gradients, the objective and the exit
    distribution at once, the last pass's gradient alone by the gradients
    and nothing else (its loss is the whole one's), the entropy's sign by
    the objective and the gate's gradient, turned round."""
    from benchmark.families import ouro_step as family

    out = _run("nemotron_grad_check.py", "--rehearse", "--table",
               "--model", "ouro")
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    assert out["system"]["correct"] and out["system"]["failed"] == []
    # what the aux's checks read stands beside the verdict
    assert out["system"]["exit_apart"] < 1e-6 \
        and out["system"]["objective_rel_diff"] < 1e-6
    assert out["pairs_on_another_expert"] is None
    found = out["reference_on_e4m3_weights"]
    cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
    assert len(cosines) == len(family.GRAD_COSINE) and max(cosines) < 0.999
    assert not found["correct"] \
        and "gradient_matches_reference" in found["failed"]
    faults = {k[len("reference_with_"):]: v for k, v in out.items()
              if k.startswith("reference_with")}
    assert len(faults) == 6
    for name, read in faults.items():
        assert not read["correct"], name
        assert "gradient_matches_reference" in read["failed"], name
    # the forward pass is the whole one's: the gradients alone tell
    assert faults["gradient_of_the_last_pass_alone"]["loss_rel_diff"] == 0
    assert faults["gradient_of_the_last_pass_alone"]["failed"] == [
        "gradient_matches_reference"]
    for name in ("final_norm_once_after_the_last_pass",
                 "post_norms_left_out",
                 "last_pass_takes_its_own_gates_share"):
        assert {"step0_matches_reference",
                "exit_distribution_matches_reference"} \
            <= set(faults[name]["failed"]), name
    sign = faults["entropy_term_added"]
    assert "objective_matches_reference" in sign["failed"]
    assert sign["least_grad_cosine"] < -0.5


def test_nemotron_grad_check_rehearses_phi4flash():
    """``tools/nemotron_grad_check.py --model phi4flash``: a scalar loss over
    a stack whose second half reads the first's memory. The system's
    gradients are the reference's and it comes out correct through
    ``phi4flash_step.step0_checks`` and the loss's tolerance; the reference
    on 8-bit weights and eight of the ten faults the limits are there for do
    not (the other two: below), every one by the gradients (the loss's
    tolerance catches them too at these sizes, but is not what holds them on
    the chip)."""
    from benchmark.families import phi4flash_step as family

    out = _run("nemotron_grad_check.py", "--rehearse", "--table",
               "--model", "phi4flash", timeout=600)
    assert out["worst"]["cosine"] > 1 - 1e-9
    assert out["loss"]["rel_diff"] < 1e-5
    system = out["system"]
    assert system["correct"] and system["failed"] == []
    # the lambda vectors' scalar, the system's beside the reference's own
    scalars = {k: v for k, v in system.items() if k.startswith("lambda_")}
    assert len(scalars) == len(family.LAMBDA_WITNESSES) == 12
    assert all(abs(own - whole) < 1e-3 * whole
               for own, whole in scalars.values())
    assert out["pairs_on_another_expert"] is None
    found = out["reference_on_e4m3_weights"]
    cosines = [v for k, v in found.items() if k.startswith("grad_cosine")]
    assert len(cosines) == len(family.GRAD_COSINE)
    assert max(cosines) < 0.999
    assert not found["correct"] \
        and "gradient_matches_reference" in found["failed"]
    faults = {k[len("reference_with_"):]: v for k, v in out.items()
              if k.startswith("reference_with")}
    assert sorted(faults) == [
        "cross_reads_the_window_layers_kv", "head_norm_left_out",
        "lambda_gradient_dropped", "lambda_gradient_of_the_wrong_sign",
        "lambda_init_at_the_cuts_own_depth", "memory_taken_after_the_gate",
        "one_minus_lambda_init_left_out", "rms_norm_for_layer_norm",
        "skip_left_out_of_the_memory", "window_read_as_full"]
    for name, read in faults.items():
        if name.startswith("lambda_gradient"):
            # the forward pass whole: the lambda witnesses' alone to tell,
            # and at the rehearsal's sizes the scalars lie under the floor
            # fixed from the chip's bf16 readings (the rule itself:
            # test_phi4flash.py::test_step0_checks_name_the_fault; at the
            # published sizes each fails at every seed, PERF.md section 6).
            # Held here: the reading, none or the opposite, and a verdict
            # that follows it
            times = 0.0 if name.endswith("dropped") else -1.0
            pairs = [v for k, v in read.items() if k.startswith("lambda_")]
            assert len(pairs) == 12
            assert all(abs(own - times * whole) <= 1e-6 * whole
                       for own, whole in pairs)
            assert read["correct"] == all(
                abs(own - whole) <= max(family.LAMBDA_TOLERANCE * whole,
                                        family.LAMBDA_FLOOR)
                for own, whole in pairs)
            assert set(read["failed"]) <= {"gradient_matches_reference"}
            continue
        assert not read["correct"], name
        assert "gradient_matches_reference" in read["failed"], name


def test_scope_table_rehearses():
    """tools/scope_table.py at the Phi-4-mini-flash cell's tiny sizes on the
    CPU: the loaded step carries each of the four scopes no metric reads yet
    (and not one it never opens), and no time is printed."""
    out = _run("scope_table.py", "--rehearse", "--scopes",
               "ps.mamba/s6,ps.gmu,ps.attn/cross,ps.attn/diff,ps.moe/route")
    assert out["device"] == "cpu" and "scan" not in out
    assert out["scopes"] == {"ps.mamba/s6": True, "ps.gmu": True,
                             "ps.attn/cross": True, "ps.attn/diff": True,
                             "ps.moe/route": False}


def test_window_table_rehearses():
    """tools/window_table.py at tiny shapes on the CPU: the band step's
    three calls run under the tool's own wrappers at both cells' names and
    print no time."""
    out = _run("window_table.py", "--rehearse")
    assert out["device"] == "cpu"
    assert sorted(out["shapes"]) == ["mellum", "trinity"]
    for rows in out["shapes"].values():
        assert rows["ms"] == {} and rows["window"] < rows["q"][1]
