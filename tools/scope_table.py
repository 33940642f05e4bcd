#!/usr/bin/env python3
"""A cell's traced step by any named scopes: the device time under each
``jax.named_scope`` of a list, forward, recomputation and backward together,
for scopes that no per-layer metric reads yet.

    chiprun --chips 1 -- python3 tools/scope_table.py --seed 6500000201 \\
        --scopes ps.mamba/s6,ps.gmu,ps.attn/cross,ps.attn/diff

``BENCHMARK.json`` holds its 128 ``per_layer`` entries, so a scope that a PR
opens after that has no reader in ``benchmark/layer_metrics`` until a
``benchmark`` PR makes room (``PERF.md`` section 7). This tool is the same
reduction outside the manifest: it builds the cell as ``benchmark/run.py``
does, runs the loop with a traced segment and no window to speak of, and
walks the trace once with ``benchmark/harness/tracered.py`` and
``benchmark/layer_metrics/scope.py::loaded_op_names`` (an event's
instruction name in the optimized HLO of the loaded executables gives its
``op_name``). A scope's Mosaic calls are listed by instruction with their
time a step (``mosaic_calls``: six under ``ps.mamba/s6``, the two Mamba-1
layers' forward, recomputed forward and backward; what the scope holds
beyond them is XLA's around the calls). With ``--ops copy,transpose`` the
events whose instruction name starts with one of these are listed too, a scope
at a time, by name and result shape with their count and time a step
(``ops``: the re-laid copies XLA puts around a Mosaic call show here, ``PERF.md``
section 6, PR 69). The **innermost** listed scope takes
an event's time
(``layer_metrics/decoder.py::scope_of`` over the listed names); an event
under none of them is counted under ``(none listed)``. The tool goes with the
``benchmark`` PR that lists these scopes' metrics (``PERF.md`` section 7, row
0). With the cell's facts
beside them: where the family states ``scan_bytes``, the time under
``ps.mamba/s6`` is also given as a share of the least the HBM's peak allows.
``--rehearse`` runs the cell's tiny sizes on the CPU and lists which of the
scopes the loaded step carries, without a time. Results go to stdout and to
``chiprun_out/scope_table.json``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys
import time

_T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "phi-4-mini-flash-reasoning.s16384.b1.zipf"
SCOPES = "ps.mamba/s6,ps.gmu,ps.attn/cross,ps.attn/diff"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--scopes", default=SCOPES, help="comma-separated")
    ap.add_argument("--ops", default="", help="comma-separated starts of "
                    "instruction names to list by shape under each scope")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    scopes = tuple(args.scopes.split(","))
    listed = tuple("%" + start for start in args.ops.split(",") if start)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == args.workload)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
    # the traced segment is what is read: no step beyond loss_at_n's least
    traffic["loss_step"] = 8

    import jax

    from benchmark.harness import loop, tracered
    from benchmark.harness.compilelog import CompileLog
    from benchmark.layer_metrics import decoder, scope

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("scope_table: no TPU found; --rehearse runs the tiny sizes on "
              "the CPU", file=sys.stderr)
        return 1
    family = importlib.import_module(f"benchmark.families.{config['family']}")
    built = family.build(config, traffic, int(cell["chips"]), args.seed)
    try:
        r = loop.run(built, traffic, 0.0, not args.rehearse, CompileLog(),
                     _T_START)
        op_names = scope.loaded_op_names() or {}
    finally:
        built.close()
    out = {"workload": args.workload, "seed": args.seed,
           "device": jax.devices()[0].device_kind, "scopes": {}}
    if args.rehearse:
        carried = {decoder.scope_of(own, op_name, scopes)
                   for own, op_name in op_names.items()}
        out["scopes"] = {s: s in carried for s in scopes}
    else:
        devices, steps = r["trace"]["devices"], r["traced_steps"]
        per_ms = 1e3 / steps / len(devices)
        seconds, kernels, by_shape = {}, {}, {}
        for d in devices.values():
            for name, sec in d["ops"].items():
                own = tracered.parts(name)["own"]
                found = decoder.scope_of(own, op_names.get(own) or "", scopes)
                key = found or "(none listed)"
                seconds[key] = seconds.get(key, 0.0) + sec
                if found and tracered.is_custom_call_to(
                        name, ("tpu_custom_call",)):
                    kernels.setdefault(found, {})[own] = per_ms * sec
                if found and listed and own.startswith(listed):
                    # "%copy.12 = bf16[1,32,8192,128]{..} copy(..)": the
                    # instruction's name without its number, and its shape
                    kind = re.sub(r"[.\d]+$", "", own[1:]) + " " + re.sub(
                        r"\{[^}]*\}", "", name.partition(" = ")[2].split(" ")[0])
                    seen = by_shape.setdefault(found, {}).setdefault(
                        kind, [0, 0.0])
                    seen[0] += 1 / len(devices)
                    seen[1] += per_ms * sec
        out["traced_steps"] = steps
        out["scopes"] = {s: per_ms * sec for s, sec in sorted(seconds.items())}
        # a scope's Mosaic calls, each instruction of the step once: their
        # count says a kernel engaged, the rest of the scope is XLA's around
        out["mosaic_calls"] = {s: dict(sorted(calls.items()))
                               for s, calls in sorted(kernels.items())}
        if listed:
            out["ops"] = {s: dict(sorted(kinds.items()))
                          for s, kinds in sorted(by_shape.items())}
        facts = r["facts"]
        under = out["scopes"].get("ps.mamba/s6")
        if under and "scan_bytes" in facts:
            # peaks.json has no vector peak and the scan has no matrix
            # product: its floor is its bytes over the HBM's peak
            with open(os.path.join(ROOT, "benchmark", "harness",
                                   "peaks.json")) as f:
                peak = json.load(f)["devices"][out["device"]][
                    "hbm_bytes_per_s"]
            least_ms = 1e3 * facts["scan_bytes"] / peak
            out["scan"] = {"least_ms": least_ms,
                           "hbm_share_percent": 100.0 * least_ms / under}
    print(json.dumps(out))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scope_table.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
