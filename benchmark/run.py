#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from BENCHMARK.json:
the configuration's file, ``families/<family>.py`` named in it,
``traffic/<traffic>.json``, and for each per-layer metric ``<group>.<rest>``
the reader ``layer_metrics/<group>.py``. Adding a configuration, a traffic
mix or a per-layer metric is adding files and entries; no file here changes.

The last line of standard output is one JSON object. On anything but a TPU
named in ``harness/peaks.json``, or with fewer chips than the cell asks for,
the command exits 1 and prints no result. ``--rehearse`` runs the cell's
control flow on the CPU at the tiny sizes under ``"rehearse"`` in the
configuration and traffic files, and prints no metric: its line lists the
names the cell's readers would give, those the manifest lists for the cell
(``rehearsed``) and those it does not (``unlisted``).
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def _fail(message: str) -> int:
    print(f"benchmark: {message}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, control flow only: no metric")
    args = ap.parse_args(argv)

    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = _named(manifest["workloads"], args.workload, "workload")
    entry = _named(manifest["configs"], cell["config"], "configuration")
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    chips = int(cell["chips"])
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])
    if args.rehearse:
        config.update(config.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")

    sys.path.insert(0, ROOT)
    import jax

    from benchmark.harness import loop, tracered
    from benchmark.harness.compilelog import CompileLog

    peaks = {}
    if not args.rehearse:
        # before any backend exists: a chip that cannot be opened is an
        # error, never a CPU run under a device metric's name
        jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(f"no TPU found ({e})")
    dev = devices[0]
    if not args.rehearse:
        if dev.platform != "tpu":
            return _fail(f"no TPU found: jax reports {dev.platform!r}")
        table = _load(os.path.join(HERE, "harness", "peaks.json"))["devices"]
        if dev.device_kind not in table:
            return _fail(f"no peaks recorded for device_kind "
                         f"{dev.device_kind!r} in harness/peaks.json")
        peaks = table[dev.device_kind]
    if len(devices) != chips:
        return _fail(f"workload {cell['name']} asks for {chips} chip(s), "
                     f"jax reports {len(devices)}")

    compiles = CompileLog()
    family = importlib.import_module(f"benchmark.families.{config['family']}")
    built = family.build(config, traffic, chips, args.seed)
    try:
        r = loop.run(built, traffic, seconds, bool(args.trace), compiles,
                     _T_START)
    finally:
        built.close()
    r.update(chips=chips, peaks=peaks, cell=cell)

    # -- the metrics of this run: end to end, or per layer from the readers
    values, unlisted = {}, []
    if args.trace:
        listed = [m for m in manifest["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        readers = {}
        # a rehearsal asks every group's reader, a listed name or not: what
        # they give beyond the cell's lists is ``unlisted`` in its line
        for m in manifest["per_layer"] if args.rehearse else listed:
            group = m["name"].split(".", 1)[0]
            if group not in readers:
                module = importlib.import_module(
                    f"benchmark.layer_metrics.{group}")
                readers[group] = module.read(r)
        for m in listed:
            group = m["name"].split(".", 1)[0]
            if m["name"] in readers[group]:
                values[m["name"]] = {"value": readers[group][m["name"]],
                                     "unit": m["unit"]}
        unlisted = sorted({name for got in readers.values() for name in got
                           if name not in values})
    else:
        for m in manifest["end_to_end"]:
            values[m["name"]] = {"value": r[m["name"]], "unit": m["unit"]}

    # which block was slow and what the host did longest: a stalled run's
    # own account (the program's spans say more in a traced run's idle gaps)
    slow = max(range(len(r["block_s"])), key=r["block_s"].__getitem__)
    longest = {name: round(1e3 * max(durations), 3)
               for name, durations in r["spans"].items() if durations}
    print(f"[{cell['name']}] seed {args.seed}: {r['steps']} steps in "
          f"{r['window_s']:.3f} s (longest block {r['block_s'][slow]:.4f} s, "
          f"number {slow} of {len(r['block_s'])}, the median "
          f"{sorted(r['block_s'])[len(r['block_s']) // 2]:.4f}; longest host "
          f"span, ms: {longest}), setup {r['setup_s']:.1f} s "
          f"{r['setup_phases_s']} "
          f"({r['setup_compile_s']:.1f} s compiling or loading, cache "
          f"{r['cache']}), checks {r['checks']}, reference {r['reference']}, "
          f"facts {r['facts']}", file=sys.stderr)
    line = {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"]}
    if args.rehearse:
        # a CPU run's numbers are never written under a device metric's name
        line.update(metrics={}, rehearsed=sorted(values), unlisted=unlisted,
                    device={"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(devices)})
        print(json.dumps(line))
        return 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": r["peak_bytes"]}
    if args.trace:
        trace = r["trace"]
        if not trace["devices"] or trace["busy_s"] <= 0:
            return _fail("the traced run saw no operation on the device")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        ops = {}
        for d in trace["devices"].values():
            for name, sec in d["ops"].items():
                ops[name] = ops.get(name, 0.0) + sec / len(trace["devices"])
        line["breakdown"] = {"device_ops": tracered.top(ops),
                             "idle_gaps": tracered.top(trace["idle_gaps"])}
    line.update(metrics=values, device=device)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
