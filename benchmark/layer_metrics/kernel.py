"""kernels: ``ops/flash_attention.py``, ``ops/kda_mosaic.py``, ``ops/ssd.py``.
A kernel's share of its roofline is the larger of its operations over peak
FLOP/s and its bytes over peak bytes/s, forward and backward, both from
shapes (``families/flash.py`` for every flash kernel), over the device time
of its calls in the traced steps. Which events are its calls: in a decoder,
which opens scopes, ``layer_metrics/decoder.py`` finds them under the
attention's, the rule's or the scan's; in BERT, which opens none, they are
the events that are themselves custom calls to the configuration's
``kernel_targets`` (Mosaic kernels).

The selective scan (``ops/selective_scan.py``) has no matrix product: its
least time is the larger of the family's ``scan_flops`` over the vector
unit's peak and ``scan_bytes`` over the HBM's, and its calls are whatever
runs under ``ps.mamba/s6``, read by the mark as ``decoder.MARKS`` are. It is
read here and not in ``decoder.py``: ``tests/test_phases.py`` holds that
reader's result on a hand-made step of this kind to the names it had before.
No cell lists the two names yet (``tests/test_phi4flash.py`` holds the one
cell that opens the mark at two names): they go to stderr (PERF.md section
7, row 0)."""

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import decoder, scope

#: the program's mark around the recurrence (``ps_tpu/obs/phases.py::MAMBA_S6``;
#: ``benchmark/check/check_decoder.py`` holds it equal)
S6 = "ps.mamba/s6"


def selective_scan(r: dict) -> dict:
    """``kernel.s6_ms`` and ``kernel.s6_roofline`` of a step whose family
    states the scan's cost: the names at 0.0 in a rehearsal, nothing on a
    program without the mark."""
    facts, peaks = r["facts"], r.get("peaks") or {}
    if "scan_flops" not in facts:
        return {}
    op_names = scope.loaded_op_names() or {}
    if not any(S6 in op_name for op_name in op_names.values()):
        return {}
    if not peaks:   # --rehearse: the names, no value
        return {"kernel.s6_ms": 0.0, "kernel.s6_roofline": 0.0}
    trace, steps = r.get("trace"), r.get("traced_steps")
    if not trace or not steps:
        return {}
    seconds = decoder.mark_seconds(trace, op_names, S6) / steps
    if seconds <= 0:
        return {}
    out = {"kernel.s6_ms": 1e3 * seconds}
    if "vector_ops_per_s" in peaks:
        least = max(facts["scan_flops"] / peaks["vector_ops_per_s"],
                    facts["scan_bytes"] / peaks["hbm_bytes_per_s"])
        out["kernel.s6_roofline"] = 100.0 * least / seconds
    # no name of the manifest's yet (PERF.md section 7, row 0): the reading
    # of whoever runs the cell
    print("kernel: " + ", ".join(f"{k} {v:.4f}" for k, v in out.items()),
          file=sys.stderr)
    return out


def read(r: dict) -> dict:
    facts, trace = r["facts"], r["trace"]
    if decoder.chip_flops(facts, r.get("counters") or {}):
        return {**{k: v for k, v in decoder.read(r).items()
                   if k in decoder.ROOFLINES}, **selective_scan(r)}
    if "flash_flops" not in facts or not trace or not r["traced_steps"]:
        return {}
    targets = facts["kernel_targets"]
    seconds = tracered.op_seconds(
        trace, lambda name: tracered.is_custom_call_to(name, targets))
    if seconds <= 0:
        return {}
    least = decoder.least_s(facts, r["peaks"], "flash") * r["traced_steps"]
    return {"kernel.flash_roofline": 100.0 * least / seconds}
