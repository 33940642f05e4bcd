"""expert layer: ``ps_tpu/models/olmoe.py`` and ``ps_tpu/ops/moe.py`` inside
the fused step.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::MOE_SCOPES``), found as ``layer_metrics/scope.py``
finds the step's phases: an event's instruction name in the optimized HLO of
the loaded executables gives its ``op_name``. The scopes nest under
``ps.grad``, so the five times below are parts of ``scope.forward_ms`` +
``scope.backward_ms``, forward and backward together, with one exception:
XLA:TPU rewrites ``ragged_dot`` into Mosaic custom calls that it names
itself (``%ragged-dot-none``, ``%ragged-dot-metadata``; ``op_name``
``"ragged-dot-none"``, no scope), so those are taken by their own instruction
name, count in ``moe.expert_ms``, and stand in ``scope.unattributed_share``,
not in ``scope.forward_ms`` / ``backward_ms``. What the step's gradient
holds beside the five (embedding lookup and its gradient, norms and
residuals outside the scopes) goes to stderr with its largest ops.

On a program without the scopes nothing below finds anything to read and the
time metrics are left out.
"""

from __future__ import annotations

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import scope

# The names of ps_tpu/obs/phases.py::MOE_SCOPES, copied: the yardstick also
# reads trees that lack them. tests/test_phases.py holds the two sets equal.
MOE_ROUTE = "ps.moe/route"
MOE_DISPATCH = "ps.moe/dispatch"
MOE_EXPERT = "ps.moe/expert"
MOE_COMBINE = "ps.moe/combine"
ATTN = "ps.attn"
HEAD = "ps.head"
MOE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD)
#: the custom calls XLA:TPU makes of ``jax.lax.ragged_dot``, by the start of
#: their own instruction name
GROUPED_MATMUL = "%ragged-dot"

#: scope -> metric; dispatch and combine are one metric
SCOPE_METRICS = {MOE_ROUTE: "moe.route_ms", MOE_DISPATCH: "moe.dispatch_ms",
                 MOE_COMBINE: "moe.dispatch_ms", MOE_EXPERT: "moe.expert_ms",
                 ATTN: "moe.attn_ms", HEAD: "moe.head_ms"}


def scope_of(own: str, op_name: str):
    """The scope of one device event, from its own instruction name and the
    ``op_name`` of that instruction; ``None`` where it has none of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    return next((s for s in MOE_SCOPES if s in op_name), None)


def scope_times(r: dict, op_names: dict) -> dict:
    """The time metrics from a result and ``{instruction name: op_name}``."""
    trace, steps = r["trace"], r["traced_steps"]
    devices = trace["devices"]
    per_ms = 1e3 / steps / len(devices)   # seconds over chips -> ms a step
    by_metric = {m: 0.0 for m in SCOPE_METRICS.values()}
    flash_s = grouped_s = grad_s = 0.0
    rest = {}
    targets = r["facts"].get("kernel_targets", ())
    for d in devices.values():
        for name, sec in d["ops"].items():
            own = tracered.parts(name)["own"]
            op_name = op_names.get(own) or ""
            found = scope_of(own, op_name)
            grouped = own.startswith(GROUPED_MATMUL)
            if grouped:
                grouped_s += sec
            if grouped or scope.GRAD in op_name:
                grad_s += sec
            if found is None:
                if scope.GRAD in op_name:
                    rest[name] = rest.get(name, 0.0) + sec
                continue
            by_metric[SCOPE_METRICS[found]] += sec
            if found == ATTN and tracered.is_custom_call_to(name, targets):
                flash_s += sec
    if not any(by_metric.values()):
        return {}
    out = {m: per_ms * sec for m, sec in by_metric.items()}
    facts, peaks = r["facts"], r["peaks"]
    if out["moe.expert_ms"] > 0:
        out["moe.expert_mxu_share"] = 100.0 * (
            facts["moe_expert_flops_per_step"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["moe.expert_ms"])
    if flash_s > 0 and "moe_flash_flops" in facts:
        least = max(facts["moe_flash_flops"] / peaks["bf16_flops_per_s"],
                    facts["moe_flash_bytes"] / peaks["hbm_bytes_per_s"])
        out["moe.flash_roofline"] = 100.0 * least / (
            flash_s / steps / len(devices))
    five = sum(out[m] for m in set(SCOPE_METRICS.values()))
    print(f"moe: the five scopes {five:.4f} ms a step of "
          f"{per_ms * grad_s:.4f} under {scope.GRAD} with the grouped "
          f"matmuls ({per_ms * grouped_s:.4f} ms of {GROUPED_MATMUL} custom "
          f"calls, which carry no scope and stand in "
          f"scope.unattributed_share); the rest "
          f"{per_ms * sum(rest.values()):.4f} ms (embedding and its "
          f"gradient, norms and residuals outside the scopes), the largest:",
          file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"moe:   {per_ms * sec:9.4f} ms  {name[:96]}  "
              f"[{(op_names.get(own) or '')[:96]}]", file=sys.stderr)
    return out


def rehearsed(facts: dict, op_names: dict) -> dict:
    """What a ``--rehearse`` run can say: no chip, so no time and no peak,
    but the step is loaded and its marks are there. Each time metric whose
    scope some instruction of the loaded step carries, and the shares that
    are made of them, at 0.0: ``run.py`` lists the names and prints no
    value."""
    found = {scope_of(own, op_name) for own, op_name in op_names.items()}
    out = {SCOPE_METRICS[s]: 0.0 for s in found if s is not None}
    if "moe.expert_ms" in out:
        out["moe.expert_mxu_share"] = 0.0
    if "moe.attn_ms" in out and "moe_flash_flops" in facts:
        out["moe.flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    out = {}
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    if "moe_load_max_over_mean" in counters:
        out["moe.load_max_over_mean"] = counters["moe_load_max_over_mean"]
        out["moe.dropped_tokens"] = counters["moe_dropped_tokens"]
    flops = facts.get("moe_flops_per_step")
    if not flops:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["moe.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    out["moe.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
