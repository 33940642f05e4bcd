"""hybrid decoder and its held experts: ``ps_tpu/models/lfm2.py``,
``ps_tpu/ops/gated_conv.py`` and ``ps_tpu/ops/moe.py`` inside the fused step.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::LFM2_SCOPES``), found as ``layer_metrics/moe.py``
finds OLMoE's: an event's instruction name in the optimized HLO of the loaded
executables gives its ``op_name``. The scopes nest under ``ps.grad``, so the
times below are parts of ``scope.forward_ms`` + ``scope.backward_ms``,
forward, recomputation and backward together, but for XLA:TPU's
``%ragged-dot*`` custom calls, which carry no scope: they are taken by their
own instruction name and count in ``lfm2.expert_ms``. ``ps.conv/gate`` nests
under ``ps.conv``: ``lfm2.conv_ms`` holds ``lfm2.conv_gate_ms``. What the
step's gradient holds beside the scopes (embedding lookup and its gradient,
norms and residuals) goes to stderr with its largest ops.

The shares: ``lfm2.conv_gate_hbm_share`` is the gated convolution's bytes
from shapes over the HBM's peak over its time; ``lfm2.expert_mxu_share`` the
FLOPs of the pairs the step computed here (its own counter, not T x 4) over
the MXU's peak over ``lfm2.expert_ms``; ``lfm2.flash_roofline`` as
``moe.flash_roofline``, with K and V read at their own head count;
``lfm2.mfu`` the step's FLOPs from shapes with the held pairs counted.

On a program without the scopes or the counters nothing below finds anything
to read, and the metrics are left out.
"""

from __future__ import annotations

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import scope

# The names of ps_tpu/obs/phases.py::LFM2_SCOPES, copied: the yardstick also
# reads trees that lack them. tests/test_phases.py holds the two sets equal.
MOE_ROUTE = "ps.moe/route"
MOE_DISPATCH = "ps.moe/dispatch"
MOE_EXPERT = "ps.moe/expert"
MOE_COMBINE = "ps.moe/combine"
ATTN = "ps.attn"
HEAD = "ps.head"
CONV = "ps.conv"
CONV_GATE = "ps.conv/gate"
FFN = "ps.ffn"
LFM2_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD,
               CONV, CONV_GATE, FFN)
#: the custom calls XLA:TPU makes of ``jax.lax.ragged_dot``, by the start of
#: their own instruction name
GROUPED_MATMUL = "%ragged-dot"

#: scope -> metric; dispatch and combine are one metric; the gate counts in
#: its own metric and in the mixer's
SCOPE_METRICS = {MOE_ROUTE: "lfm2.route_ms", MOE_DISPATCH: "lfm2.dispatch_ms",
                 MOE_COMBINE: "lfm2.dispatch_ms", MOE_EXPERT: "lfm2.expert_ms",
                 ATTN: "lfm2.attn_ms", HEAD: "lfm2.head_ms",
                 CONV: "lfm2.conv_ms", CONV_GATE: "lfm2.conv_gate_ms",
                 FFN: "lfm2.dense_ffn_ms"}
#: the metrics whose sum is the time under the scopes (the gate's is inside
#: the mixer's)
PARTS = ("lfm2.route_ms", "lfm2.dispatch_ms", "lfm2.expert_ms",
         "lfm2.attn_ms", "lfm2.head_ms", "lfm2.conv_ms", "lfm2.dense_ffn_ms")


def scope_of(own: str, op_name: str):
    """The innermost scope of one device event, from its own instruction
    name and the ``op_name`` of that instruction; ``None`` where it has none
    of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    if CONV_GATE in op_name:
        return CONV_GATE
    return next((s for s in LFM2_SCOPES if s in op_name), None)


def scope_times(r: dict, op_names: dict) -> dict:
    """The time metrics and the shares made of them, from a result and
    ``{instruction name: op_name}``."""
    trace, steps = r["trace"], r["traced_steps"]
    devices = trace["devices"]
    per_ms = 1e3 / steps / len(devices)   # seconds over chips -> ms a step
    by_metric = {m: 0.0 for m in SCOPE_METRICS.values()}
    flash_s = grouped_s = grad_s = 0.0
    rest = {}
    facts, peaks = r["facts"], r["peaks"]
    counters = r.get("counters") or {}
    targets = facts.get("kernel_targets", ())
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
            if found == CONV_GATE:
                by_metric[SCOPE_METRICS[CONV]] += sec
            if found == ATTN and tracered.is_custom_call_to(name, targets):
                flash_s += sec
    if not any(by_metric.values()):
        return {}
    out = {m: per_ms * sec for m, sec in by_metric.items()}
    live = counters.get("lfm2_live_pairs_per_step")
    if out["lfm2.expert_ms"] > 0 and live is not None:
        out["lfm2.expert_mxu_share"] = 100.0 * (
            live * facts["lfm2_flops_per_pair"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["lfm2.expert_ms"])
    if out["lfm2.conv_gate_ms"] > 0:
        out["lfm2.conv_gate_hbm_share"] = 100.0 * (
            facts["lfm2_conv_gate_bytes_per_step"] / peaks["hbm_bytes_per_s"]
        ) / (1e-3 * out["lfm2.conv_gate_ms"])
    if flash_s > 0 and "lfm2_flash_flops" in facts:
        least = max(facts["lfm2_flash_flops"] / peaks["bf16_flops_per_s"],
                    facts["lfm2_flash_bytes"] / peaks["hbm_bytes_per_s"])
        out["lfm2.flash_roofline"] = 100.0 * least / (
            flash_s / steps / len(devices))
    parts = sum(out[m] for m in PARTS)
    print(f"lfm2: the scopes {parts:.4f} ms a step of "
          f"{per_ms * grad_s:.4f} under {scope.GRAD} with the grouped "
          f"matmuls ({100 * parts / (per_ms * grad_s):.2f}%; "
          f"{per_ms * grouped_s:.4f} ms of {GROUPED_MATMUL} custom calls, "
          f"which carry no scope and stand in scope.unattributed_share; the "
          f"flash kernel {per_ms * flash_s:.4f} ms); the rest "
          f"{per_ms * sum(rest.values()):.4f} ms (embedding and its "
          f"gradient, norms and residuals outside the scopes), the largest:",
          file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"lfm2:   {per_ms * sec:9.4f} ms  {name[:96]}  "
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
    if "lfm2.expert_ms" in out:
        out["lfm2.expert_mxu_share"] = 0.0
    if "lfm2.conv_gate_ms" in out:
        out["lfm2.conv_gate_hbm_share"] = 0.0
    if "lfm2.attn_ms" in out and "lfm2_flash_flops" in facts:
        out["lfm2.flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    out = {}
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    if "lfm2_held_pair_share" in counters:
        out["lfm2.held_pair_share"] = counters["lfm2_held_pair_share"]
        out["lfm2.load_max_over_mean"] = counters["lfm2_load_max_over_mean"]
        out["lfm2.dropped_tokens"] = counters["lfm2_dropped_tokens"]
    dense = facts.get("lfm2_dense_flops_per_step")
    if not dense or "lfm2_live_pairs_per_step" not in counters:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["lfm2.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    flops = dense + (counters["lfm2_live_pairs_per_step"]
                     * facts["lfm2_flops_per_pair"])
    out["lfm2.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
