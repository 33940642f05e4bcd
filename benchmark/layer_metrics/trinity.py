"""Trinity's attention of two kinds and its held experts:
``ps_tpu/models/trinity.py``, ``ps_tpu/ops/flash_attention.py`` with and
without a window at 32 query heads on 4 K/V heads and ``ps_tpu/ops/moe.py``
inside the fused step.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::TRINITY_SCOPES``), found as ``layer_metrics/kimi.py``
finds Kimi-Linear's: an event's instruction name in the optimized HLO of the
loaded executables gives its ``op_name``. The scopes nest under ``ps.grad``,
so the times below are parts of ``scope.forward_ms`` + ``scope.backward_ms``,
forward, recomputation and backward together, but for XLA:TPU's
``%ragged-dot*`` custom calls, which carry no scope: they are taken by their
own instruction name and count in ``trinity.expert_ms``. ``ps.attn/window``,
``ps.attn/full`` and ``ps.attn/gate`` nest under ``ps.attn``:
``trinity.attn_ms`` holds ``trinity.window_core_ms`` (the four windowed
layers' attention calls: the Mosaic kernels and the packing around them),
``trinity.full_core_ms`` (the one full layer's) and ``trinity.attn_gate_ms``.
What the step's gradient holds beside the scopes (embedding lookup and its
gradient, norms and residuals) goes to stderr with its largest ops.

The shares, none of which can pass 100%: ``trinity.window_flash_roofline`` is
the least time the operations and bytes of the band's pairs allow, forward and
both backward calls (``trinity_step.flash_cost``), over the time of the Mosaic
calls under ``ps.attn/window``, numerator and denominator over the same calls;
``trinity.full_flash_roofline`` the same of the triangle's pairs and the calls
under ``ps.attn/full``; ``trinity.expert_mxu_share`` the FLOPs of the pairs the
step computed here (its own counter, not T x 8) over the MXU's peak over
``trinity.expert_ms``; ``trinity.mfu`` the step's FLOPs from shapes with the
held pairs counted. ``trinity.window_live_step_share`` is a count, not a time:
the grid steps the windowed forward computes over those the causal one would
at the tiles the kernel chose, against which the band's share of the pairs
(0.234 at 16,384) says what the tiles give back of the skip.

On a program without the scopes or the counters nothing below finds anything
to read, and the metrics are left out.
"""

from __future__ import annotations

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import scope
from benchmark.layer_metrics.lfm2 import (ATTN, FFN, GROUPED_MATMUL, HEAD,
                                          MOE_COMBINE, MOE_DISPATCH,
                                          MOE_EXPERT, MOE_ROUTE)

# The names of ps_tpu/obs/phases.py::TRINITY_SCOPES, copied (those LFM2 has
# come from its reader's copy): the yardstick also reads trees that lack them.
# tests/test_phases.py holds the two sets equal.
MOE_SHARED = "ps.moe/shared"
ATTN_WINDOW = "ps.attn/window"
ATTN_FULL = "ps.attn/full"
ATTN_GATE = "ps.attn/gate"
TRINITY_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                  HEAD, FFN, MOE_SHARED, ATTN_WINDOW, ATTN_FULL, ATTN_GATE)

#: scope -> metric; dispatch and combine are one metric; the two cores and
#: the gate count in their own metrics and in the attention's
SCOPE_METRICS = {MOE_ROUTE: "trinity.route_ms",
                 MOE_DISPATCH: "trinity.dispatch_ms",
                 MOE_COMBINE: "trinity.dispatch_ms",
                 MOE_EXPERT: "trinity.expert_ms",
                 MOE_SHARED: "trinity.shared_ffn_ms",
                 ATTN: "trinity.attn_ms", HEAD: "trinity.head_ms",
                 FFN: "trinity.dense_ffn_ms",
                 ATTN_WINDOW: "trinity.window_core_ms",
                 ATTN_FULL: "trinity.full_core_ms",
                 ATTN_GATE: "trinity.attn_gate_ms"}
#: the metrics whose sum is the time under the scopes (the cores' and the
#: gate's are inside the attention's)
PARTS = ("trinity.route_ms", "trinity.dispatch_ms", "trinity.expert_ms",
         "trinity.shared_ffn_ms", "trinity.attn_ms", "trinity.dense_ffn_ms",
         "trinity.head_ms")
#: the scopes around a kernel call -> the share of its roofline
CORES = {ATTN_WINDOW: "window", ATTN_FULL: "full"}
#: innermost first: a scope that holds another (``ps.attn``) is a prefix of
#: it, so shorter, and comes after it
_INNERMOST_FIRST = sorted(TRINITY_SCOPES, key=len, reverse=True)


def scope_of(own: str, op_name: str):
    """The innermost scope of one device event, from its own instruction
    name and the ``op_name`` of that instruction; ``None`` where it has none
    of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    return next((s for s in _INNERMOST_FIRST if s in op_name), None)


def _roofline(facts: dict, peaks: dict, what: str, seconds_a_step: float):
    least = max(facts[f"trinity_{what}_flops"] / peaks["bf16_flops_per_s"],
                facts[f"trinity_{what}_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds_a_step


def scope_times(r: dict, op_names: dict) -> dict:
    """The time metrics and the shares made of them, from a result and
    ``{instruction name: op_name}``."""
    trace, steps = r["trace"], r["traced_steps"]
    devices = trace["devices"]
    per_ms = 1e3 / steps / len(devices)   # seconds over chips -> ms a step
    by_metric = {m: 0.0 for m in SCOPE_METRICS.values()}
    flash_s = {what: 0.0 for what in CORES.values()}
    grouped_s = grad_s = 0.0
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
            if found in (ATTN_WINDOW, ATTN_FULL, ATTN_GATE):
                by_metric[SCOPE_METRICS[ATTN]] += sec
            if found in CORES and tracered.is_custom_call_to(name, targets):
                flash_s[CORES[found]] += sec
    if not any(by_metric.values()):
        return {}
    out = {m: per_ms * sec for m, sec in by_metric.items()}
    live = counters.get("trinity_live_pairs_per_step")
    if out["trinity.expert_ms"] > 0 and live is not None:
        out["trinity.expert_mxu_share"] = 100.0 * (
            live * facts["trinity_flops_per_pair"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["trinity.expert_ms"])
    for what, sec in flash_s.items():
        if sec > 0 and f"trinity_{what}_flash_flops" in facts:
            out[f"trinity.{what}_flash_roofline"] = _roofline(
                facts, peaks, f"{what}_flash", sec / steps / len(devices))
    parts = sum(out[m] for m in PARTS)
    print(f"trinity: the scopes {parts:.4f} ms a step of "
          f"{per_ms * grad_s:.4f} under {scope.GRAD} with the grouped "
          f"matmuls ({100 * parts / (per_ms * grad_s):.2f}%; "
          f"{per_ms * grouped_s:.4f} ms of {GROUPED_MATMUL} custom calls, "
          f"which carry no scope and stand in scope.unattributed_share; the "
          f"flash kernels of the windowed layers "
          f"{per_ms * flash_s['window']:.4f} ms, of the full one "
          f"{per_ms * flash_s['full']:.4f} ms); the rest "
          f"{per_ms * sum(rest.values()):.4f} ms (embedding and its "
          f"gradient, norms and residuals outside the scopes), the largest:",
          file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"trinity:   {per_ms * sec:9.4f} ms  {name[:96]}  "
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
    if "trinity.expert_ms" in out:
        out["trinity.expert_mxu_share"] = 0.0
    for what in CORES.values():
        if (f"trinity.{what}_core_ms" in out
                and f"trinity_{what}_flash_flops" in facts):
            out[f"trinity.{what}_flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    out = {}
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    if "trinity_held_pair_share" in counters:
        out["trinity.held_pair_share"] = counters["trinity_held_pair_share"]
        out["trinity.load_max_over_mean"] = \
            counters["trinity_load_max_over_mean"]
        out["trinity.dropped_tokens"] = counters["trinity_dropped_tokens"]
    if "trinity_window_live_step_share" in facts:
        out["trinity.window_live_step_share"] = \
            facts["trinity_window_live_step_share"]
    dense = facts.get("trinity_dense_flops_per_step")
    if not dense or "trinity_live_pairs_per_step" not in counters:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["trinity.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    flops = dense + (counters["trinity_live_pairs_per_step"]
                     * facts["trinity_flops_per_pair"])
    out["trinity.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
