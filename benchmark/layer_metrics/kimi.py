"""Kimi-Linear's mixers and its held experts: ``ps_tpu/models/kimi_linear.py``,
``ps_tpu/ops/kda.py``, ``ps_tpu/ops/flash_attention.py`` at 192 / 128 and
``ps_tpu/ops/moe.py`` inside the fused step.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::KIMI_SCOPES``), found as ``layer_metrics/lfm2.py``
finds LFM2's: an event's instruction name in the optimized HLO of the loaded
executables gives its ``op_name``. The scopes nest under ``ps.grad``, so the
times below are parts of ``scope.forward_ms`` + ``scope.backward_ms``,
forward, recomputation and backward together, but for XLA:TPU's
``%ragged-dot*`` custom calls, which carry no scope: they are taken by their
own instruction name and count in ``kimi.expert_ms``. ``ps.kda/conv`` and
``ps.kda/core`` nest under ``ps.kda``: ``kimi.kda_ms`` holds
``kimi.kda_conv_ms`` and ``kimi.kda_core_ms``. What the step's gradient holds
beside the scopes (embedding lookup and its gradient, norms and residuals)
goes to stderr with its largest ops.

The shares, none of which can pass 100%: ``kimi.kda_core_roofline`` is the
least time the chunked rule's operations and bytes allow, forward and backward
(``kimi_step.kda_core_cost``), over ``kimi.kda_core_ms``;
``kimi.flash_roofline`` the same of the Mosaic calls under ``ps.attn``, **the
forward and both backward calls in numerator and denominator**
(``kimi_step.flash_cost``; the three older flash readers count the forward's
operations over all three calls' time, PERF.md section 7 row 11);
``kimi.expert_mxu_share`` the FLOPs of the pairs the step computed here (its
own counter, not T x 8) over the MXU's peak over ``kimi.expert_ms``;
``kimi.mfu`` the step's FLOPs from shapes with the held pairs counted.

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

# The names of ps_tpu/obs/phases.py::KIMI_SCOPES, copied (those LFM2 has come
# from its reader's copy): the yardstick also reads trees that lack them.
# tests/test_phases.py holds the two sets equal.
KDA = "ps.kda"
KDA_CONV = "ps.kda/conv"
KDA_CORE = "ps.kda/core"
MOE_SHARED = "ps.moe/shared"
KIMI_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD,
               FFN, KDA, KDA_CONV, KDA_CORE, MOE_SHARED)

#: scope -> metric; dispatch and combine are one metric; the taps and the
#: rule count in their own metrics and in the mixer's
SCOPE_METRICS = {MOE_ROUTE: "kimi.route_ms", MOE_DISPATCH: "kimi.dispatch_ms",
                 MOE_COMBINE: "kimi.dispatch_ms", MOE_EXPERT: "kimi.expert_ms",
                 MOE_SHARED: "kimi.shared_ffn_ms", ATTN: "kimi.mla_ms",
                 HEAD: "kimi.head_ms", FFN: "kimi.dense_ffn_ms",
                 KDA: "kimi.kda_ms", KDA_CONV: "kimi.kda_conv_ms",
                 KDA_CORE: "kimi.kda_core_ms"}
#: the metrics whose sum is the time under the scopes (the taps' and the
#: rule's are inside the mixer's)
PARTS = ("kimi.route_ms", "kimi.dispatch_ms", "kimi.expert_ms",
         "kimi.shared_ffn_ms", "kimi.kda_ms", "kimi.mla_ms",
         "kimi.dense_ffn_ms", "kimi.head_ms")
#: innermost first: a scope that holds another (``ps.kda``) is a prefix of
#: it, so shorter, and comes after it
_INNERMOST_FIRST = sorted(KIMI_SCOPES, key=len, reverse=True)


def scope_of(own: str, op_name: str):
    """The innermost scope of one device event, from its own instruction
    name and the ``op_name`` of that instruction; ``None`` where it has none
    of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    return next((s for s in _INNERMOST_FIRST if s in op_name), None)


def _roofline(facts: dict, peaks: dict, what: str, seconds_a_step: float):
    least = max(facts[f"kimi_{what}_flops"] / peaks["bf16_flops_per_s"],
                facts[f"kimi_{what}_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds_a_step


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
            if found in (KDA_CONV, KDA_CORE):
                by_metric[SCOPE_METRICS[KDA]] += sec
            if found == ATTN and tracered.is_custom_call_to(name, targets):
                flash_s += sec
    if not any(by_metric.values()):
        return {}
    out = {m: per_ms * sec for m, sec in by_metric.items()}
    live = counters.get("kimi_live_pairs_per_step")
    if out["kimi.expert_ms"] > 0 and live is not None:
        out["kimi.expert_mxu_share"] = 100.0 * (
            live * facts["kimi_flops_per_pair"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["kimi.expert_ms"])
    if out["kimi.kda_core_ms"] > 0 and "kimi_kda_core_flops" in facts:
        out["kimi.kda_core_roofline"] = _roofline(
            facts, peaks, "kda_core", 1e-3 * out["kimi.kda_core_ms"])
    if flash_s > 0 and "kimi_flash_flops" in facts:
        out["kimi.flash_roofline"] = _roofline(
            facts, peaks, "flash", flash_s / steps / len(devices))
    parts = sum(out[m] for m in PARTS)
    print(f"kimi: the scopes {parts:.4f} ms a step of "
          f"{per_ms * grad_s:.4f} under {scope.GRAD} with the grouped "
          f"matmuls ({100 * parts / (per_ms * grad_s):.2f}%; "
          f"{per_ms * grouped_s:.4f} ms of {GROUPED_MATMUL} custom calls, "
          f"which carry no scope and stand in scope.unattributed_share; the "
          f"flash kernels {per_ms * flash_s:.4f} ms); the rest "
          f"{per_ms * sum(rest.values()):.4f} ms (embedding and its "
          f"gradient, norms and residuals outside the scopes), the largest:",
          file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"kimi:   {per_ms * sec:9.4f} ms  {name[:96]}  "
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
    if "kimi.expert_ms" in out:
        out["kimi.expert_mxu_share"] = 0.0
    if "kimi.kda_core_ms" in out and "kimi_kda_core_flops" in facts:
        out["kimi.kda_core_roofline"] = 0.0
    if "kimi.mla_ms" in out and "kimi_flash_flops" in facts:
        out["kimi.flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    out = {}
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    if "kimi_held_pair_share" in counters:
        out["kimi.held_pair_share"] = counters["kimi_held_pair_share"]
        out["kimi.load_max_over_mean"] = counters["kimi_load_max_over_mean"]
        out["kimi.dropped_tokens"] = counters["kimi_dropped_tokens"]
    dense = facts.get("kimi_dense_flops_per_step")
    if not dense or "kimi_live_pairs_per_step" not in counters:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["kimi.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    flops = dense + (counters["kimi_live_pairs_per_step"]
                     * facts["kimi_flops_per_pair"])
    out["kimi.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
