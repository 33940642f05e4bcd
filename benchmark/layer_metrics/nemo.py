"""Nemotron-H's mixers and its latent experts: ``ps_tpu/models/nemotron_h.py``,
``ps_tpu/ops/ssd.py``, ``ps_tpu/ops/flash_attention.py`` at 4 query heads on 1
K/V head and ``ps_tpu/ops/moe.py`` inside the fused step.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::NEMOTRON_SCOPES``), found as ``layer_metrics/kimi.py``
finds Kimi-Linear's: an event's instruction name in the optimized HLO of the
loaded executables gives its ``op_name``. The scopes nest under ``ps.grad``,
so the times below are parts of ``scope.forward_ms`` + ``scope.backward_ms``,
forward, recomputation and backward together, but for XLA:TPU's
``%ragged-dot*`` custom calls, which carry no scope: they are taken by their
own instruction name and count in ``nemo.expert_ms``. ``ps.mamba/conv`` and
``ps.mamba/ssd`` nest under ``ps.mamba``: ``nemo.mamba_ms`` holds
``nemo.mamba_conv_ms`` and ``nemo.ssd_ms``. What the step's gradient holds
beside the scopes (embedding lookup and its gradient, norms and residuals)
goes to stderr with its largest ops.

The shares, none of which can pass 100%: ``nemo.ssd_roofline`` is the least
time the chunked scan's operations and bytes allow, forward and backward
(``nemotron_h_step.ssd_cost``), over ``nemo.ssd_ms``; ``nemo.flash_roofline``
the same of the Mosaic calls under ``ps.attn``, the forward and both backward
calls in numerator and denominator (``kimi_step.flash_cost``);
``nemo.expert_mxu_share`` the FLOPs of the pairs the step computed here (its
own counter, not T x 22) over the MXU's peak over ``nemo.expert_ms``;
``nemo.mfu`` the step's FLOPs from shapes with the held pairs counted.

On a program without the scopes or the counters nothing below finds anything
to read, and the metrics are left out.
"""

from __future__ import annotations

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import scope
from benchmark.layer_metrics.lfm2 import (ATTN, GROUPED_MATMUL, HEAD,
                                          MOE_COMBINE, MOE_DISPATCH,
                                          MOE_EXPERT, MOE_ROUTE)

# The names of ps_tpu/obs/phases.py::NEMOTRON_SCOPES, copied (those LFM2 has
# come from its reader's copy): the yardstick also reads trees that lack them.
# tests/test_phases.py holds the two sets equal.
MAMBA = "ps.mamba"
MAMBA_CONV = "ps.mamba/conv"
MAMBA_SSD = "ps.mamba/ssd"
MOE_LATENT = "ps.moe/latent"
MOE_SHARED = "ps.moe/shared"
NEMOTRON_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                   HEAD, MAMBA, MAMBA_CONV, MAMBA_SSD, MOE_LATENT, MOE_SHARED)

#: scope -> metric; dispatch and combine are one metric; the filter and the
#: scan count in their own metrics and in the mixer's
SCOPE_METRICS = {MOE_ROUTE: "nemo.route_ms", MOE_DISPATCH: "nemo.dispatch_ms",
                 MOE_COMBINE: "nemo.dispatch_ms", MOE_EXPERT: "nemo.expert_ms",
                 MOE_SHARED: "nemo.shared_ffn_ms",
                 MOE_LATENT: "nemo.latent_ms", ATTN: "nemo.attn_ms",
                 HEAD: "nemo.head_ms", MAMBA: "nemo.mamba_ms",
                 MAMBA_CONV: "nemo.mamba_conv_ms", MAMBA_SSD: "nemo.ssd_ms"}
#: the metrics whose sum is the time under the scopes (the filter's and the
#: scan's are inside the mixer's)
PARTS = ("nemo.route_ms", "nemo.dispatch_ms", "nemo.expert_ms",
         "nemo.shared_ffn_ms", "nemo.latent_ms", "nemo.mamba_ms",
         "nemo.attn_ms", "nemo.head_ms")
#: innermost first: a scope that holds another (``ps.mamba``) is a prefix of
#: it, so shorter, and comes after it
_INNERMOST_FIRST = sorted(NEMOTRON_SCOPES, key=len, reverse=True)


def scope_of(own: str, op_name: str):
    """The innermost scope of one device event, from its own instruction
    name and the ``op_name`` of that instruction; ``None`` where it has none
    of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    return next((s for s in _INNERMOST_FIRST if s in op_name), None)


def _roofline(facts: dict, peaks: dict, what: str, seconds_a_step: float):
    least = max(facts[f"nemo_{what}_flops"] / peaks["bf16_flops_per_s"],
                facts[f"nemo_{what}_bytes"] / peaks["hbm_bytes_per_s"])
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
            if found in (MAMBA_CONV, MAMBA_SSD):
                by_metric[SCOPE_METRICS[MAMBA]] += sec
            if found == ATTN and tracered.is_custom_call_to(name, targets):
                flash_s += sec
    if not any(by_metric.values()):
        return {}
    out = {m: per_ms * sec for m, sec in by_metric.items()}
    live = counters.get("nemo_live_pairs_per_step")
    if out["nemo.expert_ms"] > 0 and live is not None:
        out["nemo.expert_mxu_share"] = 100.0 * (
            live * facts["nemo_flops_per_pair"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["nemo.expert_ms"])
    if out["nemo.ssd_ms"] > 0 and "nemo_ssd_flops" in facts:
        out["nemo.ssd_roofline"] = _roofline(
            facts, peaks, "ssd", 1e-3 * out["nemo.ssd_ms"])
    if flash_s > 0 and "nemo_flash_flops" in facts:
        out["nemo.flash_roofline"] = _roofline(
            facts, peaks, "flash", flash_s / steps / len(devices))
    parts = sum(out[m] for m in PARTS)
    print(f"nemo: the scopes {parts:.4f} ms a step of "
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
        print(f"nemo:   {per_ms * sec:9.4f} ms  {name[:96]}  "
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
    if "nemo.expert_ms" in out:
        out["nemo.expert_mxu_share"] = 0.0
    if "nemo.ssd_ms" in out and "nemo_ssd_flops" in facts:
        out["nemo.ssd_roofline"] = 0.0
    if "nemo.attn_ms" in out and "nemo_flash_flops" in facts:
        out["nemo.flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    out = {}
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    if "nemo_held_pair_share" in counters:
        out["nemo.held_pair_share"] = counters["nemo_held_pair_share"]
        out["nemo.load_max_over_mean"] = counters["nemo_load_max_over_mean"]
        out["nemo.dropped_tokens"] = counters["nemo_dropped_tokens"]
    dense = facts.get("nemo_dense_flops_per_step")
    if not dense or "nemo_live_pairs_per_step" not in counters:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["nemo.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    flops = dense + (counters["nemo_live_pairs_per_step"]
                     * facts["nemo_flops_per_pair"])
    out["nemo.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
