"""Mellum's token exchange and its experts, its attention kernels of two
kinds and the store's collectives: ``ps_tpu/models/mellum.py``,
``ps_tpu/ops/moe.py``'s exchange across the chips that share a layer and
``ps_tpu/ops/flash_attention.py`` with and without a window, inside the fused
step of a four-chip host.

Device time by the ``jax.named_scope`` the model opens inside its loss
(``ps_tpu/obs/phases.py::MELLUM_SCOPES``), found as
``layer_metrics/trinity.py`` finds Trinity's: an event's instruction name in
the optimized HLO of the loaded executables gives its ``op_name``. The scopes
nest under ``ps.grad``, forward, recomputation and backward together, but for
XLA:TPU's ``%ragged-dot*`` custom calls, which carry no scope: they are taken
by their own instruction name and count in ``mellum.expert_ms``.
``ps.moe/exchange`` is opened around each collective of the exchange inside
``ps.moe/dispatch`` and ``ps.moe/combine``: ``mellum.dispatch_ms`` is those
two **less** the exchange, ``mellum.exchange_ms`` the exchange alone. The
time metrics are means over the chips a step, but the exchange's and the
store's collectives, which are the worst chip's: every chip waits for the
slowest. The time under the other scopes (the router, the attention with its
two cores, the head) goes to stderr with the rest of the step, for
``PERF.md``'s breakdown, and is no metric.

``mellum.exchange_ms``: the device time of everything under
``ps.moe/exchange``. ``mellum.exchange_exposed_ms``: of it, the collectives'
own self time, during which nothing else runs on that chip (the reducer's
``exposed_collective_s`` restricted to the scope).
``mellum.store_collective_ms``: the self time of every other collective of
the step: the ZeRO gather and reduce-scatter of the dense leaves, the routers'
and the loss's reductions.

The shares, none of which can pass 100%: ``mellum.exchange_ici_share`` is the
bytes a chip had to send to other chips in a step over the interconnect's
peak over ``mellum.exchange_ms``. The bytes: the step's own counter of rows
bound for other chips (mean over the chips), times a row's bytes, times the
exchanges of rows a layer ran, **counted in the trace**: the ``all-to-all``
instructions under the scope whose result is the first trip's buffer (each
runs once a step; a further trip's, in a loop's body, move the same rows as
often), over the layers. A program that keeps what it received and exchanges
four times a layer reads four. The rows that had to move, not the buffer, so
padding lowers the reading. ``mellum.window_flash_roofline`` /
``mellum.full_flash_roofline`` as ``trinity.*``: the least time the
operations and bytes of the band's / the triangle's pairs allow, forward and
both backward calls, over the time of the Mosaic calls under the core's
scope. ``mellum.mfu``: the step's FLOPs from shapes
(``mellum_step.step_flops``) a chip.

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
from benchmark.layer_metrics.trinity import ATTN_FULL, ATTN_WINDOW

# The names of ps_tpu/obs/phases.py::MELLUM_SCOPES, copied (the others come
# from their readers' copies): the yardstick also reads trees that lack them.
# tests/test_phases.py holds the two sets equal.
MOE_EXCHANGE = "ps.moe/exchange"
MELLUM_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                 HEAD, ATTN_WINDOW, ATTN_FULL, MOE_EXCHANGE)

#: scope -> the time metric it feeds; dispatch and combine are one metric.
#: The other scopes' time is printed, not reported
SCOPE_METRICS = {MOE_DISPATCH: "mellum.dispatch_ms",
                 MOE_COMBINE: "mellum.dispatch_ms",
                 MOE_EXCHANGE: "mellum.exchange_ms",
                 MOE_EXPERT: "mellum.expert_ms"}
#: the scopes around a kernel call -> the share of its roofline
CORES = {ATTN_WINDOW: "window", ATTN_FULL: "full"}
#: the exchange is opened inside dispatch and combine, the cores inside the
#: attention: the inner scope first
_INNERMOST_FIRST = (MOE_EXCHANGE, ATTN_WINDOW, ATTN_FULL, MOE_ROUTE,
                    MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD)


def scope_of(own: str, op_name: str):
    """The innermost scope of one device event, from its own instruction
    name and the ``op_name`` of that instruction; ``None`` where it has none
    of them."""
    if own.startswith(GROUPED_MATMUL):
        return MOE_EXPERT
    return next((s for s in _INNERMOST_FIRST if s in op_name), None)


def is_row_exchange(name: str, buffer_rows) -> bool:
    """Whether a device event under ``ps.moe/exchange`` is one exchange of
    the first trip's rows: an ``all-to-all`` (its start, where XLA splits
    one) whose result has a dimension of ``buffer_rows``. The group sizes'
    exchange and a further trip's small buffers are not."""
    opcode = tracered.parts(name)["opcode"]
    shape = name.partition(" = ")[2].partition("]")[0].partition("[")[2]
    return (opcode.startswith("all-to-all") and not opcode.endswith("-done")
            and str(buffer_rows) in shape.split(","))


def _roofline(facts: dict, peaks: dict, what: str, seconds_a_step: float):
    least = max(facts[f"mellum_{what}_flops"] / peaks["bf16_flops_per_s"],
                facts[f"mellum_{what}_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds_a_step


def scope_times(r: dict, op_names: dict) -> dict:
    """The time metrics and the shares made of them, from a result and
    ``{instruction name: op_name}``."""
    trace, steps = r["trace"], r["traced_steps"]
    devices = trace["devices"]
    per_ms = 1e3 / steps / len(devices)   # seconds over chips -> ms a step
    by_scope = {s: 0.0 for s in MELLUM_SCOPES}
    flash_s = {what: 0.0 for what in CORES.values()}
    exchange_s, exposed_s, store_s, exchanges = [], [], [], []  # a chip each
    grouped_s = grad_s = 0.0
    rest = {}
    facts, peaks = r["facts"], r["peaks"]
    counters = r.get("counters") or {}
    targets = facts.get("kernel_targets", ())
    buffer_rows = facts.get("mellum_exchange_buffer_rows")
    for d in devices.values():
        exchange_s.append(0.0), exposed_s.append(0.0), store_s.append(0.0)
        exchanges.append(0)
        for name, sec in d["ops"].items():
            own = tracered.parts(name)["own"]
            op_name = op_names.get(own) or ""
            found = scope_of(own, op_name)
            grouped = own.startswith(GROUPED_MATMUL)
            collective = tracered.is_collective(name)
            if grouped:
                grouped_s += sec
            if grouped or scope.GRAD in op_name:
                grad_s += sec
            if found == MOE_EXCHANGE:
                exchange_s[-1] += sec
                if collective:
                    exposed_s[-1] += sec
                exchanges[-1] += is_row_exchange(name, buffer_rows)
            elif collective:
                store_s[-1] += sec
            if found is None:
                if scope.GRAD in op_name:
                    rest[name] = rest.get(name, 0.0) + sec
                continue
            by_scope[found] += sec
            if found in CORES and tracered.is_custom_call_to(name, targets):
                flash_s[CORES[found]] += sec
    if not any(by_scope.values()):
        return {}
    out = {m: 0.0 for m in SCOPE_METRICS.values()}
    for s, metric in SCOPE_METRICS.items():
        out[metric] += per_ms * by_scope[s]
    out["mellum.exchange_ms"] = 1e3 * max(exchange_s) / steps
    out["mellum.exchange_exposed_ms"] = 1e3 * max(exposed_s) / steps
    out["mellum.store_collective_ms"] = 1e3 * max(store_s) / steps
    rows = counters.get("mellum_exchange_rows_per_step")
    a_layer = max(exchanges) / facts.get("mellum_layers", 1)
    if out["mellum.exchange_ms"] > 0 and rows is not None and a_layer:
        out["mellum.exchange_ici_share"] = 100.0 * (
            rows * facts["mellum_exchange_bytes_per_row"] * a_layer
            / (peaks["ici_bits_per_s"] / 8)) / (
                1e-3 * out["mellum.exchange_ms"])
    for what, sec in flash_s.items():
        if sec > 0 and f"mellum_{what}_flash_flops" in facts:
            out[f"mellum.{what}_flash_roofline"] = _roofline(
                facts, peaks, f"{what}_flash", sec / steps / len(devices))
    scoped = sum(by_scope.values())
    print(f"mellum: under the scopes {per_ms * scoped:.4f} ms a step, mean "
          f"of the chips, of {per_ms * grad_s:.4f} under {scope.GRAD} with "
          f"the grouped matmuls ({100 * scoped / grad_s:.2f}%): "
          + ", ".join(f"{s} {per_ms * sec:.4f}"
                      for s, sec in by_scope.items())
          + f" (the cores' beside {ATTN}'s own; the exchange by chip "
          f"{[round(1e3 * s / steps, 3) for s in exchange_s]}, "
          f"{a_layer:g} exchanges of rows a layer; "
          f"{per_ms * grouped_s:.4f} ms of {GROUPED_MATMUL} custom calls, "
          f"which carry no scope and stand in scope.unattributed_share; the "
          f"flash kernels of the windowed layers "
          f"{per_ms * flash_s['window']:.4f} ms, of the full one "
          f"{per_ms * flash_s['full']:.4f} ms; the collectives outside the "
          f"exchange by chip {[round(1e3 * s / steps, 3) for s in store_s]}); "
          f"the rest {per_ms * sum(rest.values()):.4f} ms (embedding and its "
          f"gradient, norms and residuals outside the scopes), the largest:",
          file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"mellum:   {per_ms * sec:9.4f} ms  {name[:96]}  "
              f"[{(op_names.get(own) or '')[:96]}]", file=sys.stderr)
    return out


def rehearsed(facts: dict, op_names: dict) -> dict:
    """What a ``--rehearse`` run can say: no chip, so no time and no peak,
    but the step is loaded and its marks are there. Each time metric whose
    scope some instruction of the loaded step carries, and the shares that
    are made of them, at 0.0: ``run.py`` lists the names and prints no
    value."""
    found = {scope_of(own, op_name) for own, op_name in op_names.items()}
    out = {SCOPE_METRICS[s]: 0.0 for s in found if s in SCOPE_METRICS}
    if "mellum.exchange_ms" in out:
        out.update({"mellum.exchange_ici_share": 0.0,
                    "mellum.exchange_exposed_ms": 0.0,
                    "mellum.store_collective_ms": 0.0})
    for scope_name, what in CORES.items():
        if scope_name in found and f"mellum_{what}_flash_flops" in facts:
            out[f"mellum.{what}_flash_roofline"] = 0.0
    return out


def read(r: dict) -> dict:
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    out = {}
    if "mellum_dropped_tokens" in counters:
        out["mellum.dropped_tokens"] = counters["mellum_dropped_tokens"]
    flops = facts.get("mellum_step_flops")
    if not flops:
        return out
    peaks = r.get("peaks") or {}
    if not peaks:   # --rehearse, the one run without a device's peaks
        out["mellum.mfu"] = 0.0
        out.update(rehearsed(facts, scope.loaded_op_names() or {}))
        return out
    out["mellum.mfu"] = 100.0 * flops * (r["steps"] / r["window_s"]) / (
        peaks["bf16_flops_per_s"])
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names))
    return out
