"""decoder layers, whichever model opens them: the scopes of
``ps_tpu/obs/phases.py`` that a decoder's loss opens (attention and its cores,
the mixers, the dense SwiGLU, the router, dispatch and combine, the experts,
the shared one, the latent, the exchange, the head), each read as one metric
in every cell whose step carries it. A family states its facts and counters
under one set of keys (below); a model that opens a scope gets its metric
with no line written for it.

Device time by ``jax.named_scope``, found as ``layer_metrics/scope.py`` finds
the step's phases: an event's instruction name in the optimized HLO of the
loaded executables gives its ``op_name``. The innermost scope takes an
event's time; a scope opened inside another scope of this table
(``ps.conv/gate`` in ``ps.conv``, the two cores and the gate in ``ps.attn``,
the taps and the rule in ``ps.kda``, the filter and the scan in ``ps.mamba``)
counts in its own metric and in the outer one's. The finer marks of
``MARKS`` (the own blocks, the latent projections and the rotation inside
``ps.attn``, the gate inside ``ps.mamba``, the prediction module around its
own attention, experts and head pass, and its join) are read where they
stand, as ``layer_metrics/ouro.py`` reads its two: every event whose
``op_name`` carries the mark, which stays in its outer scope's metric too.
``ps.moe/exchange`` is
opened inside dispatch and combine, which are no scope of the exchange's
name: ``decoder.dispatch_ms`` is those two **less** the exchange. The scopes
nest under ``ps.grad``, so the times are parts of ``scope.forward_ms`` +
``scope.backward_ms``: forward, recomputation and backward together. They
are means over the chips a step, but the exchange's and the store's
collectives, which are the worst chip's: every chip waits for the slowest.

The shares, none of which can pass 100%: a kernel's ``*_roofline`` is the
least time its operations and bytes allow, forward and backward, from shapes
(``families/flash.py``, ``kimi_step.kda_core_cost``,
``nemotron_h_step.ssd_cost``), over the time of the flash calls (the Mosaic
calls under the attention's scope but the rotation's, ``ops/rope.py``'s,
which are no part of the count; those under ``ps.attn/window`` apart), or
of everything under the rule's or the scan's; ``decoder.expert_mxu_share`` the
FLOPs of the pairs the step computed here (its own counter where a share is
held) over the MXU's peak over ``decoder.expert_ms``;
``decoder.conv_gate_hbm_share`` the gated convolution's bytes over the HBM's
peak over its time; ``decoder.exchange_ici_share`` the bytes a chip had to
send to other chips (the step's counter of rows, a row's bytes, the
exchanges of rows a layer ran **counted in the trace**) over the
interconnect's peak over ``decoder.exchange_ms``.

To stderr, for ``PERF.md``'s breakdown and no metric: the scopes' sum beside
the time under ``ps.grad``, the kernels' calls, the collectives by chip, and
what the gradient holds beside the scopes (embedding lookup and its gradient,
norms and residuals) with its largest ops.

On a program without the scopes or the counters nothing below finds anything
to read, and the metrics are left out.
"""

from __future__ import annotations

import sys

from benchmark.harness import tracered
from benchmark.layer_metrics import scope

# The decoders' scope names of ps_tpu/obs/phases.py, copied once: the
# yardstick also reads trees that lack them. tests/test_phases.py holds them
# equal to the program's.
MOE_ROUTE = "ps.moe/route"
MOE_DISPATCH = "ps.moe/dispatch"
MOE_EXPERT = "ps.moe/expert"
MOE_COMBINE = "ps.moe/combine"
MOE_SHARED = "ps.moe/shared"
MOE_LATENT = "ps.moe/latent"
MOE_EXCHANGE = "ps.moe/exchange"
ATTN = "ps.attn"
ATTN_WINDOW = "ps.attn/window"
ATTN_FULL = "ps.attn/full"
ATTN_GATE = "ps.attn/gate"
HEAD = "ps.head"
FFN = "ps.ffn"
CONV = "ps.conv"
CONV_GATE = "ps.conv/gate"
KDA = "ps.kda"
KDA_CONV = "ps.kda/conv"
KDA_CORE = "ps.kda/core"
MAMBA = "ps.mamba"
MAMBA_CONV = "ps.mamba/conv"
MAMBA_SSD = "ps.mamba/ssd"

#: scope -> its one metric; dispatch and combine are one
METRICS = {
    MOE_ROUTE: "decoder.route_ms", MOE_DISPATCH: "decoder.dispatch_ms",
    MOE_COMBINE: "decoder.dispatch_ms", MOE_EXPERT: "decoder.expert_ms",
    MOE_SHARED: "decoder.shared_ffn_ms", MOE_LATENT: "decoder.latent_ms",
    MOE_EXCHANGE: "decoder.exchange_ms", ATTN: "decoder.attn_ms",
    ATTN_WINDOW: "decoder.window_core_ms", ATTN_FULL: "decoder.full_core_ms",
    ATTN_GATE: "decoder.attn_gate_ms", HEAD: "decoder.head_ms",
    FFN: "decoder.dense_ffn_ms", CONV: "decoder.conv_ms",
    CONV_GATE: "decoder.conv_gate_ms", KDA: "decoder.kda_ms",
    KDA_CONV: "decoder.kda_conv_ms", KDA_CORE: "decoder.kda_core_ms",
    MAMBA: "decoder.mamba_ms", MAMBA_CONV: "decoder.mamba_conv_ms",
    MAMBA_SSD: "decoder.ssd_ms"}
SCOPES = tuple(METRICS)
#: the finer marks, as the program writes them -> their metrics. Not among
#: the scopes above: ``scope_of`` leaves their events with the scope around
#: them (``tests/test_phases.py`` holds that, and that ``METRICS`` has none of
#: them), so each is read by the mark itself, anywhere in the ``op_name``:
#: ``ps.mtp`` lies around other scopes, the others inside one.
#: ``benchmark/check/check_decoder.py`` holds them equal to the program's
MARKS = {"ps.attn/inblock": "decoder.inblock_ms",
         "ps.attn/latent": "decoder.attn_latent_ms",
         "ps.attn/rope": "decoder.attn_rope_ms",
         "ps.mamba/gate": "decoder.mamba_gate_ms",
         "ps.mtp": "decoder.mtp_ms", "ps.mtp/join": "decoder.mtp_join_ms"}
#: how the own instruction name of the rotation's Mosaic calls starts
#: (``ops/rope.py`` names its kernels ``rope`` and ``rope_transposed``): under
#: ``ps.attn`` and no flash call
ROTATION = "%rope"
#: the kernels whose cost a family states as ``<key>_flops`` and
#: ``<key>_bytes``: roofline -> (key, the time it is held against: a scope's
#: metric, or the Mosaic calls of that kind under the attention's scope)
ROOFLINES = {"kernel.flash_roofline": ("flash", None),
             "kernel.window_flash_roofline": ("window_flash", None),
             "kernel.kda_core_roofline": ("kda_core", "decoder.kda_core_ms"),
             "kernel.ssd_roofline": ("ssd", "decoder.ssd_ms")}
#: the counters of a step (or, where nothing is held and nothing can drop,
#: the facts) that are metrics as they stand
COUNTS = {"load_max_over_mean": "decoder.load_max_over_mean",
          "held_pair_share": "decoder.held_pair_share",
          "dropped_tokens": "decoder.dropped_tokens",
          "masked_share": "decoder.masked_share",
          # no family states it since PR 67 (the causal grid it was counted
          # from went with PR 53); tests/test_phases.py's hand-made result
          # does, and holds the name
          "window_live_step_share": "decoder.window_live_step_share"}


def outer_of(found: str):
    """The scope of this table that ``found`` is opened inside, by its name
    (``ps.attn/gate`` in ``ps.attn``); ``None`` for ``ps.moe/*``, whose
    ``ps.moe`` is no scope."""
    outer = found.rpartition("/")[0]
    return outer if outer in METRICS else None


def scope_of(own: str, op_name: str, scopes=SCOPES):
    """The innermost of ``scopes`` of one device event, from the ``op_name``
    of its instruction: the one named last in the path, the longer name where
    two start together. ``None`` where it has none of them."""
    at = {s: op_name.rfind(s) for s in scopes}
    found = max(scopes, key=lambda s: (at[s], len(s)))
    return found if at[found] >= 0 else None


def mark_seconds(trace: dict, op_names: dict, mark: str) -> float:
    """Seconds in a reduced trace, mean of the chips, of every event whose
    ``op_name`` carries ``mark``, wherever in the name stack."""
    return tracered.op_seconds(trace, lambda name: mark in (
        op_names.get(tracered.parts(name)["own"]) or ""))


def is_row_exchange(name: str, buffer_rows) -> bool:
    """Whether a device event under ``ps.moe/exchange`` is one exchange of
    the first trip's rows: an ``all-to-all`` (its start, where XLA splits
    one) whose result has a dimension of ``buffer_rows``. The group sizes'
    exchange and a further trip's small buffers are not."""
    opcode = tracered.parts(name)["opcode"]
    shape = name.partition(" = ")[2].partition("]")[0].partition("[")[2]
    return (opcode.startswith("all-to-all") and not opcode.endswith("-done")
            and str(buffer_rows) in shape.split(","))


def live_pairs(facts: dict, counters: dict):
    """Token-expert pairs a chip computed in a step, all layers: the step's
    own counter where a share is held, the fact where every routed pair is
    computed."""
    return counters.get("live_pairs_per_step",
                        facts.get("live_pairs_per_step"))


def chip_flops(facts: dict, counters: dict):
    """A chip's FLOPs a step from shapes: the dense part plus the pairs it
    computed; ``None`` where the family states none, or states a pair's and
    nothing counted the pairs."""
    dense = facts.get("dense_flops_per_step")
    if not dense:
        return None
    if "flops_per_pair" not in facts:
        return dense
    live = live_pairs(facts, counters)
    return None if live is None else dense + live * facts["flops_per_pair"]


def least_s(facts: dict, peaks: dict, key: str) -> float:
    """Seconds a step the chip's peaks allow the kernel ``key``: the larger
    of its operations over peak FLOP/s and its bytes over peak bytes/s."""
    return max(facts[f"{key}_flops"] / peaks["bf16_flops_per_s"],
               facts[f"{key}_bytes"] / peaks["hbm_bytes_per_s"])


def scope_times(r: dict, op_names: dict, scope_of=scope_of) -> dict:
    """The time metrics and the shares made of them, from a result and
    ``{instruction name: op_name}``: one walk over the trace."""
    trace, steps = r["trace"], r["traced_steps"]
    devices = trace["devices"]
    per_ms = 1e3 / steps / len(devices)   # seconds over chips -> ms a step
    by_scope, by_mark = {}, {}
    flash_s = {"flash": 0.0, "window_flash": 0.0}
    exchange_s, exposed_s, store_s, exchanges = [], [], [], []  # a chip each
    grad_s = 0.0
    rest = {}
    facts, peaks = r["facts"], r["peaks"]
    counters = r.get("counters") or {}
    targets = facts.get("kernel_targets", ())
    for d in devices.values():
        exchange_s.append(0.0), exposed_s.append(0.0), store_s.append(0.0)
        exchanges.append(0)
        for name, sec in d["ops"].items():
            own = tracered.parts(name)["own"]
            op_name = op_names.get(own) or ""
            found = scope_of(own, op_name)
            collective = tracered.is_collective(name)
            if found is not None or scope.GRAD in op_name:
                grad_s += sec
            for mark in MARKS:
                if mark in op_name:
                    by_mark[mark] = by_mark.get(mark, 0.0) + sec
            if found == MOE_EXCHANGE:
                exchange_s[-1] += sec
                if collective:
                    exposed_s[-1] += sec
                exchanges[-1] += is_row_exchange(
                    name, facts.get("exchange_buffer_rows"))
            elif collective:
                store_s[-1] += sec
            if found is None:
                if scope.GRAD in op_name:
                    rest[name] = rest.get(name, 0.0) + sec
                continue
            by_scope[found] = by_scope.get(found, 0.0) + sec
            outer = outer_of(found)
            if outer:
                by_scope[outer] = by_scope.get(outer, 0.0) + sec
            if (ATTN in (found, outer) and not own.startswith(ROTATION)
                    and tracered.is_custom_call_to(name, targets)):
                flash_s["window_flash" if found == ATTN_WINDOW
                        else "flash"] += sec
    if not any(by_scope.values()):
        return {}
    out = {}
    for s, sec in by_scope.items():
        out[METRICS[s]] = out.get(METRICS[s], 0.0) + per_ms * sec
    out.update({MARKS[mark]: per_ms * sec for mark, sec in by_mark.items()})
    if MOE_EXCHANGE in by_scope:
        out["decoder.exchange_ms"] = 1e3 * max(exchange_s) / steps
        out["decoder.exchange_exposed_ms"] = 1e3 * max(exposed_s) / steps
        out["decoder.store_collective_ms"] = 1e3 * max(store_s) / steps
        rows = counters.get("exchange_rows_per_step")
        a_layer = max(exchanges) / facts.get("layers", 1)
        if out["decoder.exchange_ms"] > 0 and rows is not None and a_layer:
            out["decoder.exchange_ici_share"] = 100.0 * (
                rows * facts["exchange_bytes_per_row"] * a_layer
                / (peaks["ici_bits_per_s"] / 8)) / (
                    1e-3 * out["decoder.exchange_ms"])
    live = live_pairs(facts, counters)
    if (out.get("decoder.expert_ms", 0) > 0 and live is not None
            and "flops_per_pair" in facts):
        out["decoder.expert_mxu_share"] = 100.0 * (
            live * facts["flops_per_pair"] / peaks["bf16_flops_per_s"]
        ) / (1e-3 * out["decoder.expert_ms"])
    if (out.get("decoder.conv_gate_ms", 0) > 0
            and "conv_gate_bytes_per_step" in facts):
        out["decoder.conv_gate_hbm_share"] = 100.0 * (
            facts["conv_gate_bytes_per_step"] / peaks["hbm_bytes_per_s"]
        ) / (1e-3 * out["decoder.conv_gate_ms"])
    for roofline, (key, against) in ROOFLINES.items():
        seconds = 1e-3 * out.get(against, 0.0) if against \
            else flash_s[key] / steps / len(devices)
        if seconds > 0 and f"{key}_flops" in facts:
            out[roofline] = 100.0 * least_s(facts, peaks, key) / seconds
    _say(out, by_scope, per_ms, grad_s, flash_s, rest, op_names,
         [[round(1e3 * s / steps, 3) for s in chip]
          for chip in (exchange_s, store_s)])
    return out


def _say(out, by_scope, per_ms, grad_s, flash_s, rest, op_names, by_chip):
    # the outermost scopes' sum is the time under the scopes: an inner one's
    # is inside its outer one's
    scoped = sum(sec for s, sec in by_scope.items() if not outer_of(s))
    print(f"decoder: under the scopes {per_ms * scoped:.4f} ms a step, mean "
          f"of the chips, of {per_ms * grad_s:.4f} under {scope.GRAD} "
          f"({100 * scoped / grad_s:.2f}%): "
          + ", ".join(f"{s} {per_ms * sec:.4f}"
                      for s, sec in sorted(by_scope.items()))
          + f" (an inner scope's inside its outer one's; the flash kernels "
          f"of the windowed layers {per_ms * flash_s['window_flash']:.4f} "
          f"ms, of the others {per_ms * flash_s['flash']:.4f} ms; the "
          f"exchange by chip {by_chip[0]}, the collectives outside it "
          f"{by_chip[1]}); the rest {per_ms * sum(rest.values()):.4f} ms "
          f"(embedding and its gradient, norms and residuals outside the "
          f"scopes), the largest:", file=sys.stderr)
    for name, sec in tracered.top(rest, n=8, width=None):
        own = tracered.parts(name)["own"]
        print(f"decoder:   {per_ms * sec:9.4f} ms  {name[:96]}  "
              f"[{(op_names.get(own) or '')[:96]}]", file=sys.stderr)
    print("decoder: " + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
        out.items())), file=sys.stderr)


def rehearsed(facts: dict, op_names: dict, scope_of=scope_of) -> dict:
    """What a ``--rehearse`` run can say: no chip, so no time and no peak,
    but the step is loaded and its marks are there. Each time metric whose
    scope some instruction of the loaded step carries, and the shares that
    are made of them, at 0.0: ``run.py`` lists the names and prints no
    value."""
    found = {scope_of(own, op_name) for own, op_name in op_names.items()}
    found.discard(None)
    out = {METRICS[s]: 0.0 for s in found}
    out.update({metric: 0.0 for mark, metric in MARKS.items()
                if any(mark in op_name for op_name in op_names.values())})
    if "decoder.expert_ms" in out and "flops_per_pair" in facts:
        out["decoder.expert_mxu_share"] = 0.0
    if "decoder.conv_gate_ms" in out and "conv_gate_bytes_per_step" in facts:
        out["decoder.conv_gate_hbm_share"] = 0.0
    if "decoder.exchange_ms" in out:
        out.update({"decoder.exchange_ici_share": 0.0,
                    "decoder.exchange_exposed_ms": 0.0,
                    "decoder.store_collective_ms": 0.0})
    kinds = {"window_flash": ATTN_WINDOW in found,
             "flash": bool(found & {ATTN, ATTN_FULL})}
    for roofline, (key, against) in ROOFLINES.items():
        if f"{key}_flops" in facts and (against in out if against
                                        else kinds[key]):
            out[roofline] = 0.0
    return out


def read(r: dict, scope_of=scope_of) -> dict:
    """``decoder.*`` and the kernels' ``kernel.*_roofline`` of a decoder's
    result; ``step.mfu`` is ``layer_metrics/step.py``'s, from
    ``chip_flops``. The trace is walked once a run: the result is kept in
    ``r``, where ``layer_metrics/kernel.py`` finds the rooflines."""
    if "decoder" not in r:
        r["decoder"] = _read(r, scope_of)
    return r["decoder"]


def _read(r: dict, scope_of) -> dict:
    counters, facts = r.get("counters") or {}, r.get("facts") or {}
    out = {metric: source[key] for key, metric in COUNTS.items()
           for source in (counters, facts) if key in source}
    if not chip_flops(facts, counters):
        return out
    if not r.get("peaks"):   # --rehearse, the one run without a device's peaks
        out.update(rehearsed(facts, scope.loaded_op_names() or {}, scope_of))
        return out
    trace = r.get("trace")
    if trace and trace.get("devices") and r.get("traced_steps"):
        op_names = scope.loaded_op_names()
        if op_names:
            out.update(scope_times(r, op_names, scope_of))
    return out
