"""The phases inside the fused step: device time by ``jax.named_scope``.

The program opens a named scope where each phase of a step is written
(``ps_tpu/obs/phases.py``), and JAX puts the scope into the ``op_name`` of
every HLO instruction traced under it. The trace names a device event by its
HLO line *without* that metadata, so an event's phase is found by its own
instruction name (``%fusion.216``) in the optimized HLO of the executables
that are loaded: ``client.live_executables()`` -> ``hlo_modules()`` -> text.
That needs no lowering and no compile.

Times are the self times of ``harness/tracered.py`` (a ``while`` does not
count its body twice), mean of chips, per traced step. A device's ops run
one at a time, so the phases and the unattributed rest add up to
``step.device_ms``. A fusion carries one ``op_name``, its root's: a fusion
that XLA built across two phases counts wholly in one. XLA fuses the dense
optimizer's update into the fusions that end the backward pass, so
``scope.apply_ms`` (and ``scope.collective_apply_ms``: GSPMD names the
parameter all-gather after the forward op that uses it) is a lower bound of
the server-side apply, not its cost; the reader says so beside the values.

On a program without the scopes (the parent of the PR that added them, or an
executable served from a compile cache written before them: jax leaves
metadata out of the cache's key) no executable carries a mark. Then
everything is reported as unattributed, with the remedy on stderr.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, Optional

from benchmark.harness import tracered

# The names of ps_tpu/obs/phases.py, copied: the yardstick also reads trees
# that lack that file. tests/test_phases.py holds the two sets equal.
GRAD = "ps.grad"
APPLY = "ps.apply"
LOOKUP = "ps.lookup"
ROW_APPLY = "ps.row_apply"
ROW_EXCHANGE = "ps.row_apply/exchange"
ROW_DEDUPE = "ps.row_apply/dedupe"
ROW_GATHER = "ps.row_apply/gather"
ROW_UPDATE = "ps.row_apply/update"
ROW_SCATTER = "ps.row_apply/scatter"
# a transform of the name stack: ``transpose(jvp(..))`` on ordinary backward
# ops, ``transpose(ps.grad)/jvp(..)`` on those of a custom_vjp backward rule;
# the primitive ``transpose`` has no parenthesis
BACKWARD_MARK = "transpose("
DEVICE_PHASES = (GRAD, APPLY, LOOKUP, ROW_APPLY, ROW_EXCHANGE, ROW_DEDUPE,
                 ROW_GATHER, ROW_UPDATE, ROW_SCATTER)

#: phase -> metric; the children of row_apply count in row_apply_ms too
PHASE_METRICS = {"forward": "scope.forward_ms",
                 "backward": "scope.backward_ms",
                 "apply": "scope.apply_ms",
                 "lookup": "scope.lookup_ms",
                 "row_apply": "scope.row_apply_ms"}
ROW_CHILD_METRICS = {ROW_DEDUPE: "scope.row_dedupe_ms",
                     ROW_GATHER: "scope.row_gather_ms",
                     ROW_SCATTER: "scope.row_scatter_ms"}
COLLECTIVE_METRICS = {"forward": "scope.collective_forward_ms",
                      "backward": "scope.collective_backward_ms",
                      "apply": "scope.collective_apply_ms"}

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w\-.]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names_of(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of one
    optimized HLO module; an instruction without metadata maps to ``""``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found = _OP_NAME.search(line)
            out[m.group(1)] = found.group(1) if found else ""
    return out


#: the ``op_name`` given to an instruction name that two marked executables
#: put in different phases: it carries no mark, so such an op counts as
#: unattributed and shows its reason among the unattributed ops
CLASH = "<in different phases in two marked executables>"


def merge_marked(modules: Iterable[Dict[str, str]]
                 ) -> Optional[Dict[str, str]]:
    """One ``{instruction name: op_name}`` over the modules that carry a
    phase mark; ``None`` where none does. Modules without a mark (the
    reference, the pools) are left out, so that an instruction name they
    share with the step does not shadow the step's. An instruction name is
    unique within one module only, and the trace does not say which module
    an event ran in: a name that two marked modules (a second step, the
    eager ``SparseEmbedding.push``, a recompile) put in different phases
    maps to ``CLASH`` and is named on stderr, so that the first module seen
    never decides the phase of the other's events."""
    marked: Dict[str, str] = {}
    clashes = set()
    n_marked = 0
    for names in modules:
        if not any(phase_of(v)[0] for v in names.values()):
            continue
        n_marked += 1
        for k, v in names.items():
            if phase_of(marked.setdefault(k, v)) != phase_of(v):
                clashes.add(k)
    for k in clashes:
        marked[k] = CLASH
    print(f"scope: {n_marked} loaded executable(s) carry a phase mark"
          + (f"; {len(clashes)} instruction name(s) stand in different "
             f"phases in two of them and count as unattributed: "
             f"{sorted(clashes)[:10]}" if clashes else ""), file=sys.stderr)
    return marked or None


def loaded_op_names() -> Optional[Dict[str, str]]:
    """``merge_marked`` over the optimized HLO of every loaded executable."""
    import jax

    return merge_marked(
        op_names_of(module.to_string())
        for exe in jax.devices()[0].client.live_executables()
        for module in exe.hlo_modules())


def phase_of(op_name: str) -> tuple:
    """``(phase, row child or None)`` of one ``op_name``; phase is ``None``
    where it carries no mark."""
    if ROW_APPLY in op_name:
        child = next((c for c in (ROW_EXCHANGE, ROW_DEDUPE, ROW_GATHER,
                                  ROW_UPDATE, ROW_SCATTER) if c in op_name),
                     None)
        return "row_apply", child
    if LOOKUP in op_name:
        return "lookup", None
    if APPLY in op_name:
        return "apply", None
    if GRAD in op_name:
        return ("backward" if BACKWARD_MARK in op_name else "forward"), None
    return None, None


def phase_times(r: dict, op_names: Optional[Dict[str, str]]) -> dict:
    """The metrics of this file from a result ``r`` and the map from
    instruction name to ``op_name`` (``None``: no executable has a mark)."""
    trace = r.get("trace")
    steps = r.get("traced_steps")
    if not trace or not trace.get("devices") or not steps:
        return {}
    if op_names is None:
        print("scope: no loaded executable carries a phase mark "
              f"({GRAD}, {APPLY}, {LOOKUP}, {ROW_APPLY}): all device time is "
              "reported as unattributed. Either the program has no "
              "jax.named_scope, or its step was served from a compile "
              "cache written before the scopes (jax leaves metadata out of "
              "the cache's key): remove the cache directory "
              "(<checkout>/.jax_cache or $JAX_COMPILATION_CACHE_DIR) once "
              "and run again.", file=sys.stderr)
        op_names = {}
    devices = trace["devices"]
    n = len(devices)
    per_ms = 1e3 / steps / n   # seconds summed over chips -> ms a step
    by_phase = {p: {} for p in PHASE_METRICS}  # phase -> {event: seconds}
    by_child = {c: 0.0 for c in ROW_CHILD_METRICS}
    unattributed: Dict[str, float] = {}
    why = {}
    coll = {}  # device -> {phase or None: seconds of collective ops}
    for dev, d in devices.items():
        coll[dev] = {}
        for name, sec in d["ops"].items():
            op_name = op_names.get(tracered.parts(name)["own"])
            phase, child = phase_of(op_name or "")
            into = unattributed if phase is None else by_phase[phase]
            into[name] = into.get(name, 0.0) + sec
            if phase is None:
                why[name] = ("<not in a marked executable>" if op_name is None
                             else op_name or "<no op_name>")
            if child in by_child:
                by_child[child] += sec
            if tracered.is_collective(name):
                coll[dev][phase] = coll[dev].get(phase, 0.0) + sec
    out = {metric: per_ms * sum(by_phase[p].values())
           for p, metric in PHASE_METRICS.items()}
    out.update({metric: per_ms * by_child[c]
                for c, metric in ROW_CHILD_METRICS.items()})
    rest = sum(unattributed.values())
    total = rest + sum(sum(v.values()) for v in by_phase.values())
    out["scope.unattributed_share"] = 100.0 * rest / total if total else 0.0
    if r.get("chips", 1) > 1:
        # the worst chip, as collective.exposed_ms takes it
        worst = max(coll, key=lambda dev: sum(coll[dev].values()))
        for p, metric in COLLECTIVE_METRICS.items():
            out[metric] = 1e3 / steps * coll[worst].get(p, 0.0)
        other = sum(sec for p, sec in coll[worst].items()
                    if p not in COLLECTIVE_METRICS)
        print(f"scope: collective time with no phase of "
              f"{sorted(COLLECTIVE_METRICS)}: {1e3 / steps * other:.4f} ms a "
              f"step on the worst chip", file=sys.stderr)

    # -- for the reader of the run: the largest ops of each phase
    def show(title, ops, note=None):
        print(f"scope: {title}: {per_ms * sum(ops.values()):.4f} ms a step; "
              "the largest:", file=sys.stderr)
        for name, sec in tracered.top(ops, n=10 if note else 5, width=None):
            print(f"scope:   {per_ms * sec:9.4f} ms  {name[:96]}"
                  + (f"  [{note[name][:96]}]" if note else ""),
                  file=sys.stderr)

    print(f"scope: {per_ms * total:.4f} ms a step of device ops",
          file=sys.stderr)
    print(f"scope: {PHASE_METRICS['apply']} and "
          f"{COLLECTIVE_METRICS['apply']} are a lower bound of the "
          "server-side apply, not its cost: a fusion counts wholly in its "
          "root's phase, XLA fuses the optimizer's update into the fusions "
          "that end the backward pass, and GSPMD names the parameter "
          "all-gather after the forward op that uses it. The apply's "
          "traffic has to be counted in bytes from the HLO.",
          file=sys.stderr)
    for p in PHASE_METRICS:
        if by_phase[p]:
            show(p, by_phase[p])
    show("unattributed", unattributed, why)
    return out


def read(r: dict) -> dict:
    if not r.get("trace") or not r["trace"].get("devices"):
        return {}
    return phase_times(r, loaded_op_names())
