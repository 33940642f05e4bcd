"""looped decoder: ``ps_tpu/models/ouro.py``, a stack of layers run
``total_ut_steps`` times a step on the same weights, a readout and an exit
gate after every pass.

Three of the times are ``layer_metrics/decoder.py``'s under this layer's
names, read through its ``read`` and computed there: the attention's, the
SwiGLU's and the head's scopes, each opened ``total_ut_steps`` times a layer
or a step. Two scopes are this model's own (``ps_tpu/obs/phases.py::LOOP`` and
``EXIT``, copied: the yardstick also reads trees that lack them;
``tests/test_phases.py`` holds them equal): ``ouro.loop_ms`` is the device
time of every event under ``ps.loop``, all that runs once a pass on the shared
weights (the layer applications, with their attention and SwiGLU inside it,
their norms and residuals, and the final norm that closes a pass), forward,
recomputation and backward; ``ouro.exit_ms`` of every event under ``ps.exit``
(the gates, the exit distribution, its entropy, the weighted sum). The head's
scope and these two are disjoint, and all three nest under ``ps.grad``: their
sum is a part of ``scope.forward_ms`` + ``scope.backward_ms``. Means over the
chips, a traced step, found as ``layer_metrics/scope.py`` finds the step's
phases.

The two counters are the step's own ``aux``, as the family gives them: the
mean over the steps that ``loss_at_n`` reads of the expected number of passes
``sum_t t p_t`` and of the exit distribution's entropy, a position.

On a program without the scopes or the counters nothing here finds anything
to read, and the metrics are left out.
"""

from __future__ import annotations

from benchmark.layer_metrics import decoder, scope

LOOP = "ps.loop"
EXIT = "ps.exit"
#: the model's own scopes -> their metrics
MARKS = {LOOP: "ouro.loop_ms", EXIT: "ouro.exit_ms"}
#: ``decoder.py``'s metrics under this layer's names
OF_DECODER = {"decoder.attn_ms": "ouro.attn_ms",
              "decoder.dense_ffn_ms": "ouro.dense_ffn_ms",
              "decoder.head_ms": "ouro.head_ms"}
#: the step's counters that are metrics as they stand
COUNTS = {"expected_passes": "ouro.expected_passes",
          "exit_entropy": "ouro.exit_entropy"}


def read(r: dict) -> dict:
    counters = r.get("counters") or {}
    out = {metric: counters[key] for key, metric in COUNTS.items()
           if key in counters}
    times = decoder.read(r)
    out.update({mine: times[theirs] for theirs, mine in OF_DECODER.items()
                if theirs in times})
    if not any(theirs in times for theirs in OF_DECODER):
        return out          # no trace, or no scope in the loaded step
    op_names = scope.loaded_op_names() or {}
    trace, steps = r.get("trace"), r.get("traced_steps")
    for mark, metric in MARKS.items():
        if not any(mark in op_name for op_name in op_names.values()):
            continue
        if not r.get("peaks"):   # --rehearse: the name, no value
            out[metric] = 0.0
        elif trace and steps:
            out[metric] = 1e3 / steps * decoder.mark_seconds(
                trace, op_names, mark)
    return out
