"""``trinity.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``trinity-mini.s16384.b1.zipf``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, ATTN_FULL, ATTN_GATE, ATTN_WINDOW, FFN, HEAD, MOE_COMBINE,
    MOE_DISPATCH, MOE_EXPERT, MOE_ROUTE, MOE_SHARED)

#: ``ps_tpu/obs/phases.py::TRINITY_SCOPES``, in its order
TRINITY_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                  HEAD, FFN, MOE_SHARED, ATTN_WINDOW,
                  ATTN_FULL, ATTN_GATE)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.route_ms", "decoder.dispatch_ms", "decoder.expert_ms",
    "decoder.shared_ffn_ms", "decoder.attn_ms", "decoder.head_ms",
    "decoder.dense_ffn_ms", "decoder.window_core_ms", "decoder.full_core_ms",
    "decoder.attn_gate_ms", "kernel.window_flash_roofline",
    "decoder.expert_mxu_share", "decoder.held_pair_share",
    "decoder.load_max_over_mean", "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "trinity", TRINITY_SCOPES, LISTED, RENAMED)
