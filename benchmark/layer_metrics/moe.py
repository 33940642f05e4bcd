"""``moe.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``olmoe-1b-7b.s4096.zipf``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, HEAD, MOE_COMBINE, MOE_DISPATCH, MOE_EXPERT, MOE_ROUTE)

#: ``ps_tpu/obs/phases.py::MOE_SCOPES``, in its order
MOE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.route_ms", "decoder.dispatch_ms", "decoder.expert_ms",
    "decoder.attn_ms", "decoder.head_ms", "decoder.expert_mxu_share",
    "decoder.load_max_over_mean", "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "moe", MOE_SCOPES, LISTED, RENAMED)
