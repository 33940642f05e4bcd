"""``mellum.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``mellum2-12b-a2.5b.s8192.b1.zipf.x4``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, ATTN_FULL, ATTN_WINDOW, HEAD, MOE_COMBINE, MOE_DISPATCH,
    MOE_EXCHANGE, MOE_EXPERT, MOE_ROUTE)
from benchmark.layer_metrics.decoder import is_row_exchange  # noqa: F401

#: ``ps_tpu/obs/phases.py::MELLUM_SCOPES``, in its order
MELLUM_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                 HEAD, ATTN_WINDOW, ATTN_FULL, MOE_EXCHANGE)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.dispatch_ms", "decoder.expert_ms", "decoder.exchange_ms",
    "decoder.exchange_exposed_ms", "decoder.exchange_ici_share",
    "decoder.store_collective_ms", "kernel.window_flash_roofline",
    "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "mellum", MELLUM_SCOPES, LISTED, RENAMED)
_INNERMOST_FIRST = MELLUM_SCOPES   # tests/test_phases.py reads the set
