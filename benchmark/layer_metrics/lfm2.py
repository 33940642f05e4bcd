"""``lfm2.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``lfm2-24b-a2b.s8192.zipf``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, CONV, CONV_GATE, FFN, HEAD, MOE_COMBINE, MOE_DISPATCH,
    MOE_EXPERT, MOE_ROUTE)

#: ``ps_tpu/obs/phases.py::LFM2_SCOPES``, in its order
LFM2_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD,
               CONV, CONV_GATE, FFN)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.route_ms", "decoder.dispatch_ms", "decoder.expert_ms",
    "decoder.attn_ms", "decoder.head_ms", "decoder.conv_ms",
    "decoder.conv_gate_ms", "decoder.dense_ffn_ms",
    "decoder.conv_gate_hbm_share", "decoder.expert_mxu_share",
    "decoder.held_pair_share", "decoder.load_max_over_mean",
    "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "lfm2", LFM2_SCOPES, LISTED, RENAMED)
