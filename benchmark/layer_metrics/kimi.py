"""``kimi.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``kimi-linear-48b-a3b.s8192.b1.zipf``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, FFN, HEAD, KDA, KDA_CONV, KDA_CORE, MOE_COMBINE,
    MOE_DISPATCH, MOE_EXPERT, MOE_ROUTE, MOE_SHARED)

#: ``ps_tpu/obs/phases.py::KIMI_SCOPES``, in its order
KIMI_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD,
               FFN, KDA, KDA_CONV, KDA_CORE, MOE_SHARED)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.route_ms", "decoder.dispatch_ms", "decoder.expert_ms",
    "decoder.shared_ffn_ms", "decoder.attn_ms", "decoder.head_ms",
    "decoder.dense_ffn_ms", "decoder.kda_ms", "decoder.kda_conv_ms",
    "decoder.kda_core_ms", "kernel.kda_core_roofline",
    "decoder.expert_mxu_share", "decoder.held_pair_share",
    "decoder.load_max_over_mean", "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {"decoder.attn_ms": "kimi.mla_ms"}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "kimi", KIMI_SCOPES, LISTED, RENAMED)
