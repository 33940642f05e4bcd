"""``nemo.*``: the names ``layer_metrics/decoder.py``'s metrics have in the
cell ``nemotron-3-super-120b-a12b.s8192.b1.zipf``, which
``BENCHMARK.json`` lists and ``tests/`` hold (``layer_metrics/twin.py`` says
why they stay, and when they go). Nothing is computed here."""

from benchmark.layer_metrics import twin
from benchmark.layer_metrics.decoder import (  # noqa: F401  (tests read them)
    ATTN, HEAD, MAMBA, MAMBA_CONV, MAMBA_SSD, MOE_COMBINE,
    MOE_DISPATCH, MOE_EXPERT, MOE_LATENT, MOE_ROUTE, MOE_SHARED)

#: ``ps_tpu/obs/phases.py::NEMOTRON_SCOPES``, in its order
NEMOTRON_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN,
                   HEAD, MAMBA, MAMBA_CONV, MAMBA_SSD,
                   MOE_LATENT, MOE_SHARED)
#: what the cell lists, under the one reader's names
LISTED = (
    "decoder.route_ms", "decoder.dispatch_ms", "decoder.expert_ms",
    "decoder.shared_ffn_ms", "decoder.latent_ms", "decoder.attn_ms",
    "decoder.head_ms", "decoder.mamba_ms", "decoder.mamba_conv_ms",
    "decoder.ssd_ms", "kernel.ssd_roofline", "decoder.expert_mxu_share",
    "decoder.held_pair_share", "decoder.load_max_over_mean",
    "decoder.dropped_tokens")
#: those it had under another name than its prefix gives
RENAMED = {}
SCOPE_METRICS, scope_of, scope_times, read = twin.make(
    "nemo", NEMOTRON_SCOPES, LISTED, RENAMED)
