"""kernels: ``ops/flash_attention.py``, ``ops/kda_mosaic.py``, ``ops/ssd.py``.
A kernel's share of its roofline is the larger of its operations over peak
FLOP/s and its bytes over peak bytes/s, forward and backward, both from
shapes (``families/flash.py`` for every flash kernel), over the device time
of its calls in the traced steps. Which events are its calls: in a decoder,
which opens scopes, ``layer_metrics/decoder.py`` finds them under the
attention's, the rule's or the scan's; in BERT, which opens none, they are
the events that are themselves custom calls to the configuration's
``kernel_targets`` (Mosaic kernels)."""

from benchmark.harness import tracered
from benchmark.layer_metrics import decoder


def read(r: dict) -> dict:
    facts, trace = r["facts"], r["trace"]
    if decoder.chip_flops(facts, r.get("counters") or {}):
        return {k: v for k, v in decoder.read(r).items()
                if k in decoder.ROOFLINES}
    if "flash_flops" not in facts or not trace or not r["traced_steps"]:
        return {}
    targets = facts["kernel_targets"]
    seconds = tracered.op_seconds(
        trace, lambda name: tracered.is_custom_call_to(name, targets))
    if seconds <= 0:
        return {}
    least = decoder.least_s(facts, r["peaks"], "flash") * r["traced_steps"]
    return {"kernel.flash_roofline": 100.0 * least / seconds}
