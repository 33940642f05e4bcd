"""fused dense step / sparse step: ``kv/store.py::make_step`` and
``train.py::make_composite_step`` as one device program.

``step.mfu``: the step's FLOPs from shapes times steps a second over the
chips' peak. A dense family states the whole step's as ``flops_per_step``
(ResNet and BERT from a constant copied into their configuration,
``flops.by_seq_len``); a decoder states a chip's in its parts, the dense
FLOPs and a routed pair's, and the pairs a step come from its own counter
where it holds a share (``layer_metrics/decoder.py::chip_flops``)."""

from benchmark.layer_metrics import decoder


def read(r: dict) -> dict:
    out = {}
    trace = r["trace"]
    if trace and trace["devices"] and r["traced_steps"]:
        # union of device-op intervals over the steps traced, mean of chips
        out["step.device_ms"] = 1e3 * trace["busy_s"] / r["traced_steps"]
    flops = r["facts"].get("flops_per_step")
    if flops is None:
        a_chip = decoder.chip_flops(r["facts"], r.get("counters") or {})
        flops = a_chip and a_chip * r["chips"]
    if flops and not r["peaks"]:   # --rehearse: the name, no value
        out["step.mfu"] = 0.0
    elif flops and "bf16_flops_per_s" in r["peaks"]:
        steps_per_s = r["steps"] / r["window_s"]
        out["step.mfu"] = 100.0 * flops * steps_per_s / (
            r["chips"] * r["peaks"]["bf16_flops_per_s"])
    return out
