"""fused dense step / sparse step: ``kv/store.py::make_step`` and
``train.py::make_composite_step`` as one device program."""


def read(r: dict) -> dict:
    out = {}
    trace = r["trace"]
    if trace and trace["devices"] and r["traced_steps"]:
        # union of device-op intervals over the steps traced, mean of chips
        out["step.device_ms"] = 1e3 * trace["busy_s"] / r["traced_steps"]
    flops = r["facts"].get("flops_per_step")
    if flops and "bf16_flops_per_s" in r["peaks"]:
        steps_per_s = r["steps"] / r["window_s"]
        out["step.mfu"] = 100.0 * flops * steps_per_s / (
            r["chips"] * r["peaks"]["bf16_flops_per_s"])
    return out
