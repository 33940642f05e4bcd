"""kernels: ``ops/flash_attention.py``. The larger of operations over peak
FLOP/s and bytes over peak bytes/s (both from shapes, families/dense_step.py
``flash_forward_cost``) over the summed device time of the events that are
themselves custom calls to the configuration's ``kernel_targets`` (Mosaic
kernels), in the traced steps."""

from benchmark.harness import tracered


def floors(facts: dict, peaks: dict) -> dict:
    """Seconds per step at each peak: which of the two bounds the kernel."""
    return {"compute_s": facts["kernel_flops"] / peaks["bf16_flops_per_s"],
            "memory_s": facts["kernel_bytes"] / peaks["hbm_bytes_per_s"]}


def read(r: dict) -> dict:
    facts, trace = r["facts"], r["trace"]
    if "kernel_flops" not in facts or not trace or not r["traced_steps"]:
        return {}
    targets = facts["kernel_targets"]
    seconds = tracered.op_seconds(
        trace, lambda name: tracered.is_custom_call_to(name, targets))
    if seconds <= 0:
        return {}
    least = max(floors(facts, r["peaks"]).values()) * r["traced_steps"]
    return {"kernel.flash_roofline": 100.0 * least / seconds}
