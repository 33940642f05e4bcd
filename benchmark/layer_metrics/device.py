"""device: XLA:TPU as the trace and ``memory_stats()`` see it."""


def read(r: dict) -> dict:
    out = {"device.peak_hbm_gib": r["alloc_peak_bytes"] / 2 ** 30}
    trace = r["trace"]
    if trace and trace["devices"]:
        out["device.idle_share"] = trace["idle_share"]
    return out
