"""sparse step: ``kv/sparse.py`` and ``ops/sparse_apply.py``."""


def read(r: dict) -> dict:
    out = {}
    if "dropped_rows" in r["counters"]:
        out["sparse.dropped_rows"] = float(r["counters"]["dropped_rows"])
    floor = r["facts"].get("hbm_floor_bytes_per_step")
    trace = r["trace"]
    if floor and trace and trace["devices"] and r["traced_steps"]:
        step_s = trace["busy_s"] / r["traced_steps"]
        out["sparse.hbm_floor_share"] = 100.0 * (
            floor / r["peaks"]["hbm_bytes_per_s"]) / step_s
    return out
