"""placement / collectives: ``parallel/sharding.py`` and GSPMD."""


def read(r: dict) -> dict:
    out = {}
    nbytes = r["facts"].get("collective_bytes_per_step")
    if nbytes:
        out["collective.bytes_per_step"] = nbytes / 1e6
    trace = r["trace"]
    if trace and trace["devices"] and r["traced_steps"] and r["chips"] > 1:
        worst = max(d["exposed_collective_s"]
                    for d in trace["devices"].values())
        out["collective.exposed_ms"] = 1e3 * worst / r["traced_steps"]
    return out
