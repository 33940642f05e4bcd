"""loop layer: the benchmark's own driving loop over ``step(batch)``."""

import statistics

from benchmark.harness import stats


def read(r: dict) -> dict:
    out = {}
    if r["spans"]["loop.dispatch"]:
        out["loop.dispatch_ms"] = 1e3 * statistics.median(
            r["spans"]["loop.dispatch"])
    if r["block_s"]:
        out["loop.stall_share"] = stats.stall_share(r["block_s"],
                                                    r["window_s"])
    return out
