"""input layer: ``data/prefetch.py`` and ``KVStore.shard_batch``, seen from
the loop: the host span around ``next(stream)`` over the window."""


def read(r: dict) -> dict:
    waits = r["spans"]["input.next"]
    if not waits:
        return {}
    return {"input.wait_share": 100.0 * sum(waits) / r["window_s"]}
