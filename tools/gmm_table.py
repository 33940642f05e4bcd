"""The standalone table of ``ps_tpu/ops/grouped_matmul.py``: forward, rows'
gradient and stacks' gradient, ``jax.lax.ragged_dot`` and its autodiff
against ``gmm`` / ``tgmm``, in ms and as a share of the MXU's peak, at the
six expert cells' shapes and group-size patterns. On the chip only::

    chiprun --chips 1 -- python3 tools/gmm_table.py [--rows 256,512] [--cells mellum,olmoe]

``--rows`` adds forced row tiles beside ``tiles(..)``'s choice (the matrix
whole); ``--rehearse`` runs tiny shapes on the CPU and prints no time.
The result also goes to ``chiprun_out/gmm_table.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ps_tpu.ops import grouped_matmul as G  # noqa: E402

#: bf16 FLOP/s of one chip, by ``device_kind`` (Google Cloud, "TPU v5e")
PEAK = {"TPU v5 lite": 197e12}

#: cell -> (rows, width of a row, width of an expert, groups, pattern):
#: 'padded': a third of the rows live, on a few of the groups, the last group
#: takes the zero rows behind them (``expected_rows``); 'live': the same, and
#: the rows behind belong to no group; 'zipf': every row live, Zipf(1) sizes
CELLS = {
    "mellum": (49152, 2304, 896, 16, "padded"),
    "olmoe": (65536, 2048, 1024, 64, "zipf"),
    "lfm2": (24576, 2048, 1536, 8, "live"),
    "trinity": (49152, 2048, 1024, 16, "padded"),
    "nemotron": (8704, 1024, 2688, 8, "padded"),
    "kimi": (6144, 2304, 1024, 8, "live"),
}


def group_sizes(rows: int, groups: int, pattern: str, seed: int):
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, groups + 1)
    p = rng.permutation(p / p.sum())
    if pattern == "zipf":
        return rng.multinomial(rows, p).astype(np.int32)
    sizes = rng.multinomial(rows // 3, p).astype(np.int32)
    if pattern == "padded":
        sizes[-1] += rows - sizes.sum()
    return sizes


#: calls in a chain: one call's time is the chain's less one call's, over
#: the calls between, so the host's dispatch and its wait are not in it
CHAIN = 9


def chained(fn, calls: int):
    """``fn`` ``calls`` times in one program, each after the one before: its
    group sizes wait for the last output (plus 0, unless that is NaN)."""
    def run(lhs, rhs, g, s):
        out = fn(lhs, rhs, g, s)
        for _ in range(calls - 1):
            first = out.reshape(-1)[0]
            out = fn(lhs, rhs, g, s + (first != first).astype(s.dtype))
        return out

    return jax.jit(run)


def timed(fn, *args, reps: int = 5):
    """Seconds of one call on the device, and its output."""
    def median(run):
        out = jax.block_until_ready(run(*args))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            times.append(time.perf_counter() - t0)
        return statistics.median(times), out

    one, out = median(chained(fn, 1))
    many, _ = median(chained(fn, CHAIN))
    return (many - one) / (CHAIN - 1), out


def calls(tiling):
    """forward, rows' gradient, stacks' gradient: name -> (fn of (lhs, rhs,
    g, sizes), the reference's)."""
    def ref_lhs(lhs, rhs, g, s):
        return jax.vjp(lambda x: jax.lax.ragged_dot(x, rhs, s), lhs)[1](g)[0]

    def ref_rhs(lhs, rhs, g, s):
        return jax.vjp(lambda w: jax.lax.ragged_dot(lhs, w, s), rhs)[1](g)[0]

    return {
        "forward": (lambda lhs, rhs, g, s: G._gmm(
            lhs, rhs, s, transpose_rhs=False, tiling=tiling),
            lambda lhs, rhs, g, s: jax.lax.ragged_dot(lhs, rhs, s)),
        "rows_grad": (lambda lhs, rhs, g, s: G._gmm(
            g, rhs, s, transpose_rhs=True,
            tiling=tiling and (tiling[0], tiling[2], tiling[1])), ref_lhs),
        "stacks_grad": (lambda lhs, rhs, g, s: G.tgmm(
            lhs, g, s, tiling=tiling), ref_rhs),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    device = jax.devices()[0]
    if not a.rehearse and device.platform != "tpu":
        sys.exit("no TPU found: the table is a chip run")
    peak = None if a.rehearse else PEAK[device.device_kind]
    table = []
    for cell in a.cells.split(","):
        m, d, f, e, pattern = CELLS[cell]
        if a.rehearse:
            m, d, f = m // 64, d // 8, f // 7
        sizes = group_sizes(m, e, pattern, a.seed)
        live = int(sizes.sum())
        for k, n in ((d, f), (f, d)):   # gate / up, then down
            keys = jax.random.split(jax.random.PRNGKey(a.seed), 3)
            lhs = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
            rhs = jax.random.normal(keys[1], (e, k, n), jnp.bfloat16) * 0.05
            g = jax.random.normal(keys[2], (m, n), jnp.bfloat16)
            if pattern == "padded":   # zeros in, as dispatch leaves them
                lhs = lhs.at[m // 3:].set(0)
            s = jnp.asarray(sizes)
            chosen = G.tiles(m, k, n, e, 2)
            tilings = [None] + [(int(tm), k, n) for tm in
                                a.rows.split(",") if tm]
            flops = 2.0 * live * k * n
            for tiling in tilings:
                for what, (ours, ref) in calls(tiling).items():
                    row = {"cell": cell, "m": m, "k": k, "n": n, "groups": e,
                           "live_rows": live, "what": what,
                           "tiles": list(tiling or chosen)}
                    t_ours, out = timed(ours, lhs, rhs, g, s)
                    if tiling is None:
                        t_ref, want = timed(ref, lhs, rhs, g, s)
                        cut = slice(None) if what == "stacks_grad" else (
                            slice(0, live))
                        row["max_abs_diff"] = float(jnp.max(jnp.abs(
                            out[cut].astype(jnp.float32)
                            - want[cut].astype(jnp.float32))))
                        row["max_abs"] = float(jnp.max(jnp.abs(
                            want[cut].astype(jnp.float32))))
                    if peak:
                        row["gmm_ms"] = 1e3 * t_ours
                        row["gmm_mxu_share"] = 100 * flops / t_ours / peak
                        if tiling is None:
                            row["ragged_dot_ms"] = 1e3 * t_ref
                            row["ragged_dot_mxu_share"] = (
                                100 * flops / t_ref / peak)
                    table.append(row)
                    print(json.dumps(row), flush=True)
    if not a.rehearse:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/gmm_table.json", "w") as fh:
            json.dump({"device": device.device_kind, "rows": table}, fh)


if __name__ == "__main__":
    main()
