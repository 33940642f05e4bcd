"""``ops/ssd.py``'s scan alone at the two cells' shapes (Granite-4.0-H-Micro's
whole mixer, ``bf16[1, 8192, 64, 64]`` on one B/C group of state 128 in chunks
of 256, and Nemotron-H's share of 16 heads in chunks of 128): the Mosaic
calls (``ops/ssd_mosaic.py``: ``forward`` with the entering states kept, and
``backward``), the XLA form (its forward, and its gradient whole: forward and
backward in one program, as autodiff makes it) and the least time
``benchmark/families/nemotron_h_step.py::ssd_cost`` gives one layer's work at
the chip's peaks: the table of ``ops/ssd_mosaic.py``'s docstring. On the chip
only::

    chiprun --chips 1 -- python3 tools/ssd_table.py [--cells granite,nemotron]

A time is the median of ``--chains`` chains of ``--per-chain`` calls inside
one jitted loop, so that no dispatch stands between two calls; each call's
rates ``A`` are the one before's plus zero times a number of its result,
which keeps the call in the loop. The largest distance between the kernels'
results and the XLA form's, as a share of the latter's largest, is reported
beside them. ``--rehearse`` runs two chunks on the CPU in interpret mode and
prints no time. The result also goes to ``chiprun_out/pr59/ssd_table.json``
(``--out``).
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

from benchmark.families.nemotron_h_step import ssd_cost  # noqa: E402
from ps_tpu.ops import mosaic, ssd, ssd_mosaic  # noqa: E402

#: sequence, heads, the configuration's chunk
CELLS = {"granite": (8192, 64, 256), "nemotron": (8192, 16, 128)}
WIDTH, STATE = 64, 128
#: the chips' published peaks, the benchmark's table
PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "harness", "peaks.json")


def _ms(call, a, operands, chains: int, calls: int) -> float:
    """``call(a, *operands)`` -> arrays; one number of each goes into the
    next call's rates, so that none of them is dead code."""
    def chain(a, *operands):
        def step(_, a):
            return a + 0.0 * sum(out[(-1,) * out.ndim].astype(a.dtype)
                                 for out in call(a, *operands))

        return jax.lax.fori_loop(0, calls, step, a)

    run = jax.jit(chain)
    jax.block_until_ready(run(a, *operands))
    times = []
    for _ in range(chains):
        start = time.perf_counter()
        jax.block_until_ready(run(a, *operands))
        times.append(1e3 * (time.perf_counter() - start) / calls)
    return statistics.median(times)


def _distance(got, want) -> float:
    got, want = (t.astype(jnp.float32) for t in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="granite,nemotron")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chains", type=int, default=5)
    ap.add_argument("--per-chain", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/pr59/ssd_table.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    interpret = mosaic.interpret()
    chains, calls = (1, 1) if args.rehearse else (args.chains, args.per_chain)
    table = {"device": device.device_kind, "seed": args.seed, "cells": {}}
    if not args.rehearse:  # a device the table does not list is an error
        with open(PEAKS) as f:
            peak = json.load(f)["devices"][device.device_kind]
    for name in args.cells.split(","):
        seq, heads, chunk = CELLS[name]
        if args.rehearse:
            seq = 2 * chunk
        rng = np.random.default_rng(args.seed)
        x, dy = (jnp.asarray(rng.normal(size=(1, seq, heads, WIDTH)),
                             jnp.bfloat16) for _ in range(2))
        b, c = (jnp.asarray(rng.normal(size=(1, seq, 1, STATE)), jnp.bfloat16)
                for _ in range(2))
        dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(1, seq, heads)),
                         jnp.float32)
        a = -jnp.asarray(rng.uniform(1.0, 16.0, size=(heads,)), jnp.float32)
        assert ssd_mosaic.takes(x, b, chunk)
        # ``x``, ``y`` and their cotangents enter and leave a program as the
        # mixer has them, [1, T, H P]: a [.., H, P] argument of a program has
        # a tiled layout of its own that a copy would have to undo
        shape = x.shape
        x, dy = x.reshape(1, seq, -1), dy.reshape(1, seq, -1)

        def plain(a, x, dt, b, c):
            return ssd._ssd_plain(x.reshape(shape), dt, a, b, c,
                                  chunk).reshape(x.shape)

        def plain_gradient(a, x, dt, b, c, dy):
            y, transposed = jax.vjp(plain, a, x, dt, b, c)
            da, dx, ddt, db, dc = transposed(dy)
            return dx, ddt, da, db, dc, y

        def forward(a, x, dt, b, c, keep=True):
            y, kept = ssd_mosaic.forward(x.reshape(shape), dt, a, b, c,
                                         interpret=interpret, keep=keep)
            return (y.reshape(x.shape), *kept)

        def backward(a, x, dt, b, c, states, dy):
            dx, *rest = ssd_mosaic.backward(
                x.reshape(shape), dt, a, b, c, states, dy.reshape(shape),
                interpret=interpret)
            return (dx.reshape(x.shape), *rest)

        states = forward(a, x, dt, b, c)[1]
        forms = {
            "kernel.forward": (forward, (x, dt, b, c)),
            "kernel.backward": (backward, (x, dt, b, c, states, dy)),
            "xla.forward": (lambda *args: (plain(*args),), (x, dt, b, c)),
            "xla.gradient": (plain_gradient, (x, dt, b, c, dy))}
        flops, nbytes = ssd_cost(1, seq, heads, WIDTH, 1, STATE, chunk, 1)
        row = table["cells"][name] = {
            "x": list(shape), "chunk": chunk,
            "heads_a_step": ssd_mosaic.heads_a_step(heads, WIDTH), "ms": {}}
        if not args.rehearse:
            row["least_ms"] = 1e3 * max(flops / peak["bf16_flops_per_s"],
                                        nbytes / peak["hbm_bytes_per_s"])
        for form, (call, operands) in forms.items():
            ms = _ms(call, a, operands, chains, calls)
            print(name, form, "-" if args.rehearse else f"{ms:.3f} ms",
                  flush=True)
            if not args.rehearse:
                row["ms"][form] = ms
        got = (forward(a, x, dt, b, c, keep=False)[0],
               *backward(a, x, dt, b, c, states, dy))
        *want, y = jax.jit(plain_gradient)(a, x, dt, b, c, dy)
        row["largest_distance"] = dict(zip(
            ("y", "dx", "ddt", "dA", "dB", "dC"),
            (_distance(g, w) for g, w in zip(got, [y] + want))))
        print(name, row["largest_distance"], flush=True)
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
