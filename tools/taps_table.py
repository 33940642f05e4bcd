"""``ops/gated_conv.py::conv_silu``'s forward and backward alone at the three
cells' shapes (``bf16[1, 8192, C]``, four taps: Granite's 4,352 channels with
a bias, Kimi's 4,096 without, Nemotron-H's share of 1,280 with): the Mosaic
calls, the XLA form, and the spelling both replaced (``shift`` on the f32
cast, kept here alone): the table of the module's docstring. On the chip
only::

    chiprun --chips 1 -- python3 tools/taps_table.py [--cells granite,kimi]

A time is the median of ``--chains`` chains of ``--per-chain`` calls inside
one jitted loop, so that no dispatch stands between two calls; each call's
filter is the one before's plus zero times a row of its result, which keeps
the call in the loop and carries a [C, taps] array and no copy of ``[S, C]``
from one to the next. GB/s is the bytes a call needs by its shapes (2 passes
of ``tokens x C x itemsize`` forward, 3 backward) over that time. The largest
distance between the kernels' results and the XLA form's is reported beside
them.
``--rehearse`` runs tiny shapes on the CPU in interpret mode and prints no
time. The result also goes to ``chiprun_out/pr57/taps_table.json``
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

from ps_tpu.ops import gated_conv, mosaic  # noqa: E402

#: sequence, channels, bias
CELLS = {"granite": (8192, 4352, True), "kimi": (8192, 4096, False),
         "nemotron": (8192, 1280, True)}
TAPS = 4


def _ms(call, w, operands, chains: int, calls: int) -> float:
    """``call(w, *operands) -> [..., C]``: any result of the call whose last
    row goes into the next call's filter. The operands are the program's
    arguments, not constants folded into it."""
    def chain(w, *operands):
        def step(_, w):
            row = call(w, *operands).reshape(-1, w.shape[0])[-1]
            return w + 0.0 * row[:, None].astype(w.dtype)

        return jax.lax.fori_loop(0, calls, step, w)

    run = jax.jit(chain)
    jax.block_until_ready(run(w, *operands))
    times = []
    for _ in range(chains):
        start = time.perf_counter()
        jax.block_until_ready(run(w, *operands))
        times.append(1e3 * (time.perf_counter() - start) / calls)
    return statistics.median(times)


def _shift_forms():
    """The two rules as they stood before PR 57."""
    def z_of(w, x, b):
        z = gated_conv.causal_taps(x.astype(jnp.float32), w)
        return z if b is None else z + b

    def backward(w, x, b, dy):
        z = z_of(w, x, b)
        gate = jax.nn.sigmoid(z)
        dz = dy.astype(jnp.float32) * gate * (1 + z * (1 - gate))
        dw = jnp.stack([jnp.sum(dz * gated_conv.shift(
            x.astype(jnp.float32), TAPS - 1 - j), axis=(0, 1))
            for j in range(TAPS)], axis=-1)
        # one result as wide as the channels, that depends on all three
        return (gated_conv.causal_taps(dz, w, -1).astype(x.dtype)
                + (dw.sum(-1) + dz.sum((0, 1))).astype(x.dtype),)

    return (lambda w, x, b, dy: jax.nn.silu(z_of(w, x, b)).astype(x.dtype),
            backward)


def _distance(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(a, b))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="granite,kimi,nemotron")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chains", type=int, default=5)
    ap.add_argument("--per-chain", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/pr57/taps_table.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    interpret = mosaic.interpret()
    chains, calls = (1, 1) if args.rehearse else (args.chains, args.per_chain)
    table = {"device": device.device_kind, "seed": args.seed, "cells": {}}
    for name in args.cells.split(","):
        seq, channels, bias = CELLS[name]
        if args.rehearse:  # one block of the kernels' and no more
            seq, channels = gated_conv._BLOCK // 128, 128
        rng = np.random.default_rng(args.seed)
        x, dy = (jnp.asarray(rng.normal(size=(1, seq, channels)),
                             jnp.bfloat16) for _ in range(2))
        w = jnp.asarray(0.5 * rng.normal(size=(channels, TAPS)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(channels,)), jnp.float32) \
            if bias else None
        assert gated_conv.path(x, w) == "kernel"

        forms = {
            "kernel": (lambda w, x, b, dy: gated_conv.forward(
                x, w, b, interpret=interpret),
                lambda w, x, b, dy: gated_conv.backward(
                    x, w, b, dy, interpret=interpret)),
            "xla": (lambda w, x, b, dy: gated_conv._conv_silu(x, w, b),
                    lambda w, x, b, dy: gated_conv._conv_silu_bwd(
                        (x, w, b), dy)),
            "shift": _shift_forms()}
        operands = (x, b, dy)
        row = table["cells"][name] = {
            "x": [1, seq, channels], "bias": bias,
            "tiles": gated_conv.tiles(seq, channels, 2), "ms": {}, "GB/s": {}}
        results = {}
        for form, (forward, backward) in forms.items():
            if form != "shift":  # whose backward gives one array, for time
                results[form] = (forward(w, *operands),
                                 *backward(w, *operands)[:2])
            for call, one, passes in (
                    ("forward", forward, 2),
                    ("backward", lambda *a: backward(*a)[0], 3)):
                ms = _ms(one, w, operands, chains, calls)
                print(name, form, call,
                      "-" if args.rehearse else f"{ms:.3f} ms", flush=True)
                if not args.rehearse:
                    row["ms"][f"{form}.{call}"] = ms
                    row["GB/s"][f"{form}.{call}"] = \
                        passes * x.size * x.dtype.itemsize / ms / 1e6
        # the kernel's d[w | b] is [8, C], the XLA form's dw [C, taps]
        y, dx, dwb = results["kernel"]
        row["largest_distance"] = dict(zip(
            ("y", "dx", "dw"),
            (_distance([a], [r]) for a, r in zip(
                (y, dx, dwb[:TAPS].T), results["xla"]))))
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
