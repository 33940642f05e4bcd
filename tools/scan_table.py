"""``ops/selective_scan.py``'s scan alone at the Phi-4-mini-flash cell's shape
(``x`` ``bf16[1, 16384, 5120]``, ``dt`` f32, 16 states): the Mosaic calls
(``ops/selective_scan_mosaic.py``: ``forward`` with the entering states kept,
and ``backward``) over the tiles of tokens and blocks of channels asked for,
the XLA form (its forward, and its gradient whole: forward and backward in one
program, as autodiff makes it) over the chunks and unrolls asked for, and the
least time ``benchmark/families/phi4flash_step.py::scan_cost``'s bytes give one
layer's forward and backward at the HBM's peak: the table of
``ops/selective_scan.py``'s docstring. On the chip only::

    chiprun --chips 1 -- python3 tools/scan_table.py \\
        [--tiles 128x512,256x512] [--xla 64x4]

A time is the median of ``--chains`` chains of ``--per-chain`` calls inside
one jitted loop (``tools/ssd_table.py``'s way: no dispatch between two calls).
A tile or block other than the module's is set on the module for the length
of its row (the kernels read ``TILE`` and ``_BLOCK`` as they are traced). The
largest distance between the kernels' results and the XLA form's, as a share
of the latter's largest, is reported beside them. ``--rehearse`` runs two
tiles of 256 channels on the CPU in interpret mode and prints no time. The
result also goes to ``chiprun_out/pr66/scan_table.json`` (``--out``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.families.phi4flash_step import scan_cost  # noqa: E402
from ps_tpu.ops import mosaic, selective_scan  # noqa: E402
from ps_tpu.ops import selective_scan_mosaic as kernels  # noqa: E402
from tools.ssd_table import PEAKS, _distance, _ms  # noqa: E402

SEQ, CHANNELS, STATE = 16384, 5120, 16


def _pairs(text: str):
    return [tuple(int(n) for n in pair.split("x"))
            for pair in text.split(",") if pair]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default=f"{kernels.TILE}x{kernels._BLOCK}",
                    help="tile of tokens x block of channels, comma-separated")
    ap.add_argument("--xla", default=f"{selective_scan.CHUNK}x"
                    f"{selective_scan.UNROLL}", help="chunk x unroll")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chains", type=int, default=5)
    ap.add_argument("--per-chain", type=int, default=4)
    ap.add_argument("--out", default="chiprun_out/pr66/scan_table.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    interpret = mosaic.interpret()
    chains, calls = (1, 1) if args.rehearse else (args.chains, args.per_chain)
    seq, channels = (256, 256) if args.rehearse else (SEQ, CHANNELS)
    rng = np.random.default_rng(args.seed)
    x, b, c = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for shape in ((1, seq, channels), (1, seq, STATE),
                             (1, seq, STATE)))
    dy = jnp.asarray(rng.normal(size=(1, seq, channels)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(1, seq, channels)),
                     jnp.float32)
    # mamba_ssm's A, 1 .. N a channel, jittered so that no two are one number
    a = -jnp.asarray(np.arange(1, STATE + 1) * rng.uniform(
        0.9, 1.1, size=(channels, STATE)), jnp.float32)
    table = {"device": device.device_kind, "seed": args.seed,
             "x": list(x.shape), "ms": {}, "largest_distance": {}}
    if not args.rehearse:  # a device the table does not list is an error
        with open(PEAKS) as f:
            peak = json.load(f)["devices"][device.device_kind]
        table["least_ms"] = 1e3 * scan_cost(
            1, seq, channels, STATE, 1)[1] / peak["hbm_bytes_per_s"]

    def time_of(name, call, operands):
        ms = _ms(call, a, operands, chains, calls)
        print(name, "-" if args.rehearse else f"{ms:.3f} ms", flush=True)
        if not args.rehearse:
            table["ms"][name] = ms

    def xla_gradient(chunk, unroll):
        def gradient(a, x, dt, b, c, dy):
            y, transposed = jax.vjp(
                lambda a, x, dt, b, c: selective_scan._xla(
                    x, dt, a, b, c, chunk, unroll), a, x, dt, b, c)
            da, dx, ddt, db, dc = transposed(dy)
            return dx, ddt, da, db, dc, y

        return gradient

    want = None
    for chunk, unroll in _pairs(args.xla):
        time_of(f"xla {chunk}x{unroll} forward",
                lambda a, x, dt, b, c: (selective_scan._xla(
                    x, dt, a, b, c, chunk, unroll),), (x, dt, b, c))
        time_of(f"xla {chunk}x{unroll} gradient", xla_gradient(chunk, unroll),
                (x, dt, b, c, dy))
        if want is None:
            *want, y = jax.jit(xla_gradient(chunk, unroll))(a, x, dt, b, c, dy)
            want = [y] + want

    def forward(a, x, dt, b, c):
        y, kept = kernels.forward(x, dt, a, b, c, interpret=interpret,
                                  keep=True)
        return (y, *kept)

    def backward(a, x, dt, b, c, states, dy):
        return kernels.backward(x, dt, a, b, c, states, dy,
                                interpret=interpret)

    ours = kernels.TILE, kernels._BLOCK
    for tile, block in _pairs(args.tiles):
        kernels.TILE, kernels._BLOCK = tile, block
        jax.clear_caches()
        try:
            states = forward(a, x, dt, b, c)[1]
            time_of(f"kernel {tile}x{block} forward", forward, (x, dt, b, c))
            time_of(f"kernel {tile}x{block} backward", backward,
                    (x, dt, b, c, states, dy))
            got = (forward(a, x, dt, b, c)[0],
                   *backward(a, x, dt, b, c, states, dy))
            table["largest_distance"][f"{tile}x{block}"] = dict(zip(
                ("y", "dx", "ddt", "dA", "dB", "dC"),
                (_distance(g, w) for g, w in zip(got, want))))
            print(tile, block, table["largest_distance"][f"{tile}x{block}"],
                  flush=True)
        finally:
            kernels.TILE, kernels._BLOCK = ours
            jax.clear_caches()
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
