"""The windowed flash calls alone (``ps_tpu/ops/flash_attention.py``'s band
step: forward, dq, dk / dv), timed at the two cells' shapes for each
``block x r`` asked for: the table of the module's docstring. On the chip
only::

    chiprun --chips 1 -- python3 tools/window_table.py \
        [--shapes trinity,mellum] [--bands 1024x256,1024x128] [--parent DIR]

``trinity`` is [32, 16384, 128] on [4, 16384, 128] under 2,048 keys, ``mellum``
[32, 8192, 128] on [4, 8192, 128] under 1,024, bf16. ``--parent DIR`` times
the three calls of the ``ps_tpu/ops/flash_attention.py`` under ``DIR`` (a
``git archive`` of a commit whose windowed calls are the tiled kernels') at
the tiles it chooses, and reports the largest distance between its results
and the band step's at the first band. A time is the median of ``--chains``
chains of ``--per-chain`` calls. ``--rehearse`` runs tiny shapes on the CPU and
prints no time. The result also goes to ``chiprun_out/pr53/window_table.json``
(``--out``).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ps_tpu.ops import mosaic  # noqa: E402

fa = importlib.import_module("ps_tpu.ops.flash_attention")

#: sequence, query heads, K/V heads, head width, window
SHAPES = {"trinity": (16384, 32, 4, 128, 2048),
          "mellum": (8192, 32, 4, 128, 1024)}
REHEARSAL = {"trinity": (512, 4, 1, 64, 256), "mellum": (512, 4, 1, 64, 128)}


def _module_at(root: str):
    spec = importlib.util.spec_from_file_location(
        "parent_flash", os.path.join(root, "ps_tpu", "ops",
                                     "flash_attention.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ms(fn, args, chains: int, calls: int) -> float:
    run = jax.jit(fn)
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(chains):
        start = time.perf_counter()
        for _ in range(calls):
            out = run(*args)
        jax.block_until_ready(out)
        times.append(1e3 * (time.perf_counter() - start) / calls)
    return statistics.median(times)


def _band_calls(window, block, r, d, interpret):
    """The band step's three calls at one ``block x r``."""
    kw = dict(scale=d ** -0.5, window=window, block=block, r=r,
              interpret=interpret)

    def forward(q, k, v, mask, *rest):
        return fa._band_fwd(q, k, v, mask, **kw)

    def dq(q, k, v, mask, do, lse, delta):
        return fa._band_dq(q, do, lse, delta, k, v, mask, **kw)

    def dkv(q, k, v, mask, do, lse, delta):
        return fa._band_dkv(q, do, lse, delta, k, v, mask, **kw)

    return {"forward": forward, "dq": dq, "dkv": dkv}


def _tiled_calls(m, window, seq, d, interpret):
    """The parent's three calls at the tiles it chooses."""
    fwd = dict(zip(("block_q", "block_k"), m.forward_tiles(seq, d, 2, True)))
    bwd = dict(zip(("block_q", "block_k"), m.backward_tiles(seq, d, 2, True)))
    kw = dict(scale=d ** -0.5, causal=True, window=window,
              interpret=interpret)

    def forward(q, k, v, mask, *rest):
        return m._flash_fwd(q, k, v, mask, **kw, **fwd)

    def dq(q, k, v, mask, do, lse, delta):
        return m._flash_dq(q, do, lse, delta, k, v, mask, **kw, **bwd)

    def dkv(q, k, v, mask, do, lse, delta):
        return m._flash_dkv(q, do, lse, delta, k, v, mask, **kw, **bwd)[:2]

    return {"forward": forward, "dq": dq, "dkv": dkv}


def _distance(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--shapes", default="trinity,mellum")
    ap.add_argument("--bands", default="1024x256")
    ap.add_argument("--calls", default="forward,dq,dkv")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chains", type=int, default=5)
    ap.add_argument("--per-chain", type=int, default=8)
    ap.add_argument("--out", default="chiprun_out/pr53/window_table.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    interpret = mosaic.interpret()
    bands = [tuple(int(x) for x in b.split("x"))
             for b in args.bands.split(",")]
    if args.rehearse:
        bands = [(256, 128)]
    chains, per_chain = (1, 1) if args.rehearse else (args.chains,
                                                      args.per_chain)
    table = {"device": device.device_kind, "seed": args.seed, "shapes": {}}
    for name in args.shapes.split(","):
        seq, h, h_kv, d, window = (REHEARSAL if args.rehearse
                                   else SHAPES)[name]
        rng = np.random.default_rng(args.seed)

        def operand(heads, dtype=jnp.bfloat16):
            return jnp.asarray(rng.normal(size=(heads, seq, d)), dtype)

        q, k, v, do = operand(h), operand(h_kv), operand(h_kv), operand(h)
        mask = jnp.ones((1, seq), jnp.int32)
        forms = {f"band.{block}x{r}": _band_calls(window, block, r, d,
                                                  interpret)
                 for block, r in bands}
        if args.parent:
            forms["parent"] = _tiled_calls(_module_at(args.parent), window,
                                           seq, d, interpret)
        first = next(iter(forms.values()))
        out, lse = jax.jit(first["forward"])(q, k, v, mask)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]
        operands = (q, k, v, mask, do, lse, delta)
        rows = table["shapes"][name] = {
            "q": [h, seq, d], "kv": [h_kv, seq, d], "window": window,
            "ms": {}, "largest_distance_from_the_first": {}}
        base, results = next(iter(forms)), {}
        for form, calls in forms.items():
            for call in args.calls.split(","):
                fn = calls[call]
                try:
                    got = jax.jit(fn)(*operands)
                    ms = _ms(fn, operands, chains, per_chain)
                except Exception as e:  # a band Mosaic refuses: say so, go on
                    print(name, form, call, "refused:", str(e)[:300],
                          flush=True)
                    continue
                if not args.rehearse:
                    rows["ms"][f"{form}.{call}"] = ms
                print(name, form, call,
                      "-" if args.rehearse else f"{ms:.3f} ms", flush=True)
                if form == base:
                    results[call] = got
                elif call in results:
                    rows["largest_distance_from_the_first"][
                        f"{form}.{call}"] = _distance(got, results[call])
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
