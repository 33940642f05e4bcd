"""``ops/kda.py``'s rule alone at the Qwen3-Next cell's shape (q and k
``bf16[1, 8192, 16, 128]`` read by 32 value heads, ``g`` and ``beta``
``f32[1, 8192, 32]``): the scalar-decay Mosaic calls
(``ops/kda_mosaic.py::scalar_forward`` with the states and inverses kept, and
``scalar_backward``) beside the per-channel kernels on broadcast operands
(what ``kda`` ran at these shapes before PR 61: the decay broadcast to the
head's channels and the key heads repeated, inside the timed program, and
autodiff's sums back), with the scalar body's ablations (``--ablate``: a
piece of ``_scalar_chunk`` replaced by something free of the same shape, so
the results are wrong and only the times are read) and the least time
``benchmark/families/qwen3_next_step.py::gdn_core_cost`` gives one layer's
work at the chip's peaks: the table of ``ops/kda.py``'s docstring. On the
chip only::

    chiprun --chips 1 -- python3 tools/gdn_table.py [--ablate 1]

A time is the median of ``--chains`` chains of ``--per-chain`` calls inside
one jitted loop (``tools/ssd_table.py``'s way: no dispatch between two calls;
each call's ``beta`` is the one before's plus zero times a number of its
result). The largest distance between the scalar calls' results and the
per-channel kernels', as a share of the latter's largest, is reported beside
them. ``--rehearse`` runs two chunks of two key heads on the CPU in interpret
mode and prints no time. The result also goes to
``chiprun_out/pr61/gdn_table.json`` (``--out``).
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

from benchmark.families.qwen3_next_step import gdn_core_cost  # noqa: E402
from ps_tpu.ops import kda, kda_mosaic, mosaic  # noqa: E402
from tools.ssd_table import PEAKS, _distance, _ms  # noqa: E402

SEQ, KEYS, VALUES, WIDTH, CHUNK = 8192, 16, 32, 128, 64


def _operands(seed: int, seq: int, keys: int, values: int):
    """Unit q and k in bf16, decays from the configuration's range of
    ``exp(A_log)`` (1e-6 to 16) times a softplus around 1.3."""
    rng = np.random.default_rng(seed)
    q, k = (rng.normal(size=(1, seq, keys, WIDTH)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * WIDTH ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v, do = (rng.normal(size=(1, seq, values, WIDTH)) for _ in range(2))
    g = -rng.uniform(1e-6, 16.0, size=values) * rng.uniform(
        0.3, 2.3, size=(1, seq, values))
    beta = rng.uniform(0.05, 0.95, size=(1, seq, values))
    return ([jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
            + [jnp.asarray(x, jnp.float32) for x in (g, beta)],
            jnp.asarray(do, jnp.bfloat16))


#: a piece of ``_scalar_chunk`` -> what stands in for it in an ablation
ABLATIONS = {
    "inverse": ("_inverses", lambda mats, eye: [eye - a for a in mats]),
    "run_sum": ("_run_sums", lambda runs, x: (x,)),
    "solve": ("_solve", lambda a, rhs, inverse: rhs + 0.0 * jnp.sum(a)),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chains", type=int, default=5)
    ap.add_argument("--per-chain", type=int, default=4)
    ap.add_argument("--ablate", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/pr61/gdn_table.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    chains, calls = (1, 1) if args.rehearse else (args.chains, args.per_chain)
    seq, keys, values = (2 * CHUNK, 2, 4) if args.rehearse \
        else (SEQ, KEYS, VALUES)
    (q, k, v, g, beta), do = _operands(args.seed, seq, keys, values)
    static = dict(chunk=CHUNK, mxu=jnp.bfloat16, interpret=mosaic.interpret())
    assert kda.path(q, k, v, CHUNK, g) == "scalar_kernel"

    def broadcast(q, k, g):
        r = values // keys
        return (jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2),
                jnp.broadcast_to(g[..., None], (*g.shape, WIDTH)))

    def scalar_forward(beta, q, k, v, g):
        out, kept = kda_mosaic.scalar_forward(q, k, v, g, beta, keep=True,
                                              **static)
        return (out, *kept)

    def scalar_backward(beta, q, k, v, g, states, inverses, do):
        return kda_mosaic.scalar_backward(q, k, v, g, beta,
                                          (states, inverses), do, **static)

    def general_forward(beta, q, k, v, g):
        q, k, g = broadcast(q, k, g)
        out, kept = kda_mosaic.forward(q, k, v, g, beta, keep=True, **static)
        return (out, *kept)

    def general_gradient(beta, q, k, v, g, do):
        def rule(q, k, v, g, beta):
            q, k, g = broadcast(q, k, g)
            return kda.kda(q, k, v, g, beta)

        out, transposed = jax.vjp(rule, q, k, v, g, beta)
        return (*transposed(do), out)

    def scalar_gradient(beta, q, k, v, g, do):
        out, transposed = jax.vjp(kda.kda, q, k, v, g, beta)
        return (*transposed(do), out)

    _, states, inverses = jax.jit(scalar_forward)(beta, q, k, v, g)
    forms = {
        "scalar.forward": (scalar_forward, (q, k, v, g)),
        "scalar.backward": (scalar_backward,
                            (q, k, v, g, states, inverses, do)),
        "scalar.gradient": (scalar_gradient, (q, k, v, g, do)),
        "per_channel.forward": (general_forward, (q, k, v, g)),
        "per_channel.gradient": (general_gradient, (q, k, v, g, do))}
    table = {"device": device.device_kind, "seed": args.seed,
             "q": list(q.shape), "v": list(v.shape),
             "keys_a_step": kda_mosaic.keys_a_step(keys, values // keys),
             "ms": {}, "ablated_ms": {}}
    if not args.rehearse:  # a device the table does not list is an error
        with open(PEAKS) as f:
            peak = json.load(f)["devices"][device.device_kind]
        flops, nbytes = gdn_core_cost(1, seq, keys, values, WIDTH, WIDTH,
                                      CHUNK, 1)
        table["least_ms"] = 1e3 * max(flops / peak["bf16_flops_per_s"],
                                      nbytes / peak["hbm_bytes_per_s"])
    for form, (call, operands) in forms.items():
        ms = _ms(call, beta, operands, chains, calls)
        print(form, "-" if args.rehearse else f"{ms:.3f} ms", flush=True)
        if not args.rehearse:
            table["ms"][form] = ms
    got = jax.jit(scalar_gradient)(beta, q, k, v, g, do)
    want = jax.jit(general_gradient)(beta, q, k, v, g, do)
    table["largest_distance"] = dict(zip(
        ("dq", "dk", "dv", "dg", "dbeta", "o"),
        (_distance(a, b) for a, b in zip(got, want))))
    print(table["largest_distance"], flush=True)
    for piece, (name, free) in ABLATIONS.items() if args.ablate else ():
        kept = getattr(kda_mosaic, name)
        setattr(kda_mosaic, name, free)
        try:
            ms = {form: _ms(forms[form][0], beta, forms[form][1], chains,
                            calls)
                  for form in ("scalar.forward", "scalar.backward")}
        finally:
            setattr(kda_mosaic, name, kept)
        print("without", piece, "-" if args.rehearse else ms, flush=True)
        if not args.rehearse:
            table["ablated_ms"][piece] = ms
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
