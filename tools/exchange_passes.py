"""The source side of one trip of the exchange (``ps_tpu/ops/moe.py``:
``send``, ``receive`` and their cotangents), timed alone at the Mellum
cell's shapes: 8,192 tokens of 2,304, eight picks of 64 experts on four
owners, buffers ``[4, 49152, 2304]``. On the chip only::

    chiprun --chips 1 -- python3 tools/exchange_passes.py [--parent DIR]

``--parent DIR`` times the same four passes of the ``ps_tpu/ops/moe.py`` under
``DIR`` beside them (a ``git archive`` of another commit). Beside the module's
own forms, the forms it was chosen against: ``receive`` by contiguous writes
into sorted-pair order and the one-chip ``combine`` or ``_sum_rows`` over
them, and ``send``'s runs written one owner at a time. ``--rehearse`` runs
tiny shapes on the CPU and prints no time. The result also goes to
``chiprun_out/exchange_passes.json``.
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

from ps_tpu.ops import moe  # noqa: E402

CHIPS = 4


def _module_at(root: str):
    """``ps_tpu/ops/moe.py`` under ``root``, beside this tree's."""
    spec = importlib.util.spec_from_file_location(
        "parent_moe", os.path.join(root, "ps_tpu", "ops", "moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _passes(m):
    """The four passes of module ``m``, each of (routing, x, rows, g)."""
    def trip_of(routing):
        return m._trip(routing, 0, CHIPS)

    def send_bwd(routing, x, rows, g):
        return jax.vjp(lambda x: m.send(x, trip_of(routing)), x)[1](rows)

    def receive_bwd(routing, x, rows, g):
        def run(rows, weights):
            return m.receive(rows, trip_of(routing._replace(weights=weights)))

        return jax.vjp(run, rows, routing.weights)[1](g)

    return {
        "send": lambda routing, x, rows, g: m.send(x, trip_of(routing)),
        "receive": lambda routing, x, rows, g: m.receive(
            rows, trip_of(routing)),
        "send_bwd": send_bwd, "receive_bwd": receive_bwd}


def _sorted_by_writes(rows, trip):
    """``rows`` [n, C, D] back in sorted-pair order [T * k, D] by one
    contiguous write an owner, a later owner's over an earlier one's dead
    tail."""
    pairs = trip.order.shape[0]
    out = jnp.zeros((pairs + rows.shape[1], rows.shape[-1]), rows.dtype)
    for d in range(rows.shape[0]):
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(trip.live[d, :, None], rows[d], 0), trip.start[d],
            axis=0)
    return out[:pairs]


def _receive_by_writes(routing, x, rows, g):
    trip = moe._trip(routing, 0, CHIPS)
    return moe.combine(_sorted_by_writes(rows, trip), routing)


def _receive_by_run_sums(routing, x, rows, g):
    t, k = routing.experts.shape
    trip = moe._trip(routing, 0, CHIPS)
    index = moe._WindowIndex(
        routing.order, jnp.ones(t * k, bool), routing.inverse,
        jnp.arange(t * k, dtype=jnp.int32),
        k * jnp.arange(t, dtype=jnp.int32), jnp.ones((t, k), bool))
    return moe._sum_rows(_sorted_by_writes(rows, trip), index,
                         routing.weights)


def _send_by_writes(routing, x, rows, g):
    """``send``'s buffers written one owner's run at a time into zeros,
    where the module stacks the four runs under one select."""
    trip = moe._trip(routing, 0, CHIPS)
    k = trip.here.shape[-1]
    c = trip.live.shape[1]
    in_order = jnp.pad(jnp.take(x, trip.order // k, axis=0, mode="clip"),
                       ((0, c), (0, 0)))
    out = jnp.zeros((CHIPS, c, x.shape[-1]), x.dtype)
    for d in range(CHIPS):
        run = jax.lax.dynamic_slice_in_dim(in_order, trip.start[d], c)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(trip.live[d, :, None], run, 0)[None], d, axis=0)
    return out


def _gather_alone(routing, x, rows, g):
    k = routing.experts.shape[-1]
    return jnp.take(x, routing.order // k, axis=0, mode="clip")


def _ms(fn, args, reps: int) -> float:
    run = jax.jit(fn)
    jax.block_until_ready(run(*args))
    times = []
    for _ in range(reps):
        start = time.perf_counter()
        jax.block_until_ready(run(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if not args.rehearse and device.platform != "tpu":
        print("no TPU found: a time comes from the chip", file=sys.stderr)
        return 1
    t, d, e, k = (64, 32, 16, 4) if args.rehearse else (8192, 2304, 64, 8)
    rng = np.random.default_rng(args.seed)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(d, e)) / d ** 0.5, jnp.float32)
    routing = jax.jit(lambda x, r: moe.route(x, r, k, renormalize=True))(
        x, router)
    c = moe.exchange_rows(t, k, CHIPS)
    rows = jnp.asarray(rng.normal(size=(CHIPS, c, d)), jnp.bfloat16)
    operands = (routing, x, rows, x)
    forms = {f"new.{name}": fn for name, fn in _passes(moe).items()}
    forms.update({"new.receive.by_writes_and_combine": _receive_by_writes,
                  "new.receive.by_writes_and_run_sums": _receive_by_run_sums,
                  "new.send.by_writes": _send_by_writes,
                  "gather_of_pairs_alone": _gather_alone})
    if args.parent:
        forms.update({f"parent.{name}": fn for name, fn
                      in _passes(_module_at(args.parent)).items()})
    table = {"device": device.device_kind, "tokens": t, "width": d,
             "pairs": t * k, "buffers": [CHIPS, c, d], "seed": args.seed,
             "ms": {}}
    for name, fn in forms.items():
        ms = _ms(fn, operands, 1 if args.rehearse else args.reps)
        if not args.rehearse:
            table["ms"][name] = ms
        print(name, "-" if args.rehearse else f"{ms:.3f} ms", flush=True)
    want = moe.receive(rows, moe._trip(routing, 0, CHIPS))
    for other in (_receive_by_writes, _receive_by_run_sums):
        got = other(*operands)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        table.setdefault("max_abs_difference", {})[other.__name__] = err
    assert bool(jnp.array_equal(_send_by_writes(*operands),
                                moe.send(x, moe._trip(routing, 0, CHIPS))))
    if not args.rehearse:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/exchange_passes.json", "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
