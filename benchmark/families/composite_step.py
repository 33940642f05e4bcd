"""Family of the composite step: ``ps.init`` -> dense ``KVStore`` + two
``SparseEmbedding`` tables -> ``make_composite_step`` -> ``shard_batch``,
the library calls of ``chip_smoke.py``'s Wide&Deep leg at the size of the
configuration file.

The yardstick's own pieces live here: the id generator (copied from
``ps_tpu/data/synthetic.py::criteo_batches``), the plain reference (the
forward pass on ``jnp.take`` rows with no store, and one push: gradients
of those rows, duplicates summed and the row rules applied in numpy), and
the HBM byte model (copied from
``ps_tpu/ops/sparse_apply.py::hbm_bytes_model``).
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmark.harness.loop import Cell, seed_key

# Tolerance of the step-0 check, relative to the reference loss. Both sides
# compute in f32; the fused step's matmuls run at the TPU's default
# precision (bf16 passes), the reference at "highest". The tower is three
# layers deep and the loss a mean over 4096 examples: measured 4e-7 to 5e-6
# of the loss on the chip over four seeds (my chip runs, PR 24). 1/64 of a
# bf16 roundoff (6e-5) leaves 12x room and would fail a tower computed in
# bf16 throughout.
TOLERANCE = (2.0 ** -14,
             "f32 tower at default TPU matmul precision against 'highest': "
             "1/64 bf16 roundoff of the loss, 12x the largest seen")

#: per-row optimizer state scalars (ps_tpu/optim/rowwise.py)
STATE_SCALARS_PER_ROW = {"sgd": 0, "adagrad": 1}

# Tolerance of the step-0 push check: the distance between the rows the
# program wrote and the rows of the plain reference push below, over the
# length of the reference's update. The reference takes its gradient at the
# program's own arithmetic (f32, the TPU's default matmul precision), so
# only summation order differs: measured 1.2e-7 (deep table) and 8.8e-7
# (wide) on the chip; at "highest" the gradients themselves differ by 6.3%
# and 0.03% (my chip runs, PR 24). 2**-14 leaves 70x room. A dropped update
# reads 1.0, a table or an update kept in bf16 about 2e-3.
PUSH_TOLERANCE = 2.0 ** -14
#: rows read before and after step 0 that must pass through bit for bit
#: where the batch did not touch them
PROBE_ROWS = 8192


def reference_push(rule: dict, old, acc, gsum):
    """One row-wise update in numpy, written from the rules' definitions:
    SGD ``w - lr g``; row-wise Adagrad with one accumulator a row,
    ``a += mean(g^2)``, ``w -= lr g / sqrt(a + 1e-8)``. ``gsum`` holds each
    touched row once, its duplicates' gradients summed."""
    lr = rule["learning_rate"]
    if rule["name"] == "sgd":
        return old - lr * gsum
    if rule["name"] == "adagrad":
        acc = acc + np.mean(gsum * gsum, axis=-1)
        return old - lr * gsum / np.sqrt(acc + 1e-8)[:, None]
    raise ValueError(f"no reference for row optimizer {rule['name']!r}")


def criteo_pool(batch, num_dense, num_sparse, vocab, dist, seed, count):
    """``criteo_batches``: dense [B,13] f32, ids [B,26] int32, label [B]
    f32. ``dist`` chooses the ids: Zipf(a) folded into the vocabulary as
    the original draws them."""
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(count):
        dense = rng.normal(0.0, 1.0, size=(batch, num_dense)).astype(
            np.float32)
        if dist["kind"] != "zipf":
            raise ValueError(f"unknown id distribution {dist['kind']!r}")
        raw = rng.zipf(dist["a"], size=(batch, num_sparse))
        sparse = ((raw - 1) % vocab).astype(np.int32)
        logits = 0.5 * dense[:, 0] + 0.1 * (sparse[:, 0] % 7 - 3)
        label = (logits + rng.normal(0, 1, size=batch) > 0).astype(np.float32)
        pool.append({"dense": dense, "sparse": sparse, "label": label})
    return pool


def fused_apply_bytes(dim, batch_rows, state_scalars, table_itemsize=4):
    """``hbm_bytes_model``'s fused tier: read and write the touched rows
    and their state, plus the batch-sized summed grads and counts."""
    row = dim * table_itemsize + state_scalars * 4
    grad_row = (dim + 1) * 4
    return batch_rows * (2 * row + 2 * grad_row)


def build(config: dict, traffic: dict, chips: int, seed: int) -> Cell:
    import jax
    import jax.numpy as jnp

    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.kv.sparse import SparseEmbedding
    from ps_tpu.models.wide_deep import (WideDeep, WideDeepConfig, bce_loss,
                                         make_ids_fn, make_wide_deep_loss_fn)

    ps.init(backend="tpu")
    cfg = WideDeepConfig(num_dense=config["num_dense"],
                         num_sparse=config["num_sparse"],
                         per_feature_vocab=config["per_feature_vocab"],
                         embed_dim=config["embed_dim"],
                         mlp=tuple(config["mlp"]))
    per_chip = int(traffic["per_chip_batch"])
    batch = per_chip * chips
    key = seed_key(seed)
    k_dense, k_deep, k_wide = jax.random.split(key, 3)
    model = WideDeep(cfg)
    rows = (2, cfg.num_sparse, cfg.embed_dim)
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((2, cfg.num_dense)), jnp.zeros(rows),
        jnp.zeros(rows[:2] + (1,))))(k_dense)["params"]
    opt = dict(config["optimizer"])
    dense = ps.KVStore(optimizer=opt.pop("name"), placement="sharded", **opt)
    dense.init(params)
    tables = {}
    for name, k, dim in (("deep", k_deep, cfg.embed_dim), ("wide", k_wide, 1)):
        o = dict(config[f"{name}_optimizer"])
        tables[name] = SparseEmbedding(
            cfg.total_rows, dim, optimizer=o.pop("name"),
            exchange=traffic["exchange"],
            fused_apply=config["fused_apply"], **o)
        tables[name].init(k, scale=config["init_scale"])
    fused = ps.make_composite_step(dense, tables,
                                   make_wide_deep_loss_fn(model),
                                   make_ids_fn(cfg))

    pool = criteo_pool(batch, cfg.num_dense, cfg.num_sparse,
                       cfg.per_feature_vocab, traffic["ids"], seed,
                       int(traffic["pool"]))
    # distinct rows a step touches: the mean over a sample of the pool
    unique_rows = float(np.mean([
        np.unique(np.asarray(b["sparse"], np.int64)
                  + np.arange(cfg.num_sparse) * cfg.per_feature_vocab).size
        for b in pool[:16]]))

    def step(b):
        return fused(b)[0]

    @jax.jit
    def forward(params, deep_table, wide_table, b):
        gids = cfg.global_ids(b["sparse"])
        logits = model.apply({"params": params}, b["dense"],
                             jnp.take(deep_table, gids, axis=0),
                             jnp.take(wide_table, gids, axis=0))
        return bce_loss(logits, b["label"])

    @jax.jit
    def rows_and_grads(params, tabs, states, b, probe):
        """What the plain reference push needs, read before step 0 donates
        the tables: the rows the batch reads, their optimizer state, the
        loss's gradient with respect to them, and the probe rows."""
        gids = cfg.global_ids(b["sparse"])
        rows = {n: jnp.take(t, gids, axis=0) for n, t in tabs.items()}
        grads = jax.grad(lambda r: bce_loss(model.apply(
            {"params": params}, b["dense"], r["deep"], r["wide"]),
            b["label"]))(rows)
        acc = {n: jnp.take(s, gids, axis=0) for n, s in states.items()}
        return gids, rows, grads, acc, {
            n: jnp.take(t, probe, axis=0) for n, t in tabs.items()}

    probe = np.linspace(0, cfg.total_rows - 1, PROBE_ROWS).astype(np.int32)
    expected = {}

    def reference_loss(b):
        tabs = {n: t.table for n, t in tables.items()}
        states = {n: t.state() for n, t in tables.items()
                  if config[f"{n}_optimizer"]["name"] == "adagrad"}
        gids, rows, grads, acc, probed = jax.device_get(rows_and_grads(
            dense.params(), tabs, states, b, probe))
        uniq, first, inv = np.unique(gids.reshape(-1), return_index=True,
                                     return_inverse=True)
        for n, t in tables.items():
            gsum = np.zeros((uniq.size, t.dim), np.float32)
            np.add.at(gsum, inv, grads[n].reshape(-1, t.dim))
            old = rows[n].reshape(-1, t.dim)[first]
            a = acc[n].reshape(-1)[first] if n in acc else None
            expected[n] = (old, reference_push(
                config[f"{n}_optimizer"], old, a, gsum))
        expected.update(gids=gids, first=first, uniq=uniq, probed=probed)
        with jax.default_matmul_precision("highest"):
            return float(forward(dense.params(), tables["deep"].table,
                                 tables["wide"].table, b))

    def after_step0():
        """The push of step 0 against the plain reference: every touched
        row moved as the reference moves it, every probed row the batch did
        not touch is as it was."""
        uniq, detail, ok = expected["uniq"], {}, True
        untouched = ~np.isin(probe, uniq)
        for n, t in tables.items():
            # read at the batch's own ids, a shape every seed shares
            got = np.asarray(jnp.take(t.table, expected["gids"], axis=0)
                             ).reshape(-1, t.dim)[expected["first"]]
            old, want = expected[n]
            rel = float(np.linalg.norm(got - want)
                        / np.linalg.norm(want - old))
            detail[f"push_rel_diff.{n}"] = rel
            ok &= rel <= PUSH_TOLERANCE
            after = np.asarray(jnp.take(t.table, probe, axis=0))
            same = np.array_equal(after[untouched],
                                  expected["probed"][n][untouched])
            detail[f"untouched_same.{n}"] = bool(same)
            ok &= same
        detail["rows_touched"] = int(uniq.size)
        detail["rows_probed_untouched"] = int(untouched.sum())
        return {"checks": {"step0_push_matches_reference": bool(ok)},
                "detail": detail}

    def counters():
        return {"dropped_rows": sum(t.dropped_rows for t in tables.values()),
                "rows_pushed": sum(t.rows_pushed for t in tables.values())}

    facts = {
        "unique_rows_per_step": unique_rows,
        "fused_tier": tables["deep"].fused_tier,
        "hbm_floor_bytes_per_step": sum(
            fused_apply_bytes(
                t.dim, unique_rows / chips,
                STATE_SCALARS_PER_ROW[config[f"{n}_optimizer"]["name"]])
            for n, t in tables.items()),
    }
    stream = device_prefetch(itertools.cycle(pool), place=dense.shard_batch)
    return Cell(samples_per_step_per_chip=per_chip, stream=stream, step=step,
                reference_loss=reference_loss, tolerance=TOLERANCE,
                counters=counters, facts=facts, close=ps.shutdown,
                after_step0=after_step0)
