#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ps-tpu still starts on the chip.

One process drives the device path a user drives — ``ps.init(backend="tpu")``
→ ``KVStore.init`` → ``make_step`` / ``make_composite_step`` →
``shard_batch`` → step — once, at the full width of the three models the
repo trains, on however many TPU chips JAX reports (one, or the four of one
host):

1. ResNet-50, 224², bf16, per-chip batch 256, momentum — the library calls
   of ``examples/train_resnet50.py``; 2 warm-up + 5 more steps.
2. BERT-base, sequence 512, per-chip batch 32, ``attn="flash"``, LAMB —
   2 warm-up steps + 3, plus the flash kernel against the einsum attention
   of ``ps_tpu/models/bert.py`` at those shapes, forward and gradients.
3. Wide&Deep, 26 x 100k rows x 16, per-chip batch 4096 — 2 + 3 composite
   steps per exchange, then the same pushes through every ``fused_apply``
   tier, compared under the contract of ``tests/test_sparse_apply.py``.

It refuses to run anywhere but on a TPU whose ``device_kind`` has an entry in
``ps_tpu/utils/chips.py``, never catches a leg's failure, and ends with one
JSON line: ``{"ok": true, "device": {...}}``. The step times it prints are
single observations labelled with the device — not a metric; nothing is
claimed from them (the benchmark is ROADMAP.md S0's).

Run it through the chip tool: ``python3 chip_smoke.py``. Inputs and weights
come from seeds; nothing is read from disk but the repo's own code.
"""

from __future__ import annotations

import importlib.metadata
import itertools
import json
import math
import sys
import time

# jax's monitoring event names (jax/_src/dispatch.py, jax/_src/compiler.py)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

#: bf16 unit roundoff (8 bits of precision)
_BF16_U = 2.0 ** -8


def _require(ok: bool, what: str) -> None:
    """A failed check is a failed run (a raise, so ``python -O`` keeps it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileLog:
    """Counts every XLA compile request of the process and the persistent
    cache's hits and misses, from jax's own monitoring events. A request
    answered from the cache still counts: ``count`` moving after warm-up
    means the step was retraced, wherever the executable came from."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_):
        if event == _COMPILE_EVENT:
            self.count += 1
            self.seconds += seconds

    def _event(self, event: str, **_):
        if event == _CACHE_HIT_EVENT:
            self.hits += 1
        elif event == _CACHE_MISS_EVENT:
            self.misses += 1

    def snapshot(self):
        return (self.count, self.seconds, self.hits, self.misses)

    def report(self, leg: str, since) -> None:
        n, s, h, m = (a - b for a, b in zip(self.snapshot(), since))
        print(f"[{leg}] compile requests {n}, {s:.1f} s in the compiler or "
              f"the cache (persistent cache: {h} hits, {m} entries written)")


def _run_steps(leg, step_fn, batches, compiles, warmup):
    """Drive ``step_fn`` over ``batches``, blocking on each loss. Returns the
    losses; requires them finite and the steps after ``warmup`` free of
    compilation."""
    losses, seconds, ncompiles = [], [], []
    for batch in batches:
        c0 = compiles.count
        t0 = time.perf_counter()
        loss = step_fn(batch)
        loss.block_until_ready()
        seconds.append(time.perf_counter() - t0)
        ncompiles.append(compiles.count - c0)
        losses.append(float(loss))
    print(f"[{leg}] loss per step: {[round(x, 4) for x in losses]}")
    print(f"[{leg}] compile requests per step: {ncompiles}")
    print(f"[{leg}] step seconds (single observations, blocked on the "
          f"loss): {[round(x, 4) for x in seconds]}")
    _require(all(math.isfinite(x) for x in losses), f"{leg}: loss not finite")
    _require(sum(ncompiles[warmup:]) == 0,
             f"{leg}: compiled after warm-up: {ncompiles}")
    return losses


def _memory(leg):
    """Print and return each device's peak_bytes_in_use."""
    import jax

    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
    print(f"[{leg}] peak_bytes_in_use per device (live arrays; the "
          f"process's peak so far): {[round(p / 2**30, 2) for p in peaks]} "
          f"GiB")
    return peaks


def _max_diff(got, ref):
    """(max|got - ref|, max|ref|) over two pytrees of arrays, in float32."""
    import jax
    import numpy as np

    pairs = zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref))
    err = scale = 0.0
    for g, r in pairs:
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        _require(bool(np.isfinite(g).all()), "non-finite value")
        err = max(err, float(np.max(np.abs(g - r))))
        scale = max(scale, float(np.max(np.abs(r))))
    return err, scale


# -- leg 1: ResNet-50 ---------------------------------------------------------


def resnet_leg(compiles, *, image_size=224, per_chip_batch=256,
               dtype="bfloat16", warmup=2, steps=5):
    import jax
    import jax.numpy as jnp

    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch, threaded_source
    from ps_tpu.data.synthetic import imagenet_batches
    from ps_tpu.models.resnet import ResNet50, make_loss_fn
    from ps_tpu.parallel.sharding import replicated

    leg = "resnet50"
    since = compiles.snapshot()
    ctx = ps.init(backend="tpu")
    ndev = len(jax.devices())
    batch_size = per_chip_batch * ndev
    placement = "sharded" if ndev > 1 else "replicated"

    model = ResNet50(dtype=jnp.dtype(dtype))
    variables = model.init(
        jax.random.key(0), jnp.zeros((2, image_size, image_size, 3)),
        train=False,
    )
    params, model_state = variables["params"], variables["batch_stats"]
    model_state = jax.device_put(model_state, replicated(ctx.mesh))
    store = ps.KVStore(optimizer="momentum", learning_rate=0.1, momentum=0.9,
                       placement=placement)
    store.init(params)
    run = store.make_step(make_loss_fn(model, label_smoothing=0.1),
                          has_aux=True)
    print(f"[{leg}] {image_size}x{image_size} {dtype}, global batch "
          f"{batch_size} on {ndev} device(s), placement={placement}, "
          f"mesh {dict(ctx.mesh.shape)}")

    # two seeded batches, cycled: pure-noise images carry nothing to learn,
    # so a falling loss has to come from fitting batches seen before — which
    # is what shows the gradient reached the server apply with the right sign
    distinct = list(imagenet_batches(batch_size, image_size=image_size,
                                     seed=0, steps=2))
    source = itertools.islice(itertools.cycle(distinct), warmup + steps)
    stream = device_prefetch(threaded_source(source), place=store.shard_batch)

    last = {"model_state": model_state}

    def step(batch):
        loss, _, last["model_state"] = run(batch, last["model_state"])
        last["batch"] = batch
        return loss

    losses = _run_steps(leg, step, stream, compiles, warmup)
    jax.block_until_ready(store.params())
    _require(losses[-1] < losses[warmup],
             f"{leg}: loss did not fall over the timed steps: {losses}")

    ca = run.cost_analysis(last["batch"], last["model_state"])
    flops = ca.get("flops") if ca else None
    print(f"[{leg}] live cost analysis: "
          + (f"{flops:.4e} flops/step" if flops else f"no flops ({ca!r})"))

    if ndev > 1:
        params_kv = store._engine.get_tree_and_state()[0]
        key = max((k for k, v in params_kv.items()
                   if not v.sharding.is_fully_replicated),
                  key=lambda k: params_kv[k].size)
        leaf = params_kv[key]
        momentum = [x for x in jax.tree_util.tree_leaves(
            store.optimizer_state(key)) if x.shape == leaf.shape]
        _require(len(momentum) == 1, f"{leg}: momentum of {key} not found")
        for name, x in (("param", leaf), ("momentum", momentum[0])):
            devs = {s.device for s in x.addressable_shards}
            print(f"[{leg}] {name} {key} {x.shape}: shards of "
                  f"{x.addressable_shards[0].data.shape} on devices "
                  f"{sorted(d.id for d in devs)}")
            _require(len(devs) == ndev,
                     f"{leg}: {name} {key} sits on {len(devs)} devices")
        text = run.compiled_text(last["batch"], last["model_state"])
        found = {op: op in text for op in ("reduce-scatter", "all-gather")}
        print(f"[{leg}] collectives in the compiled step: {found}")
        _require(all(found.values()),
                 f"{leg}: sharded step lacks a collective: {found}")
    compiles.report(leg, since)
    # everything this leg placed went through the store, so the chips must
    # peak alike: a global batch or a whole model left on the first chip of
    # four doubles its peak. (The later legs build their references on the
    # first chip on purpose, so there the peaks are only printed.)
    peaks = _memory(leg)
    _require(max(peaks) <= 2 * min(peaks),
             f"{leg}: peak memory uneven across devices: {peaks}")
    ps.shutdown()
    return bool(flops)  # did the live (pre-compile) cost analysis give flops


# -- leg 2: BERT-base with the flash kernel -----------------------------------


def flash_parity(*, batch=32, seq=512):
    """The Pallas kernel against the einsum attention of models/bert.py:
    the same SelfAttention parameters and seeded input through both ``attn``
    settings, forward and gradients, under a padding mask that really pads.
    Then the kernel at the tiles it chooses from its shapes against itself
    at forced 128-wide blocks, and the causal call on fewer K/V heads
    (LFM2's 4:1 at head 64) against an f32 einsum attention on repeated
    K and V, output and all three gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_tpu.models.bert import BertConfig, SelfAttention
    from ps_tpu.ops import flash_attention

    leg = "bert"
    rng = np.random.default_rng(0)
    width = BertConfig().hidden_size  # 12 heads x 64
    x = jnp.asarray(rng.standard_normal((batch, seq, width)), jnp.bfloat16)
    # row b keeps seq - b*seq/(2*batch) tokens: row 0 is full, the last row
    # about half — so whole key blocks are masked and others are cut mid-way
    lengths = seq - (seq // (2 * batch)) * np.arange(batch)
    mask = jnp.asarray(np.arange(seq)[None, :] < lengths[:, None], jnp.int32)
    modules = {attn: SelfAttention(BertConfig(dtype=jnp.bfloat16, attn=attn))
               for attn in ("full", "flash")}
    params = modules["full"].init(jax.random.key(1), x, mask)

    def value_and_grads(module):
        def fn(p, x):
            out = module.apply(p, x, mask)
            return jnp.sum(jnp.square(out.astype(jnp.float32))), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1), has_aux=True))(params, x)
        return {"out": out, "dparams": grads[0], "dx": grads[1]}

    ref, got = value_and_grads(modules["full"]), value_and_grads(
        modules["flash"])
    # Tolerance: both paths compute in bf16 (unit roundoff u = 2**-8) but
    # round in different places — the einsum path rounds the scores and the
    # probabilities to bf16, the kernel keeps the scores in f32 and rounds
    # only the probabilities — so they agree to a few u of the largest
    # entry, not bitwise. A masking or block-indexing bug is off by O(1).
    for name in ("out", "dx", "dparams"):
        err, scale = _max_diff(got[name], ref[name])
        print(f"[{leg}] flash vs einsum attention, {name}: max|diff| "
              f"{err:.3e} = {err / (_BF16_U * scale):.2f} u x max|ref| "
              f"({scale:.3e})")
        _require(err <= 8 * _BF16_U * scale,
                 f"flash {name} differs from the einsum path by "
                 f"{err / (_BF16_U * scale):.1f} u x max|ref| (bound 8)")

    # the tiles forward_tiles chooses from the shapes (sequence-wide here)
    # against forced 128-wide blocks: same math, other tiling, so only the
    # f32 accumulation order and the bf16 rounding of the probabilities
    # differ — 2 u of the largest entry
    from ps_tpu.ops.flash_attention import forward_tiles

    q, k, v = (jnp.asarray(rng.standard_normal((batch, seq, 12, 64)),
                           jnp.bfloat16) for _ in range(3))
    tiles = forward_tiles(seq, 64, q.dtype.itemsize, False)
    chosen = flash_attention(q, k, v, mask=mask)
    narrow = flash_attention(q, k, v, mask=mask, block_q=128, block_k=128)
    err, scale = _max_diff(chosen, narrow)
    print(f"[{leg}] flash at its chosen {tiles} blocks vs 128-wide: "
          f"max|diff| {err:.3e} = {err / (_BF16_U * scale):.2f} u x max|ref|")
    _require(err <= 2 * _BF16_U * scale,
             f"flash at its chosen {tiles} blocks differs from 128-wide "
             f"blocks")

    # causal, four query heads to a K/V head, several tiles a head in both
    # backward calls: dk and dv are summed over the group inside the
    # kernel. The kernel rounds p and dS to bf16 and each gradient once
    # more: 4 u of the largest entry against f32 on the same inputs.
    gq, gseq, heads, kv_heads = 4, 2048, 8, 2
    q = jnp.asarray(rng.standard_normal((gq, gseq, heads, 64)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.standard_normal((gq, gseq, kv_heads, 64)),
                        jnp.bfloat16) for _ in range(2))

    def einsum_attention(q, k, v):
        k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / 8.0
        s = jnp.where(jnp.tril(jnp.ones((gseq, gseq), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision="highest")

    def out_and_grads(attn, *args):
        def fn(q, k, v):
            out = attn(q, k, v).astype(jnp.float32)
            return jnp.sum(jnp.square(out)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1, 2), has_aux=True))(*args)
        return (out,) + grads

    got = out_and_grads(lambda q, k, v: flash_attention(q, k, v, causal=True),
                        q, k, v)
    want = out_and_grads(einsum_attention,
                         *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        err, scale = _max_diff(g, w)
        print(f"[{leg}] causal flash on {kv_heads} K/V heads for {heads} vs "
              f"f32 einsum, {name}: max|diff| {err:.3e} = "
              f"{err / (_BF16_U * scale):.2f} u x max|ref|")
        _require(err <= 4 * _BF16_U * scale,
                 f"grouped causal flash {name} differs from the f32 einsum "
                 f"by {err / (_BF16_U * scale):.1f} u x max|ref| (bound 4)")


def bert_leg(compiles, *, seq_len=512, per_chip_batch=32, num_layers=12,
             warmup=2, steps=3):
    import jax
    import jax.numpy as jnp

    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.data.synthetic import mlm_batches
    from ps_tpu.models.bert import BertConfig, BertMLM, make_mlm_loss_fn

    leg = "bert"
    since = compiles.snapshot()
    ps.init(backend="tpu")
    ndev = len(jax.devices())
    batch_size = per_chip_batch * ndev
    cfg = BertConfig(dtype=jnp.bfloat16, attn="flash", num_layers=num_layers)
    model = BertMLM(cfg)
    shape = (2, seq_len)
    params = model.init(jax.random.key(0), jnp.zeros(shape, jnp.int32),
                        jnp.ones(shape, jnp.int32))["params"]
    store = ps.KVStore(optimizer="lamb", learning_rate=1e-3,
                       weight_decay=0.01,
                       placement="sharded" if ndev > 1 else "replicated")
    store.init(params)
    run = store.make_step(make_mlm_loss_fn(model))
    print(f"[{leg}] BERT-base ({num_layers} layers) seq {seq_len} bf16 "
          f"attn=flash, global batch {batch_size} on {ndev} device(s), LAMB")
    stream = device_prefetch(
        mlm_batches(batch_size, seq_len, vocab_size=cfg.vocab_size, seed=0,
                    steps=warmup + steps),
        place=store.shard_batch)
    last = {}

    def step(batch):
        last["batch"] = batch
        return run(batch)[0]

    _run_steps(leg, step, stream, compiles, warmup)
    jax.block_until_ready(store.params())
    # the kernel must have gone through Mosaic, not the interpreter
    text = run.compiled_text(last["batch"])
    _require("tpu_custom_call" in text,
             f"{leg}: no Mosaic custom call in the compiled step")
    flash_parity(batch=per_chip_batch, seq=seq_len)
    compiles.report(leg, since)
    _memory(leg)
    ps.shutdown()


# -- leg 3: Wide&Deep ---------------------------------------------------------


def _widedeep_steps(compiles, cfg, batch_size, exchange, warmup, steps):
    """Composite steps through examples/train_widedeep.py's library calls."""
    import jax
    import jax.numpy as jnp

    import ps_tpu as ps
    from ps_tpu.data.prefetch import device_prefetch
    from ps_tpu.data.synthetic import criteo_batches
    from ps_tpu.kv.sparse import SparseEmbedding
    from ps_tpu.models.wide_deep import (WideDeep, make_ids_fn,
                                         make_wide_deep_loss_fn)

    leg = f"widedeep/{exchange}"
    model = WideDeep(cfg)
    batch0 = next(criteo_batches(2, vocab_size=cfg.per_feature_vocab))
    rows_shape = (2, cfg.num_sparse, cfg.embed_dim)
    params = model.init(
        jax.random.key(0), jnp.asarray(batch0["dense"]),
        jnp.zeros(rows_shape), jnp.zeros(rows_shape[:2] + (1,)),
    )["params"]
    dense = ps.KVStore(optimizer="adam", learning_rate=1e-3,
                       placement="sharded")
    dense.init(params)
    deep = SparseEmbedding(cfg.total_rows, cfg.embed_dim, optimizer="adagrad",
                           learning_rate=0.05, exchange=exchange)
    deep.init(jax.random.key(1), scale=0.01)
    wide = SparseEmbedding(cfg.total_rows, 1, optimizer="sgd",
                           learning_rate=0.05, exchange=exchange)
    wide.init(jax.random.key(2), scale=0.01)
    run = ps.make_composite_step(
        dense, {"deep": deep, "wide": wide},
        make_wide_deep_loss_fn(model), make_ids_fn(cfg))
    print(f"[{leg}] {cfg.num_sparse} x {cfg.per_feature_vocab} rows x "
          f"{cfg.embed_dim}, global batch {batch_size}, adagrad deep table "
          f"+ sgd dim-1 wide table, fused_apply tier {deep.fused_tier!r}")
    stream = device_prefetch(
        criteo_batches(batch_size, vocab_size=cfg.per_feature_vocab, seed=0,
                       steps=warmup + steps),
        place=dense.shard_batch)
    _run_steps(leg, lambda batch: run(batch)[0], stream, compiles, warmup)
    jax.block_until_ready((dense.params(), deep.table, wide.table))
    for name, emb in (("deep", deep), ("wide", wide)):
        print(f"[{leg}] {name}: dropped_rows {emb.dropped_rows} of "
              f"{emb.rows_pushed} pushed")
        _require(emb.exchange != "gather" or emb.dropped_rows == 0,
                 f"{leg}: the lossless exchange dropped rows")


def _tier_parity(cfg, batch_size, pushes=3):
    """The same pushed ids through every fused_apply tier on the chip, under
    the contract tests/test_sparse_apply.py states: 'jax' equals the masked
    full-table apply ('off') bitwise for SGD and Adagrad, and within 1e-6
    relative for Adam."""
    import jax
    import numpy as np

    from ps_tpu.data.synthetic import criteo_batches
    from ps_tpu.kv.sparse import SparseEmbedding
    from ps_tpu.ops.sparse_apply import TIERS

    leg = "widedeep/tiers"
    rng = np.random.default_rng(0)
    id_lists = [np.asarray(cfg.global_ids(b["sparse"])).reshape(-1)
                for b in criteo_batches(batch_size,
                                        vocab_size=cfg.per_feature_vocab,
                                        seed=1, steps=pushes)]
    print(f"[{leg}] {pushes} pushes of {id_lists[0].size} ids "
          f"({np.unique(id_lists[0]).size} distinct in the first)")
    for optimizer, dim in (("adagrad", cfg.embed_dim), ("sgd", 1),
                           ("adam", cfg.embed_dim)):
        table0 = (0.01 * rng.standard_normal((cfg.total_rows, dim))
                  ).astype(np.float32)
        grads = [(0.01 * rng.standard_normal((ids.size, dim))
                  ).astype(np.float32) for ids in id_lists]
        results = {}
        for tier in TIERS:
            emb = SparseEmbedding(cfg.total_rows, dim, optimizer=optimizer,
                                  learning_rate=0.05, fused_apply=tier)
            emb.init(table0)
            seconds = []
            for ids, g in zip(id_lists, grads):
                t0 = time.perf_counter()
                emb.push(ids, g)
                jax.block_until_ready(emb.table)
                seconds.append(round(time.perf_counter() - t0, 4))
            print(f"[{leg}] {optimizer} dim {dim} tier {tier!r}: push "
                  f"seconds (first compiles; single observations) {seconds}")
            # the table first, then the per-row optimizer state's leaves
            results[tier] = [np.asarray(x) for x in jax.tree_util.tree_leaves(
                (emb.table, emb.state()))]
        base = results["off"]
        _require(not np.array_equal(base[0][:cfg.total_rows], table0),
                 f"{leg}: {optimizer} pushes left the table untouched")
        for tier in (t for t in TIERS if t != "off"):
            if optimizer in ("sgd", "adagrad"):
                _require(all(np.array_equal(a, b)
                             for a, b in zip(results[tier], base)),
                         f"{leg}: {optimizer} tier {tier!r} is not bitwise "
                         f"equal to 'off'")
                print(f"[{leg}] {optimizer} tier {tier!r} == 'off' bitwise "
                      f"(table and state)")
            else:
                for a, b in zip(results[tier], base):
                    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
                print(f"[{leg}] {optimizer} tier {tier!r} == 'off' within "
                      f"1e-6 relative")


def widedeep_leg(compiles, *, per_feature_vocab=100_000, embed_dim=16,
                 per_chip_batch=4096, warmup=2, steps=3):
    import jax

    import ps_tpu as ps
    from ps_tpu.models.wide_deep import WideDeepConfig

    since = compiles.snapshot()
    ps.init(backend="tpu")
    ndev = len(jax.devices())
    cfg = WideDeepConfig(per_feature_vocab=per_feature_vocab,
                         embed_dim=embed_dim)
    batch_size = per_chip_batch * ndev
    # on one chip 'a2a' degenerates to the gather path (kv/sparse.py): the
    # capacity-bounded exchange only exists between chips
    for exchange in ("gather", "a2a") if ndev > 1 else ("gather",):
        _widedeep_steps(compiles, cfg, batch_size, exchange, warmup, steps)
    _tier_parity(cfg, batch_size)
    compiles.report("widedeep", since)
    _memory("widedeep")
    ps.shutdown()


# -- entry --------------------------------------------------------------------


def main() -> int:
    import jax

    # before any backend exists: a chip that cannot be opened is an error
    # here, never a CPU run of the full-size models
    jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found: jax reports platform "
              f"{dev.platform!r} ({dev.device_kind!r})", file=sys.stderr)
        return 1

    from ps_tpu.utils.chips import peak_bf16_tflops

    peak = peak_bf16_tflops(dev)  # raises on a device_kind without an entry
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {device}, table peak {peak} bf16 TFLOP/s")
    print(f"versions: jax {jax.__version__}, jaxlib "
          f"{importlib.metadata.version('jaxlib')}, libtpu "
          f"{importlib.metadata.version('libtpu')}")

    compiles = CompileLog()
    t0 = time.perf_counter()
    live_cost_analysis = resnet_leg(compiles)
    print(f"compile cache directory: {jax.config.jax_compilation_cache_dir}")
    bert_leg(compiles)
    widedeep_leg(compiles)
    print(f"total: {time.perf_counter() - t0:.1f} s on {device}; compile "
          f"requests {compiles.count}, {compiles.seconds:.1f} s compiling or "
          f"loading; persistent cache {compiles.hits} hits, "
          f"{compiles.misses} entries written; live cost analysis: "
          f"{live_cost_analysis}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
