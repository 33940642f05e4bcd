"""The phases inside the fused steps and the spans on the host path into
them: ``ps_tpu/obs/phases.py``, ``Tracer.program_span``, and the benchmark's
readers ``benchmark/layer_metrics/scope.py``, ``host.py``, ``setup.py`` and,
of the scopes a decoder's loss opens, ``decoder.py``; then the benchmark's
command rehearsing a cell and what its manifest lists for one.

A tiny ``make_step`` and a tiny ``make_composite_step`` on the virtual mesh
stand for the real ones: the scopes and spans are written in the step
builders, not in the models.
"""

import contextlib
import functools
import importlib
import json
import os
import re
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ps_tpu as ps
from benchmark.check import check_pace
from benchmark.harness import tracered
from benchmark.layer_metrics import decoder, host, pace, scope, step
from benchmark.layer_metrics import kernel as kernel_metrics
from benchmark.layer_metrics import ouro as ouro_metrics
from benchmark.layer_metrics import setup as setup_metrics
from ps_tpu import obs
from ps_tpu.data.prefetch import device_prefetch, threaded_source
from ps_tpu.kv.sparse import SparseEmbedding
from ps_tpu.obs import pace as pace_account
from ps_tpu.obs import phases

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_METADATA = re.compile(r',?\s*metadata=\{(?:[^{}"]|"[^"]*")*\}')
#: the module's tables of source files, functions and stack frames
_DEBUG_TABLES = re.compile(r"^FileNames\n.*?(?=^(?:%|ENTRY))", re.M | re.S)
BATCH, ROWS, DIM = 16, 64, 4


def _loaded():
    return jax.devices()[0].client.live_executables()


def _dense_step():
    """``(run, batch)`` of a tiny ``KVStore.make_step``."""
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="momentum", learning_rate=0.1,
                       placement="sharded")
    store.init({"w": jnp.ones((8, 8)), "b": jnp.zeros((8,))})

    def loss_fn(params, batch):
        return jnp.mean(_sin(batch @ params["w"] + params["b"]) ** 2)

    return store.make_step(loss_fn), store.shard_batch(jnp.ones((BATCH, 8)))


@jax.custom_vjp
def _sin(x):
    """A backward rule of its own, as the flash attention has: JAX names
    its ops ``transpose(ps.grad)/jvp(..)``, not ``transpose(jvp(..))``."""
    return jnp.sin(x)


_sin.defvjp(lambda x: (jnp.sin(x), x), lambda x, g: (g * jnp.cos(x),))


def _composite_step():
    """``(run, batch)`` of a tiny ``make_composite_step`` with one table."""
    ps.init(backend="tpu")
    dense = ps.KVStore(optimizer="adam", learning_rate=0.01,
                       placement="sharded")
    dense.init({"w": jnp.ones((DIM, 1))})
    emb = SparseEmbedding(ROWS, DIM, optimizer="adagrad", learning_rate=0.05)
    emb.init(jax.random.key(1))

    def loss_fn(params, rows, batch):
        return jnp.mean((rows["emb"] @ params["w"] - batch["y"]) ** 2)

    run = ps.make_composite_step(dense, {"emb": emb}, loss_fn,
                                 lambda batch: {"emb": batch["ids"]})
    ids = (np.arange(BATCH, dtype=np.int32) * 5) % ROWS
    ids[1] = ids[0]  # a duplicate row, so the dedupe has work
    return run, dense.shard_batch({"ids": ids,
                                   "y": np.ones((BATCH, 1), np.float32)})


BUILDERS = {"make_step": _dense_step, "make_composite_step": _composite_step}
PHASES_OF = {
    "make_step": (phases.GRAD, phases.APPLY),
    "make_composite_step": phases.DEVICE_PHASES,
}


def _step_hlo(kind):
    """The optimized HLO of the step's executable, found as the benchmark
    finds it: among the executables that its first call loaded."""
    run, batch = BUILDERS[kind]()
    before = _loaded()
    run(batch)
    texts = [m.to_string() for e in _loaded() if e not in before
             for m in e.hlo_modules()]
    ps.shutdown()
    return max(texts, key=len)


@pytest.fixture
def no_compile_cache():
    """The persistent compile cache off: its key leaves metadata out, so a
    step compiled with the scopes would be served for the one without (and a
    cache written by an older tree for either)."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_step_hlo_carries_every_phase(kind, no_compile_cache):
    op_names = list(scope.op_names_of(_step_hlo(kind)).values())
    for phase in PHASES_OF[kind]:
        assert any(phase in n for n in op_names), phase
    grad = [n for n in op_names if phases.GRAD in n]
    assert any(phases.BACKWARD_MARK in n for n in grad)
    assert any(phases.BACKWARD_MARK not in n for n in grad)
    # what the reader makes of them: every phase it reports has an op
    found = {scope.phase_of(n) for n in op_names}
    assert {"forward", "backward", "apply"} <= {p for p, _ in found}
    if kind == "make_step":  # the custom_vjp rule's cos is a backward op
        rule = [n for n in op_names if n.endswith("/cos")]
        assert rule and all(scope.phase_of(n)[0] == "backward" for n in rule)
    if kind == "make_composite_step":
        assert {(p, c) for p, c in found if p == "row_apply"} >= {
            ("row_apply", c) for c in (
                phases.ROW_EXCHANGE, phases.ROW_DEDUPE, phases.ROW_GATHER,
                phases.ROW_UPDATE, phases.ROW_SCATTER)}
        assert ("lookup", None) in found


def _without_metadata(hlo_text):
    return _DEBUG_TABLES.sub("", _METADATA.sub("", hlo_text))


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_marks_change_no_instruction(kind, monkeypatch, no_compile_cache):
    marked = _step_hlo(kind)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _step_hlo(kind)
    assert phases.GRAD in marked and phases.GRAD not in bare
    assert "ENTRY" in _without_metadata(marked)
    assert _without_metadata(marked) == _without_metadata(bare)


#: the scopes the program opens that no reader has a name for yet (PERF.md
#: section 7): Phi-4-mini-flash's cross core and combine, inside
#: decoder.attn_ms, and its gmu, with the gradient's rest. A reader that
#: gives one a name takes nothing from these tests
UNREAD = {phases.GMU, phases.ATTN_CROSS, phases.ATTN_DIFF}


def _readers_scopes():
    """Every scope or mark some reader of ``layer_metrics/`` turns into a
    metric, whichever table holds it: ``decoder.py``'s scopes and, while it
    keeps them apart, its finer marks; ``ouro.py``'s two; the selective
    scan's, ``kernel.py``'s until the one reader has it."""
    return (set(decoder.METRICS) | set(getattr(decoder, "MARKS", ()))
            | set(ouro_metrics.MARKS)
            | ({kernel_metrics.S6} if hasattr(kernel_metrics, "S6")
               else set()))


def _read_as(mark):
    """The scope of ``decoder.py``'s table that takes the events under the
    program's ``mark``: itself where the table has it, else the scope around
    it by its name, ``None`` (the gradient's rest) where the table has
    neither."""
    if mark in decoder.METRICS:
        return mark
    return decoder.outer_of(mark)


def test_program_and_benchmark_share_their_names():
    assert set(phases.DEVICE_PHASES) == set(scope.DEVICE_PHASES)
    assert phases.BACKWARD_MARK == scope.BACKWARD_MARK
    assert set(phases.HOST_SPANS) == set(host.HOST_SPANS)
    for name in ("GRAD", "APPLY", "LOOKUP", "ROW_APPLY", "ROW_EXCHANGE",
                 "ROW_DEDUPE", "ROW_GATHER", "ROW_UPDATE", "ROW_SCATTER"):
        assert getattr(phases, name) == getattr(scope, name)
    for name in ("STEP_RUN", "STEP_LAUNCH", "INPUT_PLACE",
                 "INPUT_SOURCE_WAIT", "INPUT_PRODUCE"):
        assert getattr(phases, name) == getattr(host, name)
    # the decoders' scopes: every name the one reader copies is the
    # program's, every scope it has a metric for is one a model opens (the
    # twelve ``*_SCOPES``), and every scope a model opens has a reader in
    # ``layer_metrics/`` but the three of ``UNREAD``, whichever table of
    # which reader holds it (``_readers_scopes``)
    copied = [name for name in vars(decoder)
              if name.isupper() and hasattr(phases, name)]
    assert len(copied) >= 21
    for name in copied:
        assert getattr(phases, name) == getattr(decoder, name), name
    assert set(decoder.SCOPES) == set(decoder.METRICS)
    families = [name for name in vars(phases) if name.endswith("_SCOPES")]
    opened = set().union(*(getattr(phases, name) for name in families))
    assert len(families) == 12
    assert (ouro_metrics.LOOP, ouro_metrics.EXIT) == (phases.LOOP,
                                                      phases.EXIT)
    assert set(ouro_metrics.MARKS) == {phases.LOOP, phases.EXIT}
    # a reader for every scope a model opens but the three named above, and
    # no reader's name for a scope that no model opens
    assert set(decoder.METRICS) <= opened and UNREAD <= opened
    assert opened - UNREAD <= _readers_scopes() <= opened
    assert not opened & set(phases.DEVICE_PHASES)
    # every span the program records has a metric that reads it
    ring = [_span(name, 10.0, f"s{i}", "s0" if name == host.STEP_LAUNCH
                  else None, nbytes=1)
            for i, name in enumerate(phases.HOST_SPANS)]
    for i, name in enumerate(phases.HOST_SPANS):
        rest = [s for s in ring if s.name != name]
        assert host.span_metrics(rest) != host.span_metrics(ring), name


def _expert_step():
    """``(run, batch)`` of ``make_step(has_aux=True)`` on a tiny OLMoE."""
    from ps_tpu.models import olmoe

    cfg = olmoe.OlmoeConfig(
        vocab_size=64, hidden_size=32, intermediate_size=16,
        num_hidden_layers=1, num_attention_heads=2, num_experts=4,
        num_experts_per_tok=2, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: olmoe.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 17, dtype=np.int32).reshape(8, 17) * 7) % 64
    return (store.make_step(olmoe.make_loss_fn(cfg), has_aux=True),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_expert_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """The scopes the model opens inside its loss nest under ``ps.grad``,
    each with forward and backward ops, and the benchmark's reader finds
    them (its names are the program's:
    ``test_program_and_benchmark_share_their_names``)."""
    monkeypatch.setitem(BUILDERS, "expert", _expert_step)
    names = scope.op_names_of(_step_hlo("expert"))
    for s in phases.MOE_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
        # under ps.grad: scope.py counts them as forward or backward
        assert {scope.phase_of(n)[0] for n in under} == {"forward",
                                                         "backward"}, s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == set(phases.MOE_SCOPES) | {None}
    assert decoder.scope_of("%fusion.7", "jit(f)/ps.apply/mul") is None


def _hybrid_step():
    """``(run, batch, bias)`` of ``make_step(has_aux=True)`` on a tiny
    LFM2: a dense conv layer, an attention and a conv layer with experts,
    two of eight held."""
    from ps_tpu.models import lfm2

    cfg = lfm2.Lfm2Config(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, router_width=8,
        num_experts=2, expert_start=2, num_experts_per_tok=2,
        dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: lfm2.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 17, dtype=np.int32).reshape(8, 17) * 7) % 64
    step = store.make_step(lfm2.make_loss_fn(cfg), has_aux=True)
    bias = lfm2.init_expert_bias(cfg)
    return (lambda batch: step(batch, bias),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_hybrid_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What LFM2 adds to the scopes (``ps.conv``, ``ps.conv/gate``,
    ``ps.ffn``) and the six it shares with OLMoE: each in the lowered step's
    ``op_name``s under ``ps.grad``, forward and backward, the expert
    layer's also inside its recomputation; the reader finds each."""
    assert phases.LFM2_SCOPES[:6] == phases.MOE_SCOPES
    monkeypatch.setitem(BUILDERS, "hybrid", _hybrid_step)
    names = scope.op_names_of(_step_hlo("hybrid"))
    for s in phases.LFM2_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == set(phases.LFM2_SCOPES) | {None}
    # the gate is the innermost scope of its ops, and the mixer's holds it
    assert decoder.scope_of(
        "%fusion.1", "jit(f)/ps.grad/jvp(ps.conv)/ps.conv/gate/mul") \
        == phases.CONV_GATE
    assert decoder.outer_of(phases.CONV_GATE) == phases.CONV


def _kimi_step():
    """``(run, batch)`` of ``make_step(has_aux=True)`` on a tiny Kimi-Linear:
    a dense KDA layer, a KDA and a latent-attention layer with experts, two
    of eight held, beside the shared one."""
    from ps_tpu.models import kimi_linear

    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=3, kda_layers=(1, 2),
        full_attn_layers=(3,), kda_num_heads=2, kda_head_dim=16,
        gate_low_rank=8, num_attention_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        router_width=8, num_experts=2, expert_start=2,
        num_experts_per_token=2, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: kimi_linear.init_params(k, cfg))(
        jax.random.key(0)))
    # 64 tokens: one chunk of the rule
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    step = store.make_step(kimi_linear.make_loss_fn(cfg), has_aux=True)
    bias = kimi_linear.init_expert_bias(cfg)
    return (lambda batch: step(batch, bias),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_kimi_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Kimi-Linear adds to the scopes (``ps.kda``, ``ps.kda/conv``,
    ``ps.kda/core``, ``ps.moe/shared``) beside the six it shares with OLMoE
    and ``ps.ffn``, and the three of its latent layer
    (``models/blocks.py::mla_block``: ``ps.attn/full``, ``ps.attn/latent``,
    ``ps.attn/rope``): each in the lowered step's ``op_name``s under
    ``ps.grad``, forward and backward; the reader finds each it has a name
    for."""
    assert phases.KIMI_SCOPES[:6] == phases.MOE_SCOPES
    monkeypatch.setitem(BUILDERS, "kimi", _kimi_step)
    names = scope.op_names_of(_step_hlo("kimi"))
    for s in phases.KIMI_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.KIMI_SCOPES) & set(decoder.METRICS)) | {None}
    # the latent layer's projections and the making of the 192-wide q and k
    # count in decoder.attn_ms, as the attention's own
    for s in (phases.ATTN_LATENT, phases.ATTN_ROPE):
        inner = {decoder.scope_of("%x", n) for n in names.values() if s in n}
        assert inner == {_read_as(s)} and phases.ATTN in (
            _read_as(s), decoder.outer_of(s)), s
    # the rule and the taps are the innermost scopes of their ops, and the
    # mixer's holds them; the shared expert is not the routed ones'
    for inner in (phases.KDA_CORE, phases.KDA_CONV):
        assert decoder.scope_of(
            "%fusion.1", f"jit(f)/ps.grad/jvp(ps.kda)/checkpoint/{inner}/mul"
        ) == inner
        assert decoder.outer_of(inner) == phases.KDA
    assert decoder.scope_of(
        "%fusion.2", "jit(f)/ps.grad/jvp(ps.moe/shared)/dot_general") \
        == phases.MOE_SHARED


def _nemotron_step():
    """``(run, batch)`` of ``make_step(has_aux=True)`` on a tiny Nemotron-H:
    a Mamba-2 layer, an expert layer with two of eight held in a latent, an
    attention layer."""
    from ps_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3,
        hybrid_override_pattern="ME*", mamba_num_heads=2, mamba_head_dim=8,
        n_groups=1, ssm_state_size=8, chunk_size=32, num_attention_heads=2,
        num_key_value_heads=1, head_dim=16, router_width=8,
        n_routed_experts=2, expert_start=2, num_experts_per_tok=3,
        moe_latent_size=16, moe_intermediate_size=12,
        moe_shared_expert_intermediate_size=24, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: nemotron_h.init_params(k, cfg))(
        jax.random.key(0)))
    # 64 tokens: two chunks of the scan
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    step = store.make_step(nemotron_h.make_loss_fn(cfg), has_aux=True)
    bias = nemotron_h.init_expert_bias(cfg)
    return (lambda batch: step(batch, bias),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_nemotron_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Nemotron-H adds to the scopes (``ps.mamba``, ``ps.mamba/conv``,
    ``ps.mamba/ssd``, ``ps.mamba/gate``, ``ps.moe/latent``) beside the six it
    shares with OLMoE and Kimi-Linear's ``ps.moe/shared``: each in the
    lowered step's ``op_name``s under ``ps.grad``, forward and backward,
    though every layer is under a ``jax.checkpoint``; the reader finds each
    but the gate's, which it reads as the mixer's."""
    assert phases.NEMOTRON_SCOPES[:6] == phases.MOE_SCOPES
    monkeypatch.setitem(BUILDERS, "nemotron", _nemotron_step)
    names = scope.op_names_of(_step_hlo("nemotron"))
    for s in phases.NEMOTRON_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.NEMOTRON_SCOPES) & set(decoder.METRICS)) | {None}
    assert {decoder.scope_of("%x", n) for n in names.values()
            if phases.MAMBA_GATE in n} == {_read_as(phases.MAMBA_GATE)}
    assert decoder.outer_of(phases.MAMBA_GATE) == phases.MAMBA
    # the scan and the filter are the innermost scopes of their ops, and the
    # mixer's holds them; the latent projections are not the routed experts'
    for inner in (phases.MAMBA_SSD, phases.MAMBA_CONV):
        assert decoder.scope_of(
            "%fusion.1",
            f"jit(f)/ps.grad/jvp()/checkpoint/ps.mamba/{inner}/mul") == inner
        assert decoder.outer_of(inner) == phases.MAMBA
    assert decoder.scope_of(
        "%fusion.2", "jit(f)/ps.grad/jvp()/checkpoint/ps.moe/latent/dot") \
        == phases.MOE_LATENT


def _trinity_step():
    """``(run, batch)`` of ``make_step(has_aux=True)`` on a tiny Trinity: a
    dense layer and an expert layer with two of eight held, the first seeing
    a window of 16 keys, the second every earlier key."""
    from ps_tpu.models import trinity

    cfg = trinity.TrinityConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, sliding_window=16, router_width=8, num_experts=2,
        expert_start=2, num_experts_per_tok=3, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: trinity.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    step = store.make_step(trinity.make_loss_fn(cfg), has_aux=True)
    bias = trinity.init_expert_bias(cfg)
    return (lambda batch: step(batch, bias),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_trinity_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Trinity adds to the scopes (``ps.attn/window``, ``ps.attn/full``,
    ``ps.attn/gate``, all inside ``ps.attn``) beside the six it shares with
    OLMoE, LFM2's ``ps.ffn`` and Kimi-Linear's ``ps.moe/shared``: each in the
    lowered step's ``op_name``s under ``ps.grad``, forward and backward,
    though every layer is under a ``jax.checkpoint``; the reader finds
    each."""
    assert phases.TRINITY_SCOPES[:6] == phases.MOE_SCOPES
    monkeypatch.setitem(BUILDERS, "trinity", _trinity_step)
    names = scope.op_names_of(_step_hlo("trinity"))
    for s in phases.TRINITY_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == set(phases.TRINITY_SCOPES) | {None}
    # the cores and the gate are the innermost scopes of their ops, and the
    # attention's holds them
    for inner in (phases.ATTN_WINDOW, phases.ATTN_FULL, phases.ATTN_GATE):
        assert decoder.scope_of(
            "%fusion.1",
            f"jit(f)/ps.grad/jvp()/checkpoint/ps.attn/{inner}/mul") == inner
        assert decoder.outer_of(inner) == phases.ATTN
    assert decoder.scope_of(
        "%fusion.2", "jit(f)/ps.grad/jvp()/checkpoint/ps.attn/dot") \
        == phases.ATTN


# -- scope.py on a hand-made result ------------------------------------------

def _ev(own, opcode="fusion", shape="f32[8]"):
    # as long as a real event's name: an HLO line with its operands
    return (f"{own} = {shape} {opcode}(%p0), kind=kLoop, "
            "calls=%fused_computation, " + "backend_config={} " * 8)


_OPS = {  # seconds over 2 traced steps, on each of 2 chips
    _ev("%fwd"): 0.007,
    _ev("%fwd_transpose"): 0.003,  # the primitive, not the transform
    _ev("%bwd"): 0.012,
    _ev("%rule_of_custom_vjp"): 0.008,
    _ev("%upd"): 0.002,
    _ev("%take"): 0.003,
    _ev("%sort"): 0.004,
    _ev("%rows"): 0.005,
    _ev("%put"): 0.006,
    _ev("%rule"): 0.001,
    _ev("%all-reduce.1", "all-reduce"): 0.008,
    _ev("%all-gather.2", "all-gather"): 0.0005,
    _ev("%bare"): 0.0015,    # an instruction XLA made without metadata
    _ev("%alien"): 0.0005,   # not in any marked module
}
_OP_NAMES = {
    "%fwd": "jit(fused)/ps.grad/jvp(M)/dot_general",
    "%bwd": "jit(fused)/ps.grad/transpose(jvp(M))/dot_general",
    "%rule_of_custom_vjp": "jit(fused)/ps.grad/transpose(ps.grad)/jvp(M)/exp",
    "%fwd_transpose": "jit(fused)/ps.grad/jvp(M)/transpose",
    "%upd": "jit(fused)/ps.apply/sub",
    "%take": "jit(fused)/ps.lookup/jit(_take)/gather",
    "%sort": "jit(fused)/ps.row_apply/shard_map/ps.row_apply/dedupe/sort",
    "%rows": "jit(fused)/ps.row_apply/shard_map/ps.row_apply/gather/gather",
    "%put": "jit(fused)/ps.row_apply/shard_map/ps.row_apply/scatter/scatter",
    "%rule": "jit(fused)/ps.row_apply/shard_map/ps.row_apply/update/mul",
    "%all-reduce.1": "jit(fused)/ps.grad/transpose(jvp(M))/psum",
    "%all-gather.2": "jit(fused)/ps.apply/all_gather",
    "%bare": "",
}


def _result(chips=2):
    devices = {f"/device:TPU:{i}": {"ops": dict(_OPS)} for i in range(chips)}
    return {"trace": {"devices": devices}, "traced_steps": 2, "chips": chips}


def test_scope_phases_and_rest_add_up_to_the_busy_time():
    out = scope.phase_times(_result(), _OP_NAMES)
    busy_ms = 1e3 * sum(_OPS.values()) / 2
    rest_ms = out["scope.unattributed_share"] / 100.0 * busy_ms
    assert rest_ms == pytest.approx(1e3 * (0.0015 + 0.0005) / 2)
    total = sum(out[m] for m in scope.PHASE_METRICS.values()) + rest_ms
    assert total == pytest.approx(busy_ms)
    assert out["scope.forward_ms"] == pytest.approx(5.0)
    assert out["scope.backward_ms"] == pytest.approx(14.0)  # with its psum
    assert out["scope.apply_ms"] == pytest.approx(1.25)
    assert out["scope.lookup_ms"] == pytest.approx(1.5)
    assert out["scope.row_apply_ms"] == pytest.approx(8.0)
    assert out["scope.row_dedupe_ms"] == pytest.approx(2.0)
    assert out["scope.row_gather_ms"] == pytest.approx(2.5)
    assert out["scope.row_scatter_ms"] == pytest.approx(3.0)


def test_scope_splits_collectives_by_phase(capsys):
    out = scope.phase_times(_result(), _OP_NAMES)
    assert out["scope.collective_backward_ms"] == pytest.approx(4.0)
    assert out["scope.collective_apply_ms"] == pytest.approx(0.25)
    assert out["scope.collective_forward_ms"] == 0.0
    assert "collective time with no phase" in capsys.readouterr().err
    assert not any("collective" in k
                   for k in scope.phase_times(_result(chips=1), _OP_NAMES))


def test_scope_op_without_op_name_is_unattributed(capsys):
    out = scope.phase_times(_result(), _OP_NAMES)
    assert out["scope.unattributed_share"] == pytest.approx(
        100.0 * 0.002 / sum(_OPS.values()))
    listed = capsys.readouterr().err.split("scope: unattributed")[1]
    assert "%bare" in listed and "[<no op_name>]" in listed
    assert "%alien" in listed and "[<not in a marked executable>]" in listed
    assert "%fwd" not in listed


def test_scope_without_any_mark_says_so(capsys):
    out = scope.phase_times(_result(), None)
    assert out["scope.unattributed_share"] == pytest.approx(100.0)
    assert all(out[m] == 0.0 for m in scope.PHASE_METRICS.values())
    err = capsys.readouterr().err
    assert "no loaded executable carries a phase mark" in err
    assert ".jax_cache" in err
    assert scope.phase_times({"trace": None, "traced_steps": 0}, None) == {}


def test_scope_reads_marks_from_the_loaded_executables(capsys):
    run, batch = _dense_step()
    run(batch)
    names = scope.loaded_op_names()
    assert names and any(phases.APPLY in v for v in names.values())
    assert all(k.startswith("%") for k in names)
    assert "carry a phase mark" in capsys.readouterr().err


def test_scope_two_marked_executables_that_disagree(capsys):
    """An instruction name is unique within one module only: where two
    marked modules put one name in different phases, neither decides."""
    step = {"%fusion.1": "jit(a)/ps.grad/jvp(M)/dot_general",
            "%fusion.2": "jit(a)/ps.apply/sub",
            "%copy.3": ""}
    push = {"%fusion.1": "jit(b)/ps.row_apply/ps.row_apply/scatter/scatter",
            "%fusion.2": "jit(b)/ps.apply/mul",   # the same phase: no clash
            "%fusion.9": "jit(b)/ps.row_apply/ps.row_apply/gather/gather"}
    pool = {"%fusion.2": "jit(make_pool)/iota", "%fusion.7": ""}  # no mark
    for order in ((step, push, pool), (pool, push, step)):
        names = scope.merge_marked(order)
        assert names["%fusion.1"] == scope.CLASH
        assert scope.phase_of(names["%fusion.1"]) == (None, None)
        assert scope.phase_of(names["%fusion.2"])[0] == "apply"
        assert scope.phase_of(names["%fusion.9"])[0] == "row_apply"
        assert "%fusion.7" not in names
        err = capsys.readouterr().err
        assert "2 loaded executable(s)" in err and "%fusion.1" in err
    assert scope.merge_marked([pool]) is None
    # and the time of such an op is shown as unattributed, with the reason
    r = {"trace": {"devices": {"d0": {"ops": {_ev("%fusion.1"): 0.002,
                                              _ev("%fusion.2"): 0.006}}}},
         "traced_steps": 1, "chips": 1}
    out = scope.phase_times(r, scope.merge_marked([step, push]))
    assert out["scope.unattributed_share"] == pytest.approx(25.0)
    assert out["scope.apply_ms"] == pytest.approx(6.0)
    assert f"[{scope.CLASH}]" in capsys.readouterr().err


# -- decoder.py on a decoder's hand-made result ------------------------------

_CALL = 'custom_call_target="tpu_custom_call"'
_JVP = "jit(f)/ps.grad/jvp({})/"
_CP = "jit(f)/ps.grad/jvp()/checkpoint/"
_BACK = "jit(f)/ps.grad/transpose(jvp())/"
_RULE = "jit(f)/ps.grad/transpose(ps.grad)/checkpoint/"
_LOOPED = "jit(f)/ps.grad/jvp()/while/body/{}/"
_TARGETS = {"kernel_targets": ["tpu_custom_call"]}
#: what every decoder's step has beside its scopes: the head, the embedding
#: (under ps.grad, in no scope) and the apply
_REST = [("%ce", "fusion", 0.005, "jit(f)/ps.grad/jvp(ps.head)/reduce"),
         ("%embed", "fusion", 0.001, "jit(f)/ps.grad/jvp()/gather"),
         ("%adam", "fusion", 0.007, "jit(f)/ps.apply/mul")]
#: the expert layer of the five that hold a share: 0.5 ms of routing, 2 of
#: dispatch and combine, 4 in the grouped matmul, a Mosaic call under its
#: scope as every chip run has carried it since PR 47
_EXPERTS = [("%route", "fusion", 0.001, _CP + "ps.moe/route/dot"),
            ("%rows", "fusion", 0.003,
             "jit(f)/ps.grad/checkpoint/ps.moe/dispatch/gather"),
            ("%back", "fusion", 0.001, _BACK + "ps.moe/combine/gather"),
            ("%gmm", "custom-call", 0.008,
             _CP + "ps.moe/expert/pallas_call")]
_HELD = {"live_pairs_per_step": 1000.0, "load_max_over_mean": 3.0,
         "dropped_tokens": 0.0}
#: 1000 live pairs of 1e6 FLOPs in expert_ms 4: a quarter of the MXU's peak
_EXPERTS_WANT = {"decoder.route_ms": 0.5, "decoder.dispatch_ms": 2.0,
                 "decoder.expert_ms": 4.0, "decoder.expert_mxu_share": 25.0,
                 "decoder.head_ms": 2.5, "decoder.load_max_over_mean": 3.0,
                 "decoder.dropped_tokens": 0.0}
_ROWS, _SIZES = "bf16[4,96,8]", "s32[4,2]"
_EXCHANGE = _CP + "ps.moe/combine/ps.moe/exchange/all_to_all"

#: a decoder's hand-made result under the one set of keys (PERF.md section 4,
#: "How a drawn decoder joins"): its events (name, opcode, seconds in two
#: traced steps, op_name and, for a collective, its shape) on each of
#: ``chips`` (one unless said; ``on_chip`` has what a chip reads otherwise),
#: its facts and counters, and what ``decoder.read`` and ``step.read`` make
#: of them: every number the cell's own reader was held to before PR 49
#: merged the six. The next decoder is one more case.
READER_CASES = {
    "olmoe": {   # no share held: the pairs a step are a fact
        "events": [
            ("%route", "fusion", 0.002,
             _JVP.format("ps.moe/route") + "dot_general"),
            ("%sorted", "fusion", 0.001,
             _JVP.format("ps.moe/dispatch") + "gather"),
            ("%back", "fusion", 0.003,
             "jit(f)/ps.grad/transpose(jvp(ps.moe/combine))/gather"),
            ("%gmm", "custom-call", 0.020,
             _JVP.format("ps.moe/expert") + "pallas_call"),
            ("%flash", "custom-call", 0.010,
             _JVP.format("ps.attn") + "pallas_call"),
            ("%qkv", "fusion", 0.004,
             "jit(f)/ps.grad/transpose(jvp(ps.attn))/dot_general"),
            ("%ce", "fusion", 0.006, "jit(f)/ps.grad/jvp(ps.head)/reduce"),
            ("%embed", "fusion", 0.0005, "jit(f)/ps.grad/jvp()/gather"),
            ("%adam", "fusion", 0.007, "jit(f)/ps.apply/mul")],
        "facts": {**_TARGETS, "dense_flops_per_step": 4.5e9,
                  "flops_per_pair": 0.5e6, "live_pairs_per_step": 1000.0,
                  "flash_flops": 1e9, "flash_bytes": 1.0},
        "counters": {"load_max_over_mean": 7.25, "dropped_tokens": 0.0},
        "want": {"decoder.route_ms": 1.0,
                 "decoder.dispatch_ms": 2.0,           # with combine
                 "decoder.expert_ms": 10.0, "decoder.attn_ms": 7.0,
                 "decoder.head_ms": 3.0,
                 "decoder.expert_mxu_share": 5.0,      # 0.5 of 10 ms
                 "kernel.flash_roofline": 20.0,        # 1 of 5 ms
                 "decoder.load_max_over_mean": 7.25,
                 "decoder.dropped_tokens": 0.0},
        "mfu": 5.0, "device_ms": 26.75},
    "lfm2": {
        "events": [
            ("%in", "fusion", 0.004, _JVP.format("ps.conv") + "dot_general"),
            ("%gate", "fusion", 0.002,
             "jit(f)/ps.grad/transpose(jvp(ps.conv))/ps.conv/gate/m"),
            ("%swiglu", "fusion", 0.006,
             _JVP.format("ps.ffn") + "dot_general"),
            ("%flash", "custom-call", 0.010,
             _JVP.format("ps.attn") + "pallas_call"),
            ("%qkv", "fusion", 0.004,
             "jit(f)/ps.grad/transpose(jvp(ps.attn))/dot_general"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "conv_gate_bytes_per_step": 0.5e9,
                  "flash_flops": 1e9, "flash_bytes": 1.0},
        "counters": {**_HELD, "held_pair_share": 0.125},
        "want": {**_EXPERTS_WANT,
                 "decoder.conv_ms": 3.0,               # with its gate
                 "decoder.conv_gate_ms": 1.0, "decoder.dense_ffn_ms": 3.0,
                 "decoder.attn_ms": 7.0,
                 "decoder.conv_gate_hbm_share": 50.0,
                 "kernel.flash_roofline": 20.0,        # 1 of 5 ms
                 "decoder.held_pair_share": 0.125},
        "mfu": 5.0, "device_ms": 26.0},
    "kimi": {
        "events": [
            ("%qkv", "fusion", 0.004, _JVP.format("ps.kda") + "dot_general"),
            ("%taps", "fusion", 0.002,
             _JVP.format("ps.kda") + "checkpoint/ps.kda/conv/mul"),
            ("%scan", "fusion", 0.010,
             "jit(f)/ps.grad/transpose(jvp(ps.kda))/checkpoint/"
             "ps.kda/core/while/body/dot_general"),
            ("%swiglu", "fusion", 0.006,
             _JVP.format("ps.ffn") + "dot_general"),
            ("%shared", "fusion", 0.002,
             _JVP.format("ps.moe/shared") + "dot_general"),
            ("%flash", "custom-call", 0.010,
             _JVP.format("ps.attn") + "pallas_call"),
            ("%latent", "fusion", 0.004,
             "jit(f)/ps.grad/transpose(jvp(ps.attn))/dot_general"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "kda_core_flops": 1.0,
                  "kda_core_bytes": 1e9, "flash_flops": 2e9,
                  "flash_bytes": 1.0},
        "counters": {**_HELD, "held_pair_share": 0.03125},
        "want": {**_EXPERTS_WANT,
                 "decoder.kda_ms": 8.0,                # with taps and rule
                 "decoder.kda_conv_ms": 1.0, "decoder.kda_core_ms": 5.0,
                 "decoder.dense_ffn_ms": 3.0, "decoder.shared_ffn_ms": 1.0,
                 "decoder.attn_ms": 7.0,               # the latent attention
                 "kernel.kda_core_roofline": 20.0,     # 1 of 5 ms
                 "kernel.flash_roofline": 40.0,        # 2 of 5 ms
                 "decoder.held_pair_share": 0.03125},
        "mfu": 5.0, "device_ms": 32.0},
    "nemotron": {
        "events": [
            ("%in_proj", "fusion", 0.004, _CP + "ps.mamba/dot"),
            ("%taps", "fusion", 0.002, _CP + "ps.mamba/ps.mamba/conv/mul"),
            ("%scan", "fusion", 0.010,
             "jit(f)/ps.grad/transpose(jvp())/checkpoint/ps.mamba/"
             "ps.mamba/ssd/while/body/dot_general"),
            ("%down", "fusion", 0.003, _CP + "ps.moe/latent/dot"),
            ("%shared", "fusion", 0.002, _CP + "ps.moe/shared/dot"),
            ("%flash", "custom-call", 0.010, _CP + "ps.attn/pallas_call"),
            ("%qkv", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "ssd_flops": 1.0, "ssd_bytes": 1e9,
                  "flash_flops": 2e9, "flash_bytes": 1.0},
        "counters": {**_HELD, "held_pair_share": 0.015625},
        "want": {**_EXPERTS_WANT,
                 "decoder.mamba_ms": 8.0,              # with filter and scan
                 "decoder.mamba_conv_ms": 1.0, "decoder.ssd_ms": 5.0,
                 "decoder.latent_ms": 1.5, "decoder.shared_ffn_ms": 1.0,
                 "decoder.attn_ms": 7.0,
                 "kernel.ssd_roofline": 20.0,          # 1 of 5 ms
                 "kernel.flash_roofline": 40.0,        # 2 of 5 ms
                 "decoder.held_pair_share": 0.015625},
        "mfu": 5.0, "device_ms": 30.5},
    "trinity": {
        "events": [
            ("%qkv", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            ("%gate", "fusion", 0.002, _CP + "ps.attn/ps.attn/gate/mul"),
            ("%pack", "fusion", 0.001,
             _CP + "ps.attn/ps.attn/window/transpose"),
            ("%band", "custom-call", 0.008,
             _CP + "ps.attn/ps.attn/window/pallas_call"),
            ("%triangle", "custom-call", 0.010,
             _RULE + "ps.attn/ps.attn/full/pallas_call"),
            ("%dense", "fusion", 0.003, _CP + "ps.ffn/dot"),
            ("%shared", "fusion", 0.002, _CP + "ps.moe/shared/dot"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "window_flash_flops": 1e9,
                  "window_flash_bytes": 1.0, "flash_flops": 1.0,
                  "flash_bytes": 2e9, "window_live_step_share": 0.284},
        "counters": {**_HELD, "held_pair_share": 0.125},
        "want": {**_EXPERTS_WANT,
                 "decoder.attn_ms": 12.5,              # cores and gate in
                 "decoder.window_core_ms": 4.5, "decoder.full_core_ms": 5.0,
                 "decoder.attn_gate_ms": 1.0, "decoder.dense_ffn_ms": 1.5,
                 "decoder.shared_ffn_ms": 1.0,
                 # the kernels alone in the denominators, each kind over
                 # its own calls
                 "kernel.window_flash_roofline": 25.0,
                 "kernel.flash_roofline": 40.0,
                 "decoder.held_pair_share": 0.125},
        # a fact no family states since PR 67 (the grid it was counted from
        # went with PR 53): the reader may still know the key, or not
        "may": {"decoder.window_live_step_share": 0.284},
        "mfu": 5.0, "device_ms": 28.0},
    "mellum": {   # four chips share each layer; no share held, none dropped
        "chips": 4,
        "events": [
            ("%qkv", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            ("%pack", "fusion", 0.001,
             _CP + "ps.attn/ps.attn/window/transpose"),
            ("%band", "custom-call", 0.008,
             _CP + "ps.attn/ps.attn/window/pallas_call"),
            ("%triangle", "custom-call", 0.010,
             _RULE + "ps.attn/ps.attn/full/pallas_call"),
            ("%all-to-all.1", "all-to-all", 0.004,
             _CP + "ps.moe/dispatch/ps.moe/exchange/all_to_all", _ROWS),
            ("%all-to-all.2", "all-to-all", 0.0035, _EXCHANGE, _ROWS),
            # the group sizes' exchange and a further trip's small buffer
            ("%all-to-all.3", "all-to-all", 0.00025, _EXCHANGE, _SIZES),
            ("%all-to-all.4", "all-to-all", 0.00025, _EXCHANGE,
             "bf16[4,8,8]"),
            ("%copy.9", "fusion", 0.002, _EXCHANGE),
            ("%all-gather.1", "all-gather", 0.002,
             "jit(f)/ps.apply/sharding_constraint"),
            *_EXPERTS, *_REST],
        # every chip waits for the slowest: the exchange's and the store's
        # collectives are the worst chip's, which need not be the same one
        "on_chip": {2: {"%all-to-all.1": 0.006}, 1: {"%all-gather.1": 0.003}},
        "facts": {**_TARGETS, "dense_flops_per_step": 5e9,
                  "exchange_bytes_per_row": 150.0,
                  "exchange_buffer_rows": 96, "layers": 1,
                  "window_flash_flops": 1e9, "window_flash_bytes": 1.0,
                  "flash_flops": 1.0, "flash_bytes": 2e9},
        "counters": {"exchange_rows_per_step": 1e6, "dropped_tokens": 0.0},
        "want": {"decoder.route_ms": 0.5,
                 "decoder.dispatch_ms": 2.0,           # less the exchange
                 "decoder.expert_ms": 4.0, "decoder.head_ms": 2.5,
                 "decoder.attn_ms": 11.5, "decoder.window_core_ms": 4.5,
                 "decoder.full_core_ms": 5.0,
                 "decoder.exchange_ms": 6.0,           # with its copy
                 "decoder.exchange_exposed_ms": 5.0,
                 "decoder.store_collective_ms": 1.5,
                 # two exchanges of rows in the one layer, counted in the
                 # trace: 1e6 rows x 150 B x 2 over 1e11 B/s is 3 ms of the 6
                 "decoder.exchange_ici_share": 50.0,
                 "kernel.window_flash_roofline": 25.0,
                 "kernel.flash_roofline": 40.0,
                 "decoder.dropped_tokens": 0.0},
        "mfu": 5.0, "device_ms": 30.5},
    "sdar": {
        "events": [
            ("%qkv", "fusion", 0.004, _CP + "ps.attn/dot_general"),
            ("%clean", "custom-call", 0.006,
             _CP + "ps.attn/ps.attn/full/pallas_call"),
            ("%strict", "custom-call", 0.004,
             _CP + "ps.attn/ps.attn/full/pallas_call"),
            ("%dkv", "custom-call", 0.010,
             _RULE + "ps.attn/ps.attn/full/pallas_call"),
            ("%pack", "fusion", 0.002,
             _CP + "ps.attn/ps.attn/full/transpose"),
            # the program's own scope, no metric of its own: inside attn_ms
            ("%own", "fusion", 0.003, _CP + "ps.attn/ps.attn/inblock/reduce"),
            ("%merge", "fusion", 0.001,
             _RULE + "ps.attn/ps.attn/inblock/exp"),
            ("%route", "fusion", 0.002, _CP + "ps.moe/route/dot"),
            ("%rows", "fusion", 0.003, _CP + "ps.moe/dispatch/gather"),
            ("%back", "fusion", 0.001, _CP + "ps.moe/combine/gather"),
            ("%gmm", "custom-call", 0.008, _CP + "ps.moe/expert/pallas_call"),
            ("%ce", "fusion", 0.004, "jit(f)/ps.grad/jvp(ps.head)/reduce"),
            ("%embed", "fusion", 0.001, "jit(f)/ps.grad/jvp()/gather"),
            ("%adam", "fusion", 0.005, "jit(f)/ps.apply/mul")],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "flash_flops": 2e9,
                  "flash_bytes": 1.0},
        "counters": {"dropped_tokens": 0.0, "live_pairs_per_step": 1000.0,
                     "held_pair_share": 0.125, "load_max_over_mean": 6.5,
                     "masked_share": 0.5},
        "want": {"decoder.attn_ms": 15.0,     # the cores and the own blocks in
                 "decoder.full_core_ms": 11.0,  # the kernels and the packing
                 "decoder.route_ms": 1.0, "decoder.dispatch_ms": 2.0,
                 "decoder.expert_ms": 4.0, "decoder.head_ms": 2.0,
                 "decoder.expert_mxu_share": 25.0,      # 1 of 4 ms
                 # 2 ms of MXU over the three Mosaic calls' 10 ms
                 "kernel.flash_roofline": 20.0,
                 "decoder.held_pair_share": 0.125,
                 "decoder.load_max_over_mean": 6.5,
                 "decoder.dropped_tokens": 0.0,
                 # the own blocks' scope under a name of its own (PR 67),
                 # the counter as it stands
                 "decoder.inblock_ms": 2.0, "decoder.masked_share": 0.5},
        "mfu": 5.0, "device_ms": 27.0},
    "joyai": {   # six latent layers, one of them in the prediction module
        "events": [
            ("%latent", "fusion", 0.004,
             _CP + "ps.attn/ps.attn/latent/dot_general"),
            ("%roll", "fusion", 0.002,
             _CP + "ps.attn/ps.attn/rope/jit(_roll_static)/slice"),
            ("%fwd", "custom-call", 0.006,
             _CP + "ps.attn/ps.attn/full/pallas_call"),
            ("%dkv", "custom-call", 0.008,
             _RULE + "ps.attn/ps.attn/full/pallas_call"),
            ("%out", "fusion", 0.002, _BACK + "ps.attn/dot_general"),
            ("%dense", "fusion", 0.003, _CP + "ps.ffn/dot"),
            ("%shared", "fusion", 0.002, _CP + "ps.moe/shared/dot"),
            # the module: its own scope around everything, the inner scopes
            # under their own names, the join under none the reader knows
            ("%join", "fusion", 0.002,
             _JVP.format("ps.mtp") + "ps.mtp/join/dot_general"),
            ("%mtp_latent", "fusion", 0.002,
             "jit(f)/ps.grad/transpose(jvp(ps.mtp))/jvp(ps.mtp)/checkpoint/"
             "rematted_computation/ps.attn/ps.attn/latent/dot_general"),
            ("%mtp_fwd", "custom-call", 0.006,
             _JVP.format("ps.mtp") + "ps.attn/ps.attn/full/pallas_call"),
            ("%mtp_head", "fusion", 0.003,
             "jit(f)/ps.grad/transpose(jvp(ps.mtp))/ps.head/jvp(ps.mtp)/"
             "ps.head/checkpoint/rematted_computation/dot_general"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "flash_flops": 2e9,
                  "flash_bytes": 1.0},
        "counters": {**_HELD, "held_pair_share": 0.0625, "ce": 9.5,
                     "mtp_ce": 9.75, "mtp_positions": 8191.0},
        "want": {**_EXPERTS_WANT,
                 "decoder.attn_ms": 15.0,     # latent, rope and the cores in
                 "decoder.full_core_ms": 10.0,
                 "decoder.head_ms": 4.0,      # both passes
                 "decoder.dense_ffn_ms": 1.5, "decoder.shared_ffn_ms": 1.0,
                 # 2 ms of MXU over the three Mosaic calls' 10 ms
                 "kernel.flash_roofline": 20.0,
                 "decoder.held_pair_share": 0.0625,
                 # the four finer scopes the program opens, each under a
                 # name of its own (PR 67): the module whole, by its mark
                 # around its own attention, experts and head pass
                 "decoder.mtp_ms": 6.5, "decoder.mtp_join_ms": 1.0,
                 "decoder.attn_latent_ms": 3.0, "decoder.attn_rope_ms": 1.0},
        # what the reader may come to say of this result without this file
        # changing: the step's other counters as they stand
        "may": {"decoder.ce": 9.5, "decoder.mtp_ce": 9.75,
                "decoder.mtp_positions": 8191.0},
        "mfu": 5.0, "device_ms": 33.0},
    "qwen3_next": {   # three delta-rule layers and one gated attention layer
        "events": [
            ("%qkvz", "fusion", 0.004, _CP + "ps.kda/dot_general"),
            ("%taps", "custom-call", 0.002,
             _CP + "ps.kda/ps.kda/conv/pallas_call"),
            # the broadcast of the decay and the repeat of the key heads are
            # under the rule's scope with its two Mosaic calls
            ("%spread", "fusion", 0.002,
             _CP + "ps.kda/ps.kda/core/broadcast_in_dim"),
            ("%rule", "custom-call", 0.008,
             _RULE + "ps.kda/ps.kda/core/pallas_call"),
            ("%q", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            ("%turn", "fusion", 0.001, _CP + "ps.attn/ps.attn/rope/mul"),
            ("%fwd", "custom-call", 0.004,
             _CP + "ps.attn/ps.attn/full/pallas_call"),
            ("%dkv", "custom-call", 0.006,
             _RULE + "ps.attn/ps.attn/full/pallas_call"),
            ("%gate", "fusion", 0.002, _CP + "ps.attn/ps.attn/gate/mul"),
            # the shared expert with its own sigmoid gate
            ("%shared", "fusion", 0.002, _CP + "ps.moe/shared/logistic"),
            *_EXPERTS, *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 4e9,
                  "flops_per_pair": 1e6, "kda_core_flops": 1.0,
                  "kda_core_bytes": 0.5e9, "flash_flops": 2e9,
                  "flash_bytes": 1.0},
        "counters": {**_HELD, "held_pair_share": 0.0625},
        "want": {**_EXPERTS_WANT,
                 "decoder.kda_ms": 8.0,        # with taps, spread and rule
                 "decoder.kda_conv_ms": 1.0, "decoder.kda_core_ms": 5.0,
                 "decoder.attn_ms": 8.5,       # rope, cores and gate in it
                 "decoder.full_core_ms": 5.0, "decoder.attn_gate_ms": 1.0,
                 "decoder.shared_ffn_ms": 1.0,
                 # the scalar-decay rule's least bytes: 0.5 of 5 ms, the
                 # broadcast's time in the 5
                 "kernel.kda_core_roofline": 10.0,
                 # 2 ms of MXU over the two Mosaic calls' 5 ms; the taps'
                 # and the rule's calls lie under ps.kda and are not its
                 "kernel.flash_roofline": 40.0,
                 "decoder.held_pair_share": 0.0625,
                 "decoder.attn_rope_ms": 0.5},       # by its mark (PR 67)
        "mfu": 5.0, "device_ms": 30.5},
    "granite": {   # dense: no expert, no counter; a mixer and a SwiGLU a layer
        "events": [
            ("%in", "fusion", 0.004, _CP + "ps.mamba/dot_general"),
            ("%taps", "fusion", 0.002, _CP + "ps.mamba/ps.mamba/conv/mul"),
            # a block of heads under its own checkpoint inside the map
            ("%scan", "fusion", 0.010,
             "jit(f)/ps.grad/transpose(jvp())/checkpoint/ps.mamba/"
             "ps.mamba/ssd/while/body/checkpoint/rematted_computation/"
             "dot_general"),
            ("%gated", "fusion", 0.004, _CP + "ps.mamba/ps.mamba/gate/mul"),
            ("%flash", "custom-call", 0.010, _CP + "ps.attn/pallas_call"),
            ("%qkv", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            ("%swiglu", "fusion", 0.012, _CP + "ps.ffn/dot_general"),
            *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 5e9, "ssd_flops": 1.0,
                  "ssd_bytes": 1e9, "flash_flops": 2e9, "flash_bytes": 1.0},
        "counters": {},
        "want": {"decoder.mamba_ms": 10.0,     # with filter, scan and gate
                 "decoder.mamba_conv_ms": 1.0, "decoder.ssd_ms": 5.0,
                 "decoder.attn_ms": 7.0, "decoder.dense_ffn_ms": 6.0,
                 "decoder.head_ms": 2.5,
                 "kernel.ssd_roofline": 20.0,          # 1 of 5 ms
                 "kernel.flash_roofline": 40.0,        # 2 of 5 ms
                 "decoder.mamba_gate_ms": 2.0},        # by its mark (PR 67)
        "mfu": 5.0, "device_ms": 29.5},
    "phi4flash": {   # dense; a second half that reads the first's memory
        "events": [
            ("%in", "fusion", 0.004, _CP + "ps.mamba/dot_general"),
            ("%taps", "fusion", 0.002, _CP + "ps.mamba/ps.mamba/conv/mul"),
            # the selective scan: no name of its own yet, inside ps.mamba
            ("%s6", "fusion", 0.010,
             _BACK + "checkpoint/ps.mamba/ps.mamba/s6/while/body/mul"),
            ("%band", "custom-call", 0.002,
             _CP + "ps.attn/ps.attn/window/pallas_call"),
            ("%full", "custom-call", 0.008,
             _CP + "ps.attn/ps.attn/full/pallas_call"),
            # another layer's K and V: a flash call under ps.attn, not the
            # window's, so with the full layer's in the roofline
            ("%cross", "custom-call", 0.008,
             _RULE + "ps.attn/ps.attn/cross/pallas_call"),
            ("%diff", "fusion", 0.002, _CP + "ps.attn/ps.attn/diff/mul"),
            ("%qkv", "fusion", 0.004, _BACK + "ps.attn/dot_general"),
            # a mixer no scope of the reader's is around: the gradient's rest
            ("%gmu", "fusion", 0.006, _CP + "ps.gmu/dot_general"),
            ("%swiglu", "fusion", 0.012, _CP + "ps.ffn/dot_general"),
            *_REST],
        "facts": {**_TARGETS, "dense_flops_per_step": 5e9, "scan_flops": 1.0,
                  "scan_bytes": 1e9, "flash_flops": 2e9, "flash_bytes": 1.0,
                  "window_flash_flops": 0.25e9, "window_flash_bytes": 1.0},
        "counters": {},
        "want": {"decoder.mamba_ms": 8.0,      # with the taps and the scan
                 "decoder.mamba_conv_ms": 1.0,
                 "decoder.attn_ms": 12.0,      # three cores, combine, qkv
                 "decoder.window_core_ms": 1.0, "decoder.full_core_ms": 4.0,
                 "decoder.dense_ffn_ms": 6.0, "decoder.head_ms": 2.5,
                 "kernel.flash_roofline": 25.0,          # 2 of 4 + 4 ms
                 "kernel.window_flash_roofline": 25.0},  # 0.25 of 1 ms
        # what the one reader may come to say of this result without this
        # file changing (PERF.md section 7): the scan, which
        # ``layer_metrics/kernel.py`` reads by its mark today (its share
        # needs a peak this hand-made result has none of), and the three
        # scopes no reader has a name for yet
        "may": {"kernel.s6_ms": 5.0, "decoder.gmu_ms": 3.0,
                "decoder.cross_core_ms": 4.0, "decoder.attn_diff_ms": 1.0},
        "mfu": 5.0, "device_ms": 35.5},
    "ouro": {   # dense and looped: the passes a while loop, the head and the
        # exit beside it; a layer application under its checkpoint
        "events": [
            ("%qkv", "fusion", 0.004,
             _LOOPED.format("ps.loop/checkpoint/ps.attn") + "dot_general"),
            ("%flash", "custom-call", 0.010,
             "jit(f)/ps.grad/transpose(ps.grad)/while/body/ps.loop/"
             "checkpoint/ps.attn/pallas_call"),
            ("%swiglu", "fusion", 0.012,
             _LOOPED.format("ps.loop/checkpoint/ps.ffn") + "dot_general"),
            # norms and residuals of an application, and the final norm:
            # in the loop, in no scope of decoder.py's
            ("%post_norm", "fusion", 0.003,
             _LOOPED.format("ps.loop/checkpoint") + "rsqrt"),
            ("%final_norm", "fusion", 0.001,
             _LOOPED.format("ps.loop/checkpoint") + "mul"),
            ("%readout", "fusion", 0.006,
             _LOOPED.format("ps.head") + "while/body/checkpoint/dot_general"),
            ("%gate", "fusion", 0.001,
             _LOOPED.format("ps.exit/checkpoint") + "logistic"),
            ("%weighted", "fusion", 0.001, _JVP.format("ps.exit") + "mul"),
            ("%embed", "fusion", 0.001, "jit(f)/ps.grad/jvp()/gather"),
            ("%adam", "fusion", 0.007, "jit(f)/ps.apply/mul")],
        "facts": {**_TARGETS, "dense_flops_per_step": 5e9, "passes": 4,
                  "flash_flops": 2e9, "flash_bytes": 1.0},
        "counters": {"expected_passes": 1.875, "exit_entropy": 1.2},
        "want": {"decoder.attn_ms": 7.0, "decoder.dense_ffn_ms": 6.0,
                 "decoder.head_ms": 3.0,
                 "kernel.flash_roofline": 40.0},       # 2 of 5 ms
        # what layer_metrics/ouro.py makes of the same result: the loop
        # (attention, SwiGLU, norms and residuals, the final norm) and the
        # exit by their own marks, the two counters as they stand; loop +
        # head + exit lie under ps.grad. And, while it gives them, decoder.py's
        # three under its names (a second name for one reading: they may go)
        "ouro": {"ouro.loop_ms": 15.0, "ouro.exit_ms": 1.0,
                 "ouro.expected_passes": 1.875, "ouro.exit_entropy": 1.2},
        "ouro_may": {"ouro.attn_ms": 7.0, "ouro.dense_ffn_ms": 6.0,
                     "ouro.head_ms": 3.0},
        "mfu": 5.0, "device_ms": 23.0}}


@pytest.mark.parametrize("case", sorted(READER_CASES))
def test_the_one_reader_reads_a_decoders_hand_made_result(monkeypatch, case):
    """Through ``layer_metrics/decoder.py::read`` and ``scope_times`` with
    the one set of keys: each scope's metric by the innermost scope, a scope
    opened inside another in both, dispatch and combine as one and less the
    exchange; a kernel's roofline over the Mosaic calls under the
    attention's scope, each kind over its own (the grouped matmul's under
    ``ps.moe/expert`` in none), or over the rule's or the scan's scope; the
    shares; the counters as they stand; ``step.mfu`` from the dense part
    and the pairs; a scope the reader has no name for (``ps.attn/inblock``)
    in its outer one."""
    made = READER_CASES[case]
    chips = made.get("chips", 1)
    devices = {}
    for chip in range(chips):
        here = made.get("on_chip", {}).get(chip, {})
        devices[f"d{chip}"] = {"ops": {
            _ev(own, opcode, *shape) + (
                _CALL if opcode == "custom-call" else ""): here.get(own, sec)
            for own, opcode, sec, _, *shape in made["events"]}}
    names = {own: op_name for own, _, _, op_name, *_ in made["events"]}
    busy_s = sum(sec for _, _, sec, *_ in made["events"])
    assert 1e3 * busy_s / 2 == pytest.approx(made["device_ms"])
    facts = made["facts"]
    r = {"trace": {"devices": devices, "busy_s": busy_s},
         "traced_steps": 2, "chips": chips, "counters": made["counters"],
         "facts": facts,
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12,
                   "ici_bits_per_s": 8e11},
         "steps": 10, "window_s": 1.0}
    times = decoder.scope_times(r, names)
    want, may = made["want"], made.get("may", {})
    assert {k for k in want if k not in decoder.COUNTS.values()} \
        <= set(times) <= set(want) | set(may)
    monkeypatch.setattr(scope, "loaded_op_names", lambda: names)
    whole = decoder.read(r)
    assert set(want) <= set(whole) <= set(want) | set(may), sorted(
        set(whole) ^ set(want))
    for k, v in {**want, **may}.items():
        assert whole.get(k, v) == pytest.approx(v, rel=1e-9), k
    got = step.read(r)
    assert got["step.mfu"] == pytest.approx(made["mfu"], rel=1e-9)
    assert got["step.device_ms"] == pytest.approx(made["device_ms"], rel=1e-9)
    if "exchange_buffer_rows" in facts:
        # which events are an exchange of the first trip's rows
        assert [decoder.is_row_exchange(n, 96) for n in (
            _ev("%a", "all-to-all", _ROWS),
            _ev("%a", "all-to-all-start", _ROWS),
            _ev("%a", "all-to-all-done", _ROWS),
            _ev("%a", "all-to-all", _SIZES), _ev("%a", "fusion", _ROWS),
            _ev("%a", "all-to-all", "bf16[4,960,8]"))
        ] == [True, True, False, False, False, False]
        # a program without the rows' exchange (or whose buffers changed
        # shape), or without the counter of rows, reads no share of the
        # interconnect rather than a wrong one
        for other in ({**r, "facts": {**facts, "exchange_buffer_rows": 97}},
                      {**r, "counters": {"dropped_tokens": 0.0}}):
            assert set(times) - set(decoder.scope_times(other, names)) == {
                "decoder.exchange_ici_share"}
    if "ouro" in made:
        mine = ouro_metrics.read({**r, "decoder": whole})
        both = {**made["ouro"], **made["ouro_may"]}
        assert set(made["ouro"]) <= set(mine) <= set(both)
        assert mine == pytest.approx({k: both[k] for k in mine}, rel=1e-9)
        assert (mine["ouro.loop_ms"] + whole["decoder.head_ms"]
                + mine["ouro.exit_ms"]) <= 1e3 * busy_s / 2
        # a rehearsal lists the names and no value; a program without the
        # marks or the counters gives nothing
        listed = ouro_metrics.read({**{k: v for k, v in r.items()
                                       if k != "decoder"},
                                    "peaks": {}, "trace": None})
        assert set(listed) == set(mine) and not any(
            listed[k] for k in listed if k.endswith("_ms"))
        monkeypatch.setattr(scope, "loaded_op_names", lambda: {})
        assert ouro_metrics.read(
            {**r, "counters": {}, "decoder": {}}) == {}
        monkeypatch.setattr(scope, "loaded_op_names", lambda: names)
    # a program without the scopes or the counters: nothing to read, nothing
    # at 0
    r["trace"]["devices"] = {"d0": {"ops": {_ev("%qkv"): 0.004}}}
    assert decoder.scope_times(r, {}) == {}
    assert decoder.read({"counters": {}, "facts": {}}) == {}


# -- the program's spans -------------------------------------------------------

def _spans_since(mark, name):
    return [s for s in obs.tracer().spans()
            if s.name == name and s.t0 >= mark]


@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_each_step_leaves_a_run_and_a_launch_span(kind):
    import time

    run, batch = BUILDERS[kind]()
    mark = time.perf_counter()
    for _ in range(5):
        run(batch)
    runs = _spans_since(mark, phases.STEP_RUN)
    launches = _spans_since(mark, phases.STEP_LAUNCH)
    assert len(runs) == len(launches) == 5
    assert [s.args["step"] for s in runs] == list(range(5))
    for outer, inner in zip(runs, launches):
        assert inner.parent_id == outer.span_id and outer.parent_id is None
        assert inner.args["step"] == outer.args["step"]
        assert outer.t0 <= inner.t0
        assert inner.dur_us <= outer.dur_us
    assert obs.tracer().sample == 0.0  # recorded with sampling off
    # launched with no wait between: every launch says how many of the
    # wrapper's own steps the chip still held, the first that there was none
    assert all(isinstance(s.args[phases.IN_FLIGHT], int) for s in launches)
    assert launches[0].args[phases.IN_FLIGHT] == 0
    assert phases.DRAINED_AT_MOST_MS not in launches[0].args
    assert all(0 <= s.args[phases.IN_FLIGHT] <= i
               for i, s in enumerate(launches))


# -- the step's pace -------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BUILDERS))
def test_a_step_waited_for_leaves_the_next_launch_drained(kind):
    """``block_until_ready`` between the calls: launches 2-5 find nothing of
    their wrapper's in flight and say for how long at most, the first is
    never counted, and the counter moves by four."""
    import time

    run, batch = BUILDERS[kind]()
    before = pace_account.METERS["drained"].value
    mark = time.perf_counter()
    for _ in range(5):
        run(batch)[0].block_until_ready()
    launches = _spans_since(mark, phases.STEP_LAUNCH)
    assert [s.args[phases.IN_FLIGHT] for s in launches] == [0] * 5
    assert [phases.DRAINED_AT_MOST_MS in s.args for s in launches] \
        == [False] + [True] * 4
    assert pace_account.METERS["drained"].value - before == 4
    assert pace_account.METERS["in_flight"].value == 0
    # an upper bound: since the launch before began, so longer than from
    # that launch's return to this launch
    for prev, last in zip(launches, launches[1:]):
        since_return = last.t0 - (prev.t0 + 1e-6 * prev.dur_us)
        assert 1e3 * since_return < last.args[phases.DRAINED_AT_MOST_MS] \
            <= 1e3 * (last.t0 - mark)


class _Loss:
    """A stub loss: ready when told."""

    def __init__(self, ready=False):
        self.ready, self.asked = ready, 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def _account():
    """``(account, clock, events)``: a ``StepPace`` on a clock that a test
    sets, counting no compile, its events in a list."""
    clock = types.SimpleNamespace(now=100.0, compiles=0)
    events = []
    account = pace_account.StepPace(
        clock=lambda: clock.now, compiles=lambda: clock.compiles,
        record_event=lambda kind, **f: events.append((kind, f)))
    return account, clock, events


def _step(account, clock, loss, step, run_s=0.010, gap_s=0.0):
    """One step of ``run`` as the wrapper makes it: ``gap_s`` after the
    step before, the launch at the start, ``run_s`` long."""
    clock.now += gap_s
    t0 = clock.now
    args = account.launching()
    clock.now = t0 + run_s
    account.ran(loss, step, t0)
    return args


def test_the_account_counts_the_steps_in_flight_in_order():
    account, clock, _ = _account()
    losses = [_Loss() for _ in range(6)]
    seen = [_step(account, clock, loss, i)[phases.IN_FLIGHT]
            for i, loss in enumerate(losses[:4])]
    assert seen == [0, 1, 2, 3]
    # they finish in order: the account asks from the oldest and stops at
    # the first that is not ready, so a later one that says ready waits
    losses[0].ready = losses[2].ready = True
    asked = [loss.asked for loss in losses]
    assert _step(account, clock, losses[4], 4) == {phases.IN_FLIGHT: 3}
    assert [loss.asked - a for loss, a in zip(losses, asked)] \
        == [1, 1, 0, 0, 0, 0]
    losses[1].ready = True
    assert _step(account, clock, losses[5], 5) == {phases.IN_FLIGHT: 2}
    assert pace_account.METERS["in_flight"].value == 2
    # a loss its holder deleted is nothing to wait for
    class Deleted(_Loss):
        def is_ready(self):
            raise RuntimeError("Array has been deleted.")
    account, clock, _ = _account()
    _step(account, clock, Deleted(), 0)
    assert phases.DRAINED_AT_MOST_MS in _step(account, clock, _Loss(), 1)


def test_the_account_never_holds_more_than_its_bound():
    account, clock, _ = _account()
    seen = [_step(account, clock, _Loss(), i)[phases.IN_FLIGHT]
            for i in range(pace_account.MAX_IN_FLIGHT + 10)]
    assert max(seen) == pace_account.MAX_IN_FLIGHT == 64
    assert seen[:3] == [0, 1, 2] and seen[-1] == 64
    assert len(account._flying) == 64


def test_a_drained_launch_says_for_how_long_at_most():
    account, clock, events = _account()
    before = pace_account.METERS["drained"].value
    first = _Loss(ready=True)
    # the wrapper's first launch found nothing in flight: never drained
    assert _step(account, clock, first, 0) == {phases.IN_FLIGHT: 0}
    # 0.25 s after the first launch began, all that was launched is done:
    # the chip cannot have waited longer than since that launch began
    second = _step(account, clock, _Loss(), 1, gap_s=0.240)
    assert second == {phases.IN_FLIGHT: 0,
                      phases.DRAINED_AT_MOST_MS: pytest.approx(250.0)}
    # the second is still running at the third launch, finished at the
    # fourth: the bound runs from the third launch, the last the chip got
    third = _step(account, clock, _Loss(ready=True), 2, gap_s=0.5)
    assert third == {phases.IN_FLIGHT: 1}
    account._flying[0].ready = True
    fourth = _step(account, clock, _Loss(), 3, gap_s=0.030)
    assert fourth[phases.DRAINED_AT_MOST_MS] == pytest.approx(40.0)
    assert pace_account.METERS["drained"].value - before == 2
    assert events == []    # a job the host bounds drains every step


def test_a_slow_step_is_an_event_with_its_median():
    account, clock, events = _account()
    before = pace_account.METERS["slow"].value
    ready = _Loss(ready=True)
    # not before eight steps were seen, however long
    for i in range(8):
        _step(account, clock, ready, i, run_s=0.100 if i == 3 else 0.010)
    assert events == []
    # 8 medians exactly is not over them; a little more is
    _step(account, clock, ready, 8, run_s=0.080)
    assert events == []
    _step(account, clock, _Loss(), 9)
    _step(account, clock, ready, 10, run_s=0.0801)
    assert events == [(phases.SLOW_STEP, {
        "step": 10, "ms": 80.1, "median_ms": 10.0, "in_flight": 1})]
    # a step in which a compile landed is ``recompile``'s, and is left out
    # of the median too
    clock.now += 1.0
    t0 = clock.now
    account.launching()
    clock.compiles += 1
    clock.now += 30.0
    account.ran(ready, 11, t0)
    assert len(events) == 1 and 30.0 not in account._run_s
    assert pace_account.METERS["slow"].value - before == 1
    # under the floor of 1 ms nothing is slow: steps of 50 us, one of 0.9 ms
    account, clock, events = _account()
    for i in range(20):
        _step(account, clock, ready, i, run_s=50e-6)
    _step(account, clock, ready, 20, run_s=0.9e-3)
    assert events == []
    _step(account, clock, ready, 21, run_s=1.1e-3)
    assert [f["step"] for _, f in events] == [21]
    # the median is of the last 64
    assert account._run_s.maxlen == pace_account.MEDIAN_OF == 64


def test_a_slow_step_reaches_the_flight_recorder():
    """Through the process's own recorder, as ``recompile`` does."""
    clock = types.SimpleNamespace(now=5.0)
    account = pace_account.StepPace(clock=lambda: clock.now,
                                    compiles=lambda: 0)
    ready = _Loss(ready=True)
    for i in range(9):
        _step(account, clock, ready, 7000 + i,
              run_s=0.500 if i == 8 else 0.010)
    mine = [e for e in obs.flight().events()
            if e["kind"] == phases.SLOW_STEP and e["step"] == 7008]
    assert len(mine) == 1
    assert (mine[0]["ms"], mine[0]["median_ms"], mine[0]["in_flight"]) \
        == (500.0, 10.0, 0)


def test_program_and_benchmark_share_the_pace_names():
    for name in ("STEP_RUN", "STEP_LAUNCH", "COMPILE_BACKEND", "IN_FLIGHT",
                 "DRAINED_AT_MOST_MS"):
        assert getattr(phases, name) == getattr(pace, name), name
    for name in ("SLOW_FACTOR", "SLOW_FLOOR_S", "MEDIAN_OF", "SLOW_AFTER"):
        assert getattr(pace_account, name) == getattr(pace, name), name
    assert {m.name for m in pace_account.METERS.values()} == {
        phases.STEP_IN_FLIGHT, phases.STEP_DRAINED_LAUNCHES,
        phases.STEP_SLOW}
    text = obs.default_registry().render_prometheus()
    for name in (phases.STEP_IN_FLIGHT, phases.STEP_DRAINED_LAUNCHES,
                 phases.STEP_SLOW):
        assert name in text
    # each argument of the launch has a metric that reads it
    ring, r = check_pace.handmade()
    read = pace.span_metrics(ring, r["window"])
    for key in (phases.IN_FLIGHT, phases.DRAINED_AT_MOST_MS):
        bare = [types.SimpleNamespace(
            name=s.name, t0=s.t0, dur_us=s.dur_us,
            args={k: v for k, v in s.args.items() if k != key})
            for s in ring]
        assert pace.span_metrics(bare, r["window"]) != read, key


@pytest.mark.parametrize("check", ["check_handmade", "check_floor"])
def test_the_pace_reader_on_a_hand_made_ring(check, capsys):
    """``benchmark/check/check_pace.py``'s cases, as its command runs them:
    one long block with a drained launch, one without, one given back by
    the next."""
    getattr(check_pace, check)()
    assert "ok" in capsys.readouterr().out


def test_the_pace_reader_reads_the_process_ring(monkeypatch, capsys):
    """``pace.read`` on the ring a tiny step left: the window placed as
    ``benchmark/run.py`` places it; on a ring whose launches do not say
    (the parent's program) nothing, and no error."""
    import time

    run, batch = _dense_step()
    start = time.perf_counter()
    monkeypatch.setattr(sys.modules["__main__"], "_T_START", start,
                        raising=False)
    for _ in range(3):          # set-up
        run(batch)[0].block_until_ready()
    t_window = time.perf_counter()
    block_s = []
    for _ in range(4):
        mark = time.perf_counter()
        run(batch)
        run(batch)[0].block_until_ready()
        block_s.append(time.perf_counter() - mark)
    window_s = time.perf_counter() - t_window
    run(batch)                  # past the window
    r = {"setup_s": t_window - start, "window_s": window_s,
         "block_s": block_s}
    out = pace.read(r)
    assert set(out) == {"pace.queue_depth_min", "pace.drained_launches",
                        "pace.slow_steps", "pace.stall_host_share",
                        "pace.stall_device_share"}
    assert out["pace.queue_depth_min"] == 0
    # each block's first launch follows a wait
    # but the window's first, which the reader leaves to the loop
    assert 3 <= out["pace.drained_launches"] <= 7
    assert "pace: over the measured window" in capsys.readouterr().err
    spans = obs.tracer().spans()
    bare = [types.SimpleNamespace(name=s.name, t0=s.t0, dur_us=s.dur_us,
                                  args={"step": s.args.get("step")})
            for s in spans]
    monkeypatch.setattr(obs.tracer(), "spans", lambda: bare)
    assert pace.read(r) == {}
    # not under benchmark/run.py: the whole ring, and no block to place
    monkeypatch.setattr(obs.tracer(), "spans", lambda: spans)
    monkeypatch.delattr(sys.modules["__main__"], "_T_START")
    assert set(pace.read(r)) == {"pace.queue_depth_min",
                                 "pace.drained_launches", "pace.slow_steps"}


def test_input_spans_carry_the_batch_number_from_both_threads():
    import threading
    import time

    mark = time.perf_counter()
    batches = [{"x": np.ones((4, 8), np.float32)} for _ in range(4)]
    out = list(device_prefetch(threaded_source(iter(batches))))
    assert len(out) == 4
    produced = _spans_since(mark, phases.INPUT_PRODUCE)
    waited = _spans_since(mark, phases.INPUT_SOURCE_WAIT)
    placed = _spans_since(mark, phases.INPUT_PLACE)
    # the producer and the consumer each look once more and find the end
    assert [s.args["seq"] for s in produced] == [0, 1, 2, 3, 4]
    assert [s.args["seq"] for s in waited] == [0, 1, 2, 3, 4]
    assert [s.args["seq"] for s in placed] == [0, 1, 2, 3]
    assert {s.args["nbytes"] for s in placed} == {4 * 8 * 4}
    here = threading.get_ident()
    assert {s._tid for s in placed + waited} == {here}
    assert here not in {s._tid for s in produced}
    assert len({s.span_id for s in produced + waited + placed}) == 14


def test_program_spans_reach_the_chrome_export():
    tr = obs.Tracer(sample=0.0)
    with tr.program_span("step.run", step=3) as outer:
        with tr.program_span("step.launch", step=3):
            # off the sampled spans' stack: an op issued in here makes its
            # own sampling decision and puts no context on the wire
            assert tr.current() is None and tr.child("hop") is obs.NOOP
    assert tr.span("sampled.root") is obs.NOOP  # sampling is still off
    events = [e for e in tr.chrome_events() if e["ph"] == "X"]
    assert [e["name"] for e in events] == ["step.launch", "step.run"]
    assert events[0]["args"]["parent_id"] == outer.span_id
    assert events[0]["args"]["step"] == 3


# -- host.py on a synthetic ring ----------------------------------------------

def _span(name, dur_us, span_id, parent_id=None, t0=0.0, **args):
    return types.SimpleNamespace(name=name, dur_us=dur_us, span_id=span_id,
                                 parent_id=parent_id, t0=t0, args=args)


def test_host_metrics_from_a_synthetic_ring():
    ring = []
    for i, (run_us, launch_us) in enumerate([(900, 700), (1000, 800),
                                             (5000, 4700)]):
        ring.append(_span(host.STEP_LAUNCH, launch_us, f"l{i}", f"r{i}",
                          step=i))
        ring.append(_span(host.STEP_RUN, run_us, f"r{i}", step=i))
        ring.append(_span(host.INPUT_PLACE, 2000 + i, f"p{i}", seq=i,
                          nbytes=3_000_000))
    ring.append(_span(host.STEP_RUN, 123, "orphan", step=9))  # launch evicted
    ring.append(_span("server_apply", 1e6, "x"))              # the van's
    out = host.span_metrics(ring)
    assert out == {
        "host.step_launch_ms": pytest.approx(0.8),
        "host.step_wrap_ms": pytest.approx(0.2),
        "host.input_place_ms": pytest.approx(2.001),
        "host.input_mb_per_step": pytest.approx(3.0),
    }
    ring.append(_span(host.INPUT_SOURCE_WAIT, 40, "w0", seq=0))
    ring.append(_span(host.INPUT_PRODUCE, 70, "q0", seq=0))
    more = host.span_metrics(ring)
    assert more["host.input_source_wait_ms"] == pytest.approx(0.04)
    assert more["host.input_produce_ms"] == pytest.approx(0.07)
    assert host.span_metrics([]) == {}


def test_host_metrics_count_the_measured_window_only(monkeypatch):
    """Warm-up, the traced steps and the steps past the window started
    outside ``[start + setup_s, start + setup_s + window_s)``."""
    ring = []
    for i, t0 in enumerate([100.5, 101.9,            # warm-up
                            102.0, 103.0, 104.0,     # the window
                            105.0, 106.0]):          # traced, past it
        slow = 1 if 102.0 <= t0 < 105.0 else 50
        ring.append(_span(host.STEP_LAUNCH, 700 * slow, f"l{i}", f"r{i}",
                          t0=t0 + 1e-4, step=i))
        ring.append(_span(host.STEP_RUN, 900 * slow, f"r{i}", t0=t0, step=i))
    assert host.span_metrics(ring, (102.0, 105.0)) == {
        "host.step_launch_ms": pytest.approx(0.7),
        "host.step_wrap_ms": pytest.approx(0.2)}
    assert host.span_metrics(ring)["host.step_launch_ms"] == \
        pytest.approx(35.0)
    r = {"setup_s": 2.0, "window_s": 3.0}
    monkeypatch.setattr(sys.modules["__main__"], "_T_START", 100.0,
                        raising=False)
    assert host.window_of(r) == (102.0, 105.0)
    monkeypatch.delattr(sys.modules["__main__"], "_T_START")
    assert host.window_of(r) is None   # not under benchmark/run.py


@functools.lru_cache(maxsize=None)
def _rehearse(cell):
    """The benchmark's own command on the CPU: the result line of a traced
    rehearsal of ``cell`` (run once a process, whoever asks)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmark", "run.py"),
         "--workload", cell, "--rehearse", "--trace", "1", "--seconds", "1"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["metrics"] == {}
    return line


# -- set-up from inside the program -------------------------------------------

SETUP_METRICS = (
    "setup.before_program_s", "setup.import_s", "setup.init_s",
    "setup.store_init_s", "setup.step_trace_lower_s",
    "setup.step_compile_or_load_s", "setup.other_compile_s",
    "setup.cache_misses", "setup.unspanned_s")


def test_program_and_benchmark_share_the_set_up_names():
    assert phases.SETUP_SPANS == setup_metrics.SETUP_SPANS
    assert phases.COMPILE_SPANS == setup_metrics.COMPILE_SPANS
    for name in ("SETUP_IMPORT", "SETUP_INIT", "SETUP_STORE_INIT",
                 "SETUP_TABLE_INIT", "COMPILE_TRACE", "COMPILE_LOWER",
                 "COMPILE_BACKEND", "COMPILE_CACHE_LOAD"):
        assert getattr(phases, name) == getattr(setup_metrics, name)
    assert set(setup_metrics.PROGRAM_SPANS) == set(
        phases.SETUP_SPANS + phases.COMPILE_SPANS + phases.HOST_SPANS)
    with open(os.path.join(_REPO, "BENCHMARK.json")) as f:
        listed = [m for m in json.load(f)["per_layer"]
                  if m["name"].startswith("setup.")]
    assert tuple(m["name"] for m in listed) == SETUP_METRICS
    for m in listed:  # every cell reports setup_s, so every cell reads them
        assert "workloads" not in m and m["moves"] == "setup_s"
        assert (m["layer"], m["source"]) == ("entry", "program_span")
    # every set-up span the program records has a metric that reads it, and
    # so has each of the compiler's but the load, which is a row of the table
    ring = _set_up_ring()
    whole = setup_metrics.span_metrics(ring, 100.0, 10.0)
    for name in phases.SETUP_SPANS[1:] + phases.COMPILE_SPANS[:3]:
        rest = [s for s in ring if s.name != name]
        assert setup_metrics.span_metrics(rest, 100.0, 10.0) != whole, name
    assert setup_metrics.span_metrics(
        [s for s in ring if s.name != phases.SETUP_IMPORT], 100.0, 10.0
    ) is None


def test_the_import_of_the_package_leaves_its_span():
    """In a process of its own (this one's ring may have been cleared): the
    first span in the ring is the import's, from the package's first line,
    and the listener is in place when the import returns."""
    code = (
        "import time; t = time.perf_counter()\n"
        "import ps_tpu; from ps_tpu import obs; import jax.numpy as jnp\n"
        "first = obs.tracer().spans()[0]\n"
        "jnp.ones(3) + 1\n"
        "names = {s.name for s in obs.tracer().spans()}\n"
        "print(first.name, first.parent_id, first.t0 == ps_tpu._T_IMPORT, "
        "t <= first.t0, first.t0 + 1e-6 * first.dur_us <= "
        "time.perf_counter(), abs((time.time() - 1e-6 * first.ts_us) - "
        "(time.perf_counter() - first.t0)) < 0.05, "
        "'compile.backend' in names)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"},
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [phases.SETUP_IMPORT, "None"] + ["True"] * 5


def test_record_program_is_a_child_of_the_open_program_span():
    tr = obs.Tracer(sample=0.0)
    now = time.perf_counter()
    root = tr.record_program("compile.backend", now - 2.0, 0.5, fun="f")
    assert (root.t0, root.dur_us, root.parent_id) == (now - 2.0, 5e5, None)
    assert root.args == {"fun": "f"} and root.trace_id == root.span_id
    with tr.program_span("step.run", step=4) as outer:
        with tr.span("sampled", parent=obs.TraceContext("t", "s")):
            # the sampled spans' stack is not the one it is parented on
            kid = tr.record_program("compile.trace", now - 0.2, 0.1)
        assert tr.open_program_spans() == (outer,)
    assert kid.parent_id == outer.span_id and kid.trace_id == outer.trace_id
    assert tr.open_program_spans() == ()
    assert [s.name for s in tr.spans()] == [
        "compile.backend", "compile.trace", "sampled", "step.run"]
    assert abs(kid.ts_us - 1e6 * (time.time() - 0.2)) < 5e4
    # a span timed outside Python keeps its start on perf_counter too
    ext = tr.record_external("slow_frame", "server", "t", None,
                             ts_us=1.0, dur_us=2.0, t0=now - 1.0, conn=3)
    assert (ext.t0, ext.args) == (now - 1.0, {"conn": 3})
    assert tr.record_external("slow_frame", "server", "t", None,
                              ts_us=1.0, dur_us=2.0).t0 == 0.0


def test_the_local_backend_leaves_its_set_up_spans_too():
    mark = time.perf_counter()
    ps.init(backend="local")
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1)
    store.init({"w": jnp.ones((4, 2))})
    (init,) = _spans_since(mark, phases.SETUP_INIT)
    assert init.args == {"backend": "local", "devices": 1}
    (placed,) = _spans_since(mark, phases.SETUP_STORE_INIT)
    assert placed.args == {"leaves": 1, "nbytes": 4 * 2 * 4}


def _counters():
    return {k: c.value for k, c in obs.compiles.COUNTERS.items()}


def _recompiles_since(wall):
    return [e for e in obs.flight().events()
            if e["kind"] == "recompile" and e["t"] >= wall]


def _inside(kid, parent):
    return parent.t0 <= kid.t0 and (
        kid.t0 + 1e-6 * kid.dur_us <= parent.t0 + 1e-6 * parent.dur_us)


def test_set_up_and_the_compilers_spans_of_a_tiny_step():
    """``ps.init`` and ``KVStore.init`` leave their spans; step 0's
    ``step.run`` gets the compiler's trace, lowering and compile as
    children, through the listener alone; a warm step leaves none; a batch
    of another shape at step n leaves them under step n, a ``recompile``
    flight event and the counters moved."""
    tracer, mark = obs.tracer(), time.perf_counter()
    run, batch = _dense_step()
    (init,) = _spans_since(mark, phases.SETUP_INIT)
    assert init.args == {"backend": "tpu", "devices": 8}
    (placed,) = _spans_since(mark, phases.SETUP_STORE_INIT)
    assert placed.args == {"leaves": 2, "nbytes": (8 * 8 + 8) * 4}
    assert init.t0 + 1e-6 * init.dur_us <= placed.t0

    def compiler_spans(since):
        return [s for s in tracer.spans()
                if s.name in phases.COMPILE_SPANS and s.t0 >= since]

    before, mark = _counters(), time.perf_counter()
    run(batch)
    (run0,) = _spans_since(mark, phases.STEP_RUN)
    (launch0,) = _spans_since(mark, phases.STEP_LAUNCH)
    assert run0.args["step"] == 0
    for name, fun in ((phases.COMPILE_TRACE, "fused_step"),
                      (phases.COMPILE_LOWER, "jit(fused_step)"),
                      (phases.COMPILE_BACKEND, "jit(fused_step)")):
        (kid,) = [s for s in compiler_spans(mark)
                  if s.name == name and s.args["fun"] == fun]
        assert kid.parent_id == launch0.span_id
        assert kid.trace_id == run0.trace_id
        assert _inside(kid, launch0) and _inside(launch0, run0)
        assert kid._tid == run0._tid
    # the step's own trace holds every jitted function it calls: one span
    assert [s.args["fun"] for s in compiler_spans(mark)
            if s.name == phases.COMPILE_TRACE
            and _inside(s, launch0)] == ["fused_step"]
    after = _counters()
    assert after["compiles"] >= before["compiles"] + 1
    assert after["seconds"] > before["seconds"]

    # warm: the listener is silent, as in a measured window
    before, mark, wall = _counters(), time.perf_counter(), time.time()
    for _ in range(3):
        run(batch)
    assert len(_spans_since(mark, phases.STEP_RUN)) == 3
    assert compiler_spans(mark) == [] and _counters() == before
    assert _recompiles_since(wall) == []

    # another shape at step 4: a retrace in the middle of a job
    wider = jnp.ones((2 * BATCH, 8))
    before, mark = _counters(), time.perf_counter()
    run(wider)
    (run4,) = _spans_since(mark, phases.STEP_RUN)
    (launch4,) = _spans_since(mark, phases.STEP_LAUNCH)
    assert run4.args["step"] == 4
    (again,) = [s for s in compiler_spans(mark)
                if s.name == phases.COMPILE_BACKEND
                and s.parent_id == launch4.span_id]
    assert again.args["fun"] == "jit(fused_step)" and _inside(again, run4)
    (event,) = _recompiles_since(wall)
    assert (event["step"], event["fun"]) == (4, "jit(fused_step)")
    assert event["seconds"] == pytest.approx(1e-6 * again.dur_us, abs=1e-5)
    assert _counters()["compiles"] == before["compiles"] + 1
    assert _counters()["seconds"] == pytest.approx(
        before["seconds"] + 1e-6 * again.dur_us, abs=1e-5)
    text = obs.default_registry().render_prometheus()
    for name in ("ps_compile_total", "ps_compile_seconds_total",
                 "ps_compile_cache_hits_total",
                 "ps_compile_cache_misses_total"):
        assert f"\n{name} " in text


@pytest.fixture
def own_compile_cache(tmp_path):
    """A persistent compile cache of this test's own that takes every
    program, however small and however fast it compiled."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (str(tmp_path), 0.0, -1)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield
    for n, v in zip(names, old):
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_a_compile_says_whether_the_cache_had_it(own_compile_cache):
    """``compile.backend`` carries the cache's last word on its thread, the
    hit's retrieval is a ``compile.cache_load`` inside it, the two counters
    move, and a step past 0 that only loads still says ``recompile``."""
    tracer = obs.tracer()

    def program(x):
        return jnp.tanh(x) * 3.0 + x[::-1]

    seen, x = [], jnp.arange(5.0)
    for step in (0, 7):
        before, mark, wall = _counters(), time.perf_counter(), time.time()
        with tracer.program_span(phases.STEP_RUN, step=step) as outer:
            jax.jit(program)(x)
        (backend,) = [s for s in _spans_since(mark, phases.COMPILE_BACKEND)
                      if s.args["fun"] == "jit(program)"]
        assert backend.parent_id == outer.span_id
        seen.append(backend.args["cache"])
        loads = _spans_since(mark, phases.COMPILE_CACHE_LOAD)
        moved = {k: v - before[k] for k, v in _counters().items()}
        if step == 0:
            assert loads == [] and (moved["hit"], moved["miss"]) == (0, 1)
            assert _recompiles_since(wall) == []  # step 0 may compile
        else:
            (load,) = loads
            assert load.args["fun"] == "jit(program)"
            assert load.parent_id == outer.span_id
            assert _inside(load, backend)
            assert (moved["hit"], moved["miss"]) == (1, 0)
            (event,) = _recompiles_since(wall)
            assert (event["step"], event["cache"]) == (7, "hit")
        assert moved["compiles"] == 1
        jax.clear_caches()  # the next jit of it asks the persistent cache
    assert seen == ["miss", "hit"]


def _off_main(span):
    span._tid = 77
    return span


def _set_up_ring(t=100.0):
    """Hand-made spans of a set-up of 10 s that began at ``t``: 2 s before
    the program, an import of 1.5, ``ps.init`` 0.5, a store's init of 1
    that compiles for 0.4, a table's of 0.05, a reference that compiles on
    its own (0.6, a miss), step 0 of 3 s whose launch traces (a nested trace inside),
    lowers and loads, a warm-up step, and a producer thread."""
    return [
        _span(phases.SETUP_IMPORT, 1.5e6, "a", t0=t + 2.0),
        _span(phases.SETUP_INIT, 0.5e6, "b", t0=t + 3.5, backend="tpu"),
        _span(phases.COMPILE_BACKEND, 0.4e6, "d", "c", t0=t + 4.2,
              fun="jit(zeros)"),
        _span(phases.SETUP_STORE_INIT, 1.0e6, "c", t0=t + 4.0, leaves=2),
        _span(phases.SETUP_TABLE_INIT, 0.05e6, "t", t0=t + 5.9, rows=64),
        _span(phases.COMPILE_TRACE, 0.1e6, "e", t0=t + 5.0, fun="ref"),
        _span(phases.COMPILE_LOWER, 0.1e6, "f", t0=t + 5.1, fun="jit(ref)"),
        _span(phases.COMPILE_BACKEND, 0.6e6, "g", t0=t + 5.2,
              fun="jit(ref)", cache="miss"),
        _span(phases.COMPILE_TRACE, 0.2e6, "j", "i", t0=t + 6.3,
              fun="matmul"),                      # inside the step's trace
        _span(phases.COMPILE_TRACE, 1.0e6, "k", "i", t0=t + 6.1,
              fun="fused_step"),
        _span(phases.COMPILE_LOWER, 0.5e6, "l", "i", t0=t + 7.1,
              fun="jit(fused_step)"),
        _span(phases.COMPILE_BACKEND, 1.2e6, "m", "i", t0=t + 7.6,
              fun="jit(fused_step)", cache="hit"),
        _span(phases.COMPILE_CACHE_LOAD, 1.0e6, "n", "i", t0=t + 7.7,
              fun="jit(fused_step)"),
        _span(phases.STEP_LAUNCH, 2.8e6, "i", "h", t0=t + 6.1, step=0),
        _span(phases.STEP_RUN, 3.0e6, "h", t0=t + 6.0, step=0),
        _span(phases.STEP_LAUNCH, 0.1e6, "p", "o", t0=t + 9.5, step=1),
        _span(phases.STEP_RUN, 0.2e6, "o", t0=t + 9.5, step=1),
        # not the main thread's: never in the table; its compile counts
        _off_main(_span(phases.INPUT_PRODUCE, 5e6, "q", t0=t + 4.0)),
        _off_main(_span(phases.COMPILE_BACKEND, 0.25e6, "r", t0=t + 8.0,
                        fun="jit(augment)", cache="miss")),
        # past set-up: the window's
        _span(phases.STEP_RUN, 0.2e6, "s", t0=t + 10.5, step=2),
        _span("server_apply", 1e6, "x", t0=t + 1.0),   # the van's
    ]


def test_set_up_metrics_from_a_hand_made_ring():
    ring = _set_up_ring()
    out = setup_metrics.span_metrics(ring, 100.0, 10.0)
    assert set(out) == set(SETUP_METRICS)
    want = {
        "setup.before_program_s": 2.0, "setup.import_s": 1.5,
        "setup.init_s": 0.5, "setup.store_init_s": 1.0 + 0.05,
        "setup.step_trace_lower_s": 1.5,      # the nested trace once
        "setup.step_compile_or_load_s": 1.2,  # the load is inside it
        "setup.other_compile_s": 0.4 + 0.6 + 0.25,
        "setup.cache_misses": 2.0,
        # 8 s of program less import, init, store and table, 0.8 of
        # reference, 3.2 of steps
        "setup.unspanned_s": 8.0 - 1.5 - 0.5 - 1.05 - 0.8 - 3.2,
    }
    for name, value in want.items():
        assert out[name] == pytest.approx(value), name
    # the table: every instant of set-up in one row, phase by phase
    ends = [105.0, 106.0, 109.5, 110.0]
    main = [s for s in ring if s.name in setup_metrics.PROGRAM_SPANS
            and getattr(s, "_tid", None) is None and s.t0 < 110.0]
    rows = setup_metrics.self_times(main, 100.0, ends, 102.0)
    by_phase = [sum(r[i] for r in rows.values()) for i in range(4)]
    assert by_phase == pytest.approx([5.0, 1.0, 3.5, 0.5])
    assert sum(by_phase) == pytest.approx(10.0, abs=1e-9)
    total = {name: sum(r) for name, r in rows.items()}
    assert total[setup_metrics.BEFORE] == pytest.approx(2.0)
    assert total[setup_metrics.UNSPANNED] == pytest.approx(
        out["setup.unspanned_s"])
    assert total[phases.SETUP_STORE_INIT] == pytest.approx(0.6)  # self time
    assert total[phases.COMPILE_TRACE] == pytest.approx(0.1 + 1.0)
    assert total[phases.COMPILE_BACKEND] == pytest.approx(
        0.4 + 0.6 + 0.2)
    assert total[phases.COMPILE_CACHE_LOAD] == pytest.approx(1.0)
    assert total[phases.STEP_LAUNCH] == pytest.approx(0.1 + 0.1)
    assert total[phases.STEP_RUN] == pytest.approx(0.2 + 0.1)
    assert rows[phases.COMPILE_BACKEND] == pytest.approx(
        [0.4, 0.6, 0.2, 0.0])
    text = setup_metrics.table(rows, ["a", "b", "c", "d"])
    assert text.splitlines()[-1].split() == [
        "total", "5.000", "1.000", "3.500", "0.500", "10.000"]
    assert text.splitlines()[1].startswith(setup_metrics.BEFORE)
    assert setup_metrics.union_s([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_set_up_reader_on_the_process_ring(monkeypatch, capsys):
    """``read`` on this process's own tracer: the nine metrics with the
    table on stderr; nothing where the program is not under
    ``benchmark/run.py``; nothing, and a word on stderr, once the ring has
    turned over and dropped the import's span."""
    r = {"setup_s": 3.0, "setup_phases_s": {"imports_and_build": 1.0,
         "reference": 0.5, "step0": 1.0, "warmup": 0.5}}
    assert setup_metrics.read(r) == {}        # no _T_START
    tr = obs.Tracer(sample=0.0, capacity=16)
    monkeypatch.setattr(obs, "tracer", lambda: tr)
    now = time.perf_counter()
    monkeypatch.setattr(sys.modules["__main__"], "_T_START", now - 4.0,
                        raising=False)
    tr.record_program(phases.SETUP_IMPORT, now - 3.5, 0.5)
    tr.record_program(phases.COMPILE_BACKEND, now - 2.5, 0.25, fun="jit(f)",
                      cache="miss")
    out = setup_metrics.read(r)
    assert set(out) == set(SETUP_METRICS)
    assert out["setup.before_program_s"] == pytest.approx(0.5)
    assert out["setup.other_compile_s"] == pytest.approx(0.25)
    assert out["setup.unspanned_s"] == pytest.approx(3.0 - 0.5 - 0.75)
    err = capsys.readouterr().err
    last = [line for line in err.splitlines() if line.startswith("total")]
    assert last[0].split() == ["total", "1.000", "0.500", "1.000", "0.500",
                               "3.000"]
    assert "2 spans in the ring" in err and "0 dropped" in err
    for step in range(16):                    # a full ring: the import goes
        with tr.program_span(phases.STEP_RUN, step=step):
            pass
    assert tr.dropped == 2
    assert setup_metrics.read(r) == {}
    assert "it turned over: 2 spans dropped" in capsys.readouterr().err


# -- Mellum: the exchange's scope, on a mesh of four ---------------------------

def _mellum_step():
    """``(run, batch)`` of ``make_step(has_aux=True)`` on a tiny Mellum over
    four devices that share each layer: a windowed layer and a full one,
    eight experts, two a chip."""
    from ps_tpu.models import mellum

    rope = {"sliding_attention": {"rope_type": "default", "rope_theta": 1e4},
            "full_attention": {"rope_type": "yarn", "rope_theta": 1e4,
                               "factor": 4,
                               "original_max_position_embeddings": 32,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.2}}
    cfg = mellum.MellumConfig.from_dict(dict(
        vocab_size=64, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"],
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        sliding_window=16, num_experts=8, num_experts_per_tok=2,
        rope_parameters=rope, dtype="float32"))
    ctx = ps.init(backend="tpu", mesh_shape={"data": 4})
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0,
                       placement="sharded",
                       partition_rules=mellum.mellum_partition_rules())
    store.init(jax.jit(lambda k: mellum.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(4 * 65, dtype=np.int32).reshape(4, 65) * 7) % 64
    step = store.make_step(mellum.make_loss_fn(cfg, mesh=ctx.mesh),
                           has_aux=True)
    return step, store.shard_batch({"inputs": ids[:, :-1],
                                    "targets": ids[:, 1:]})


def test_mellum_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Mellum adds to the scopes (``ps.moe/exchange``, opened in
    ``ops/moe.py`` around the exchange's collectives inside ``ps.moe/dispatch``
    and ``ps.moe/combine``) beside the six it shares with OLMoE and Trinity's
    two cores: each in the lowered step's ``op_name``s under ``ps.grad``,
    forward and backward, under a ``jax.checkpoint``, a ``shard_map`` and a
    ``custom_vjp``; the exchange's ops are collectives and sit under both of
    its parents; the reader finds each, and the exchange is no part of the
    scopes it is opened inside."""
    assert phases.MELLUM_SCOPES[:6] == phases.MOE_SCOPES
    assert decoder.outer_of(phases.MOE_EXCHANGE) is None
    monkeypatch.setitem(BUILDERS, "mellum", _mellum_step)
    names = scope.op_names_of(_step_hlo("mellum"))
    for s in phases.MELLUM_SCOPES:
        # the instructions of a reducer or a comparator under ``shard_map``
        # bear the scope alone ("ps.moe/route/jit(argsort)/sort"); the ops
        # that call them, which a trace shows, the whole stack
        under = [n for n in names.values() if s in n and n.startswith("jit(")]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == set(phases.MELLUM_SCOPES) | {None}
    exchanged = [(own, n) for own, n in names.items()
                 if phases.MOE_EXCHANGE in n]
    assert any("all-to-all" in own for own, _ in exchanged)
    for parent in (phases.MOE_DISPATCH, phases.MOE_COMBINE):
        assert any(parent in n for _, n in exchanged), parent
    assert tracered.is_collective("%all-to-all.7 = f32[4] all-to-all(%x)")


def _sdar_step():
    """SDAR in small through the store: two layers on a clean and a noised
    copy of eight sequences of 64 positions, blocks of 4, two of eight
    experts held, three picks."""
    from ps_tpu.models import sdar

    cfg = sdar.SdarConfig(
        vocab_size=64, hidden_size=32, moe_intermediate_size=16,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, router_width=8, num_experts=2, expert_start=2,
        num_experts_per_tok=3, block_length=4, mask_token_id=63,
        dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: sdar.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 64, dtype=np.int32).reshape(8, 64) * 7) % 63
    masked = (np.arange(8 * 64).reshape(8, 64) // 4) % 2 == 0
    step = store.make_step(sdar.make_loss_fn(cfg), has_aux=True)
    return step, store.shard_batch({
        "ids": ids, "noised_ids": np.where(masked, 63, ids).astype(np.int32),
        "weights": np.where(masked, 2.0, 0.0).astype(np.float32)})


def test_sdar_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What SDAR opens (``SDAR_SCOPES``): the six it shares with OLMoE,
    Trinity's ``ps.attn/full`` around its two attention calls, and
    ``ps.attn/inblock`` around the own-block term and the merge: each in the
    lowered step's ``op_name``s under ``ps.grad``, forward and backward,
    though every layer is under a ``jax.checkpoint``. ``ps.attn/inblock`` is
    the program's alone until a ``benchmark`` PR copies it, and the reader
    counts its ops inside ``ps.attn``."""
    assert phases.SDAR_SCOPES == phases.MOE_SCOPES + (phases.ATTN_FULL,
                                                      phases.ATTN_INBLOCK)
    monkeypatch.setitem(BUILDERS, "sdar", _sdar_step)
    names = scope.op_names_of(_step_hlo("sdar"))
    for s in phases.SDAR_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.SDAR_SCOPES) & set(decoder.METRICS)) | {None}
    # the own blocks' ops count in decoder.attn_ms, as the attention's own
    # while the reader has no name for their scope
    own_blocks = {decoder.scope_of("%x", n) for n in names.values()
                  if phases.ATTN_INBLOCK in n}
    assert own_blocks and all(
        phases.ATTN in (s, decoder.outer_of(s)) for s in own_blocks)


def _joyai_step():
    """JoyAI-LLM-Flash in small through the store: the dense layer, one
    expert layer and the prediction module on eight sequences of 64
    positions, two of eight experts held, three picks."""
    from ps_tpu.models import joyai

    cfg = joyai.JoyaiConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        router_width=8, n_routed_experts=2, expert_start=2,
        num_experts_per_tok=3, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: joyai.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    step = store.make_step(joyai.make_loss_fn(cfg), has_aux=True)
    bias = joyai.init_expert_bias(cfg)
    return (lambda batch: step(batch, bias),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_joyai_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What JoyAI-LLM-Flash opens (``JOYAI_SCOPES``): the six it shares with
    OLMoE, ``ps.ffn``, ``ps.moe/shared``, the latent layer's three
    (``ps.attn/full``, ``ps.attn/latent``, ``ps.attn/rope``) and the
    prediction module's two (``ps.mtp``, ``ps.mtp/join``): each in the
    lowered step's ``op_name``s under ``ps.grad``, forward and backward,
    though every layer and each head pass is under a ``jax.checkpoint``. The
    reader has no name for four of them today: the latent projections and the
    rotation count inside ``decoder.attn_ms``, the module's attention, experts
    and head pass under their own scopes' metrics, and the join in none."""
    assert phases.JOYAI_SCOPES == phases.MOE_SCOPES + (
        phases.FFN, phases.MOE_SHARED, phases.ATTN_FULL, phases.ATTN_LATENT,
        phases.ATTN_ROPE, phases.MTP, phases.MTP_JOIN)
    monkeypatch.setitem(BUILDERS, "joyai", _joyai_step)
    names = scope.op_names_of(_step_hlo("joyai"))
    for s in phases.JOYAI_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.JOYAI_SCOPES) & set(decoder.METRICS)) | {None}
    for s in (phases.ATTN_LATENT, phases.ATTN_ROPE):
        inner = {decoder.scope_of("%x", n) for n in names.values() if s in n}
        assert inner == {_read_as(s)} and phases.ATTN in (
            _read_as(s), decoder.outer_of(s)), s
    # under the module's scope: its attention, its experts and its head pass
    # are read as those, the join as nothing the reader knows
    module = {decoder.scope_of("%x", n) for n in names.values()
              if phases.MTP in n}
    assert {phases.ATTN, phases.ATTN_FULL, phases.MOE_EXPERT,
            phases.MOE_SHARED, phases.HEAD, _read_as(phases.MTP)} <= module
    assert {decoder.scope_of("%x", n) for n in names.values()
            if phases.MTP_JOIN in n} == {_read_as(phases.MTP_JOIN)}
    # the main head's pass and the module's: ps.head under two name stacks
    heads = [n for n in names.values() if phases.HEAD in n]
    assert any(phases.MTP in n for n in heads)
    assert any(phases.MTP not in n for n in heads)


def _granite_step():
    """``(run, batch)`` of ``make_step`` (no aux: the model has no state of
    its own) on a tiny Granite-4.0-H: a Mamba-2 layer and an attention layer,
    each with its SwiGLU."""
    from ps_tpu.models import granite_h

    cfg = granite_h.GraniteHConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        layer_types=("mamba", "attention"), mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=8, mamba_chunk_size=32, num_attention_heads=2,
        num_key_value_heads=1, shared_intermediate_size=48,
        dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: granite_h.init_params(k, cfg))(
        jax.random.key(0)))
    # 64 tokens: two chunks of the scan
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    return (store.make_step(granite_h.make_loss_fn(cfg)),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_granite_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Granite-4.0-H opens (``GRANITE_SCOPES``: no expert, so not the
    six): ``ps.attn``, ``ps.head``, ``ps.ffn`` in every layer, and the shared
    mixer's four, ``ps.mamba/gate`` the new one: each in the lowered step's
    ``op_name``s under ``ps.grad``, forward and backward, though every layer
    is under a ``jax.checkpoint``. The reader has no name for the gate's
    today: it counts inside ``decoder.mamba_ms``."""
    assert phases.GRANITE_SCOPES == (
        phases.ATTN, phases.HEAD, phases.FFN, phases.MAMBA,
        phases.MAMBA_CONV, phases.MAMBA_SSD, phases.MAMBA_GATE)
    monkeypatch.setitem(BUILDERS, "granite", _granite_step)
    names = scope.op_names_of(_step_hlo("granite"))
    for s in phases.GRANITE_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.GRANITE_SCOPES) & set(decoder.METRICS)) | {None}
    assert {decoder.scope_of("%x", n) for n in names.values()
            if phases.MAMBA_GATE in n} == {_read_as(phases.MAMBA_GATE)}
    assert decoder.outer_of(phases.MAMBA_GATE) == phases.MAMBA
    assert not [n for n in names.values() if "ps.moe" in n]


def _phi4flash_step():
    """``(run, batch)`` of ``make_step`` on a tiny Phi-4-mini-flash: layers
    2 to 7 of a model of eight, one of every kind."""
    from ps_tpu.models import phi4flash

    cfg = phi4flash.Phi4FlashConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=6, first_layer=2,
        model_layers=8, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=48, sliding_window=16, mamba_d_state=4,
        dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: phi4flash.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    return (store.make_step(phi4flash.make_loss_fn(cfg)),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_phi4flash_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Phi-4-mini-flash opens (``PHI4FLASH_SCOPES``: no expert, so not
    the six): ``ps.attn`` with the three cores and the combine, ``ps.head``,
    ``ps.ffn`` in every layer, ``ps.mamba`` with the taps and the selective
    scan, and ``ps.gmu``: each in the lowered step's ``op_name``s under
    ``ps.grad``, forward and backward, though every layer is under a
    ``jax.checkpoint`` that hands the memory and K, V on. The reader has no
    name for four of them today: the scan counts inside ``decoder.mamba_ms``,
    the cross core and the combine inside ``decoder.attn_ms``, the gmu with
    the gradient's rest."""
    assert phases.PHI4FLASH_SCOPES == (
        phases.ATTN, phases.HEAD, phases.FFN, phases.MAMBA,
        phases.MAMBA_CONV, phases.ATTN_WINDOW, phases.ATTN_FULL,
        phases.MAMBA_S6, phases.GMU, phases.ATTN_CROSS, phases.ATTN_DIFF)
    assert (phases.MAMBA_S6, phases.GMU, phases.ATTN_CROSS,
            phases.ATTN_DIFF) == ("ps.mamba/s6", "ps.gmu", "ps.attn/cross",
                                  "ps.attn/diff")
    monkeypatch.setitem(BUILDERS, "phi4flash", _phi4flash_step)
    names = scope.op_names_of(_step_hlo("phi4flash"))
    for s in phases.PHI4FLASH_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.PHI4FLASH_SCOPES) & set(decoder.METRICS)) | {None}
    assert (decoder.outer_of(phases.MAMBA_S6), decoder.outer_of(
        phases.ATTN_CROSS), decoder.outer_of(phases.ATTN_DIFF),
        decoder.outer_of(phases.GMU)) == (phases.MAMBA, phases.ATTN,
                                          phases.ATTN, None)
    for inner in (phases.MAMBA_S6, phases.ATTN_CROSS, phases.ATTN_DIFF,
                  phases.GMU):
        assert {decoder.scope_of("%x", n) for n in names.values()
                if inner in n} == {_read_as(inner)}, inner
    assert not [n for n in names.values() if "ps.moe" in n]


def _qwen3_next_step():
    """``(run, batch)`` of ``make_step`` on a tiny Qwen3-Next: a delta-rule
    layer and a gated attention layer, each with its experts."""
    from ps_tpu.models import qwen3_next

    cfg = qwen3_next.Qwen3NextConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        full_attention_interval=2, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, router_width=8, num_experts=2,
        expert_start=2, num_experts_per_tok=3, dtype=jnp.float32)
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: qwen3_next.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    return (store.make_step(qwen3_next.make_loss_fn(cfg), has_aux=True),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_qwen3_next_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Qwen3-Next opens (``QWEN3_NEXT_SCOPES``): the six it shares with
    OLMoE, the delta-rule mixer's three (Kimi-Linear's names), ``ps.moe/shared``
    around the shared expert and its gate, and under ``ps.attn`` the rotation,
    the core and the gate: each in the lowered step's ``op_name``s under
    ``ps.grad``, forward and backward, though every layer is under a
    ``jax.checkpoint``. Held equal to the reader's copy: every one of them
    but the rotation's has its metric (``decoder.METRICS``)."""
    assert phases.QWEN3_NEXT_SCOPES == phases.MOE_SCOPES + (
        phases.KDA, phases.KDA_CONV, phases.KDA_CORE, phases.MOE_SHARED,
        phases.ATTN_FULL, phases.ATTN_ROPE, phases.ATTN_GATE)
    assert set(phases.QWEN3_NEXT_SCOPES) - {phases.ATTN_ROPE} \
        <= set(decoder.METRICS)
    assert phases.ATTN_ROPE in _readers_scopes()
    monkeypatch.setitem(BUILDERS, "qwen3_next", _qwen3_next_step)
    names = scope.op_names_of(_step_hlo("qwen3_next"))
    for s in phases.QWEN3_NEXT_SCOPES:
        under = [n for n in names.values() if s in n]
        assert under and all(phases.GRAD in n for n in under), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == (set(phases.QWEN3_NEXT_SCOPES) & set(decoder.METRICS)) | {None}
    assert {decoder.scope_of("%x", n) for n in names.values()
            if phases.ATTN_ROPE in n} == {_read_as(phases.ATTN_ROPE)}
    assert decoder.outer_of(phases.ATTN_ROPE) == phases.ATTN


def _ouro_step():
    """``(run, batch)`` of ``make_step`` on a tiny Ouro: two layers run three
    times, the passes scanned."""
    from ps_tpu.models import ouro

    cfg = ouro.OuroConfig.from_dict(dict(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
        head_dim=16, total_ut_steps=3, dtype="float32"))
    ps.init(backend="tpu")
    store = ps.KVStore(optimizer="adamw", clip_by_global_norm=1.0)
    store.init(jax.jit(lambda k: ouro.init_params(k, cfg))(
        jax.random.key(0)))
    ids = (np.arange(8 * 65, dtype=np.int32).reshape(8, 65) * 7) % 64
    return (store.make_step(ouro.make_loss_fn(cfg), has_aux=True),
            store.shard_batch({"inputs": ids[:, :-1], "targets": ids[:, 1:]}))


def test_ouro_scopes_reach_the_step_hlo_forward_and_backward(
        no_compile_cache, monkeypatch):
    """What Ouro opens (``OURO_SCOPES``): ``ps.attn``, ``ps.ffn`` and
    ``ps.head`` as the dense decoders, ``ps.loop`` around the passes and
    ``ps.exit`` around the gates and the weighting: each in the lowered
    step's ``op_name``s under ``ps.grad``, forward and backward, though the
    passes are a scan and every layer application is under a
    ``jax.checkpoint``. ``decoder.py`` reads the attention and the SwiGLU by
    the innermost scope, inside the loop; the loop's own events (norms,
    residuals) and the exit's in none of its; the head's and the exit's are
    not the loop's."""
    assert phases.OURO_SCOPES == (phases.ATTN, phases.HEAD, phases.FFN,
                                  phases.LOOP, phases.EXIT)
    monkeypatch.setitem(BUILDERS, "ouro", _ouro_step)
    names = scope.op_names_of(_step_hlo("ouro"))
    for s in phases.OURO_SCOPES:
        under = [n for n in names.values() if s in n]
        # what the passes' scan finds the same in every pass (the rotation's
        # cos and sin, the mask, the targets' indices) JAX lifts out of the
        # loop and names without the transform's scope: no product among
        # them
        lifted = [n for n in under if phases.GRAD not in n]
        assert not [n for n in lifted
                    if n.endswith(("dot_general", "pallas_call"))], s
        under = [n for n in under if n not in lifted]
        assert under and len(under) > len(lifted), s
        assert any(phases.BACKWARD_MARK in n for n in under), s
        assert any(phases.BACKWARD_MARK not in n for n in under), s
    found = {decoder.scope_of(own, n) for own, n in names.items()}
    assert found == {phases.ATTN, phases.HEAD, phases.FFN, None}
    for inner in (phases.ATTN, phases.FFN):
        assert all(phases.LOOP in n for n in names.values() if inner in n)
    assert {decoder.scope_of("%x", n) for n in names.values()
            if phases.EXIT in n} == {None}
    for outside in (phases.HEAD, phases.EXIT):
        assert not [n for n in names.values()
                    if outside in n and phases.LOOP in n], outside
    assert not [n for n in names.values() if "ps.moe" in n]


# -- the benchmark's own command on the CPU, and its manifest ------------------

with open(os.path.join(_REPO, "BENCHMARK.json")) as _f:
    _MANIFEST = json.load(_f)


@functools.lru_cache(maxsize=None)
def _rehearse(cell):
    """The result line of a traced rehearsal of ``cell``: the command's own
    control flow at the tiny sizes, in a process of its own."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmark", "run.py"),
         "--workload", cell, "--rehearse", "--trace", "1", "--seconds", "1"],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_HOST_METRICS = ("host.step_launch_ms", "host.step_wrap_ms",
                 "host.input_place_ms", "host.input_mb_per_step")
#: cell -> what its rehearsal lists whatever the manifest says (metrics of
#: readers of their own, by name). One cell of each decoder, the sparse cell,
#: and a dense cell that lists nothing of its own; not all fourteen: each is
#: a process of 15-40 s
REHEARSED = {
    "widedeep-criteo.b4096.zipf": _HOST_METRICS + SETUP_METRICS,
    "bert-base.s128.full": ("entry.compile_s", "loop.dispatch_ms"),
    "olmoe-1b-7b.s4096.zipf": (),
    "lfm2-24b-a2b.s8192.zipf": (),
    "kimi-linear-48b-a3b.s8192.b1.zipf": (),
    "nemotron-3-super-120b-a12b.s8192.b1.zipf": (),
    "trinity-mini.s16384.b1.zipf": (),
    "mellum2-12b-a2.5b.s8192.b1.zipf.x4": (),
    "sdar-30b-a3b.s8192.b1.zipf.bd4": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib"),
    "joyai-llm-flash.s8192.b1.zipf": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib"),
    "granite-4.0-h-micro.s8192.b1.zipf": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib"),
    "qwen3-next-80b-a3b.s8192.b1.zipf": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib"),
    "ouro-2.6b.s8192.b1.zipf": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib", "step.mfu",
        "kernel.flash_roofline"),
    "phi-4-mini-flash-reasoning.s16384.b1.zipf": (
        "entry.compile_s", "input.wait_share", "loop.dispatch_ms",
        "setup.import_s", "device.peak_hbm_gib", "step.mfu",
        "kernel.flash_roofline")}


@pytest.mark.parametrize("cell", sorted(REHEARSED))
def test_benchmark_command_rehearses_a_cell(cell, listed_for):
    """``correct`` with every step-0 check, no value under a device metric's
    name, as many devices as the cell asks for, and of the per-layer names
    what the manifest lists for the cell: every name whose ``workloads``
    names it (a decoder's reader lists its names from the marks of the
    loaded step) and none the manifest does not give it (no other
    configuration's)."""
    line = _rehearse(cell)
    assert line["correct"] and line["metrics"] == {}
    asked = next(w for w in _MANIFEST["workloads"] if w["name"] == cell)
    assert line["device"]["count"] == asked["chips"]
    rehearsed, listed = set(line["rehearsed"]), listed_for(cell)
    assert rehearsed <= {m["name"] for m in listed}
    by_name = {m["name"] for m in listed if "workloads" in m
               # scope.py and sparse.py read a device's trace and nothing
               # else: they list nothing without one
               and (m["source"] != "device_trace"
                    or m["name"].split(".")[0] not in ("scope", "sparse"))}
    assert by_name <= rehearsed, sorted(by_name - rehearsed)
    assert set(REHEARSED[cell]) <= rehearsed


@pytest.mark.parametrize("cell", [w["name"] for w in _MANIFEST["workloads"]])
def test_every_listed_metric_has_a_reader(cell, listed_for):
    """What ``benchmark/run.py`` needs of the manifest to report a cell: each
    per-layer name listed for it resolves to a module of ``layer_metrics/``
    with a ``read``, as ``run.py`` resolves it; and of the manifest as a
    whole: no name twice, every ``workloads`` entry a cell, every ``moves``
    an end-to-end metric, at most a quarter of the cells on four chips."""
    for m in listed_for(cell):
        group = m["name"].split(".", 1)[0]
        module = importlib.import_module(f"benchmark.layer_metrics.{group}")
        assert callable(module.read), m["name"]
    cells = [w["name"] for w in _MANIFEST["workloads"]]
    names = [m["name"] for m in _MANIFEST["per_layer"]]
    assert len(set(cells)) == len(cells) and len(set(names)) == len(names)
    # the contract's limit, held here for every cell's test (and by
    # benchmark/check/check_decoder.py): how far under it is the manifest's
    assert len(names) <= 128
    end_to_end = {m["name"] for m in _MANIFEST["end_to_end"]}
    for m in _MANIFEST["per_layer"]:
        assert set(m.get("workloads", ())) <= set(cells), m["name"]
        assert m["moves"] in end_to_end, m["name"]
    four = [w for w in _MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= len(cells) // 4
