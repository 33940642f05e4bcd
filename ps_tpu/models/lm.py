"""Minimal causal transformer LM — the long-context workload.

Written TPU-first as pure functions over a flat-friendly param dict (the
same tree the PS store shards by key), so the Megatron partition rules in
:func:`lm_partition_rules` apply verbatim and the attention op is pluggable
(``models/blocks.py::make_attn_fn``): ``'full'`` for single-device/small
contexts, ``'ring'`` or ``'ulysses'`` (ps_tpu/parallel/ring_attention.py)
when activations are sharded over a 'seq' mesh axis. Pre-norm blocks,
learned positions, weight-tied readout — small on purpose: the model is the
vehicle for the parallelism, the PS protocol around it is identical to every
other workload.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ps_tpu.models.blocks import _full_attention, token_ce


def init_params(rng: np.random.Generator, *, vocab: int, d_model: int,
                n_heads: int, n_layers: int, d_ff: Optional[int] = None,
                max_len: int = 2048) -> Dict:
    """He/scaled-normal init of the full parameter tree."""
    d_ff = d_ff or 4 * d_model

    def t(*shape, scale=None):
        scale = scale if scale is not None else (1.0 / math.sqrt(shape[0]))
        return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32))

    params: Dict = {
        "embed": {"tokens": t(vocab, d_model, scale=0.02),
                  "positions": t(max_len, d_model, scale=0.02)},
        "final_norm": {"scale": jnp.ones((d_model,))},
    }
    for i in range(n_layers):
        params[f"layer{i}"] = {
            "ln1": {"scale": jnp.ones((d_model,))},
            "attn": {
                "qkv": {"kernel": t(d_model, 3 * d_model)},
                "out": {"kernel": t(d_model, d_model)},
            },
            "ln2": {"scale": jnp.ones((d_model,))},
            "mlp": {
                "in": {"kernel": t(d_model, d_ff)},
                "out": {"kernel": t(d_ff, d_model)},
            },
        }
    return params


def lm_partition_rules():
    """Megatron placement for every layer (regexes match all layer indices):
    in-projections column-parallel, out-projections row-parallel, embeddings
    vocab/position-sharded by the default heuristic (left unruled)."""
    return [
        (r"attn/qkv/kernel$", (None, "model")),
        (r"attn/out/kernel$", ("model", None)),
        (r"mlp/in/kernel$", (None, "model")),
        (r"mlp/out/kernel$", ("model", None)),
    ]


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def block_apply(lp: Dict, x: jax.Array, *, n_heads: int,
                attn_fn: Callable = _full_attention) -> jax.Array:
    """One pre-norm transformer block: activations [B, T, D] -> [B, T, D].
    The homogeneous unit the pipeline trunk repeats."""
    b, t, d_model = x.shape
    dh = d_model // n_heads
    h = _rmsnorm(x, lp["ln1"]["scale"])
    qkv = (h @ lp["attn"]["qkv"]["kernel"]).reshape(b, t, 3 * n_heads, dh)
    q, k, v = jnp.split(qkv, 3, axis=2)
    a = attn_fn(q, k, v, causal=True).reshape(b, t, d_model)
    x = x + a @ lp["attn"]["out"]["kernel"]
    h = _rmsnorm(x, lp["ln2"]["scale"])
    h = jax.nn.gelu(h @ lp["mlp"]["in"]["kernel"])
    return x + h @ lp["mlp"]["out"]["kernel"]


def embed_apply(params: Dict, tokens: jax.Array) -> jax.Array:
    """The heterogeneous FIRST stage: tokens [B, T] -> activations [B, T, D]."""
    t = tokens.shape[-1]
    return (jnp.take(params["embed"]["tokens"], tokens, axis=0)
            + params["embed"]["positions"][:t][None])


def readout_apply(params: Dict, x: jax.Array) -> jax.Array:
    """The heterogeneous LAST stage: final norm + weight-tied readout,
    activations [B, T, D] -> logits [B, T, vocab]."""
    x = _rmsnorm(x, params["final_norm"]["scale"])
    return x @ params["embed"]["tokens"].T


def apply(params: Dict, tokens: jax.Array, *, n_heads: int,
          attn_fn: Callable = _full_attention) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab]."""
    x = embed_apply(params, tokens)
    i = 0
    while f"layer{i}" in params:
        x = block_apply(params[f"layer{i}"], x, n_heads=n_heads,
                        attn_fn=attn_fn)
        i += 1
    return readout_apply(params, x)


def split_pipeline_params(params: Dict, num_stages: int) -> Dict:
    """Rearrange an :func:`init_params` tree for dp x pp training.

    Heterogeneous-stage layout (VERDICT r4 item 9): the embed and readout
    params — whose shapes differ from the trunk blocks — stay as ordinary
    (data-parallel / ZeRO) tensors under their own keys, while the
    ``n_layers`` homogeneous blocks are stacked ``[S, k, ...]`` under
    ``"stages"`` (S pipeline stages of k layers each) for ``P('pipe', ...)``
    placement. In the SPMD-stacked GPipe formulation every device executes
    every tick anyway, so placing embed/readout *inside* stage 0 / S-1
    would not save compute — it would only replicate their work across all
    M+S-1 ticks and force a union param structure (the vocab table stacked
    S times). Outside the trunk they run once per microbatch, sharded over
    'data' like any dense tensor — the TPU-native spelling of "first/last
    stages may differ".
    """
    n_layers = 0
    while f"layer{n_layers}" in params:
        n_layers += 1
    if n_layers == 0 or n_layers % num_stages:
        raise ValueError(
            f"{n_layers} layers do not split into {num_stages} equal stages"
        )
    k = n_layers // num_stages
    stages = []
    for s in range(num_stages):
        group = [params[f"layer{s * k + j}"] for j in range(k)]
        stages.append(jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *group
        ))
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *stages
    )
    return {"embed": params["embed"], "final_norm": params["final_norm"],
            "stages": stacked}


def pipeline_lm_partition_rules(extra=()):
    """Partition rules for a :func:`split_pipeline_params` tree: every
    ``stages/`` leaf's leading dim on 'pipe' (via the generic
    pipeline-rule generator); embed/readout left to the default (data)
    heuristic or to ``extra`` rules."""
    from ps_tpu.parallel.pipeline import pipeline_partition_rules

    return pipeline_partition_rules(max_rank=5, pattern=r"^stages/") \
        + list(extra)


def make_pipelined_loss_fn(*, n_heads: int, num_stages: int,
                           microbatches: int, mesh=None,
                           attn_fn: Callable = _full_attention):
    """Next-token CE through the dp x pp pipeline.

    The composite step: embed (heterogeneous first stage, once per
    microbatch, data-sharded) -> GPipe trunk over the 'pipe' axis
    (ps_tpu/parallel/pipeline.py) -> final-norm + tied readout
    (heterogeneous last stage). Parity vs the non-pipelined
    :func:`make_loss_fn` is asserted in tests/test_pipeline.py.
    ``params`` must be a :func:`split_pipeline_params` tree placed with
    :func:`pipeline_lm_partition_rules`.
    """
    from ps_tpu.parallel.pipeline import make_pipeline_fn, microbatch

    def stage_fn(stage_params, x):
        # stage_params leaves are [k, ...]: k layers of this stage,
        # statically unrolled
        k = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        for j in range(k):
            lp = jax.tree_util.tree_map(lambda l, _j=j: l[_j], stage_params)
            x = block_apply(lp, x, n_heads=n_heads, attn_fn=attn_fn)
        return x

    pipe_fn = make_pipeline_fn(stage_fn, mesh, microbatches=microbatches)

    def loss_fn(params, batch):
        x = embed_apply(params, batch["inputs"])       # [B, T, D]
        h = pipe_fn(params["stages"], microbatch(x, microbatches))
        h = h.reshape((-1,) + h.shape[2:])             # [B, T, D]
        logits = readout_apply(params, h)
        return token_ce(logits, batch["targets"])

    return loss_fn


def make_loss_fn(*, n_heads: int, attn_fn: Callable = _full_attention):
    """Next-token cross entropy, meaned over the global batch. The batch
    carries pre-shifted ``inputs``/``targets`` [B, T] (T divisible by the
    'seq' axis, so both shard cleanly — see :func:`lm_batches`)."""

    def loss_fn(params, batch):
        logits = apply(params, batch["inputs"], n_heads=n_heads,
                       attn_fn=attn_fn)
        return token_ce(logits, batch["targets"])

    return loss_fn


def lm_batches(batch_size: int, seq_len: int, *, vocab: int = 256,
               seed: int = 0, steps: Optional[int] = None):
    """Deterministic synthetic token streams with LEARNABLE structure:
    next token = (3·start + 7·position) mod vocab, plus noise tokens — a
    causal model's loss decreases fast, random guessing doesn't. Yields
    pre-shifted ``{"inputs": [B, T], "targets": [B, T]}``.
    """
    rng = np.random.default_rng(seed)
    i = 0
    while steps is None or i < steps:
        start = rng.integers(0, vocab, size=(batch_size, 1))
        ramp = np.arange(seq_len + 1)[None, :]
        toks = (start * 3 + ramp * 7) % vocab
        noise = rng.random((batch_size, seq_len + 1)) < 0.05
        toks = np.where(noise, rng.integers(0, vocab, toks.shape), toks)
        toks = toks.astype(np.int32)
        yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
        i += 1
