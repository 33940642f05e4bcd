"""Ouro (``model_type: ouro``): a looped language model, **one stack of
layers whose weights run ``total_ut_steps`` times a forward pass**, a readout
of the whole vocabulary and an exit gate after every pass, and a loss that
weights the passes' cross entropies by the exit distribution the gates give
(ByteDance Seed, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; Ouro-2.6B: 48 layers run 4 times, hidden 2,048, 16 heads of
128 on 16 K/V heads, a 5,632-wide SwiGLU, 49,152 ids, embedding and head
untied). The store's first model that reads a weight more than once a step::

    h0 = E[x]
    Layer_l(h):  a = h + N2_l(Attn_l(N1_l(h)))     four RMSNorms a layer: one
                 y = a + N4_l(SwiGLU_l(N3_l(a)))   before, one after each part
    pass t = 1..T:  u = h(t-1);  u = Layer_l(u) for l = 1..L, the SAME
                    parameters every pass;  h(t) = N_f(u)
    z(t) = h(t) W_head        lambda_t = sigmoid(h(t) . w_g + b_g)
    p_1 = lambda_1;  p_t = lambda_t prod_{j<t}(1 - lambda_j), 1 < t < T;
    p_T = prod_{j<T}(1 - lambda_j)                 (sum_t p_t = 1)
    loss = mean over positions of [sum_t p_t CE(z(t), target) - beta H(p)]

The final norm closes every pass: the next pass, the head and the gate all
read its output. Pure functions over a parameter dict, as
``models/granite_h.py``; ``rms_norm``, ``rope``, ``dense_ffn``, the attention
closure and the blocked readout are ``models/blocks.py``'s. The equations are
written out in the plain reference's docstring
(``benchmark/families/ouro_reference.py``), which this module is held to. How
they are computed here:

- **the passes**: a ``jax.lax.scan`` over one traced pass (``passes='scan'``,
  the default: one copy of the stack in the program, its compile an 8-layer
  model's; the four cotangents of a weight are summed in the backward scan's
  carry, in f32, so one gradient a weight reaches the apply) or ``T`` copies
  of the pass in the program (``passes='unroll'``), the same mathematics;
  ``PERF.md`` section 6 (PR 63) has both on the chip and why the default;
- attention: q, k and v without bias or norm, all 128 channels of q and k
  rotated (``blocks.rope``, halves against each other), causal; with
  ``attn='flash'`` the Pallas kernel at 16 heads on 16. At the cell's shape
  the rotation is ``ops/rope.py::rotate``, a Mosaic pass between the
  projection's ``bf16[1,16,8192,128]`` and the flash call that reads q (and
  k) once and writes it once, 34 MB each way, six times a layer application
  (q and k: forward, the checkpoint's recomputation, and the same pass on dq
  and dk). ``blocks.rope``'s ``jax.numpy`` expression made XLA write the
  product as ``f32[1,8192,16,128]``, two half-width ``f32[1,8192,16,64]`` and
  then the kernel's operand, 235 MB written and 268 MB read where 34 + 34
  do, in each of those six passes: about 2.5 GB an application, 81 GB a step
  of 32 (``ops/rope.py``'s table; ``PERF.md`` section 6, PR 64);
- the SwiGLU: ``blocks.dense_ffn`` (``w1`` the gate, ``w3`` up, ``w2`` down);
- the readouts: one call of ``blocks.blocked_head_nll`` over the ``T x B``
  sequences ``h(1..T)`` that the passes give, behind the loop, all ``T``
  readouts reading the one ``head/kernel``: a position's loss [T, B, S]
  comes out, logits of ``HEAD_BLOCK`` positions in all live at once
  ([T x B, HEAD_BLOCK / T, V]) and no [B, S, V] array ever; the head's
  gradient has one accumulator and is whole before the passes' backward
  loop starts;
- the gate, the exit distribution, its logarithms, the entropy and the
  weighted sum in f32, a position at a time ([T, B, S] values); the gate is a
  multiply and a sum over the channels in f32, no matmul. ``lambda_T`` is
  computed and read by nothing.

**What a layer application keeps.** Every application runs under one
``jax.checkpoint`` whose policy lists what it keeps from its forward pass
beside its input: ``ops/flash_attention.py::KEPT``, the flash call's output
and logsumexp, which only the forward kernel can produce, and nothing else
(``PRODUCTS_KEPT`` is empty). The policy is chosen for ``T x L``
applications on ``L`` layers' parameters, the same in every pass: in bf16 an
application keeps 4,096 B a token of input, 4,096 B of flash output and 64 B
of logsumexp, 8,256 B a token, 67.6e6 B at 8,192 tokens, 2.16e9 B over the 32
applications of the cell beside 9.80e9 B of parameters, moments and
gradients. The other decoders' habit of keeping q, k and v (12,288 B a token
more: 3.2e9 B over 32 applications) or the SwiGLU's first products (22,528 B
a token: 5.9e9 B) does not fit a chip here; both are made again.

``total_ut_steps`` 1 is a plain decoder: ``p_1 = 1``, ``H = 0``, the loss the
cross entropy. What the model does not compute, ``OuroConfig.from_dict``
refuses::

    step = store.make_step(make_loss_fn(config), has_aux=True)
    loss, params, aux = step(batch)

``aux``, each from the step's own batch: ``ce`` (the expected cross entropy,
the loss's first term), ``ce_pass`` [T], ``exit_mass`` [T] (mean ``p_t``),
``exit_entropy`` (mean ``H(p)``), ``expected_passes`` (mean ``sum_t t p_t``).
The phases a trace can tell apart are opened here with ``jax.named_scope``
(``obs/phases.py::OURO_SCOPES``); they nest under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from ps_tpu.models.blocks import (blocked_head_nll, dense_ffn, make_attn_fn,
                                  rms_norm, rope)
from ps_tpu.obs import phases
from ps_tpu.ops.flash_attention import KEPT

#: positions whose logits are formed at a time, over the passes' readouts
#: together: [4, 1024, 49152] bf16 and f32 are 1.2e9 B with their cotangent,
#: outside the passes' loops, where the step's peak does not lie (compiled: the
#: same 14.66e9 B at 2,048 and 4,096, 14.99e9 at 8,192)
HEAD_BLOCK = 4096
#: what a layer application keeps beside its input and the flash call's
#: residuals (module docstring): nothing
PRODUCTS_KEPT = ()
PASSES = ("scan", "unroll")


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names, and ``exit_entropy_beta``, the objective's one
    constant, which the config has no key for."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    total_ut_steps: int = 4
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    exit_entropy_beta: float = 0.05
    dtype: Any = jnp.bfloat16

    @classmethod
    def from_dict(cls, d: Dict) -> "OuroConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would
        change the mathematics."""
        for key, want in (("use_sliding_window", False),
                          ("sliding_window", None), ("rope_scaling", None),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu")):
            if d.get(key, want) != want:
                raise ValueError(f"models/ouro.py computes {key}={want!r} "
                                 f"only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        layers = kw.get("num_hidden_layers", cls.num_hidden_layers)
        kw["layer_types"] = tuple(
            kw.get("layer_types", ("full_attention",) * layers))
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        if len(config.layer_types) != config.num_hidden_layers \
                or set(config.layer_types) - {"full_attention"}:
            raise ValueError(
                f"{config.num_hidden_layers} layers of layer_types "
                f"{list(config.layer_types)}: models/ouro.py computes "
                f"'full_attention' in every layer")
        if config.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {config.total_ut_steps}: the "
                             f"stack runs once at least")
        if config.num_attention_heads % config.num_key_value_heads:
            raise ValueError(
                f"{config.num_attention_heads} query heads on "
                f"{config.num_key_value_heads} K/V heads: must divide")
        return config


def init_params(key, config: OuroConfig) -> Dict:
    """Normal(0, 0.02) matrices and gate, unit norm scales, zero gate bias,
    f32; the embedding and the head are two tensors. Jit it to make the tree
    on the device from the seed."""
    c = config
    d = c.hidden_size
    keys = iter(jax.random.split(key, 3 + 7 * c.num_hidden_layers))

    def lin(*shape):
        return {"kernel": 0.02 * jax.random.normal(next(keys), shape,
                                                   jnp.float32)}

    def ones():
        return {"scale": jnp.ones((d,), jnp.float32)}

    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    params: Dict = {"embed": {"tokens": lin(c.vocab_size, d)["kernel"]},
                    "head": lin(d, c.vocab_size),
                    "final_norm": ones(),
                    "gate": {**lin(d, 1), "bias": jnp.zeros((1,),
                                                            jnp.float32)}}
    for i in range(c.num_hidden_layers):
        params[f"layer{i}"] = {
            "attn_norm": ones(), "attn_out_norm": ones(),
            "ffn_norm": ones(), "ffn_out_norm": ones(),
            "attn": {"q": lin(d, q), "k": lin(d, kv), "v": lin(d, kv),
                     "out": lin(q, d)},
            "ffn": {"w1": lin(d, c.intermediate_size),
                    "w3": lin(d, c.intermediate_size),
                    "w2": lin(c.intermediate_size, d)}}
    return params


def attention_block(lp: Dict, x, config: OuroConfig, attn_fn: Callable):
    """Causal attention of the normed activations ``x`` [B, S, D]: no bias,
    no q / k norm, every channel of q and k rotated by its position. K and V
    reach ``attn_fn`` at their own head count."""
    c = config
    b, s, _ = x.shape

    def proj(name, n):
        return (x @ lp[name]["kernel"].astype(x.dtype)).reshape(b, s, n, -1)

    q = rope(proj("q", c.num_attention_heads), c.rope_theta)
    k = rope(proj("k", c.num_key_value_heads), c.rope_theta)
    a = attn_fn(q, k, proj("v", c.num_key_value_heads), causal=True)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(2, 3),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, config: OuroConfig, attn_fn: Callable):
    """One application of one layer, each part between its two norms,
    recomputed in the backward pass."""
    eps = config.rms_norm_eps
    with jax.named_scope(phases.ATTN):
        mixed = attention_block(
            lp["attn"], rms_norm(x, lp["attn_norm"]["scale"], eps), config,
            attn_fn)
    x = x + rms_norm(mixed, lp["attn_out_norm"]["scale"], eps)
    with jax.named_scope(phases.FFN):
        out = dense_ffn(lp["ffn"], rms_norm(x, lp["ffn_norm"]["scale"], eps))
    return x + rms_norm(out, lp["ffn_out_norm"]["scale"], eps)


def one_pass(params: Dict, u, config: OuroConfig, attn_fn: Callable):
    """The stack once and the final norm: ``h(t-1)`` [B, S, D] -> ``h(t)``.
    The norm is recomputed in the backward pass as the layers are: a pass
    keeps its input in bf16 and no f32 copy of it."""
    with jax.named_scope(phases.LOOP):
        for i in range(config.num_hidden_layers):
            u = _layer(params[f"layer{i}"], u, config, attn_fn)
        return jax.checkpoint(rms_norm, static_argnums=(2,))(
            u, params["final_norm"]["scale"], config.rms_norm_eps)


@jax.checkpoint
def exit_gate(gate: Dict, h):
    """``lambda = sigmoid(h . w_g + b_g)`` a token, in f32: [B, S]. Recomputed
    in the backward pass from ``h`` as it stands in bf16."""
    return jax.nn.sigmoid(
        jnp.sum(h.astype(jnp.float32) * gate["kernel"][:, 0], -1)
        + gate["bias"][0])


def exit_distribution(lam):
    """``p`` [T, ...] from the gates ``lam`` [T, ...]: ``p_t = lambda_t
    prod_{j<t}(1 - lambda_j)`` and the last pass takes what is left, so the
    ``p_t`` sum to one; ``lam[-1]`` is read by nothing."""
    if lam.shape[0] == 1:
        return jnp.ones_like(lam)
    left = jnp.cumprod(1.0 - lam[:-1], axis=0)           # S_1 .. S_{T-1}
    before = jnp.concatenate([jnp.ones_like(lam[:1]), left[:-1]], axis=0)
    return jnp.concatenate([lam[:-1] * before, left[-1:]], axis=0)


def entropy(p):
    """``H(p) = - sum_t p_t log p_t`` over the first axis, ``0 log 0 = 0``."""
    return -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                              0.0), axis=0)


def apply(params: Dict, batch, config: OuroConfig, attn_fn: Callable = None,
          passes: str = "scan"):
    """``batch`` -> (a position's next-token loss after every pass
    [T, B, S], the gates [T, B, S]), both f32. The passes give ``h(1..T)``
    [T, B, S, D]; the readouts and the gates follow them, outside the loop:
    one blocked readout over the ``T x B`` sequences, so the head's gradient
    has one accumulator and is whole before the passes' backward loop
    starts."""
    c = config
    if passes not in PASSES:
        raise ValueError(f"passes {passes!r}: one of {PASSES}")
    attn_fn = attn_fn or make_attn_fn("full")
    b, seq = batch["inputs"].shape
    u = jnp.take(params["embed"]["tokens"], batch["inputs"],
                 axis=0).astype(c.dtype)
    if passes == "scan":
        def once(u, _):
            h = one_pass(params, u, c, attn_fn)
            return h, h

        _, hs = jax.lax.scan(once, u, None, length=c.total_ut_steps)
    else:
        each = []
        for _ in range(c.total_ut_steps):
            u = one_pass(params, u, c, attn_fn)
            each.append(u)
        hs = jnp.stack(each)
    with jax.named_scope(phases.HEAD):
        # [T x B, block, V] logits at a time: HEAD_BLOCK positions in all
        block = max(HEAD_BLOCK // c.total_ut_steps, 1)
        nll = blocked_head_nll(
            hs.reshape(-1, seq, hs.shape[-1]), params["head"]["kernel"],
            jnp.tile(batch["targets"], (c.total_ut_steps, 1)),
            block if seq % block == 0 else seq).reshape(-1, b, seq)
    with jax.named_scope(phases.EXIT):
        lam = exit_gate(params["gate"], hs)
    return nll, lam


def make_loss_fn(config: OuroConfig, attn: str = "full",
                 passes: str = "scan", **attn_kw):
    """``loss_fn(params, batch) -> (loss, aux)`` for pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn, has_aux=True)``. ``attn`` is 'full' or
    'flash' (``models/blocks.py::make_attn_fn``); ``passes`` 'scan' or
    'unroll' (module docstring). ``loss`` is the whole objective; ``aux`` the
    module docstring's five."""
    attn_fn = make_attn_fn(attn, **attn_kw)
    c = config

    def loss_fn(params, batch):
        nll, lam = apply(params, batch, c, attn_fn, passes)
        with jax.named_scope(phases.EXIT):
            p = exit_distribution(lam)                       # [T, B, S]
            ce = jnp.mean(jnp.sum(p * nll, axis=0))
            exit_entropy = jnp.mean(entropy(p))
            loss = ce - c.exit_entropy_beta * exit_entropy
            order = jnp.arange(1, c.total_ut_steps + 1, dtype=jnp.float32)
            aux = {"ce": ce, "ce_pass": jnp.mean(nll, axis=(1, 2)),
                   "exit_mass": jnp.mean(p, axis=(1, 2)),
                   "exit_entropy": exit_entropy,
                   "expected_passes": jnp.mean(
                       jnp.tensordot(order, p, axes=1))}
        return loss, aux

    return loss_fn
