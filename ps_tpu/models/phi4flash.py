"""Phi-4-mini-flash-reasoning: SambaY, a decoder whose second half reads the
first half's memory (Microsoft, ``model_type: phi4flash``; 32 layers, hidden
2,560, 40 query heads on 20 K/V heads of 64, a 10,240-wide SwiGLU in every
layer, ``mb_per_layer`` 2, ``sliding_window`` 512, LayerNorms at 1e-5, no
positions anywhere, one tensor for the embedding and the head; Ren et al.,
arXiv:2507.06607). The store's first model in which a layer reads anything
but the residual stream. ``kinds`` gives a layer's kind from its published
index ``i`` alone, ``L`` layers in all:

    | ``i``                 | kind           | the mixer |
    | even, ``i < L/2``     | mamba          | Mamba-1 |
    | odd, ``i < L/2``      | window         | differential attention, ``0 <= q - k < sliding_window`` |
    | ``L/2``               | mamba_memory   | Mamba-1; its scan output is also the memory ``M`` |
    | ``L/2 + 1``           | full           | differential attention over every earlier key; its K, V are handed on |
    | even, ``i >= L/2 + 2``| gmu            | ``(M * silu(x W_in)) W_out`` |
    | odd, ``i >= L/2 + 2`` | cross          | a q of its own over the ``full`` layer's K and V |

Every layer is ``h = x + Mixer(LN(x))``, ``y = h + W_down(silu(g) * u)`` with
``[g | u] = LN'(h) W_gate_up``. Pure functions over a parameter dict, as
``models/granite_h.py``; the equations of each part are written out in the
plain reference's docstring (``benchmark/families/phi4flash_reference.py``),
which this module is held to. How they are computed here:

- the Mamba-1 mixer (``mamba1_block``): ``[x | z] = u W_in`` (which bears the
  name 'mamba_in'); the taps, bias and SiLU of ``ops/gated_conv.py::
  conv_silu``; ``[delta | B | C] = x W_x``; ``dt = softplus(delta W_dt +
  b_dt)`` in f32; the scan is ``ops/selective_scan.py`` (a decay for every
  channel and state: ``A`` is [5120, 16], which ``ops/ssd.py``'s scalar-decay
  form cannot express), f32 inside: at the published width two Mosaic calls,
  forward and backward, with a block of channels' state in VMEM and one state
  kept a tile of tokens (``selective_scan.path``), at the tests' and the
  rehearsal's narrow ones XLA's loops over chunks.
  Its output with the ``D x`` skip, **before** the ``silu(z)`` gate, is what
  a ``mamba_memory`` layer hands on as ``M``, in the compute dtype;
- differential attention (``blocks.diff_attention_block``): two softmax maps
  a head against one value head twice as wide, one call of the attention
  closure at keys of 64 and values of 128, the combine in f32 with
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` at the **published** index ``i``;
  ``window`` layers pass ``window=sliding_window`` (the kernel's band step),
  ``full`` sees every earlier key and hands its K and V on, ``cross``
  projects a q alone and reads them;
- the gated memory unit (``blocks.gmu_block``);
- the head: a final LayerNorm and the embedding transposed, the loss in
  blocks of ``HEAD_BLOCK`` positions (``blocks.blocked_head_nll``: the
  slice's 25,008 logits of 16,384 positions are 1.6e9 B in f32, never
  formed); the embedding's gradient is the sum of the lookup's and the
  head's.

**A cut of the stack** is ``num_hidden_layers`` consecutive layers from
``first_layer`` of a model of ``model_layers``: kinds, ``lambda_init`` and the
parameters' names (``layer<i>``) are the published index's, so the cut's
layers are the model's (``tests/test_phi4flash.py``). A ``gmu`` or ``cross``
layer without its producer in the cut is refused.

Every layer runs under one ``jax.checkpoint`` that takes and returns the
residual stream **and what later layers read**: ``M`` [B, S, d_inner] and the
pair K, V [B, S, h_kv, d] are outputs of the layer that makes them and inputs
of every layer behind it, so they live from their producer's forward to its
backward (168 MB and 84 MB in the cell, bf16) and no consumer recomputes the
first half; their cotangents are the sum over the consumers, which JAX's
transposition makes across the checkpoints. The policy lists by name what a
layer keeps beside that: ``ops/flash_attention.py::KEPT`` (the flash output
and logsumexp, which only the forward kernel can produce) and
``PRODUCTS_KEPT``, beside ``_layer``: a mixer's in projection and an
attention layer's q | k | v product, argued there from the run's peak
(14.38e9 B of the chip's 17.18e9 with them, 84%).

What the model does not compute, ``Phi4FlashConfig.from_dict`` refuses.

The loss is the next-token cross entropy alone::

    step = store.make_step(make_loss_fn(config))
    loss, params = step(batch)

The phases a trace can tell apart are opened here and in ``models/blocks.py``
with ``jax.named_scope`` (``obs/phases.py::PHI4FLASH_SCOPES``); they nest
under the step's ``ps.grad``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ps_tpu.models.blocks import (blocked_head_nll, diff_attention_block,
                                  gmu_block, layer_norm, make_attn_fn)
from ps_tpu.obs import phases
from ps_tpu.ops.flash_attention import KEPT
from ps_tpu.ops.gated_conv import conv_silu
from ps_tpu.ops.selective_scan import selective_scan

KINDS = ("mamba", "window", "mamba_memory", "full", "gmu", "cross")
#: positions whose logits are formed at a time
HEAD_BLOCK = 2048


def kinds(num_hidden_layers: int, mb_per_layer: int) -> Tuple[str, ...]:
    """The kind of every layer of the whole model (module docstring's
    table): a Mamba-side layer every ``mb_per_layer``-th index, an attention
    one between; the second half's read the first's."""
    if num_hidden_layers % 4 or mb_per_layer != 2:
        raise ValueError(
            f"{num_hidden_layers} layers with mb_per_layer {mb_per_layer}: "
            f"the pattern is written for a multiple of four layers and a "
            f"Mamba-side layer at every second")
    half = num_hidden_layers // 2
    return tuple(
        ("mamba" if i < half else "mamba_memory" if i == half else "gmu")
        if i % mb_per_layer == 0 else
        ("window" if i < half else "full" if i == half + 1 else "cross")
        for i in range(num_hidden_layers))


def lambda_init(depth: int) -> float:
    """Differential attention's constant at the published layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The keys of the published ``config.json`` that shape the model, under
    their published names; ``mamba_*`` and ``time_step_*`` are not in it
    (mamba_ssm's ``Mamba`` defaults). ``first_layer`` and ``model_layers``
    say which layers of which model ``num_hidden_layers`` counts."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    first_layer: int = 0
    model_layers: Optional[int] = None      # the whole model's; None: no cut
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    lambda_std: float = 0.1
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return math.ceil(self.hidden_size / 16)

    @property
    def layers(self) -> Tuple[Tuple[int, str], ...]:
        """(published index, kind) of the layers held here."""
        whole = kinds(self.model_layers or self.num_hidden_layers,
                      self.mb_per_layer)
        held = range(self.first_layer,
                     self.first_layer + self.num_hidden_layers)
        return tuple((i, whole[i]) for i in held)

    @classmethod
    def from_dict(cls, d: Dict) -> "Phi4FlashConfig":
        """From a ``config.json``-like dict; keys this model does not read
        are checked, not dropped in silence, where another value would change
        the mathematics. ``published.num_hidden_layers`` is ``model_layers``
        where the dict is a cut's."""
        for key, want in (("embd_pdrop", 0), ("resid_pdrop", 0),
                          ("hidden_act", "silu"), ("mlp_bias", False),
                          ("lm_head_bias", False),
                          ("tie_word_embeddings", True)):
            if d.get(key, want) != want:
                raise ValueError(f"models/phi4flash.py computes {key}="
                                 f"{want!r} only, not {d[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in names}
        kw.setdefault("model_layers",
                      d.get("published", {}).get("num_hidden_layers"))
        kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16"))
        config = cls(**kw)
        whole = config.model_layers or config.num_hidden_layers
        if config.first_layer + config.num_hidden_layers > whole:
            raise ValueError(
                f"layers {config.first_layer} to {config.first_layer} + "
                f"{config.num_hidden_layers} of a model of {whole}")
        held = [kind for _, kind in config.layers]
        for reader, producer in (("gmu", "mamba_memory"), ("cross", "full")):
            if reader in held and producer not in held:
                raise ValueError(
                    f"a {reader!r} layer reads the {producer!r} layer's "
                    f"output: the cut {held} holds no {producer!r}")
        if config.num_key_value_heads % 2 \
                or config.num_attention_heads % config.num_key_value_heads \
                or config.hidden_size % config.num_attention_heads:
            raise ValueError(
                f"{config.num_attention_heads} query heads on "
                f"{config.num_key_value_heads} K/V heads over a width of "
                f"{config.hidden_size}: pairs of heads, each must divide")
        return config


def init_params(key, config: Phi4FlashConfig) -> Dict:
    """Normal(0, 0.02) matrices and taps, zero biases, LayerNorms at 1 and 0,
    f32; ``dt_proj``'s bias the inverse softplus of ``exp(U(log
    time_step_min, log time_step_max))`` floored at ``time_step_floor``,
    ``A_log = log(1 .. d_state)`` a channel and ``D`` = 1 (mamba_ssm's
    defaults); the four lambda vectors normal(0, ``lambda_std``), the head
    norm's scale 1. One tensor, ``embed/tokens``, is the embedding and the
    head. A layer's leaves are under ``layer<published index>``. Jit it to
    make the tree on the device from the seed."""
    c = config
    d, inner, n = c.hidden_size, c.mamba_inner, c.mamba_d_state
    keys = iter(jax.random.split(key, 1 + 12 * c.num_hidden_layers))

    def lin(*shape, bias=False):
        out = {"kernel": 0.02 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)}
        if bias:
            out["bias"] = jnp.zeros(shape[-1:], jnp.float32)
        return out

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def differential():
        return {**{f"lambda_{name}": c.lambda_std * jax.random.normal(
            next(keys), (c.head_dim,), jnp.float32)
            for name in ("q1", "k1", "q2", "k2")},
            "head_norm": {"scale": jnp.ones((2 * c.head_dim,), jnp.float32)},
            "out": lin(d, d, bias=True)}

    params: Dict = {"embed": {"tokens": lin(c.vocab_size, d)["kernel"]},
                    "final_norm": norm()}
    for i, kind in c.layers:
        lp: Dict = {"norm": norm(), "ffn_norm": norm(),
                    "ffn": {"w_in": lin(d, 2 * c.intermediate_size),
                            "w_out": lin(c.intermediate_size, d)}}
        if kind in ("mamba", "mamba_memory"):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                next(keys), (inner,), jnp.float32, math.log(c.time_step_min),
                math.log(c.time_step_max))), c.time_step_floor)
            lp["mamba"] = {
                "in_proj": lin(d, 2 * inner),
                "conv": {"kernel": lin(inner, c.mamba_d_conv)["kernel"],
                         "bias": jnp.zeros((inner,), jnp.float32)},
                "x_proj": lin(inner, c.dt_rank + 2 * n),
                "dt_proj": {**lin(c.dt_rank, inner),
                            "bias": dt + jnp.log(-jnp.expm1(-dt))},
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (inner, n))),
                "D": jnp.ones((inner,), jnp.float32),
                "out_proj": lin(inner, d)}
        elif kind == "gmu":
            lp["gmu"] = {"in_proj": lin(d, inner), "out_proj": lin(inner, d)}
        else:
            kv = c.num_key_value_heads * c.head_dim
            lp["attn"] = differential()
            if kind == "cross":
                lp["attn"]["q"] = lin(d, d, bias=True)
            else:
                lp["attn"]["qkv"] = lin(d, d + 2 * kv, bias=True)
        params[f"layer{i}"] = lp
    return params


def mamba1_block(lp: Dict, x, config: Phi4FlashConfig):
    """The Mamba-1 mixer of the normed activations ``x`` [B, S, D] (module
    docstring). Returns the mixer's output and the scan's, ``y`` with the
    ``D x`` skip and before the gate, in ``x``'s dtype: the memory."""
    c = config
    inner, n = c.mamba_inner, c.mamba_d_state
    projected = checkpoint_name(
        x @ lp["in_proj"]["kernel"].astype(x.dtype), "mamba_in")
    xs, z = jnp.split(projected, [inner], axis=-1)
    with jax.named_scope(phases.MAMBA_CONV):
        xs = conv_silu(xs, lp["conv"]["kernel"], lp["conv"]["bias"])
    delta, b_in, c_in = jnp.split(
        xs @ lp["x_proj"]["kernel"].astype(x.dtype),
        [c.dt_rank, c.dt_rank + n], axis=-1)
    dt = jax.nn.softplus(
        (delta @ lp["dt_proj"]["kernel"].astype(x.dtype)).astype(jnp.float32)
        + lp["dt_proj"]["bias"])
    with jax.named_scope(phases.MAMBA_S6):
        y = selective_scan(xs, dt, -jnp.exp(lp["A_log"]), b_in, c_in,
                           lp["D"])
    memory = y.astype(x.dtype)
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return gated @ lp["out_proj"]["kernel"].astype(x.dtype), memory


def attention_block(lp: Dict, x, kv, kind: str, depth: int,
                    config: Phi4FlashConfig, attn_fn: Callable):
    """Differential attention of the normed activations ``x`` [B, S, D] in a
    ``window``, ``full`` or ``cross`` layer at published index ``depth``.
    ``kv``: the ``full`` layer's K and V where the layer is ``cross``.
    Returns the mixer's output and the K, V pair behind it (the layer's own,
    or ``kv`` as it came)."""
    c = config
    b, s, _ = x.shape

    def proj(name):
        return x @ lp[name]["kernel"].astype(x.dtype) \
            + lp[name]["bias"].astype(x.dtype)

    if kind == "cross":
        q, (k, v) = checkpoint_name(proj("q"), "attn_qkv"), kv
    else:
        q, k, v = jnp.split(
            checkpoint_name(proj("qkv"), "attn_qkv"),
            [c.hidden_size, c.hidden_size
             + c.num_key_value_heads * c.head_dim], axis=-1)
        k, v = (t.reshape(b, s, c.num_key_value_heads, c.head_dim)
                for t in (k, v))
    q = q.reshape(b, s, c.num_attention_heads, c.head_dim)
    a = diff_attention_block(
        lp, q, k, v, attn_fn, lambda_init=lambda_init(depth),
        eps=c.layer_norm_eps,
        core={"window": phases.ATTN_WINDOW, "full": phases.ATTN_FULL,
              "cross": phases.ATTN_CROSS}[kind],
        window=c.sliding_window if kind == "window" else None)
    out = a @ lp["out"]["kernel"].astype(x.dtype) \
        + lp["out"]["bias"].astype(x.dtype)
    return out, (k, v)


def swiglu(lp: Dict, x, width: int):
    """``W_down(silu(g) * u)`` with ``[g | u] = x W_gate_up``, which bears
    the name 'ffn_in'."""
    both = checkpoint_name(x @ lp["w_in"]["kernel"].astype(x.dtype), "ffn_in")
    return (jax.nn.silu(both[..., :width]) * both[..., width:]) \
        @ lp["w_out"]["kernel"].astype(x.dtype)


#: what a layer keeps beside the flash call's residuals and what it hands on
#: (module docstring), by the names the values bear where they are made: a
#: Mamba-1 mixer's in projection (335 MB a layer at 16,384 tokens in bf16) and
#: an attention layer's q | k | v product (168 MB; the cross layer's q 84 MB).
#: 697M parameters take 11.15e9 B of the chip's 17.18e9 with their moments and
#: gradients; kept, the two raise the run's peak from 14.03e9 to 14.38e9 B
#: (84%) and save five recomputed products, 19.8 ms of a 1,006.6 ms step (my
#: chip runs, PR 65). A layer's SwiGLU product 'ffn_in' is 671 MB: six of them
#: do not fit, and a name cannot keep it in some layers only; it is made again
#: (about 12 ms a layer)
PRODUCTS_KEPT = ("mamba_in", "attn_qkv")


@functools.partial(jax.checkpoint, static_argnums=(4, 5, 6, 7),
                   policy=jax.checkpoint_policies.save_only_these_names(
                       *KEPT, *PRODUCTS_KEPT))
def _layer(lp: Dict, x, memory, kv, kind: str, depth: int,
           config: Phi4FlashConfig, attn_fn: Callable):
    """One layer, the mixer and the SwiGLU each behind its LayerNorm,
    recomputed in the backward pass. Takes and returns the residual stream,
    the memory and the K, V pair: a producer replaces what it makes, every
    other layer hands on what came in (``None`` before the producer)."""
    c = config
    h = layer_norm(x, lp["norm"]["scale"], lp["norm"]["bias"],
                   c.layer_norm_eps)
    if kind in ("mamba", "mamba_memory"):
        with jax.named_scope(phases.MAMBA):
            mixed, made = mamba1_block(lp["mamba"], h, c)
        if kind == "mamba_memory":
            memory = made
    elif kind == "gmu":
        with jax.named_scope(phases.GMU):
            mixed = gmu_block(lp["gmu"], h, memory)
    else:
        with jax.named_scope(phases.ATTN):
            mixed, made = attention_block(lp["attn"], h, kv, kind, depth, c,
                                          attn_fn)
        if kind == "full":
            kv = made
    x = x + mixed
    h = layer_norm(x, lp["ffn_norm"]["scale"], lp["ffn_norm"]["bias"],
                   c.layer_norm_eps)
    with jax.named_scope(phases.FFN):
        x = x + swiglu(lp["ffn"], h, c.intermediate_size)
    return x, memory, kv


def run_layers(params: Dict, x, config: Phi4FlashConfig,
               attn_fn: Callable = None):
    """The held layers over the residual stream ``x`` [B, S, D]."""
    attn_fn = attn_fn or make_attn_fn("full")
    memory = kv = None
    for i, kind in config.layers:
        x, memory, kv = _layer(params[f"layer{i}"], x, memory, kv, kind, i,
                               config, attn_fn)
    return x


def apply(params: Dict, tokens, config: Phi4FlashConfig,
          attn_fn: Callable = None):
    """``tokens`` [B, S] int32 -> final hidden states [B, S, D] before the
    final norm: the embedding, unscaled, and the held layers."""
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0)
    return run_layers(params, x.astype(config.dtype), config, attn_fn)


def final_norm(params: Dict, hidden, config: Phi4FlashConfig):
    norm = params["final_norm"]
    return layer_norm(hidden, norm["scale"], norm["bias"],
                      config.layer_norm_eps)


def logits_of(params: Dict, hidden, config: Phi4FlashConfig):
    """Final norm and the embedding as the head: [B, S, D] -> [B, S, V]."""
    h = final_norm(params, hidden, config)
    return h @ params["embed"]["tokens"].astype(h.dtype).T


def make_loss_fn(config: Phi4FlashConfig, attn: str = "full", **attn_kw):
    """``loss_fn(params, batch) -> loss`` for pre-shifted ``batch =
    {"inputs": [B, S], "targets": [B, S]}``, for
    ``KVStore.make_step(loss_fn)``. ``attn`` is 'full' or 'flash'
    (``models/blocks.py::make_attn_fn``)."""
    attn_fn = make_attn_fn(attn, **attn_kw)

    def loss_fn(params, batch):
        hidden = apply(params, batch["inputs"], config, attn_fn)
        with jax.named_scope(phases.HEAD):
            h = final_norm(params, hidden, config)
            b, s = batch["targets"].shape
            block = next(n for n in range(min(HEAD_BLOCK, s), 0, -1)
                         if s % n == 0)
            sums = blocked_head_nll(
                h, params["embed"]["tokens"].T, batch["targets"], block,
                summed=True)
            return jnp.sum(sums) / (b * s)

    return loss_fn
