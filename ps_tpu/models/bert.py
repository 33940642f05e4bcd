"""BERT-base MLM — reference workload config 3 (BASELINE.json: "BERT-base MLM
(dense grads + server-side LAMB optimizer)"; SURVEY.md §3 row 15). The
reference was unreadable (SURVEY.md §0), so this is a standard BERT encoder
written TPU-first:

- bfloat16 compute / float32 params: attention and FFN matmuls are
  MXU-shaped ([B*S, H] x [H, 4H] etc.); LayerNorm and the softmax run in
  float32 for numerics.
- Attention is explicit einsum (no dynamic shapes, no python control flow) —
  XLA fuses scale+mask+softmax into the matmul pipeline.
- The MLM decoder ties to the token embedding (standard BERT weight tying),
  which also keeps the dominant [V, H] matrix a single sharded tensor.
- The training loss (:func:`make_mlm_loss_fn`) runs the MLM head on the
  labelled positions only, as the published trainer does
  (google-research/bert ``run_pretraining.py`` gathers its
  ``masked_lm_positions`` before the transform). ``BertMLM.apply`` without
  ``positions`` still returns every position's logits ``[B, S, V]``.

The head on the labelled positions
----------------------------------
The data generator labels a position with probability 0.15, and the head
(transform, GELU, LayerNorm, the ``[H, V]`` decoder, its softmax) on all
16,384 positions of a step was 19% of BERT-base's step on the v5e, the five
longest device ops of the program, 85% of it multiplied by 0 in the loss.

The count of labels is data, the shapes are static, and no label may be
dropped. So the batch is cut into groups of whole consecutive sequences of
at least ``_GROUP_POSITIONS`` positions (:func:`head_groups`; the group axis
leads every array, so a batch sharded over its leading axis stays where it
is: no collective but two scalar reductions, the labels' count and the
trips); a labelled position's slot is its rank among its group's labels (a
cumulative sum); and a **trip** runs the head on the rows of ``rows`` = a
quarter of a group's positions consecutive slots, picked by a 0/1 matrix a
group on the MXU (exact; 13 GFLOP a step; its transpose is the gather's
backward, so there is no gather and no scatter). The first trip is
straight-line code; further trips are a ``fori_loop`` whose bound the step
reads from its own count, ``ceil(fullest group's labels / rows)``: exact at
any count, and no buffer of ``[B, S, V]`` is ever compiled. The backward is a
``custom_vjp`` with the same loop, which runs each trip's head again and
takes its gradient at once, so nothing of a trip outlives it and a trip not
taken leaves no zero-filled residual behind.

Measured on one TPU v5e (my chip run, PR 37, ``chiprun_out/
pr37_head_bench_L0.json``): BERT-base's embeddings and head without encoder
layers, forward + backward, ms a step, at [32, 512] / [128, 128]:

====================================  ==============  =============  ==============
labels                                15%             30%            every position
====================================  ==============  =============  ==============
head on every position (the parent)   21.46 / 21.43   21.45 / 21.42  21.25 / 21.21
(a) one trip at a static capacity,    9.52 / 9.55     29.19 / 29.22  28.98 / 29.03
``lax.cond`` to the full head when a
group overflows, the ``cond`` again
in a ``custom_vjp``'s backward
(b) the loop, groups of 1,024 (this)  **7.72 / 7.69** 14.71 / 14.66  28.60 / 28.44
====================================  ==============  =============  ==============

(b) is 1.8 ms ahead of (a) on the common path, where the ``cond`` keeps XLA
from fusing across it and from sharing the two branches' buffers, costs
half of (a) at twice the labels, and the same with every position labelled
(7.3 ms over the parent there: four trips' logits computed twice). Groups of
512 / 1,024 / 2,048 / 4,096 positions at 15%: 7.48 / 7.72 / 8.03 / 7.94 ms
at [32, 512], 7.68 / 7.69 / 8.03 / 7.93 at [128, 128] ((a): 9.14-9.99): the
0/1 matrices grow with the group. 1,024 it is: a quarter is 256 rows
against 153.6 +- 11.4 labels (nine sigma; 512 positions would leave a
20%-masked batch a second trip one step in thirteen), and a chip's share of a
batch has only to be a multiple of 1,024 positions (8 x 128, 2 x 512) for
the groups to fall on chips.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ps_tpu.obs.metrics import default_registry


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_len: int = 512
    type_vocab_size: int = 2
    dtype: Any = jnp.bfloat16
    # 'full' = explicit einsum attention; 'flash' = the Pallas fused
    # kernel (ps_tpu/ops/flash_attention.py) — O(S) attention memory, the
    # seq-512 MFU lever measured in BASELINE.md r5. Sequence length must
    # be a multiple of 128 for 'flash'.
    attn: str = "full"

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        """Test-sized config (2 layers, 64 wide)."""
        defaults = dict(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=4, intermediate_size=128, max_len=64,
                        dtype=jnp.float32)
        defaults.update(kw)
        return BertConfig(**defaults)


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        head_dim = cfg.hidden_size // cfg.num_heads
        dense = lambda name: nn.DenseGeneral(  # noqa: E731
            (cfg.num_heads, head_dim), dtype=cfg.dtype,
            param_dtype=jnp.float32, name=name,
        )
        q = dense("query")(x)  # [B, S, h, d]
        k = dense("key")(x)
        v = dense("value")(x)
        if cfg.attn == "flash":
            from ps_tpu.ops import flash_attention

            out = flash_attention(q, k, v, mask=mask)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
            # mask: [B, S] with 1 = attend; softmax in f32
            bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9)
            probs = nn.softmax(
                scores.astype(jnp.float32) + bias
            ).astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.DenseGeneral(
            cfg.hidden_size, axis=(-2, -1), dtype=cfg.dtype,
            param_dtype=jnp.float32, name="out",
        )(out)


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=1e-12, dtype=jnp.float32, param_dtype=jnp.float32, name=name
        )
        # post-LN (original BERT): sublayer -> residual -> LayerNorm
        a = SelfAttention(cfg, name="attention")(x, mask)
        x = ln("ln_attention")(x + a).astype(cfg.dtype)
        h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="intermediate")(x)
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                     param_dtype=jnp.float32, name="output")(h)
        return ln("ln_output")(x + h).astype(cfg.dtype)


class BertMLM(nn.Module):
    """BERT encoder + tied-embedding MLM head.

    ``__call__(input_ids, attention_mask, token_type_ids=None,
    positions=None) -> logits`` in float32: ``[B, S, V]`` without
    ``positions``; with ``positions`` (int32 ``[B, R]``, the published
    trainer's ``masked_lm_positions``) ``[B, R, V]``, the head run on those
    positions' hidden states only. :meth:`encode` and :meth:`head` are the
    two halves, which :func:`make_mlm_loss_fn` applies on their own.
    """

    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        embed = functools.partial(nn.Embed, features=cfg.hidden_size,
                                  param_dtype=jnp.float32)
        ln = functools.partial(nn.LayerNorm, epsilon=1e-12, dtype=jnp.float32,
                               param_dtype=jnp.float32)
        self.token_embed = embed(cfg.vocab_size)
        self.position_embed = embed(cfg.max_len)
        self.type_embed = embed(cfg.type_vocab_size)
        self.ln_embed = ln()
        for i in range(cfg.num_layers):
            setattr(self, f"layer_{i}", EncoderLayer(cfg))
        self.mlm_transform = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                                      param_dtype=jnp.float32)
        self.ln_mlm = ln()
        self.mlm_bias = self.param("mlm_bias", nn.initializers.zeros_init(),
                                   (cfg.vocab_size,), jnp.float32)

    def encode(self, input_ids, attention_mask, token_type_ids=None):
        """Hidden states after the last encoder layer: ``[B, S, H]`` in
        ``cfg.dtype``."""
        cfg = self.cfg
        if input_ids.shape[1] > cfg.max_len:
            raise ValueError(
                f"sequence length {input_ids.shape[1]} exceeds max_len "
                f"{cfg.max_len}; position ids would silently clamp"
            )
        x = self.token_embed(input_ids)
        pos = jnp.arange(input_ids.shape[1])[None, :]
        x = x + self.position_embed(pos)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = x + self.type_embed(token_type_ids)
        x = self.ln_embed(x).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = getattr(self, f"layer_{i}")(x, attention_mask)
        return x

    def head(self, x):
        """MLM head on hidden states ``[..., H]``: transform, GELU,
        LayerNorm, the decoder tied to the token embedding, bias; float32
        logits ``[..., V]``."""
        x = nn.gelu(self.mlm_transform(x), approximate=True)
        x = self.ln_mlm(x).astype(self.cfg.dtype)
        logits = self.token_embed.attend(x) + self.mlm_bias
        return logits.astype(jnp.float32)

    def __call__(self, input_ids, attention_mask, token_type_ids=None,
                 positions=None):
        x = self.encode(input_ids, attention_mask, token_type_ids)
        if positions is not None:
            x = jnp.take_along_axis(x, positions[..., None], axis=1)
        return self.head(x)


def _token_ce(logits, labels):
    """Cross entropy of each position, ``lse(logits) - logits[label]``:
    the vocabulary axis is consumed by a fused reduction and no
    log-probability tensor of the logits' size exists."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    tok = jnp.take_along_axis(logits, labels[..., None], axis=-1)
    return lse - tok[..., 0].astype(jnp.float32)


def mlm_loss(logits, labels, ignore_index: int = -100):
    """Mean cross-entropy over masked positions only (labels == ignore_index
    elsewhere, matching the data generator's contract), of logits that
    cover every position: what a caller of ``BertMLM.apply`` has. The
    training loss, :func:`make_mlm_loss_fn`, never builds those logits.

    Logsumexp form (:func:`_token_ce`) instead of gathering from a
    materialized log_softmax. Same math to fp tolerance (tests/test_bert.py
    pins it)."""
    valid = labels != ignore_index
    ce = _token_ce(logits, jnp.where(valid, labels, 0))
    n = jnp.maximum(valid.sum(), 1)
    return (ce * valid).sum() / n


#: Positions a group holds at least, and the share of a group's positions
#: that one trip of the head runs on (the module docstring's table).
_GROUP_POSITIONS = 1024
_TRIP_SHARE = 4


def head_groups(batch: int, seq_len: int):
    """``(sequences a group, rows a trip)`` of the labelled head for a batch
    of ``[batch, seq_len]``: a group is the fewest whole consecutive
    sequences that hold ``_GROUP_POSITIONS`` positions (the whole batch if
    none do), a trip a quarter of a group's positions in whole sublanes."""
    per_group = next((g for g in range(1, batch + 1) if batch % g == 0
                      and g * seq_len >= _GROUP_POSITIONS), batch)
    positions = per_group * seq_len
    return per_group, min(positions, -(-positions // (8 * _TRIP_SHARE)) * 8)


def _head_slots(labels, rows, ignore_index):
    """``labels`` ``[groups, P]`` -> ``(slot, trips)``: a labelled position's
    place among its group's labelled positions (-1 elsewhere), and the
    trips of ``rows`` slots that reach the fullest group's last label."""
    valid = labels != ignore_index
    count = jnp.cumsum(valid, axis=1, dtype=jnp.int32)
    slot = jnp.where(valid, count - 1, -1)
    return slot, -(-jnp.max(count[:, -1]) // rows)


def _rows_ce(model, rows, head_params, x, labels, slot, first):
    """Summed cross entropy of the labelled positions in slots ``[first,
    first + rows)`` of every group. ``x`` ``[groups, P, H]``; the rows are
    selected by a 0/1 matrix a group (exact: one product a sum, and the
    gather's backward is the transposed product, no scatter), and the group
    axis stays in front of everything, so a batch sharded over its leading
    axis stays where it is."""
    sel = slot[:, None, :] == first + jnp.arange(rows)[None, :, None]
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    picked = jnp.einsum("grp,gph->grh", sel.astype(x.dtype), x,
                        precision=exact)
    picked_labels = jnp.sum(jnp.where(sel, labels[:, None, :], 0), axis=-1)
    logits = model.apply({"params": head_params}, picked,
                         method=BertMLM.head)
    # a slot past its group's count picked nothing: a row of zeros
    return jnp.sum(jnp.where(sel.any(axis=-1),
                             _token_ce(logits, picked_labels), 0.0))


_HEAD_PARAMS = ("token_embed", "mlm_transform", "ln_mlm", "mlm_bias")

_reg = default_registry()  # holds its instruments weakly: these live here
_head_rows = _reg.gauge(
    "ps_mlm_head_rows",
    "rows the MLM head runs on in a step whose labels fit one trip")
_head_positions = _reg.gauge(
    "ps_mlm_head_positions", "positions of a step's batch (batch x seq_len)")
_head_overflow = _reg.counter(
    "ps_mlm_head_overflow_total",
    "trips of the MLM head beyond a step's first (count_head_overflow)")


def count_head_overflow(labels, ignore_index: int = -100) -> int:
    """Trips beyond the first that the head makes on a batch with these
    ``labels`` (a host array ``[B, S]``), added to
    ``ps_mlm_head_overflow_total``: the step's own count, taken on the host
    by whoever holds the batch there (``examples/train_bert_mlm.py``). The
    step raises nothing itself: a host callback in a TPU program keeps jax
    from writing it to the persistent compile cache."""
    labels = np.asarray(labels)
    per_group, rows = head_groups(*labels.shape)
    count = (labels != ignore_index).reshape(-1, per_group * labels.shape[1])
    extra = max(-(-int(count.sum(axis=1).max()) // rows) - 1, 0)
    _head_overflow.inc(extra)
    return extra


def _summed_ce(model, rows):
    """``summed_ce(head_params, x, labels, slot, trips)``: the cross entropy
    summed over every labelled position of ``x`` ``[groups, P, H]``, trip by
    trip of ``rows`` slots a group (:func:`_rows_ce`): the first trip
    straight-line, the others a loop ``trips`` bounds, and the backward the
    same loop."""
    ce = functools.partial(_rows_ce, model, rows)

    @jax.custom_vjp
    def summed_ce(head_params, x, labels, slot, trips):
        return jax.lax.fori_loop(
            1, trips,
            lambda k, total: total + ce(head_params, x, labels, slot,
                                        k * rows),
            ce(head_params, x, labels, slot, 0))

    def forward(*args):
        return summed_ce(*args), args

    def backward(saved, g):
        # each trip's head again, and its gradient at once: nothing of a
        # trip outlives it, and the trips are the forward's
        head_params, x, labels, slot, trips = saved

        def grads(first):
            return jax.vjp(lambda p, x: ce(p, x, labels, slot, first),
                           head_params, x)[1](g)

        return jax.lax.fori_loop(
            1, trips,
            lambda k, acc: jax.tree_util.tree_map(jnp.add, acc,
                                                  grads(k * rows)),
            grads(0)) + (None, None, None)

    summed_ce.defvjp(forward, backward)
    return summed_ce


def make_mlm_loss_fn(model, ignore_index: int = -100):
    """PS-step loss closure: ``loss_fn(params, batch) -> loss`` over the
    data generator's {input_ids, labels, attention_mask} dict batches: the
    mean cross entropy over the labelled positions, :func:`mlm_loss` of the
    full logits to rounding, with the head run on the labelled positions
    only (the module docstring says how)."""

    def loss_fn(params, batch):
        labels = batch["labels"]
        b, s = labels.shape
        per_group, rows = head_groups(b, s)
        _head_rows.set(b // per_group * rows)
        _head_positions.set(b * s)
        x = model.apply({"params": params}, batch["input_ids"],
                        batch["attention_mask"], method=BertMLM.encode)
        grouped = labels.reshape(b // per_group, per_group * s)
        total = _summed_ce(model, rows)(
            {k: params[k] for k in _HEAD_PARAMS},
            x.reshape(*grouped.shape, -1), grouped,
            *_head_slots(grouped, rows, ignore_index))
        return total / jnp.maximum(jnp.sum(labels != ignore_index), 1)

    return loss_fn


def bert_partition_rules():
    """Megatron tensor-parallel placement for :class:`BertMLM` params
    (pass to ``KVStore(partition_rules=...)`` on a mesh with a 'model'
    axis): Q/K/V shard the HEADS dim (column-parallel with their biases),
    the attention out-projection and the FFN output are row-parallel
    (biases replicate — they add after the contraction's psum), the FFN
    intermediate is column-parallel. Embeddings/LayerNorms are left to the
    default heuristic. Parity vs pure data parallelism is asserted in
    tests/test_bert.py."""
    return [
        (r"attention/(query|key|value)/kernel$", (None, "model", None)),
        (r"attention/(query|key|value)/bias$", ("model", None)),
        (r"attention/out/kernel$", ("model", None, None)),
        (r"attention/out/bias$", (None,)),
        (r"/intermediate/kernel$", (None, "model")),
        (r"/intermediate/bias$", ("model",)),
        (r"/output/kernel$", ("model", None)),
        (r"/output/bias$", (None,)),
    ]
