"""Wide-&-Deep for Criteo-style CTR — reference workload config 4
(BASELINE.json: "sparse push/pull: Wide-&-Deep on Criteo (row-sparse
embedding tables)"; SURVEY.md §3 row 16).

The module holds only the DENSE parameters (wide linear + deep MLP); the
embedding tables live in ps_tpu SparseEmbedding stores and their gathered
rows come in as inputs — mirroring the reference split where tables are
server-resident and workers hold only activations. All 26 categorical
features share one row space via per-feature id offsets (the standard
hashed-Criteo layout), so one sharded table serves the deep side (dim D)
and one the wide side (dim 1).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class WideDeepConfig:
    num_dense: int = 13
    num_sparse: int = 26
    per_feature_vocab: int = 100_000
    embed_dim: int = 16
    mlp: Sequence[int] = (256, 128, 64)

    @property
    def total_rows(self) -> int:
        return self.num_sparse * self.per_feature_vocab

    def global_ids(self, sparse_ids):
        """Map per-feature ids [B, F] into the shared row space."""
        offsets = jnp.arange(self.num_sparse, dtype=jnp.int32) * self.per_feature_vocab
        return sparse_ids + offsets[None, :]


class WideDeep(nn.Module):
    """Dense half of Wide-&-Deep: ``(dense, deep_rows, wide_rows) -> logit``.

    deep_rows: [B, F, D] gathered deep-embedding rows.
    wide_rows: [B, F, 1] gathered wide (per-id weight) rows.
    """

    cfg: WideDeepConfig

    @nn.compact
    def __call__(self, dense, deep_rows, wide_rows):
        cfg = self.cfg
        # wide: linear over dense features + sum of per-id weights
        wide = nn.Dense(1, name="wide_dense")(dense) + wide_rows.sum(axis=1)
        # deep: MLP over [dense ; flattened embeddings]
        x = jnp.concatenate(
            [dense, deep_rows.reshape(deep_rows.shape[0], -1)], axis=-1
        )
        for i, width in enumerate(cfg.mlp):
            x = nn.relu(nn.Dense(width, name=f"mlp_{i}")(x))
        deep = nn.Dense(1, name="deep_out")(x)
        return (wide + deep)[..., 0]


def bce_loss(logits, labels):
    """Mean sigmoid binary cross-entropy (labels in {0,1})."""
    logits = logits.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def make_wide_deep_loss_fn(model: WideDeep):
    """Composite-step loss closure for ps_tpu.make_composite_step:
    ``loss_fn(dense_params, rows, batch)`` with rows = {'deep', 'wide'}."""

    def loss_fn(params, rows, batch):
        logits = model.apply(
            {"params": params}, batch["dense"], rows["deep"], rows["wide"]
        )
        return bce_loss(logits, batch["label"])

    return loss_fn


def make_ids_fn(cfg: WideDeepConfig):
    def ids_fn(batch):
        gids = cfg.global_ids(batch["sparse"])
        return {"deep": gids, "wide": gids}

    return ids_fn
