"""Server-side optimizers.

The reference holds optimizer state on the server next to each parameter key
and applies SGD/Adam/LAMB per key in C++/CUDA (SURVEY.md §3 row 5, verified).
On TPU the "server" is a sharding of the parameter pytree over the mesh, so
the per-key apply is just an optax update compiled by XLA — state lives
sharded exactly like the parameters ("next to" them in the PS sense).

:func:`make_optimizer` accepts either a name ('sgd' | 'momentum' | 'adam' |
'adamw' | 'lamb') or any optax ``GradientTransformation``, so trainers can register
custom server optimizers the way the reference family allows.
"""

from __future__ import annotations

from typing import Optional, Union

import optax

from ps_tpu.optim.dc import delay_compensate

__all__ = ["make_optimizer", "sgd", "momentum", "adam", "adamw", "lamb", "delay_compensate"]


def sgd(learning_rate: Union[float, optax.Schedule] = 0.01) -> optax.GradientTransformation:
    """Plain SGD — the reference server's default apply rule."""
    return optax.sgd(learning_rate)


def momentum(
    learning_rate: Union[float, optax.Schedule] = 0.01, momentum: float = 0.9, nesterov: bool = False
) -> optax.GradientTransformation:
    return optax.sgd(learning_rate, momentum=momentum, nesterov=nesterov)


def adam(
    learning_rate: Union[float, optax.Schedule] = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    return optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)


def adamw(
    learning_rate: Union[float, optax.Schedule] = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 1e-4,
    clip_by_global_norm: Optional[float] = None,
) -> optax.GradientTransformation:
    """Adam with decoupled weight decay on every tensor, behind an optional
    clip of the whole gradient tree to a global norm: the recipe of decoder
    pre-training (OLMoE, arXiv:2409.02060). The clip is the one server-side
    rule here that reads every gradient before it may write any parameter."""
    opt = optax.adamw(learning_rate, b1=b1, b2=b2, eps=eps,
                      weight_decay=weight_decay)
    if clip_by_global_norm is None:
        return opt
    return optax.chain(optax.clip_by_global_norm(clip_by_global_norm), opt)


def lamb(
    learning_rate: Union[float, optax.Schedule] = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.0,
) -> optax.GradientTransformation:
    """LAMB — the reference uses it server-side for BERT (BASELINE.json
    config 3). Layerwise trust ratios are per parameter tensor, so the update
    is shard-local once each param's norm is computed; under jit on a sharded
    pytree XLA inserts the needed per-tensor norm reduces automatically."""
    return optax.lamb(learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)


_REGISTRY = {
    "sgd": sgd,
    "momentum": momentum,
    "adam": adam,
    "adamw": adamw,
    "lamb": lamb,
}


def make_optimizer(opt: Union[str, optax.GradientTransformation], **kwargs) -> optax.GradientTransformation:
    """Resolve an optimizer name or pass through an optax transformation."""
    if isinstance(opt, str):
        try:
            return _REGISTRY[opt.lower()](**kwargs)
        except KeyError:
            raise ValueError(
                f"unknown optimizer {opt!r}; known: {sorted(_REGISTRY)}"
            ) from None
    if isinstance(opt, optax.GradientTransformation):
        if kwargs:
            raise ValueError("kwargs are only valid with a string optimizer name")
        return opt
    raise TypeError(f"optimizer must be a name or optax.GradientTransformation, got {type(opt)}")
