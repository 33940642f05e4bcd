"""Device-mesh construction.

The reference organizes processes into worker/server/scheduler roles over
ZMQ; on TPU those roles become axes of a ``jax.sharding.Mesh``: the 'data'
axis is simultaneously the worker set (batch parallelism) and the server set
(parameter-shard ownership). Additional axes ('model', 'seq', ...) slot in
for tensor/sequence parallelism without changing the PS API.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(mesh_shape: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from a ``{axis_name: size}`` dict.

    Default: all visible devices on one 'data' axis. On real TPU slices,
    ``jax.experimental.mesh_utils.create_device_mesh`` picks an ICI-friendly
    device order; on CPU/virtual devices a plain reshape is used.
    """
    if devices is None:
        devices = jax.devices()
    if mesh_shape is None:
        mesh_shape = {DATA_AXIS: len(devices)}
    names = tuple(mesh_shape)
    shape = tuple(int(s) for s in mesh_shape.values())
    if any(s < 1 for s in shape):
        raise ValueError(f"mesh axes must be >= 1, got {mesh_shape}")
    needed = math.prod(shape)
    if needed > len(devices):
        raise ValueError(
            f"mesh shape {mesh_shape} needs {needed} devices, "
            f"have {len(devices)}"
        )
    devices = list(devices)[:needed]  # explicit smaller meshes are allowed
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        arr = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, names)


def parse_mesh(spec: str) -> Dict[str, int]:
    """Parse a CLI mesh string like ``"data=2,model=2,seq=2"`` into the
    ``{axis: size}`` dict :func:`make_mesh` takes — ONE spelling shared by
    every trainer that exposes a mesh flag."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        if "=" not in part:
            raise ValueError(f"bad mesh component {part!r}; want axis=size")
        k, v = part.split("=", 1)
        out[k.strip()] = int(v)
    return out
