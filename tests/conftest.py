"""Test environment: force CPU with 8 virtual devices BEFORE jax imports.

This is the TPU-native analogue of the reference family's multi-process
localhost tests (SURVEY.md §5): a real Mesh, real psum/all_to_all collectives,
no TPU needed.
"""

import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

import ps_tpu  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_ps():
    """Every test starts uninitialized."""
    if ps_tpu.is_initialized():
        ps_tpu.shutdown()
    yield
    if ps_tpu.is_initialized():
        ps_tpu.shutdown()


@pytest.fixture(scope="session")
def listed_for():
    """``cell -> the per-layer entries BENCHMARK.json lists for it``, its own
    and the list-less, asked as ``benchmark/run.py`` asks: what a test may
    know of a cell's metrics beside the readers' own names. A cell's own
    test asks of them one at least for each end-to-end metric they move,
    and no count, position or prefix."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    return lambda cell: [m for m in per_layer
                         if cell in m.get("workloads", [cell])]
