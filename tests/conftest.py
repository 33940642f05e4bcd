"""Test environment: force CPU with 8 virtual devices BEFORE jax imports.

This is the TPU-native analogue of the reference family's multi-process
localhost tests (SURVEY.md §5): a real Mesh, real psum/all_to_all collectives,
no TPU needed.
"""

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
