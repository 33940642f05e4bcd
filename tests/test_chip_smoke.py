"""chip_smoke.py off the chip, the compile cache's home, and the chip table.

The smoke itself only ever runs on a TPU (through the chip tool); tier-1
pins what must hold on a CPU: it refuses to run, says why, and prints no
result; ``ps.init`` leaves the compile cache where ``JAX_COMPILATION_CACHE_DIR``
puts it, or in ``<checkout>/.jax_cache``; and an unknown ``device_kind`` is
an error, not a ``None``.
"""

import os
import subprocess
import sys

import pytest

from ps_tpu.utils.chips import peak_bf16_tflops, peak_hbm_gbps

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one tiny jit through the mesh backend; the salt makes the program (and so
# its cache key) new on every run, and the thresholds let a sub-second
# compile be written at all
_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
import ps_tpu as ps
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
ps.init(backend="tpu")
salt = float(sys.argv[1])
jax.jit(lambda x: x * salt + 1)(jnp.arange(8.0)).block_until_ready()
print(jax.config.jax_compilation_cache_dir)
"""


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.update(extra)
    return env


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _run_cache_probe(tmp_path, **extra):
    """Run the probe from a checkout of its own: a link to the package under
    ``tmp_path``. The cache's home is found from the package's location, so
    it is ``<tmp_path>/checkout/.jax_cache``, which no other test worker
    writes (the repo's own ``.jax_cache`` gains entries from every worker
    that calls ``ps.init`` meanwhile). Returns the cache directory the probe
    reports and its checkout's cache home."""
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    os.symlink(os.path.join(_REPO, "ps_tpu"), checkout / "ps_tpu")
    salt = int.from_bytes(os.urandom(4), "little")
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(salt)],
        env=_env(PYTHONPATH=str(checkout), **extra),
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.strip().splitlines()[-1], str(checkout / ".jax_cache")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")], env=_env(),
        cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    # no result line, and no step was taken on the CPU
    assert '"ok"' not in proc.stdout and "[resnet50]" not in proc.stdout


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    reported, home = _run_cache_probe(tmp_path)
    assert reported == home
    assert _entries(home), "nothing was cached in the checkout"


def test_compile_cache_env_is_left_alone(tmp_path):
    placed = str(tmp_path / "cache")
    reported, home = _run_cache_probe(
        tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert reported == placed
    assert _entries(placed), "nothing was cached where the variable points"
    assert not _entries(home), "the checkout's cache was written too"


def test_unknown_device_kind_is_an_error():
    class Device:
        device_kind = "TPU v5 lite"

    assert peak_bf16_tflops(Device()) == 197.0
    assert peak_hbm_gbps(Device()) == 819.0
    Device.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        peak_bf16_tflops(Device())
    with pytest.raises(ValueError, match="TPU v99"):
        peak_hbm_gbps(Device())
