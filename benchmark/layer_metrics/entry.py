"""entry layer: ``ps.init``, ``TpuBackend``, the compile cache. From jax's
monitoring events (harness/compilelog.py)."""


def read(r: dict) -> dict:
    return {
        # seconds inside the compiler or loading from the persistent cache
        # during set-up
        "entry.compile_s": r["setup_compile_s"],
        "entry.compiles_in_window": float(r["compiles_in_window"]),
    }
