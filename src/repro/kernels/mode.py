"""How this process runs the device kernels: compiled or interpreted, and
where compiled programs are cached.

The backend decides the mode, here and nowhere else: compiled on the TPU,
the Pallas interpreter on the CPU (the test suite runs with
``JAX_PLATFORMS=cpu``). No other backend has a mode, so asking on one is
an error rather than a silent interpreter run.
"""
import os
from pathlib import Path

import jax

#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (the path is part of the cache key, so a
#: directory that moved between runs would never hit)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret() -> bool:
    """True on the CPU, False on the TPU; raises on any other backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas kernel mode for the {backend!r} backend")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory:
    ``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it itself,
    and no other directory is set), else :data:`CHECKOUT_CACHE_DIR`.

    For entry points (``chip_smoke.py``, the benchmarks); the library
    never calls it on import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
