"""The backend the Pallas kernels run on, and JAX's compile cache.

One switch decides how every ``pallas_call`` in the repo runs: on the
CPU the kernels run in Pallas interpret mode (the test suite), on a
TPU Mosaic compiles them.  Nothing else picks interpret mode, so a run
on the chip cannot quietly interpret a kernel.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout: the cache key includes the path, so a
# directory that moves between runs never hits
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret() -> bool:
    """True on the CPU (Pallas interpret mode), False on a TPU (Mosaic
    compiles); any other backend has no Pallas path here."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas path for JAX backend {backend!r}: the kernels run "
        "compiled on a TPU or interpreted on the CPU")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it
    is (JAX reads it itself); otherwise the cache lives at
    ``<checkout>/.jax_cache``.  Entry points call this; importing the
    package never does."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
