"""The backend switch, the chip table, the compile cache, and the VMEM
accounting the DSE plans against."""
import os
import subprocess
import sys

import jax
import pytest

from repro.core import backend, cost, measure
from repro.core.memory import vmem_bytes


def test_interpret_mode_follows_the_backend(monkeypatch):
    assert backend.interpret() is True          # the CPU suite
    assert measure.interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.interpret() is False
    assert measure.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no Pallas path"):
        backend.interpret()


def test_device_kind_lets_errors_through(monkeypatch):
    assert measure.device_kind() == "cpu"

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        measure.device_kind()


def test_chip_table_is_keyed_by_device_kind():
    v5e = cost.chip("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert cost.chip() is v5e                   # a CPU plans for the v5e
    assert (cost.PEAK_FLOPS, cost.HBM_BYTES_PER_S, cost.VMEM_BYTES) == (
        v5e.peak_flops, v5e.hbm_bytes_per_s, v5e.vmem_bytes)
    with pytest.raises(KeyError, match="TPU v9"):
        cost.chip("TPU v9")


@pytest.mark.parametrize("shape,dtype,want", [
    ((4096,), "float32", 8 * 4096 * 4),          # one lane row, 8 sublanes
    ((1024, 8), "float32", 1024 * 128 * 4),      # 8 lanes pad to 128
    ((3, 100, 200), "float32", 3 * 104 * 256 * 4),
    ((16, 512), "bfloat16", 16 * 512 * 2),       # whole bf16 tiles
    ((4, 512), "bfloat16", 16 * 512 * 2),        # bf16 sublane tile is 16
    ((), "float32", 8 * 128 * 4),
])
def test_vmem_bytes_pads_to_the_tile(shape, dtype, want):
    assert vmem_bytes(shape, dtype) == want


def test_compile_cache_dir_from_env_or_checkout(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert backend.enable_compile_cache() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    was = jax.config.jax_compilation_cache_dir
    try:
        path = backend.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compiled_programs_land_in_the_env_cache_dir(tmp_path):
    """A fresh process compiling through an entry point's helper writes
    its programs under ``JAX_COMPILATION_CACHE_DIR``."""
    code = ("from repro.core import backend\n"
            "import jax, jax.numpy as jnp\n"
            "backend.enable_compile_cache()\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert list(tmp_path.iterdir())
