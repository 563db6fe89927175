"""Persistent compilation cache placement (``repro.launch.compile_cache``):
``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache sits at
the fixed ``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, configure_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_default_cache_dir_is_fixed_under_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = configure_compile_cache()
        assert got == str(ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert configure_compile_cache() == got  # same path on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_env_cache_dir_is_used_and_written(tmp_path):
    script = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
got = configure_compile_cache()
assert jax.config.jax_compilation_cache_dir == got, got
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print("DIR", got)
"""
    env = dict(
        os.environ,
        PYTHONPATH=str(ROOT / "src"),
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=str(ROOT), timeout=300,
    )
    assert f"DIR {tmp_path}" in out.stdout, out.stderr[-2000:]
    assert any(tmp_path.iterdir()), "nothing was written to the cache dir"
