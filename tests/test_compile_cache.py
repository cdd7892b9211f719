"""JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
wins when set, else the fixed ``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _restore(old_dir, old_on):
    jax.config.update("jax_compilation_cache_dir", old_dir)
    jax.config.update("jax_enable_compilation_cache", old_on)


def test_default_dir_is_the_checkouts(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    try:
        path = compile_cache.enable()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        _restore(*old)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_env_dir_wins_and_receives_the_entries(tmp_path):
    """In a fresh process: the compiled program lands in the directory
    the environment names, and nowhere in the checkout."""
    cache = tmp_path / "cache"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()\n")
    before = set(os.listdir(ROOT / ".jax_cache")) \
        if (ROOT / ".jax_cache").is_dir() else set()
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=tmp_path,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src"),
             "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(cache),
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir())
    after = set(os.listdir(ROOT / ".jax_cache")) \
        if (ROOT / ".jax_cache").is_dir() else set()
    assert after == before
