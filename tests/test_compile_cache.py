"""Where the entry points keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


def test_default_cache_dir_is_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.setup_compile_cache()
        assert got == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins_and_receives_the_cache(tmp_path):
    cache = tmp_path / "jaxcache"
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import setup_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "print(setup_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0))"
        ".block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(cache)
    assert cache.is_dir() and any(cache.iterdir())
