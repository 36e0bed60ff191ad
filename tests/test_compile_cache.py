"""The persistent compilation cache lands where the environment says, else
at the checkout's fixed ``.jax_cache/``."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import CHECKOUT_ROOT, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_default_is_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(REPO / ".jax_cache") \
            == str(CHECKOUT_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_wins_and_receives_programs(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing is set in code and a
    compiled program is written there (fresh process: the cache dir is
    read at JAX start-up)."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
