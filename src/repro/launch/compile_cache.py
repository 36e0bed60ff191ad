"""Where JAX's persistent compilation cache lives for this checkout.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once before compiling anything.  When the
environment sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it as its
``jax_compilation_cache_dir`` option and nothing is set here.  Otherwise the
cache goes to ``.jax_cache/`` at the checkout root: a fixed path (the path
is part of the cache key, so a per-run temporary directory would never hit),
ignored by git.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/compile_cache.py -> parents[3]
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
