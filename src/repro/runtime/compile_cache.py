"""Where JAX's persistent compilation cache lives.

Entry points that compile the served path (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once, before their
first jit. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
the cache goes there; nothing else is configured. Otherwise the cache goes to
``<checkout>/.jax_cache`` — a fixed path, because the cache directory is part
of what a later run must find again (never a temporary name, a pid or a
time). Tests do not call this: they compile small shapes and keep no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return Path(configured)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
