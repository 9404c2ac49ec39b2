"""Where JAX keeps compiled programs between processes.

Every entry point (the launch drivers, the benchmark runner and
``chip_smoke.py``) calls ``enable()`` once at start-up, so a second run
on the same machine reuses the first one's compiled kernels instead of
compiling everything cold.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what the cache is found by — a per-run temp name would never hit.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing. Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
