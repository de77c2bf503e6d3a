"""Where the persistent XLA compilation cache lives.

Every entry point that compiles (``parts/common.run_part``, the
``examples/``, ``bench.py``, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, so a second
process on the same machine loads LM-large's programs instead of
compiling them again. Launcher children go through ``run_part`` and so
follow the same rule.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# The directory is part of the cache key, so it is one fixed path under
# the checkout — never a temporary name, a pid or the time, which would
# start every process cold.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set in code. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (git-ignored)."""
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
