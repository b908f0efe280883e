"""Where JAX keeps compiled executables between runs.

Compiling AlexNet's kernels takes seconds per process; JAX's persistent
compilation cache lets a later run of the same checkout load them
instead. Entry points call ``enable_compile_cache()`` at the start of
``main`` — never at import, so tests and library users keep JAX's own
defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

# the checkout root: src/repro/launch/compile_cache.py -> ../../..
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself. Otherwise the cache goes to ``<checkout>/.jax_cache``, a
    fixed path: the path is part of what JAX caches against, so a
    directory that moved between runs would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
