"""The persistent JAX compilation cache of this repository's programs.

``enable()`` is called at the top of a program (``bench/run.py``, the
examples), never as a library module is imported. A cache directory is
part of the cache's key, so it must not move between runs: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing is set
here; otherwise the cache lives in the fixed ``.jax_cache/`` directory
of this checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                         "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
