"""JAX's persistent compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile, so a second run of
the same programs reads them back instead of compiling again.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what a later run must find again (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    is set here; otherwise the cache lives at :data:`DEFAULT_DIR`."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
