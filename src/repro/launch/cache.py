"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it itself and
nothing here overrides it); otherwise the cache lives at ``.jax_cache/`` in
the checkout root. Only entry points call :func:`use_compile_cache` — tests
and library code never turn the cache on.
"""

from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    The key includes the programs' HLO metadata (JAX strips it by
    default), so a program whose ``jax.named_scope`` s changed compiles
    anew and a profiler trace names the scopes of the source that ran."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
