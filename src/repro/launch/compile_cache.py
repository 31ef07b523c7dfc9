"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is keyed in part by the cache directory's path, so the
directory must not move between runs: a fixed ``<repo>/.jax_cache`` (listed
in ``.gitignore``) unless ``JAX_COMPILATION_CACHE_DIR`` places it elsewhere.
"""
from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing here overrides it.  Otherwise the cache goes to
    ``DEFAULT_DIR``.  Call before the first compile.
    """
    path = os.environ.get(ENV_DIR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
