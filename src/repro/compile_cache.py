"""JAX's persistent compilation cache, placed from outside the program.

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and wins: the
program then sets no directory in code. Otherwise `enable(root)` puts the
cache at one fixed path inside the checkout, `<root>/.jax_cache`
(gitignored). The path is part of the cache key, so it is never a temp
name, a pid or a timestamp: a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(root: str) -> str:
    """Turn the persistent cache on and return the directory in use. Every
    program is cached, however fast it compiled: a cold call compiles the
    lockstep engine's many small cycle programs, and each one counts."""
    path = os.environ.get(ENV)
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
