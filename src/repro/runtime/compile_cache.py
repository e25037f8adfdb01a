"""Persistent compilation cache for the command-line entry points.

A cold run on the chip is mostly compilation (every engine, every
shape signature). JAX keeps compiled executables across processes in
the directory its ``jax_compilation_cache_dir`` option names. The cache
key includes that path, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The checkout's own cache directory (listed in ``.gitignore``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache before the first
    compile; returns the directory in use. Where the environment sets
    ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and this sets
    nothing; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
