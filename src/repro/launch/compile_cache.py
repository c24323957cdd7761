"""Persistent JAX compilation cache at a fixed path.

Entry points call ``enable_compile_cache()`` before their first compile, so
a process that starts after another reuses its compiled programs instead of
compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, never one built from a temporary name, a process id or the
    time, so that a later run finds what an earlier one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
