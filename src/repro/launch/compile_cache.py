"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that moves
between runs never hits. Launchers call :func:`enable` before their first
compile:

- with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and nothing
  here changes it;
- otherwise the cache goes to ``<repo>/.jax_cache`` (gitignored), a fixed
  path beside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
