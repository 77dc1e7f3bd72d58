"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``launch/train.py``)
call ``enable_compile_cache()`` first thing in ``main``; nothing calls it on
import, so tests keep JAX's defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
cache directory and this sets nothing. Otherwise the cache lives at a fixed
path inside the checkout, ``<repo>/.jax_cache`` (gitignored): a fixed path,
because the directory is part of the cache key — a name built from a
temporary directory, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory the entry points use (see module docstring)."""
    return os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
