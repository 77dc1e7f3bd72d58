"""Fixtures of the benchmark's own tests, run on the CPU by the tier-1
suite."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for _p in (REPO, REPO / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

CACHE_FLAGS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def cpu_jax():
    """JAX with its persistent compilation cache off for the test (the
    harness points it into its checkout), and the flags restored after."""
    import jax
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield jax
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
