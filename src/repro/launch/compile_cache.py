"""Persistent XLA compile cache for the command-line entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` on its own.  Where that is unset, an
entry point calls :func:`use_compile_cache` so that its compiled programs
land in one fixed directory of the checkout, and the next run in the same
checkout loads them instead of compiling again.  The cache key includes the
directory, so the path never moves.  Library code and tests set no cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax


def checkout_cache_dir() -> Path:
    """``.jax_cache/`` at the root of the checkout this package runs from
    (``<root>/src/repro/launch/compile_cache.py``)."""
    root = Path(__file__).resolve().parents[3]
    if not ((root / "pyproject.toml").is_file() and (root / "src" / "repro").is_dir()):
        raise RuntimeError(
            f"repro is not running from a source checkout ({__file__}); "
            "set JAX_COMPILATION_CACHE_DIR to choose a compile cache"
        )
    return root / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the checkout's cache unless ``JAX_COMPILATION_CACHE_DIR``
    names one; return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(checkout_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
