"""Persistent XLA compilation cache for the command-line entry points.

Called by ``launch/serve.py``, ``benchmarks/run.py`` and ``chip_smoke.py``
before their first compile — never by library code, so the tests compile
without it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_ROOT"]

#: the repository checkout this package was imported from
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory; JAX reads
    that variable itself, so nothing is set here. Otherwise the cache is
    the fixed ``.jax_cache/`` at the checkout root: the directory is part
    of where a later run looks, so a temporary or per-process path would
    never be hit again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
