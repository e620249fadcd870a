"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`place_compile_cache` once before their first compile; importing the
library sets nothing.  The cache key includes the directory, so the path is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself),
otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def place_compile_cache(checkout: str | os.PathLike = CHECKOUT) -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(checkout) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
