"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.*``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once before they compile; importing
``repro`` and running the tests never turns it on.  The cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of what a later run must find.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the checkout root: src/repro/launch/cache.py -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
