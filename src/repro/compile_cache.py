"""Where JAX keeps its persistent compilation cache.

A chip run pays minutes of compilation cold.  JAX's persistent cache only
finds an entry again under the same directory, so the directory is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, and nothing else is set here), otherwise
``<checkout>/.jax_cache``.  It is never built from a temp name, a process
id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT", "enable_compile_cache"]

#: the repository checkout this package runs from (src/repro/ -> root)
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call it once, before the first compile (entry points only)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
