"""JAX's persistent compilation cache for the entry points.

A serving process compiles one program per lane combination and batch
size; on a TPU each takes seconds.  The cache lets the next process on the
same machine load them instead.  Entry points call :func:`enable` once at
start-up; nothing here runs at import.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["enable", "DEFAULT_DIR"]

# one fixed directory inside the checkout (``.gitignore`` lists it): a
# cache directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise, on an accelerator, the cache goes to
    :data:`DEFAULT_DIR`, and every compile is kept, however short: the
    serving programs are small, and the default one-second floor would
    skip most of them.  On the CPU backend nothing is cached (returns
    ``None``): XLA:CPU compiles these programs in well under a second, and
    its reloaded entries log spurious machine-feature mismatches."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_DIR)
