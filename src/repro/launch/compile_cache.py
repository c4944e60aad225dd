"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
repo root (gitignored).  The directory is part of the cache key, so it is
fixed: never a temp, pid or time-derived path.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(environ: Mapping[str, str] = os.environ
                      ) -> Optional[str]:
    """The directory to set in code, or None where the environment
    already names one."""
    return None if environ.get(ENV_VAR) else str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.  Call
    before the first compile."""
    import jax

    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return os.environ.get(ENV_VAR) or path
