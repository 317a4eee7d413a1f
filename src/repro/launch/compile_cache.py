"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call :func:`enable_compile_cache` before their first compile
(never at import, so importing ``repro`` leaves the cache as JAX found it).
A run then loads the executables an earlier run in the same place compiled,
instead of paying XLA's compile again.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...); a
# fixed path, since a cache that moves between runs never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise an accelerator's cache lives in
    ``<checkout>/.jax_cache``; on the CPU backend nothing is cached (CPU
    compiles are cheap, and XLA:CPU warns on every reload) and None is
    returned.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
