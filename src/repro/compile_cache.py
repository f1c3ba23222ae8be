"""JAX's persistent compilation cache, at one fixed place.

Entry points call :func:`enable_compile_cache` at the start of ``main``
(never at import, so importing this package changes no JAX setting):

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
    in code and the cache lives there.
  * unset — the cache lives in ``.jax_cache/`` at the root of the checkout
    (listed in ``.gitignore``), a fixed place so that a later run of the
    same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
