"""JAX's persistent compilation cache, for the command-line entry points.

``chip_smoke.py``, ``benchmarks/run.py`` and ``python -m repro.tune`` call
:func:`enable_compile_cache` before their first compile.  The library never
calls it on import, so tests and embedding programs keep JAX's own setting.
"""

from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "CACHE_ENV_VAR", "enable_compile_cache"]

#: Environment variable JAX reads its cache directory from.
CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: Fixed cache directory inside the checkout (listed in ``.gitignore``).  The
#: path is part of each entry's key, so it must not move between runs.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
    directory and nothing is changed; otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get(CACHE_ENV_VAR, "").strip()
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
