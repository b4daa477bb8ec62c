"""JAX's persistent compilation cache, for the entry points.

A process that compiles a plane writes the executable here, and the next
process with the same program reads it back instead of compiling again.
The entry points (``launch/solve.py``, ``launch/serve.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`enable_compile_cache`
once at start-up; nothing calls it at import.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout (gitignored): the cache key includes the
# path, so a directory that moved between runs would never hit
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on the persistent compile cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory.  Otherwise, on an accelerator, the cache is
    ``.jax_cache/`` at the root of the checkout.  On the CPU backend nothing
    is set (returns None): CPU executables compile in seconds, and XLA:CPU
    logs a machine-feature error each time it loads a cached one.
    """
    import jax

    path = os.environ.get(ENV)
    if path:
        return path
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
