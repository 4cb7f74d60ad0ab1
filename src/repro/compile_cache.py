"""JAX's persistent compilation cache, placed from outside.

Program entry points call :func:`enable` once at start (never at import):
``chip_smoke.py``, ``repro.launch.solve``, ``repro.launch.serve_solver``
and ``benchmarks/run.py``.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
that directory is the cache and no other is named in code.  Otherwise the
cache lives at the fixed ``.jax_cache/`` in the root of the checkout
(listed in ``.gitignore``): the path is part of what a later run looks up,
so it is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/...``.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory :func:`enable` uses."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_dir` and
    return that path."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
