"""Where the entry points keep JAX's persistent compilation cache.

Entry points (``serve.main``, ``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable` before their first compile; importing this module sets
nothing. ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and no other
directory is used. Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part of
what a later run looks up, so it never comes from a temp name, pid or time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> pathlib.Path:
    """``<repo>/.jax_cache``: this file is ``<repo>/src/repro/launch/``."""
    return pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get(ENV) or str(default_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
