"""JAX's persistent compilation cache for the repository's entry points.

A cold process compiles every program it runs: the build, one search
program per batch bucket and capacity, one insert program per chunk shape.
The cache keeps those compiles across processes.  Its directory is part of
what makes an entry reusable, so it never comes from a temporary name, a
pid or the time:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else is;
* otherwise ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Entry points (``chip_smoke.py``, ``launch/serve.py``,
``launch/build_index.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first computation; importing
this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    path = os.environ.get(ENV) or str(REPO_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
