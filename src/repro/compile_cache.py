"""Persistent XLA compilation cache for the repo's entry-point scripts.

Compiling a study's program for the chip takes minutes, so the scripts a
user runs (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) keep
compiled executables on disk.  Importing the library never does this; a
script opts in by calling ``enable_compile_cache()`` before its first
compile.
"""
from __future__ import annotations

import os

import jax

#: fixed cache directory inside the checkout; the path is part of what the
#: cache is keyed by, so it never depends on a temp name, a pid or the time
DEFAULT_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                           "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads it
    itself) and no other directory is configured; otherwise the cache lives
    in ``DEFAULT_DIR``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
