"""Persistent XLA compilation cache for the program's entry points.

``use_compile_cache()`` is called first thing by the ``main()`` of
``chip_smoke.py``, ``repro.launch.train``, ``repro.launch.serve`` and
``benchmarks.run`` — never at import, so tests and library callers keep
JAX's defaults. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
and no other directory is set here. Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, since the cache directory is
part of what a later process must find again.
"""
from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call it before the process compiles anything. Before ``import jax``
    the directory travels as the environment variable, which JAX reads
    at import (and child processes inherit); after it, it is also set
    on the live config.
    """
    path = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
    os.environ[ENV_VAR] = path
    if "jax" in sys.modules:
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
