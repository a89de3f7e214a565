"""Where JAX keeps its persistent compilation cache.

A cache hits only when its directory stays put (the path is part of the
key), so the default is a fixed directory inside the checkout, never a
temporary or per-process one.  ``JAX_COMPILATION_CACHE_DIR`` places it
from outside; JAX reads that variable itself, so then nothing is set here.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                           "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
