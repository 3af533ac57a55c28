"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins: the
code sets no other directory. Otherwise the cache lives at one fixed path
inside the checkout (``.jax_cache/``, git-ignored). The path is fixed — never
built from a temporary name, a process id or the time — because a cache
directory that moves between runs is never found again.

Call ``setup_compile_cache()`` from an entry point's ``main()``, never at
import time, so that importing the library changes no global JAX state.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "setup_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
