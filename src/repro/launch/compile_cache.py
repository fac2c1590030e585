"""Persistent XLA compilation cache for the entry points.

Called from ``main`` of the launcher, the benchmark driver and
``chip_smoke.py``; never at import of a library module, so tests and
worker processes keep whatever cache setting their parent chose.
"""
from __future__ import annotations

import os

import jax

#: fixed cache path inside the checkout (listed in .gitignore): the same
#: directory on every run, so a later run in this checkout finds the
#: programs an earlier one compiled
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already taken it
    as its own setting and it is left alone; otherwise the cache goes to
    ``DEFAULT_DIR``.
    """
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
