"""JAX's persistent compilation cache, for the programs' entry points.

Entry points (``chip_smoke.py``, the ``benchmarks`` mains) call
:func:`enable` once, before their first compile; the library never turns
the cache on by itself, so importing it changes no global JAX state.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed under the checkout: the directory is part of what a later
# process has to find again, so it never comes from a temporary name,
# a process id or the time
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
