"""Where JAX keeps its persistent compilation cache for this checkout.

`use_compile_cache` is called at the top of the command-line entry points
(``chip_smoke.py``, ``benchmarks/run.py``), never on import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
sets nothing.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path (listed in ``.gitignore``), because a cache in a directory
that changes from run to run is never found again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
