"""Where JAX keeps its persistent compilation cache: one helper decides.

Every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, ``bench/run.py``) calls
:func:`enable_compile_cache` before it compiles anything.

  * ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it
    itself and this helper sets nothing.
  * Otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
    The path is fixed on purpose: a temporary, pid- or time-derived
    directory would never be hit again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one location and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
