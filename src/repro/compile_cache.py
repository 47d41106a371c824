"""JAX's persistent compilation cache, placed from outside the code.

A cold process recompiles every jitted engine and Pallas kernel; the
persistent cache lets later processes on the same machine skip that. Where
the cache lives is the caller's environment's decision:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  ``enable_compile_cache`` changes nothing.
* unset: the cache goes to ``.jax_cache/`` at the root of this checkout — a
  fixed path (never a temporary name, a process id or a time), so every
  run from the same checkout finds what earlier runs stored. The
  directory is git-ignored.

Entry points call ``enable_compile_cache()`` before their first compile:
``chip_smoke.py``, ``launch/serve.py``, ``repro.launch.serve`` and
``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
