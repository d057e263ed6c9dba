"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.observe``, the examples) call ``enable_compile_cache``
once, before they compile anything; importing the package never does.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so the cache
  stays there and nothing else is configured.
* unset: the cache goes to ``.jax_cache/`` at the root of the checkout
  (listed in ``.gitignore``). The path is fixed, never derived from a
  temporary name, a process id or the time: the directory is part of
  the cache key, so a path that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see
    the module docstring) and return that directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
