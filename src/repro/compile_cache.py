"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``python -m
repro.service``, ``python -m repro.replay``) call
:func:`enable_compile_cache` once at start-up; importing the library never
does, so tests and embedding programs keep JAX's defaults.

- With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
  and this module sets nothing.
- Otherwise the cache lives at ``.jax_cache/`` in the root of the checkout
  (git ignores it).  The path is fixed on purpose: it is part of the
  cache's key, so a per-run directory would never hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Switch JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
