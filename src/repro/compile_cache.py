"""JAX's persistent compilation cache at one fixed place.

Every program entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``python -m repro.dse.service``,
``examples/dse_cim.py``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` once, before its first compilation; library
modules never call it, so importing ``repro`` changes no jax state.

The cache directory is part of every cache key, so it is fixed: if
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and nothing is
set here; otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its fixed directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    return str(CHECKOUT_DIR)
