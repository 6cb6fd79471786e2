"""JAX's persistent compilation cache, for the program's entry points.

``enable()`` is called once at start-up by the command-line entry points
(``chip_smoke.py``, ``python -m repro.launch.serve_olap``), never on import
of ``repro`` and never by the tests.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
lives at ``.jax_cache`` in the root of the checkout: a fixed path, because
the path is part of the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
