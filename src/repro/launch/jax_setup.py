"""Process-level JAX set-up for the entry points: the persistent compilation
cache, and the line that names the devices a run is on.

Entry points call these from ``main``.  Importing ``repro`` changes no JAX
setting, because tests and worker processes import it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of what a later run
    must find again, so it never depends on a temporary name, a pid or the
    time."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_line() -> str:
    """Platform, device kind and device count, as JAX reports them."""
    devices = jax.devices()
    return (f"platform={devices[0].platform} "
            f"device_kind={devices[0].device_kind} count={len(devices)}")
