"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.mine``,
``benchmarks/run.py``) call :func:`use_compile_cache` once, before their
first compile. Importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the repository checkout: src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and nothing is set here. Otherwise the cache is ``<checkout>/.jax_cache``:
    the same path on every run, so a later process finds what an earlier
    one compiled."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
