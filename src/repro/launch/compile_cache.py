"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) calls :func:`use_compile_cache` before it compiles.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
nothing here overrides it. Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout (listed in ``.gitignore``): one fixed directory,
never a temporary name, a pid or a time, so that every run of the same
checkout finds what an earlier run compiled.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = os.path.normpath(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
