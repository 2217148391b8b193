"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile, so a second process on the same machine reuses what the first
compiled.  The rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and that directory
  is the cache; nothing here sets another.
* unset: the cache is ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
  The path is part of each entry's key, so it is fixed: never built from a
  temporary name, a pid or the time.
* ``JAX_ENABLE_COMPILATION_CACHE=false``: the cache stays off (the test
  suite's launcher runs use this).
"""
from __future__ import annotations

import os
from typing import Optional

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory, or
    None when it is disabled."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
