"""JAX's persistent compilation cache, placed the same way by every entry
point (``chip_smoke.py``, ``launch.train``, ``launch.serve``,
``benchmarks/run.py``).

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout: a fixed path, because the path is part of what a later process
must find again (never a temp, pid- or time-derived directory).
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
