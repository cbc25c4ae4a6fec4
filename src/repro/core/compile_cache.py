"""JAX's persistent compilation cache, as the entry points set it up.

The entry points (``chip_smoke.py``, ``examples/delivery_replay.py``,
``benchmarks/bench_engine.py``) call :func:`enable_compile_cache` before
any JAX computation.  Library import and the tests never call it.
"""
from __future__ import annotations

import os
import pathlib

# ``<checkout>/.jax_cache`` (listed in .gitignore).  Fixed, never derived
# from a temp name, a pid or the time, so a later run of the same checkout
# finds what an earlier one compiled.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` is used when it is set, and no other
    directory; otherwise :data:`DEFAULT_CACHE_DIR`.  Every program is
    kept, however quickly it compiled: the ARIMA bank programs compile in
    under JAX's default one-second threshold.
    """
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
