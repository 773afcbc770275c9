"""The one place that decides where JAX keeps its persistent compile cache.

Every bucket size compiles three programs (ops/fused_cdc.py), at real chunk
sizes tens of seconds each, and a gateway pays them again at every start
unless the cache persists. Entry points (the gateway daemon, chip_smoke.py,
bench.py, the scripts, tests/conftest.py) call :func:`configure_compile_cache`
before their first JAX use; nothing else sets a cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout: the path is part of the cache key, so a
# directory that moves between runs (/tmp names, pids, times) never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Use the directory ``JAX_COMPILATION_CACHE_DIR`` names if it is set,
    else the fixed in-checkout one; returns the directory in effect.

    Sets the environment (inherited by child processes, read by jax at
    import) and the live config (for a jax that is already imported).
    """
    path = os.environ.get(CACHE_DIR_ENV) or str(DEFAULT_CACHE_DIR)
    os.environ[CACHE_DIR_ENV] = path
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"])
    )
    return path
