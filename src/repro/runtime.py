"""Process-level JAX set-up shared by the entry points.

Importing this module touches no JAX state. The entry points
(`launch.train`, `launch.serve`, `chip_smoke.py`) call
`enable_compile_cache()` before their first compile; spawned workers that
run host code only (measurement-farm workers, hub readers, load clients)
call `keep_off_accelerator()` first thing, because an accelerator belongs
to one process at a time and the parent may hold it.
"""
from __future__ import annotations

import os
import sys

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout: the cache key includes the path, so a
# directory that moved would never hit
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "artifacts", "jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache is `DEFAULT_CACHE_DIR`.
    """
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def keep_off_accelerator() -> None:
    """Pin this process's JAX, and that of any process it starts, to the
    CPU. Call it before anything could initialise a JAX backend."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", "cpu")
