"""JAX persistent compilation cache placement for the launchers.

`JAX_COMPILATION_CACHE_DIR`, when set, wins and is left to JAX to read:
nothing else is set. Otherwise the cache lives in `.jax_cache/` at the
root of the checkout — a fixed path, because the path is part of each
entry's key, so a directory that moves between runs never hits.

Called by the entry points (`launch.serve`, `launch.train`,
`chip_smoke.py`) before their first compile, never at import.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
