"""Placement of JAX's persistent compilation cache by the entry points.

`JAX_COMPILATION_CACHE_DIR` wins when set and `enable_compile_cache` sets
nothing else; without it the cache is the fixed `.jax_cache/` of the
checkout. Each case runs in a fresh interpreter: the cache directory is
process-global JAX state.
"""

import os
import subprocess
import sys
import textwrap

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=300, cwd=_REPO,
        env={**base, "PYTHONPATH": os.path.join(_REPO, "src"),
             "JAX_PLATFORMS": "cpu", **env},
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_env_dir_holds_every_entry(tmp_path):
    cache = tmp_path / "cache"
    _run(f"""
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache
    assert enable_compile_cache() == {str(cache)!r}
    assert jax.config.jax_compilation_cache_dir == {str(cache)!r}
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert any(cache.iterdir())


def test_default_dir_is_fixed_in_checkout():
    want = os.path.join(_REPO, ".jax_cache")
    _run(f"""
    import jax
    from repro.launch.cache import enable_compile_cache
    assert jax.config.jax_compilation_cache_dir is None
    assert enable_compile_cache() == {want!r}
    assert jax.config.jax_compilation_cache_dir == {want!r}
    """)
