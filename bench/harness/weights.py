"""Seeded random weights, made by the benchmark in the program's layout.

The program's parameter tree is read as shapes only (`jax.eval_shape` of
its init); every leaf is drawn here from the seed, by the leaf's path, in
one jitted call on the device, in the dtype the configuration serves or
trains in. The reference reads the same tree by the same names, so
neither side takes weights that the other made.

Draws: matrices a truncated normal of std min(0.02, 1/sqrt(fan_in)), the
embedding std 0.02; norm scales (applied as 1 + w) and biases a normal of
std 0.1, so that they take part in the comparison instead of sitting at 0.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def seed_words(seed: int):
    """A non-negative seed of up to 64 bits as two uint32 device scalars,
    so that one compiled program serves every seed."""
    seed = int(seed)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def base_key(words):
    lo, hi = words
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def _draw(name: str, shape, key):
    leaf = name.rsplit("/", 1)[-1]
    if leaf == "embed":
        return 0.02 * jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
    if "norm" in leaf or leaf in ("bq", "bk", "bv"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = min(0.02, 1.0 / math.sqrt(fan_in))
    return std * jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)


def init_leaf(name: str, shape, words, dtype):
    """One leaf, drawn from the seed's words by the leaf's path name."""
    key = jax.random.fold_in(base_key(words), zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return _draw(name, tuple(shape), key).astype(dtype)


def param_shapes(model_cfg):
    """The program's parameter tree, as shapes (nothing is drawn)."""
    from repro.models import get_model

    return jax.eval_shape(
        lambda: get_model(model_cfg).init(jax.random.PRNGKey(0), model_cfg))


def make_params(shapes, seed: int, dtype, out_shardings=None):
    """Every leaf of `shapes` drawn from `seed`, in one jitted call."""

    def build(words):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: init_leaf(path_name(p), s.shape, words, dtype), shapes)

    return jax.jit(build, out_shardings=out_shardings)(seed_words(seed))
