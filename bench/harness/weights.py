"""Seeded random weights, made by the benchmark in the program's layout.

The program's parameter tree is read as shapes only (`jax.eval_shape` of
its init); every leaf is drawn here from the seed, by the leaf's path, in
one jitted call on the device, in the dtype the configuration serves or
trains in. The reference reads the same tree by the same names, so
neither side takes weights that the other made.

Draws: the configuration's architecture module (`arch/<name>.py`) draws
the leaves whose init it owns through its `draw(name, shape, key)`, which
returns None for the others. The rest: matrices a truncated normal of std
min(0.02, 1/sqrt(fan_in)), the embedding std 0.02; norm scales (applied
as 1 + w) and biases a normal of std 0.1, so that they take part in the
comparison instead of sitting at 0.

On several chips the reference's weights are laid out by `spread`, a
layout of the benchmark's own: each leaf split over the chips along its
largest axis that their number divides, whole on each chip where none
does.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


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


def init_leaf(name: str, shape, words, dtype, arch=None):
    """One leaf, drawn from the seed's words by the leaf's path name: by
    the architecture module where it owns the leaf, else by `_draw`."""
    key = jax.random.fold_in(base_key(words), zlib.crc32(name.encode()) & 0x7FFFFFFF)
    own = getattr(arch, "draw", None)
    x = own(name, tuple(shape), key) if own is not None else None
    if x is None:
        x = _draw(name, tuple(shape), key)
    return x.astype(dtype)


def param_shapes(model_cfg):
    """The program's parameter tree, as shapes (nothing is drawn)."""
    from repro.models import get_model

    return jax.eval_shape(
        lambda: get_model(model_cfg).init(jax.random.PRNGKey(0), model_cfg))


def make_params(shapes, seed: int, dtype, out_shardings=None, arch=None):
    """Every leaf of `shapes` drawn from `seed`, in one jitted call."""

    def build(words):
        return jax.tree_util.tree_map_with_path(
            lambda p, s: init_leaf(path_name(p), s.shape, words, dtype, arch), shapes)

    return jax.jit(build, out_shardings=out_shardings)(seed_words(seed))


def spread(shapes, devices):
    """A NamedSharding per leaf of `shapes` over `devices` (one mesh axis):
    the leaf split along its largest axis that len(devices) divides (the
    first such, on a tie), replicated where no axis does."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.asarray(devices), ("chips",))
    n = len(devices)

    def one(s):
        fits = [i for i, d in enumerate(s.shape) if d % n == 0]
        if not fits:
            return NamedSharding(mesh, PartitionSpec())
        axis = max(fits, key=lambda i: (s.shape[i], -i))
        spec = [None] * len(s.shape)
        spec[axis] = "chips"
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree.map(one, shapes)
