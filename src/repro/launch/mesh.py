"""Mesh construction: the one place the program builds a `jax.sharding.Mesh`.

The sharding rules (repro.distributed.sharding) are written for GSPMD:
`with_sharding_constraint` hints and PartitionSpec jit shardings that the
partitioner may propagate through. `jax.make_mesh` now defaults to Explicit
axes, under which those hints become asserts, so every mesh is built here
with Auto axes.

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before first jax init; everything else
sees the host's single device.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Mesh of `shape` over `axes` (Auto axis types) on `devices`
    (default: all of the backend's devices)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (data, model) single pod; 2×16×16 (pod, data, model) multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
