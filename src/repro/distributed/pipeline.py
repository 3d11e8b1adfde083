"""GPipe pipeline parallelism over a named mesh axis (shard_map + ppermute).

`pipeline_apply(block_fn, stage_params, x, axis_name)` runs S pipeline
stages (S = mesh axis size) over M microbatches with the classic GPipe
schedule: M + S − 1 ticks, activations hop stage→stage via
`lax.ppermute` each tick. Differentiable — `jax.grad` through the tick
scan yields the GPipe backward (all-forward-then-all-backward) with
reverse ppermutes, so PP training needs no hand-written backward.

Layout contract: `stage_params` leaves have leading dim S sharded over
`axis_name`; inside shard_map each stage sees its slice. `x` is
[M, microbatch, ...] and is consumed by stage 0; outputs are emitted by the
last stage and gathered. Bubble fraction = (S−1)/(M+S−1) — the launcher
picks M ≥ 4·S so the bubble stays under ~20% (flagged in EXPERIMENTS.md).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "split_stages"]


def split_stages(stacked_layer_params, n_stages: int):
    """[L, ...] stacked layer params → [S, L/S, ...] per-stage stacks."""

    def re(x):
        l = x.shape[0]
        assert l % n_stages == 0, f"layers {l} % stages {n_stages} != 0"
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    return jax.tree.map(re, stacked_layer_params)


def pipeline_apply(
    block_fn: Callable,  # (stage_param_slice, x_mb) -> y_mb
    stage_params,  # leaves [S, ...] sharded over axis_name
    x: jax.Array,  # [M, mb, ...] microbatches
    *,
    mesh: Mesh,
    axis_name: str = "pod",
) -> jax.Array:
    """Returns y [M, mb, ...] = block_fn applied by every stage in sequence."""
    n_stages = mesh.shape[axis_name]
    m = x.shape[0]

    def stage_fn(params, xs):
        # params: [1, ...] this stage's slice; xs: [M, mb, ...] (full copy on
        # stage 0's shard; other stages ignore their input replica)
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis_name)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)  # current activation
        outs = jnp.zeros((m,) + mb_shape, xs.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (if any) — others keep incoming
            inject = jnp.where(t < m, t, 0)
            state = jnp.where(idx == 0, xs[inject], state)
            y = block_fn(params, state)
            # last stage emits finished microbatch t-(S-1)
            out_t = t - (n_stages - 1)
            emit = jnp.logical_and(idx == n_stages - 1, out_t >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_t, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # hop: stage i → i+1 (ring permute; wraparound value unused)
            state = jax.lax.ppermute(
                y, axis_name, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(
            tick, (state, outs), jnp.arange(m + n_stages - 1)
        )
        # outs live on the last stage; psum broadcasts (others hold zeros)
        return jax.lax.psum(outs, axis_name)

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
