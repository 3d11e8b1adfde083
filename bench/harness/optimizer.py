"""The optimizer the training configurations state, in plain float32.

It belongs to the deployment (`deployment.optimizer` of a configuration),
not to the architecture: every architecture's reference steps with it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lr_at(opt: dict, step):
    """Linear warm-up from 0, then cosine to `final_frac` of the peak."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    t = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (opt["final_frac"] + (1 - opt["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def decays(opt: dict, name: str, ndim: int) -> bool:
    return ndim > 1 and not any(k in name for k in opt["no_decay_keywords"])


def adamw(opt: dict, params, grads, m, v, step: int):
    """One AdamW step after global-norm clipping; `step` counts from 0.
    Returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2, t = opt["b1"], opt["b2"], step + 1
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    lr = lr_at(opt, step)

    def upd(path, p, a, b):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        wd = opt["weight_decay"] if decays(opt, name, p.ndim) else 0.0
        u = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + opt["eps"]) + wd * p
        return p - lr * u

    params = jax.tree_util.tree_map_with_path(upd, params, m, v)
    return params, m, v, g
