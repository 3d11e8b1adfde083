"""The comparisons that decide `correct`.

Serving: the widest gap, over every served token of a seeded sample of
requests (the longest always among them), by which the served token's
reference logit lies below the reference's best logit at that position.
The reference reads the prompt followed by the served tokens (teacher
forcing), so every served token is judged where it was produced. The
control (`lowp`) reads the gap of the token that the lower-precision
reference puts first at the same positions.

Training: each of the first steps' losses; per leaf, the norm of the
first gradient as the optimizer got it; and per leaf, the norm of the
parameters' change after the first steps. Norms are compared as the gap
between the program's and the reference's, over the larger of the
reference's norm of that leaf and of the median leaf. Leaves whose
reference gradient is under a thousandth of the median leaf's (a key's
bias under softmax) move under Adam by round-off alone and are left out
of the change. A leaf's norm averages rounding away, so the first
gradient is also compared element by element: per leaf, the norm of the
program's gradient minus the reference's, over the same denominator
(`grad_error`); this is the number that a step in lower precision moves.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import optimizer
from harness.traffic import rng_for

SEQ_BUCKET = 1024
ROWS = 512


def serve_sample(prompts, outputs, seed: int, k: int) -> List[int]:
    """Up to k requests that served tokens: the longest, plus a seeded draw."""
    served = [i for i, o in enumerate(outputs) if len(o) > 0]
    if not served:
        return []
    longest = max(served, key=lambda i: len(prompts[i]) + len(outputs[i]))
    rest = [i for i in served if i != longest]
    pick = rng_for(seed, 4).permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + sorted(rest[j] for j in pick)


@functools.lru_cache(maxsize=None)
def _hidden_fn(ref, arch):
    return jax.jit(lambda p, t: ref.hidden(arch, p, t))


@functools.lru_cache(maxsize=None)
def _gap_fn(ref, arch, ctrl):
    """Per row: best reference logit minus the reference logit of the
    served token, and (with a control arch) of the control's first token."""

    def fn(p, h, served, h_ctrl):
        lg = ref.logits(arch, p, h)
        best = jnp.max(lg, -1)
        gap = best - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        if ctrl is None:
            return gap, gap
        top = jnp.argmax(ref.logits(ctrl, p, h_ctrl), -1)
        return gap, best - jnp.take_along_axis(lg, top[:, None], -1)[:, 0]

    return jax.jit(fn)


def serve_gap(ref, model: dict, params,
              pairs: Sequence[Tuple[np.ndarray, np.ndarray]],
              lowp=None) -> Dict[str, float]:
    """`ref` is the configuration's architecture module."""
    arch = ref.Arch.of(model)
    ctrl = ref.Arch.of(model, lowp) if lowp else None
    worst, worst_ctrl, tokens = 0.0, 0.0, 0
    for prompt, out in pairs:
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        n_pad = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        toks = jnp.asarray(np.pad(seq, (0, n_pad - len(seq)))[None])
        h = _hidden_fn(ref, arch)(params, toks)[0]
        h_ctrl = _hidden_fn(ref, ctrl)(params, toks)[0] if ctrl else h
        start = len(prompt) - 1
        for r in range(0, len(out), ROWS):
            rows = np.arange(start + r, start + r + ROWS)
            served = np.zeros(ROWS, np.int32)
            n = min(ROWS, len(out) - r)
            served[:n] = out[r:r + n]
            rows = np.minimum(rows, n_pad - 1)
            g, gc = _gap_fn(ref, arch, ctrl)(params, h[rows], jnp.asarray(served),
                                        h_ctrl[rows])
            worst = max(worst, float(jnp.max(g[:n])))
            worst_ctrl = max(worst_ctrl, float(jnp.max(gc[:n])))
        tokens += len(out)
    out = {"logit_gap": worst, "tokens_compared": tokens}
    if ctrl is not None:
        out["logit_gap_control"] = worst_ctrl
    return out


# ---- training -------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, jax.Array]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in flat}


def norm_gap(prog: Dict[str, float], refn: Dict[str, float],
             keep=None) -> Tuple[float, str]:
    """Worst leaf's |prog - ref| over max(ref leaf, median ref leaf)."""
    names = [n for n in refn if keep is None or n in keep]
    med = float(np.median([refn[n] for n in names]))
    gaps = {n: abs(prog[n] - refn[n]) / max(refn[n], med, 1e-30) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def host_leaves(tree) -> Dict[str, np.ndarray]:
    """Every leaf copied to the host in float32, by its path name."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            np.asarray(jax.device_get(x), np.float32) for path, x in flat}


def diff_gap(prog: Dict[str, np.ndarray], refa: Dict[str, np.ndarray]) -> Tuple[float, str]:
    """Worst leaf's norm of (prog - ref) over max(ref leaf, median ref leaf)."""
    refn = {n: float(np.linalg.norm(x.ravel())) for n, x in refa.items()}
    med = float(np.median(list(refn.values())))
    gaps = {n: float(np.linalg.norm((prog[n] - refa[n]).ravel()))
            / max(refn[n], med, 1e-30) for n in refa}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def moved_leaves(grad_norms: Dict[str, float]) -> set:
    med = float(np.median(list(grad_norms.values())))
    return {n for n, g in grad_norms.items() if g >= 1e-3 * med}


def train_reference(ref, model: dict, opt: dict, params, batches, steps: int,
                    delta_norms, lowp=None, half_batch=False,
                    shardings=None) -> dict:
    """Losses, the first clipped gradient's leaf norms, Adam's first
    moment after one step (the first clipped gradient times 1 - b1) on the
    host, and the change's leaf norms (`delta_norms(params)`, against the
    seed's initial weights) after `steps` AdamW steps of the plain
    reference (`ref`, the configuration's architecture module) from
    `params`, which it consumes. `shardings` (a tree like `params`, or
    None on one device) is the layout the weights were drawn in; the
    step keeps the weights and both moments on it. `half_batch` plants a
    fault: each loss is the mean over the first half of the batch's
    tokens only."""
    arch = ref.Arch.of(model, lowp)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                       out_shardings=(shardings, shardings, shardings, None, None))
    def step(p, m, v, tokens, labels, i):
        if half_batch:
            n = labels.shape[0] * labels.shape[1]
            keep = (jnp.arange(n) < n // 2).reshape(labels.shape)
            labels = jnp.where(keep, labels, -1)
        loss, g = jax.value_and_grad(lambda q: ref.loss(arch, q, tokens, labels))(p)
        p, m, v, gc = optimizer.adamw(opt, p, g, m, v, i)
        return p, m, v, loss, leaf_norms(gc)

    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p), out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    p, losses, grad_norms, moment = params, [], None, None
    del params
    for i in range(steps):
        b = batches[i]
        p, m, v, loss, gn = step(p, m, v, jnp.asarray(b["tokens"]),
                                 jnp.asarray(b["labels"]), i)
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(x) for k, x in gn.items()}
            moment = host_leaves(m)
    del m, v
    return {"losses": losses, "grad_norms": grad_norms, "moment": moment,
            "delta_norms": delta_norms(p)}


def train_numbers(prog: dict, refr: dict) -> Dict[str, float]:
    """The numbers of a training cell (the limits file says which are
    compared)."""
    keep = moved_leaves(refr["grad_norms"])
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], refr["losses"]))
    grad_gap, grad_at = norm_gap(prog["grad_norms"], refr["grad_norms"])
    delta_gap, delta_at = norm_gap(prog["delta_norms"], refr["delta_norms"], keep)
    err, err_at = diff_gap(prog["moment"], refr["moment"])
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": delta_gap, "grad_error": err,
            "_grad_leaf": grad_at, "_update_leaf": delta_at,
            "_error_leaf": err_at,
            "_left_out": sorted(set(refr["grad_norms"]) - keep)}


def verdict(numbers: Dict[str, float], limits: dict) -> Tuple[bool, Dict[str, dict]]:
    """correct iff every limited number is finite and within its limit."""
    compared, ok = {}, True
    for name, spec in limits["numbers"].items():
        value = numbers.get(name)
        limit = spec.get("limit")
        good = (value is not None and limit is not None
                and np.isfinite(value) and value <= limit)
        ok &= bool(good)
        compared[name] = {"value": value, "limit": limit}
    return ok, compared
