"""Resilient training orchestration: chaos in, bitwise-identical curve out.

`train_resilient` layers training-specific fault policy on top of the
generic `repro.runtime.resilience.run_resilient` supervisor:

* **Fault sites** — a `FaultInjector` is checked once per step at each
  train site, placed where the failure would surface in a real pipeline:
  `data_batch` before the batch is materialized, `grad_step` and
  `optimizer_update` before the fused jitted step that contains both,
  `collective` after the step (a failed cross-device reduction loses the
  step's result), and `ckpt_save` inside the supervisor's `on_save` hook
  (aborting the write). Every site raises *before* the step's result is
  committed to history, so a restart replays from the latest verified
  checkpoint and — because `SyntheticLM.batch(step)` is a pure function of
  (seed, step) and all mutable state lives in the checkpoint — the resumed
  loss curve is bitwise identical to an uninterrupted run.

* **Loss-spike rollback** — a host-side divergence detector compares each
  committed loss against the median of the trailing `spike_window` losses;
  a spike beyond `spike_threshold`× raises `DivergenceRollback` (retryable
  → the supervisor restores the last good checkpoint), rolling back past
  silently-corrupted state instead of training through it. A per-step
  rollback cap distinguishes corruption (transient: the replay is clean)
  from a genuine distribution shift (persistent: accept after the cap).

* **Counters** — restarts / rollbacks / injected faults / on-device
  skipped updates, surfaced for the launcher's status line and asserted
  by the goodput benchmark (BENCH_train.json).

The jitted step itself carries the numerics guard (non-finite-grad
skip-update + dynamic loss scaling) — see `repro.train.train_step`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.runtime.resilience import (
    DivergenceRollback,
    FaultInjector,
    RetryPolicy,
    run_resilient,
)
from repro.train.train_step import (
    TrainConfig,
    init_train_state,
    make_train_step,
)

__all__ = ["ResilienceConfig", "train_resilient"]


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Policy knobs for the supervised train loop."""

    ckpt_every: int = 10
    keep_checkpoints: Optional[int] = None  # None → keep all
    max_restarts: int = 50
    retry: RetryPolicy = RetryPolicy()
    # loss-spike divergence detector (0 → disabled)
    spike_threshold: float = 0.0  # loss > threshold * trailing median ⇒ spike
    spike_window: int = 8
    spike_warmup: int = 8  # committed steps before detection arms
    max_rollbacks_per_step: int = 2  # then accept: real shift, not corruption


def train_resilient(
    *,
    ckpt_dir: str,
    model_cfg,
    train_cfg: TrainConfig,
    data,
    total_steps: int,
    seed: int = 0,
    res: ResilienceConfig = ResilienceConfig(),
    injector: Optional[FaultInjector] = None,
    chaos_hook: Optional[Callable[[int, object], object]] = None,
    init_state_fn: Optional[Callable[[], object]] = None,
    step_fn: Optional[Callable] = None,
    on_step: Optional[Callable[[int, Dict[str, float], Dict[str, int]], None]] = None,
) -> Tuple[object, List[Dict], Dict[str, int]]:
    """Train `total_steps` under the resilience policy; returns
    (final_state, history, counters).

    `data.batch(step)` must be a pure function of step (the bitwise-resume
    contract). `chaos_hook(step, state) -> state | None` is a test hook
    that can silently corrupt state before a step — the spike detector's
    adversary. `init_state_fn` / `step_fn` override the defaults (fresh
    `init_train_state` / `jax.jit(make_train_step(...))`) so a sharded
    launcher can supply device_put state and a pjit'd step.
    """
    if init_state_fn is None:
        init_state_fn = lambda: init_train_state(
            jax.random.PRNGKey(seed), model_cfg, train_cfg
        )
    if step_fn is None:
        step_fn = jax.jit(make_train_step(model_cfg, train_cfg))

    counters = {"restarts": 0, "rollbacks": 0, "faults": 0, "skipped": 0}
    losses: Dict[int, float] = {}  # committed loss per data step (replay-safe)
    rollbacks_at: Dict[int, int] = {}

    def _spike_check(step: int, loss: float) -> None:
        if res.spike_threshold <= 0 or step < res.spike_warmup:
            return
        window = [losses[s] for s in range(max(0, step - res.spike_window), step)
                  if s in losses]
        if not window:
            return
        ref = float(np.median(window))
        if np.isfinite(loss) and loss <= res.spike_threshold * ref:
            return
        if rollbacks_at.get(step, 0) >= res.max_rollbacks_per_step:
            return  # persistent across clean replays ⇒ genuine shift: accept
        rollbacks_at[step] = rollbacks_at.get(step, 0) + 1
        counters["rollbacks"] += 1
        raise DivergenceRollback(step, loss, ref)

    def supervised_step(state, data_step: int):
        with TraceAnnotation("repro.train.step", step=data_step):
            with TraceAnnotation("repro.train.batch"):
                if injector is not None:
                    injector.check("data_batch")
                batch = jax.tree.map(jnp.asarray, data.batch(data_step))
            if chaos_hook is not None:
                corrupted = chaos_hook(data_step, state)
                if corrupted is not None:
                    state = corrupted
            with TraceAnnotation("repro.train.dispatch"):
                if injector is not None:
                    injector.check("grad_step")
                    injector.check("optimizer_update")
                new_state, metrics = step_fn(state, batch)
                if injector is not None:
                    injector.check("collective")  # a lost reduction loses the step
            with TraceAnnotation("repro.train.sync"):
                loss = float(metrics["loss"])  # host sync: the commit point
            with TraceAnnotation("repro.train.commit"):
                _spike_check(data_step, loss)
                losses[data_step] = loss
                if on_step is not None:
                    if injector is not None:
                        counters["faults"] = injector.total_fired
                    on_step(data_step, {k: float(v) for k, v in metrics.items()},
                            counters)
            return new_state, metrics

    def on_save(step: int, state) -> None:
        if injector is not None:
            injector.check("ckpt_save")

    def on_restart(n: int, exc: BaseException) -> None:
        counters["restarts"] = n

    state, history = run_resilient(
        ckpt_dir=ckpt_dir,
        init_state_fn=init_state_fn,
        step_fn=supervised_step,
        total_steps=total_steps,
        ckpt_every=res.ckpt_every,
        max_restarts=res.max_restarts,
        retry=res.retry,
        keep=res.keep_checkpoints,
        on_save=on_save,
        on_restart=on_restart,
    )
    if injector is not None:
        counters["faults"] = injector.total_fired
    if hasattr(state, "skipped"):
        counters["skipped"] = int(state.skipped)
    return state, history, counters
