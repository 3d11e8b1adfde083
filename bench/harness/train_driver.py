"""Training cells: the program's `build_trainer` step under
`train_resilient`, fed by the seeded `SyntheticLM` stream.

Set-up builds ONE object: the trainer's compiled step and a state whose
weights the benchmark draws from the seed. `train_resilient` drives it
from step 0; its first `SETUP_STEPS` steps (compile included) are set-up,
and the readings `correct` needs are taken on them (losses; the first
gradient as the optimizer got it, from Adam's first moment after one
step; the change of the weights after three). The window is the same
call continuing on the same state: steps run until `--seconds` have
passed, and the last step's end closes it. No checkpoint is written:
`ckpt_every` lies beyond the run. The objects that set-up leaves (traced
and compiled programs) are frozen out of Python's cyclic collector when
the window opens, so that its collections scan only what the window
makes; the seconds they take inside the window are logged.
"""

from __future__ import annotations

import gc
import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from harness import check, weights
from harness.serve_driver import model_config
from harness.synthetic import SyntheticLM
from harness.work import TrainWork

SETUP_STEPS = 4
CHECKED_STEPS = 3
NEVER = 10**12


class WindowClosed(Exception):
    """Raised from the step callback to end the window (not retryable)."""


def train_config(opt: dict):
    from repro.optim import AdamWConfig
    from repro.train.train_step import TrainConfig

    return TrainConfig(
        optimizer=AdamWConfig(
            lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"],
            no_decay_keywords=tuple(opt["no_decay_keywords"])),
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"])


class _GcTime:
    """Seconds spent in Python's cyclic collector while registered."""

    def __init__(self):
        self.s, self.n, self._t = 0.0, 0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.s += time.perf_counter() - self._t
            self.n += 1


class _Prefetched:
    """The batch stream as an input pipeline feeds a trainer: batch n + 1
    is made on one host thread while the device runs step n. Each batch
    is made inside a host span `bench.data`."""

    def __init__(self, data):
        self.data = data
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._next = None  # (step, future)

    def _make(self, step: int):
        with jax.profiler.TraceAnnotation("bench.data"):
            return self.data.batch(step)

    def batch(self, step: int):
        if self._next is not None and self._next[0] == step:
            out = self._next[1].result()
        else:
            if self._next is not None:
                self._next[1].result()
            out = self._make(step)
        self._next = (step + 1, self._pool.submit(self._make, step + 1))
        return out

    def close(self) -> None:
        if self._next is not None:
            self._next[1].result()
            self._next = None
        self._pool.shutdown(wait=True)


def _shardings(tree):
    return jax.tree.map(lambda s: getattr(s, "sharding", None), tree)


class TrainCell:
    def __init__(self, cell, seed: int, seconds: float, phases, clock,
                 root: str):
        from repro.launch.train import build_trainer
        from repro.launch.mesh import make_mesh
        from repro.runtime.resilience import plan_mesh

        self.cell, self.seed, self.seconds, self.root = cell, seed, seconds, root
        dep = cell.config["deployment"]
        self.mc = model_config(cell.config)
        self.opt = dep["optimizer"]
        self.tc = train_config(self.opt)
        self.data = _Prefetched(SyntheticLM(self.mc.vocab_size, cell.mix["seq_len"],
                                dep["global_batch"], seed,
                                **cell.mix.get("synthetic", {})))
        mesh = None
        if cell.chips > 1:
            plan = plan_mesh(cell.chips)
            mesh = make_mesh(plan.mesh_shape, plan.axis_names,
                             devices=jax.devices()[: cell.chips])
        init_state_fn, self.step_fn = build_trainer(self.mc, self.tc, seed=0,
                                                    mesh=mesh)
        if mesh is None:
            shapes = jax.eval_shape(init_state_fn)
        else:
            # the program's sharded init enters its mesh (`jax.set_mesh`),
            # which no trace allows, whether or not the mesh is entered
            # around `eval_shape`: it runs once, and only its shapes and
            # layout stay
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                init_state_fn())
        self.param_shapes = shapes.params
        params = weights.make_params(shapes.params, seed, jnp.float32,
                                     out_shardings=_shardings(shapes.params),
                                     arch=cell.arch)
        rest = jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 shapes._replace(params=None)),
            out_shardings=_shardings(shapes._replace(params=None)))()
        self.state0 = rest._replace(
            params=params, loss_scale=jnp.full((), self.tc.loss_scale_init,
                                               jnp.float32))
        jax.block_until_ready(self.state0)
        phases.lap("weights")
        self.phases, self.clock = phases, clock
        self._delta = jax.jit(self._delta_fn)

    def _delta_fn(self, params, words):
        """Leaf norms of params minus the seed's initial weights, drawn
        again leaf by leaf (never a whole second copy)."""
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        out = {}
        for path, x in flat:
            name = weights.path_name(path)
            x0 = weights.init_leaf(name, x.shape, words, jnp.float32, self.cell.arch)
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x - x0)))
        return out

    def delta_norms(self, params) -> dict:
        d = self._delta(params, weights.seed_words(self.seed))
        return {k: float(v) for k, v in d.items()}

    def run(self, on_window_start=None) -> dict:
        """Set-up steps, then the window; returns the step log."""
        from repro.train import ResilienceConfig, train_resilient

        log = {"losses": [], "steps": 0, "skipped": 0}
        readings = {}
        gc_time = _GcTime()
        b1 = self.opt["b1"]
        seen = [0]

        def step(state, batch):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                new, metrics = self.step_fn(state, batch)
            i = seen[0]
            seen[0] += 1
            if i == 0:
                readings["moment"] = check.host_leaves(new.opt.m)
                readings["grad_norms"] = {
                    k: float(np.linalg.norm(v.ravel())) / (1.0 - b1)
                    for k, v in readings["moment"].items()}
            if i == CHECKED_STEPS - 1:
                readings["delta_norms"] = self.delta_norms(new.params)
            return new, metrics

        def on_step(i, metrics, counters):
            now = time.monotonic()
            if i < CHECKED_STEPS:
                readings.setdefault("losses", []).append(metrics["loss"])
            if i == SETUP_STEPS - 1:
                gc.collect()
                gc.freeze()
                self.phases.lap("warm")
                if on_window_start is not None:
                    on_window_start()
                log["t0"] = time.monotonic()
                log["compiles_before"] = self.clock.mark()
                gc.callbacks.append(gc_time)
            elif i >= SETUP_STEPS:
                log["steps"] += 1
                log["skipped"] = int(metrics["skipped"])
                if now - log["t0"] >= self.seconds:
                    log["window_s"] = now - log["t0"]
                    raise WindowClosed

        ckpt_dir = os.path.join(self.root, ".bench_run", "ckpt_unused")
        state0, self.state0 = self.state0, None
        try:
            train_resilient(
                ckpt_dir=ckpt_dir, model_cfg=self.mc, train_cfg=self.tc,
                data=self.data, total_steps=NEVER, seed=0,
                res=ResilienceConfig(ckpt_every=NEVER, max_restarts=0),
                init_state_fn=lambda: state0, step_fn=step, on_step=on_step)
        except WindowClosed:
            pass
        finally:
            if gc_time in gc.callbacks:
                gc.callbacks.remove(gc_time)
            gc.unfreeze()
            self.data.close()
        del state0
        log["gc_s"], log["gc_n"] = gc_time.s, gc_time.n
        log["window_compiles"] = self.clock.names_since(log["compiles_before"])
        log["readings"] = readings
        gc.collect()
        return log

    def work(self) -> TrainWork:
        return TrainWork(self.cell.arch, self.cell.config["model"],
                         self.cell.config["deployment"]["global_batch"],
                         self.cell.mix["seq_len"])

    def reference(self, lowp=None, half_batch=False) -> dict:
        """The plain reference's readings from the seed's weights: on one
        chip there; on several, laid over the cell's chips by the
        benchmark's own layout (`weights.spread`)."""
        layout = None
        if self.cell.chips > 1:
            layout = weights.spread(self.param_shapes, jax.devices()[: self.cell.chips])
        params = weights.make_params(self.param_shapes, self.seed, jnp.float32,
                                     out_shardings=layout, arch=self.cell.arch)
        batches = [self.data.data.batch(i) for i in range(CHECKED_STEPS)]
        return check.train_reference(
            self.cell.arch, self.cell.config["model"], self.opt, params, batches,
            CHECKED_STEPS, self.delta_norms, lowp=lowp, half_batch=half_batch,
            shardings=layout)
