"""Training launcher: config → mesh → resilient jitted loop → checkpoints.

Single-host it runs for real (the end-to-end example trains paper-llama on
this container); on a TPU slice the same entry point picks up all devices
(`plan_mesh`) and shards via the rules engine. The loop runs under the
`train_resilient` supervisor (DESIGN.md §6): verified checkpoints with
newest-good fallback, non-finite-grad skip + dynamic loss scaling inside
the jitted step, loss-spike rollback, and — with `--fault-rate` — the same
deterministic chaos injection the serve launcher exposes, here at the five
train sites. Restarting after a crash with `--resume` replays to a
bitwise-identical loss curve.

    PYTHONPATH=src python -m repro.launch.train --arch paper-llama \
        --steps 200 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt

    # chaos soak + resume
    PYTHONPATH=src python -m repro.launch.train --steps 200 \
        --ckpt-dir /tmp/ckpt --fault-rate 0.1 --fault-seed 7
    PYTHONPATH=src python -m repro.launch.train --steps 400 \
        --ckpt-dir /tmp/ckpt --resume
"""

from __future__ import annotations

import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.data import DataConfig, SyntheticLM
from repro.distributed import sharding as shd
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, CompressionConfig, OptState
from repro.runtime import checkpoint as ckpt
from repro.runtime.resilience import FaultInjector, StragglerMonitor, plan_mesh
from repro.train import ResilienceConfig, train_resilient
from repro.train.train_step import TrainConfig, TrainState, init_train_state, make_train_step


def build_trainer(cfg, tc: TrainConfig, *, seed: int, mesh=None):
    """(init_state_fn, step_fn) for `train_resilient`: a fresh TrainState
    and the jitted step, sharded by the rules engine over `mesh` (None →
    one device). The step donates its input state: the supervisor never
    reuses a state it has stepped, and keeping both would double the
    parameter and optimizer memory."""
    if mesh is None:
        return (
            lambda: init_train_state(jax.random.PRNGKey(seed), cfg, tc),
            jax.jit(make_train_step(cfg, tc), donate_argnums=(0,)),
        )
    ctx = shd.ShardingCtx(mesh)

    def _sspec(state):
        pspecs = shd.param_specs(state.params)
        return TrainState(params=pspecs,
                          opt=OptState(m=pspecs, v=pspecs, step=P()),
                          residual=(pspecs if state.residual is not None else None),
                          step=P(), loss_scale=P(), good_steps=P(), skipped=P())

    with shd.activate(ctx), jax.set_mesh(mesh):
        shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(seed), cfg, tc)
        )
        sspec = _sspec(shapes)
        init = jax.jit(
            lambda: init_train_state(jax.random.PRNGKey(seed), cfg, tc),
            out_shardings=shd.to_named(sspec),
        )
        # out_shardings pins the state's layout: left to the partitioner,
        # a leaf can come back sharded differently (qwen2's stacked q bias
        # does on v5e), and the next step then refuses its own output
        inner = jax.jit(
            make_train_step(cfg, tc), in_shardings=(sspec, None),
            out_shardings=(sspec, None), donate_argnums=(0,),
        )

    def init_state_fn():
        with shd.activate(ctx), jax.set_mesh(mesh):
            return init()

    def step_fn(state, batch):
        with shd.activate(ctx), jax.set_mesh(mesh):
            return inner(state, batch)

    return init_state_fn, step_fn


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="paper-llama")
    p.add_argument("--smoke", action="store_true", help="use the reduced config")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--compression", default="none", choices=["none", "int8", "topk"])
    p.add_argument("--attn-impl", default=None)
    p.add_argument("--ckpt-dir", default=None,
                   help="checkpoint directory (default: fresh temp dir)")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--keep-checkpoints", type=int, default=3,
                   help="garbage-collect all but the newest N (0 → keep all)")
    p.add_argument("--resume", action="store_true",
                   help="continue from the newest verified checkpoint in "
                        "--ckpt-dir (without this flag a non-empty dir is "
                        "an error, so nothing resumes silently)")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="chaos injection: probability each train-site "
                        "check fires (0 → no injection)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for the deterministic fault injector")
    p.add_argument("--spike-threshold", type=float, default=0.0,
                   help="loss-spike rollback: loss > T × trailing median "
                        "restores the last good checkpoint (0 → off)")
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_smoke_config(args.arch) if args.smoke else configs.get_config(args.arch)
    if args.attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    tc = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr),
        compression=CompressionConfig(kind=args.compression),
        warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps,
        accum_steps=args.accum,
    )

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_train_ckpt_")
    existing = ckpt.valid_steps(ckpt_dir)
    if existing and not args.resume:
        raise SystemExit(
            f"{ckpt_dir} already holds checkpoints (steps {existing}); "
            f"pass --resume to continue or point --ckpt-dir elsewhere"
        )
    if args.resume and existing:
        print(f"resuming from step {existing[-1]} in {ckpt_dir}")

    n_dev = len(jax.devices())
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.global_batch, seed=args.seed,
    ))
    mesh = None
    if n_dev > 1:
        plan = plan_mesh(n_dev)
        mesh = make_mesh(plan.mesh_shape, plan.axis_names)
    init_state_fn, step_fn = build_trainer(cfg, tc, seed=args.seed, mesh=mesh)

    injector = None
    if args.fault_rate > 0:
        injector = FaultInjector(args.fault_rate, args.fault_seed,
                                 sites=FaultInjector.TRAIN_SITES)

    monitor = StragglerMonitor(
        on_straggler=lambda s, dt, mu: print(
            f"[straggler] step {s}: {dt*1e3:.0f}ms vs EWMA {mu*1e3:.0f}ms "
            f"— would flag this pod for exclusion at re-mesh"
        )
    )
    last_t = [time.monotonic()]

    def on_step(step, metrics, counters):
        now = time.monotonic()
        monitor.observe(step, now - last_t[0])
        last_t[0] = now
        if step % args.log_every == 0 or step == args.steps - 1:
            print(
                f"step {step:5d} loss {metrics['loss']:.4f} "
                f"gnorm {metrics['grad_norm']:.3f} "
                f"lr {metrics['lr']:.2e} "
                f"scale {metrics['loss_scale']:.3g} | "
                f"skipped={int(metrics['skipped'])} "
                f"rollbacks={counters['rollbacks']} "
                f"restarts={counters['restarts']} "
                f"faults={counters['faults']}",
                flush=True,
            )

    res = ResilienceConfig(
        ckpt_every=args.ckpt_every,
        keep_checkpoints=args.keep_checkpoints or None,
        spike_threshold=args.spike_threshold,
    )
    t0 = time.time()
    state, history, counters = train_resilient(
        ckpt_dir=ckpt_dir, model_cfg=cfg, train_cfg=tc, data=data,
        total_steps=args.steps, seed=args.seed, res=res, injector=injector,
        init_state_fn=init_state_fn, step_fn=step_fn,
        on_step=on_step,
    )
    print(
        f"done: {len(history)} committed steps in {time.time() - t0:.1f}s "
        f"(skipped={counters['skipped']} rollbacks={counters['rollbacks']} "
        f"restarts={counters['restarts']} faults={counters['faults']} "
        f"stragglers={len(monitor.flagged)}) — checkpoints in {ckpt_dir}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
