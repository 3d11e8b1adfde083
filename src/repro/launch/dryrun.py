import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above run before ANY jax import (jax pins device count at
first init) and exist ONLY here — tests/benches see the real single device.

Per cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(**specs)
        compiled = lowered.compile()
        memory_analysis() / cost_analysis() / HLO-collective parse → JSON

Results append incrementally to --out (default dryrun_results.json);
existing (arch, shape, mesh) entries are skipped unless --force.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi   # 2×16×16 only
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.analysis import roofline as rf
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.train.train_step import TrainConfig, TrainState, make_train_step
from repro.optim import OptState


# the chip the production meshes are made of (roofline peaks, `device_kind`)
TARGET_KIND = "TPU v5 lite"

# Per-arch memory-fit knobs for the 256-chip/16GB-HBM pod, recorded in the
# cell records: microbatch accumulation bounds live activations; bf16 Adam
# m/v halves optimizer state (math stays f32). Serve cells load bf16 weights
# (standard inference practice).
TRAIN_KNOBS = {
    "yi-34b": dict(accum_steps=2, opt_state_dtype="bfloat16"),
    "qwen3-moe-235b-a22b": dict(accum_steps=4, opt_state_dtype="bfloat16"),
    "llama4-scout-17b-a16e": dict(accum_steps=2, opt_state_dtype="bfloat16"),
    "recurrentgemma-9b": dict(opt_state_dtype="bfloat16"),
}
# archs whose optimizer state must ZeRO-shard across pods too (DESIGN.md §4)
FSDP_OVER_POD = {"qwen3-moe-235b-a22b", "llama4-scout-17b-a16e", "yi-34b"}


def _train_cfg_for(arch: str) -> TrainConfig:
    return TrainConfig(**TRAIN_KNOBS.get(arch, {}))


def _state_struct(cfg, train_cfg: TrainConfig):
    """Abstract TrainState via eval_shape (no allocation)."""
    api = get_model(cfg)
    opt_dt = jnp.dtype(train_cfg.opt_state_dtype)

    def mk():
        params = api.init(jax.random.PRNGKey(0), cfg)
        zeros = lambda p: jax.tree.map(lambda x: jnp.zeros(x.shape, opt_dt), p)
        return TrainState(
            params=params,
            opt=OptState(m=zeros(params), v=zeros(params), step=jnp.int32(0)),
            residual=None,
            step=jnp.int32(0),
            loss_scale=jnp.float32(1.0),
            good_steps=jnp.int32(0),
            skipped=jnp.int32(0),
        )

    return jax.eval_shape(mk)


def _state_specs(state_struct):
    pspec = shd.param_specs(state_struct.params)
    from jax.sharding import PartitionSpec as P

    return TrainState(
        params=pspec,
        opt=OptState(m=pspec, v=pspec, step=P()),
        residual=None,
        step=P(),
        loss_scale=P(),
        good_steps=P(),
        skipped=P(),
    )


def build_cell(arch: str, shape_name: str, *, attn_impl=None, remat=None,
               use_sp=None, extra_cfg=None, train_overrides=None):
    """Returns (step_fn, arg_structs, in_specs, model_flops, cfg)."""
    from jax.sharding import PartitionSpec as P

    cfg = configs.get_config(arch)
    overrides = dict(extra_cfg or {})
    if attn_impl:
        overrides["attn_impl"] = attn_impl
    if remat:
        overrides["remat"] = remat
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = configs.SHAPES[shape_name]
    api = get_model(cfg)
    batch_struct = configs.input_specs(cfg, shape)
    tokens_global = shape.global_batch * shape.seq_len

    if shape.kind == "train":
        train_cfg = dataclasses.replace(_train_cfg_for(arch), **(train_overrides or {}))
        train_step = make_train_step(cfg, train_cfg)
        state_struct = _state_struct(cfg, train_cfg)
        state_specs = _state_specs(state_struct)
        batch_specs = shd.batch_specs(batch_struct)
        step = train_step
        args = (state_struct, batch_struct)
        in_specs = (state_specs, batch_specs)
        donate = (0,)  # TrainState buffers reused in place (params/opt/grads)
        # metrics are replicated scalars; new state keeps the input sharding
        metrics_struct = jax.eval_shape(step, state_struct, batch_struct)[1]
        out_specs = (state_specs, jax.tree.map(lambda _: P(), metrics_struct))
        model_flops = 6.0 * cfg.active_param_count() * tokens_global
    elif shape.kind == "prefill":
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")  # inference weights
        api = get_model(cfg)
        params_struct = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
        pspecs = shd.param_specs(params_struct)
        batch_specs = shd.batch_specs(batch_struct)
        step = lambda params, batch: api.apply(params, batch, cfg, last_only=True)[0]
        args = (params_struct, batch_struct)
        in_specs = (pspecs, batch_specs)
        donate = ()
        logits_struct = jax.eval_shape(step, params_struct, batch_struct)
        out_specs = shd.batch_specs(logits_struct)
        model_flops = 2.0 * cfg.active_param_count() * tokens_global
    else:  # decode
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")  # inference weights
        api = get_model(cfg)
        params_struct = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
        pspecs = shd.param_specs(params_struct)
        cache_struct = jax.eval_shape(
            lambda: api.init_cache(shape.global_batch, shape.seq_len, cfg)
        )
        cache_specs = shd.cache_specs_tree(cache_struct)
        tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        pos = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        step = lambda params, cache, token, p: api.decode_step(params, cache, token, p, cfg)
        args = (params_struct, cache_struct, tok, pos)
        in_specs = (pspecs, cache_specs, P(), P())
        donate = (1,)  # KV cache updated in place
        logits_struct = jax.eval_shape(step, *args)[0]
        out_specs = (shd.batch_specs(logits_struct), cache_specs)
        model_flops = 2.0 * cfg.active_param_count() * shape.global_batch
    return step, args, in_specs, out_specs, donate, model_flops, cfg


def _compile_once(arch, shape_name, mesh, *, attn_impl=None, remat=None,
                  extra_cfg=None, train_overrides=None, use_sp=True, use_tp=True):
    """Lower + compile one cell variant. Returns (cost, hlo, mem, secs, cfg)."""
    t0 = time.time()
    fsdp = ("pod", "data") if arch in FSDP_OVER_POD else "data"
    ctx = shd.ShardingCtx(mesh, fsdp_axis=fsdp, use_sp=use_sp)
    ctx.tp_activations = use_tp
    with shd.activate(ctx):
        with jax.set_mesh(mesh):
            step, args, in_specs, out_specs, donate, model_flops, cfg = build_cell(
                arch, shape_name, attn_impl=attn_impl, remat=remat,
                extra_cfg=extra_cfg, train_overrides=train_overrides,
            )
            jitted = jax.jit(step, in_shardings=in_specs,
                             out_shardings=out_specs,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()

            mem = {}
            try:
                ma = compiled.memory_analysis()
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes",
                          "alias_size_in_bytes"):
                    v = getattr(ma, k, None)
                    if v is not None:
                        mem[k] = int(v)
                mem["total_bytes_per_device"] = (
                    mem.get("argument_size_in_bytes", 0)
                    + mem.get("temp_size_in_bytes", 0)
                    + mem.get("output_size_in_bytes", 0)
                    - mem.get("alias_size_in_bytes", 0)
                )
            except Exception as e:  # pragma: no cover
                mem["error"] = str(e)

            cost = {}
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                cost = {k: float(v) for k, v in ca.items()
                        if isinstance(v, (int, float))}
            except Exception as e:  # pragma: no cover
                cost = {"error": str(e)}
            hlo = compiled.as_text()
    return cost, hlo, mem, time.time() - t0, model_flops, cfg


def _corrected_cost(arch, shape_name, mesh, cfg, *, attn_impl=None, remat=None,
                    extra_cfg=None, train_overrides=None, use_sp=True, use_tp=True):
    """Trip-count-corrected (flops, bytes, collectives) via unrolled probes.

    XLA cost_analysis counts a while-loop (lax.scan) body ONCE, so the
    full-depth compile undercounts scanned layers. We compile python-unrolled
    1-block and 2-block variants at the SAME global shape; the difference is
    exactly one pattern-block's cost and
        total = c(p) + (L/p − 1) · (c(2p) − c(p))
    (embed/head/frontend costs cancel in the difference). Collective bytes
    get the same correction from the probes' HLO.
    """
    p = len(cfg.pattern)
    shape = configs.SHAPES[shape_name]
    # the microbatch-accumulation scan body is ALSO counted once by XLA's
    # cost analysis; everything inside it (the whole model) repeats
    # accum_steps times per step (optimizer runs once — negligible flops)
    tcfg = dataclasses.replace(_train_cfg_for(arch), **(train_overrides or {}))
    accum = tcfg.accum_steps if shape.kind == "train" else 1
    probes = []
    for k in (1, 2):
        ov = dict(extra_cfg or {})
        ov.update(n_layers=p * k, scan_layers=False)
        if cfg.is_encdec:
            ov["n_encoder_layers"] = k
        cost, hlo, _, secs, _, _ = _compile_once(
            arch, shape_name, mesh, attn_impl=attn_impl, remat=remat, extra_cfg=ov,
            train_overrides=train_overrides, use_sp=use_sp, use_tp=use_tp,
        )
        colls = rf.parse_hlo_collectives(hlo)
        probes.append((cost, colls, secs))
    (c1, x1, s1), (c2, x2, s2) = probes
    blocks = cfg.n_layers / p  # fractional when a remainder stack exists

    def corr(a, b):
        return (a + (blocks - 1.0) * (b - a)) * accum

    cost = {
        "flops": corr(c1.get("flops", 0.0), c2.get("flops", 0.0)),
        "bytes accessed": corr(c1.get("bytes accessed", 0.0), c2.get("bytes accessed", 0.0)),
        "transcendentals": corr(c1.get("transcendentals", 0.0), c2.get("transcendentals", 0.0)),
    }
    coll = {}
    zero = {"count": 0, "bytes": 0.0, "wire_bytes": 0.0}
    for kind in set(x1) | set(x2):
        a, b = x1.get(kind, zero), x2.get(kind, zero)
        coll[kind] = {k: corr(a[k], b[k]) for k in zero}
    return cost, coll, s1 + s2


def run_cell(arch: str, shape_name: str, multi_pod: bool, *, attn_impl=None,
             remat=None, extra_cfg=None, verbose=True, probe_cost=True,
             train_overrides=None, use_sp=True, use_tp=True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    hw = rf.peaks_for(TARGET_KIND)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    cfg0 = configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    ok, reason = configs.cell_status(cfg0, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    # full-depth compile: proves the real config lowers/compiles + memory
    cost_raw, hlo, mem, t_compile, model_flops, cfg = _compile_once(
        arch, shape_name, mesh, attn_impl=attn_impl, remat=remat,
        extra_cfg=extra_cfg, train_overrides=train_overrides, use_sp=use_sp,
        use_tp=use_tp,
    )

    probe_s = 0.0
    if probe_cost:
        cost, coll, probe_s = _corrected_cost(
            arch, shape_name, mesh, cfg, attn_impl=attn_impl, remat=remat,
            extra_cfg=extra_cfg, train_overrides=train_overrides, use_sp=use_sp,
            use_tp=use_tp,
        )
        cbytes = sum(v["wire_bytes"] for v in coll.values())
        report = rf.RooflineReport(
            arch=arch, shape=shape_name, mesh=mesh_name,
            flops_per_device=cost["flops"],
            bytes_per_device=cost["bytes accessed"],
            collective_bytes_per_device=cbytes,
            collectives={k: v for k, v in coll.items() if v["count"]},
            t_compute=cost["flops"] / hw.peak_flops,
            t_memory=cost["bytes accessed"] / hw.hbm_bw,
            t_collective=cbytes / hw.ici_bw,
            dominant="",
            model_flops=model_flops,
            useful_flops_ratio=0.0,
            chips=chips,
            memory_per_device=mem,
        )
        report.dominant = max(
            (("compute", report.t_compute), ("memory", report.t_memory),
             ("collective", report.t_collective)), key=lambda kv: kv[1])[0]
        total = report.flops_per_device * chips
        report.useful_flops_ratio = model_flops / total if total else 0.0
    else:
        report = rf.roofline(
            arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
            cost=cost_raw, hlo_text=hlo, model_flops=model_flops, hw=hw,
            memory_per_device=mem,
        )

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "compile_s": round(t_compile, 1), "probe_s": round(probe_s, 1),
        "cost_raw": {k: v for k, v in cost_raw.items()
                     if k in ("flops", "bytes accessed", "transcendentals")},
        "memory": mem,
        "roofline": report.as_dict(),
    }
    if verbose:
        dom = report.dominant
        print(
            f"[{arch} × {shape_name} × {mesh_name}] compile={t_compile:.0f}s "
            f"flops/dev={report.flops_per_device:.3e} "
            f"bytes/dev={report.bytes_per_device:.3e} "
            f"coll/dev={report.collective_bytes_per_device:.3e} "
            f"t=(c {report.t_compute*1e3:.2f} | m {report.t_memory*1e3:.2f} "
            f"| x {report.t_collective*1e3:.2f}) ms → {dom}; "
            f"useful={report.useful_flops_ratio:.2f} "
            f"mem/dev={mem.get('total_bytes_per_device', 0)/2**30:.2f}GiB",
            flush=True,
        )
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None, help="one arch (default: all)")
    p.add_argument("--shape", default=None, help="one shape (default: all)")
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--out", default="dryrun_results.json")
    p.add_argument("--force", action="store_true")
    p.add_argument("--attn-impl", default=None)
    p.add_argument("--remat", default=None)
    args = p.parse_args(argv)

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    archs = [args.arch] if args.arch else configs.ARCHS
    shapes = [args.shape] if args.shape else list(configs.SHAPES)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                if not args.force and (arch, shape, mesh_name) in done:
                    continue
                try:
                    rec = run_cell(arch, shape, multi,
                                   attn_impl=args.attn_impl, remat=args.remat)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    print(f"[{arch} × {shape} × {mesh_name}] ERROR {e}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"]) != (arch, shape, mesh_name)]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"dry-run complete: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
