"""On-chip smoke run of the FLASH-D serving and training paths.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # sharded training on four chips only

One chip: serves eight requests (prompts of 256–2048 tokens, 32 new tokens
each) of qwen2-1.5b at its published widths through `Engine.serve` with the
paged KV pool, the mixed varlen step and the Pallas kernels
(`attn_impl="flashd_pallas"`); checks the varlen and paged-decode kernels
against the `kernels/ref.py` oracle at that model's head widths; and takes
a few `train_resilient` steps of qwen3-0.6b through the training
launcher's step.

Four chips: a few sharded train steps of qwen2-1.5b on the `plan_mesh(4)`
mesh. Its first loss is compared with an unsharded forward of the same
batch on one chip of the same process.

Weights, prompts and batches are random, made from `--seed`. The run
refuses to start without a TPU; any failed phase exits non-zero. The last
line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# bf16 K/V/q and output, one-pass bf16 MXU products of f32 probabilities:
# the kernels and the f32 oracle agree to ~1e-2 at these widths.
KERNEL_TOL = 2e-2
# sharded vs unsharded loss of one batch: bf16 compute, different
# reduction orders, a loss of ~ln(vocab) ≈ 12
LOSS_TOL = 2e-2

SERVE_ARCH = "qwen2-1.5b"
TRAIN_ARCH = "qwen3-0.6b"
N_REQUESTS, NEW_TOKENS, PROMPT_RANGE = 8, 32, (256, 2048)
PREFILL_CHUNK = 256
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 1024
SHARDED_STEPS, SHARDED_BATCH, SHARDED_SEQ = 3, 4, 1024


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own events."""

    def __init__(self):
        import jax

        self.seconds, self.count = 0.0, 0

        def on_event(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok, msg):
    """A failed check fails the run (not an `assert`: it survives -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def check_kernels(cfg, seed):
    """flashd_varlen and flashd_decode_paged on the device vs the f32
    oracle `kernels.ref.decode_ref`, at the model's head widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.attention import gather_pages
    from repro.kernels import ops
    from repro.kernels.ref import decode_ref

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    page, n_tbl, b = 64, 32, 4  # 2048-token tables
    n_pages = 1 + b * n_tbl
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_pages = jax.random.normal(keys[0], (n_pages, page, hkv, d), jnp.bfloat16)
    v_pages = jax.random.normal(keys[1], (n_pages, page, hkv, d), jnp.bfloat16)
    tbl = jnp.asarray(
        1 + rng.permutation(b * n_tbl).reshape(b, n_tbl), jnp.int32
    )
    kv_len = jnp.asarray(rng.integers(1, n_tbl * page + 1, b), jnp.int32)
    kc = gather_pages(k_pages, tbl).transpose(0, 2, 1, 3)  # [B, Hkv, S, d]
    vc = gather_pages(v_pages, tbl).transpose(0, 2, 1, 3)

    def err(o, ref):
        o = jnp.asarray(o, jnp.float32)
        require(bool(jnp.all(jnp.isfinite(o))), "non-finite kernel output")
        return float(jnp.max(jnp.abs(o - jnp.asarray(ref, jnp.float32))))

    # paged decode: one query per sequence against its whole cache
    q = jax.random.normal(keys[2], (b, 1, hq, d), jnp.bfloat16)
    o = ops.pallas_decode_paged(q, k_pages, v_pages, tbl, kv_len)
    e_dec = err(o[:, 0], decode_ref(q[:, 0], kc, vc, kv_len))

    # varlen: each sequence packs a block-aligned segment of its last rows
    # (a prefill chunk); row t sees keys [0, q_pos[t]] of its sequence
    block_q, seg = 8, 61
    seq_ids, q_pos = [], []
    for s in range(b):
        n = min(seg, int(kv_len[s]))
        rows = -(-n // block_q) * block_q
        seq_ids += [s] * n + [-1] * (rows - n)
        start = int(kv_len[s]) - n
        q_pos += list(range(start, start + n)) + [-1] * (rows - n)
    seq_ids = jnp.asarray(seq_ids, jnp.int32)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    qv = jax.random.normal(keys[3], (len(q_pos), hq, d), jnp.bfloat16)
    o = ops.pallas_varlen(
        qv, k_pages, v_pages, tbl, seq_ids, q_pos, kv_len, block_q=block_q
    )
    owner = jnp.maximum(seq_ids, 0)
    ref = decode_ref(qv, kc[owner], vc[owner], jnp.maximum(q_pos + 1, 0))
    e_var = err(o, ref)
    log(f"kernels vs ref (Hq {hq}, Hkv {hkv}, d {d}, page {page}): "
        f"paged decode max|Δ| {e_dec:.3g}, varlen max|Δ| {e_var:.3g} "
        f"(tolerance {KERNEL_TOL})")
    require(e_dec < KERNEL_TOL and e_var < KERNEL_TOL, "kernel disagrees with ref")


def serve_requests(cfg, seed):
    """Engine.serve on the paged, mixed, Pallas path; returns the stats."""
    import jax
    import numpy as np

    from repro.models import get_model
    from repro.serve import Engine, ServeConfig

    cfg = dataclasses.replace(cfg, attn_impl="flashd_pallas")
    params = get_model(cfg).init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1, N_REQUESTS)
    lens[:2] = PROMPT_RANGE  # both ends of the range, always
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]
    eng = Engine(params, cfg, ServeConfig(
        max_batch=N_REQUESTS,
        max_len=PROMPT_RANGE[1] + NEW_TOKENS,
        kv_layout="paged",
        step_mode="mixed",
        prefill_chunk=PREFILL_CHUNK,
        seed=seed,
    ))
    t0 = time.perf_counter()
    outs = eng.serve(prompts, max_new_tokens=NEW_TOKENS)
    wall = time.perf_counter() - t0
    st = eng.stats()
    status = st["request_status"]
    for rid, o in enumerate(outs):
        o = np.asarray(o)
        require(status.get(rid) == "done", f"request {rid}: {status.get(rid)}")
        require(o.shape == (NEW_TOKENS,), f"request {rid}: {o.shape} tokens")
        require(np.all((o >= 0) & (o < cfg.vocab_size)), f"request {rid}: bad ids")
    require(st["downgrades"] == 0, f"{st['downgrades']} kernel downgrades")
    require(st["attn_impl"] == "flashd_pallas", st["attn_impl"])
    log(f"served {len(outs)} requests ({int(lens.sum())} prompt tokens, "
        f"{NEW_TOKENS} new each) in {wall:.1f}s wall incl. compile; "
        f"downgrades {st['downgrades']}, attn_impl {st['attn_impl']}, "
        f"kv pool {st.get('kv_pool_bytes', 0) / 2**20:.0f} MiB")
    return st


def train_steps(cfg, seed):
    """A few `train_resilient` steps through the launcher's trainer."""
    import numpy as np

    from repro.data import DataConfig, SyntheticLM
    from repro.launch.train import build_trainer
    from repro.optim import AdamWConfig
    from repro.train import ResilienceConfig, train_resilient
    from repro.train.train_step import TrainConfig

    cfg = dataclasses.replace(cfg, attn_impl="flashd_pallas")
    tc = TrainConfig(optimizer=AdamWConfig(lr=3e-3), warmup_steps=1,
                     total_steps=TRAIN_STEPS)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                                  global_batch=TRAIN_BATCH, seed=seed))
    init_state_fn, step_fn = build_trainer(cfg, tc, seed=seed)
    ckpt_dir = os.path.join(ROOT, ".chip_smoke_ckpt")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    try:
        _, history, counters = train_resilient(
            ckpt_dir=ckpt_dir, model_cfg=cfg, train_cfg=tc, data=data,
            total_steps=TRAIN_STEPS, seed=seed,
            res=ResilienceConfig(ckpt_every=TRAIN_STEPS, keep_checkpoints=1),
            init_state_fn=init_state_fn, step_fn=step_fn,
        )
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    losses = [h["loss"] for h in history]
    require(len(losses) == TRAIN_STEPS, f"{len(losses)} committed steps")
    require(all(np.isfinite(losses)), f"non-finite loss {losses}")
    require(counters["skipped"] == 0, f"{counters['skipped']} skipped updates")
    log(f"{cfg.name} train B={TRAIN_BATCH} S={TRAIN_SEQ}: losses "
        + ", ".join(f"{x:.4f}" for x in losses))


def sharded_train(cfg, seed, n_chips):
    """Sharded train steps on plan_mesh(n_chips); the first loss must match
    an unsharded forward of the same batch on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.models import get_model
    from repro.optim import AdamWConfig
    from repro.runtime.resilience import plan_mesh
    from repro.train.train_step import TrainConfig

    cfg = dataclasses.replace(cfg, attn_impl="flashd_pallas")
    tc = TrainConfig(optimizer=AdamWConfig(lr=3e-3), warmup_steps=1,
                     total_steps=SHARDED_STEPS)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=SHARDED_SEQ,
                                  global_batch=SHARDED_BATCH, seed=seed))
    # unsharded reference on one chip, freed before the sharded state
    # exists (both on chip 0 would leave it ~1.5 GiB of HBM): the same
    # parameters, since `init_train_state` draws them with `api.init` from
    # the same key and threefry draws do not depend on the sharding
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    api = get_model(cfg)
    params1 = jax.jit(lambda: api.init(jax.random.PRNGKey(seed), cfg),
                      out_shardings=one)()
    ref = float(jax.jit(lambda p, b: api.loss(p, b, cfg)[0])(
        params1, jax.device_put(data.batch(0), one)))
    del params1

    plan = plan_mesh(n_chips)
    mesh = make_mesh(plan.mesh_shape, plan.axis_names,
                     devices=jax.devices()[:n_chips])
    init_state_fn, step_fn = build_trainer(cfg, tc, seed=seed, mesh=mesh)
    state = init_state_fn()
    losses = []
    for step in range(SHARDED_STEPS):
        state, metrics = step_fn(state, jax.tree.map(jnp.asarray, data.batch(step)))
        losses.append(float(metrics["loss"]))
    require(all(np.isfinite(losses)), f"non-finite loss {losses}")
    log(f"{cfg.name} sharded train on mesh {dict(zip(plan.axis_names, plan.mesh_shape))} "
        f"B={SHARDED_BATCH} S={SHARDED_SEQ}: losses "
        + ", ".join(f"{x:.4f}" for x in losses)
        + f"; unsharded loss of step 0 {ref:.4f}, "
        f"|Δ| {abs(losses[0] - ref):.3g} (tolerance {LOSS_TOL})")
    require(abs(losses[0] - ref) < LOSS_TOL, "sharded loss disagrees")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded training phase, on four chips")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} TPU "
              f"devices", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs
    from repro.launch.cache import enable_compile_cache

    log(f"device {dev.device_kind} ×{len(devices)}, jax {jax.__version__}, "
        f"compile cache {enable_compile_cache()}")
    clock = CompileClock()

    def phase(name, fn, *a):
        t0, m = time.perf_counter(), clock.mark()
        fn(*a)
        sec, n = clock.since(m)
        log(f"phase {name}: {time.perf_counter() - t0:.1f}s wall, "
            f"{n} compiles, {sec:.1f}s compiling")

    if args.chips == 4:
        phase("sharded-train", sharded_train,
              configs.get_config(SERVE_ARCH), args.seed, args.chips)
    else:
        serve_cfg = configs.get_config(SERVE_ARCH)
        phase("kernels", check_kernels, serve_cfg, args.seed)
        phase("serve", serve_requests, serve_cfg, args.seed)
        phase("train", train_steps, configs.get_config(TRAIN_ARCH), args.seed)
    log(f"total compile {clock.seconds:.1f}s over {clock.count} compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
