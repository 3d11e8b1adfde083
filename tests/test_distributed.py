"""Distributed correctness on forced 8-device host meshes (subprocess —
the main test process must keep seeing exactly one device).

Covered: GPipe pipeline == sequential reference, sharded train step ==
single-device train step, sharding-rule divisibility fallbacks, MoE under
expert parallelism.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_in_subprocess(code: str):
    """Run `code` with 8 forced host devices; raise on failure."""
    prog = "import os\nos.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n" + textwrap.dedent(code)
    res = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": os.path.join(_REPO, "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", "/root"), "JAX_PLATFORMS": "cpu"},
        cwd=_REPO,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_pipeline_matches_sequential():
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.distributed.pipeline import pipeline_apply, split_stages

    S, L, M, mb, d = 4, 8, 8, 4, 16
    mesh = make_mesh((S,), ("pod",))
    rng = np.random.default_rng(0)
    layer_w = jnp.asarray(rng.normal(size=(L, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)

    def block_fn(stage_params, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    stage_params = split_stages(layer_w, S)
    y = pipeline_apply(block_fn, stage_params, x, mesh=mesh, axis_name="pod")

    # sequential reference
    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ layer_w[i])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5, atol=2e-5)
    print("pipeline OK")
    """)


def test_pipeline_grads_flow():
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.distributed.pipeline import pipeline_apply, split_stages

    S, L, M, mb, d = 2, 4, 4, 2, 8
    mesh = make_mesh((S,), ("pod",))
    rng = np.random.default_rng(1)
    layer_w = jnp.asarray(rng.normal(size=(L, d, d)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)

    def block_fn(stage_params, xin):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, xin, stage_params)
        return h

    def loss_pp(w):
        y = pipeline_apply(block_fn, split_stages(w, S), x, mesh=mesh, axis_name="pod")
        return jnp.sum(y ** 2)

    def loss_seq(w):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return jnp.sum(h ** 2)

    g1 = jax.grad(loss_pp)(layer_w)
    g2 = jax.grad(loss_seq)(layer_w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-5)
    print("pipeline grads OK")
    """)


def test_sharded_train_step_matches_single_device():
    _run_in_subprocess("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.distributed import sharding as shd
    from repro.train.train_step import TrainConfig, init_train_state, make_train_step
    from repro.data import DataConfig, SyntheticLM

    cfg = configs.get_smoke_config("qwen3-0.6b")
    tc = TrainConfig()
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8))
    batch = jax.tree.map(jnp.asarray, data.batch(0))

    # single-device reference
    state0 = init_train_state(jax.random.PRNGKey(0), cfg, tc)
    sref, mref = jax.jit(make_train_step(cfg, tc))(state0, batch)

    # 4x2 (data, model) mesh with full rules engine
    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = shd.ShardingCtx(mesh)
    with shd.activate(ctx), jax.set_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, tc)
        pspecs = shd.param_specs(state.params)
        from repro.train.train_step import TrainState
        from repro.optim import OptState
        sspec = TrainState(params=pspecs, opt=OptState(m=pspecs, v=pspecs, step=P()),
                           residual=None, step=P(),
                           loss_scale=P(), good_steps=P(), skipped=P())
        state = jax.device_put(state, shd.to_named(sspec))
        batch_sh = jax.device_put(batch, shd.to_named(shd.batch_specs(batch)))
        step = jax.jit(make_train_step(cfg, tc),
                       in_shardings=(sspec, shd.batch_specs(batch)))
        s1, m1 = step(state, batch_sh)

    np.testing.assert_allclose(float(mref["loss"]), float(m1["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(sref.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
    print("sharded == single OK")
    """)


@pytest.mark.parametrize("attn_impl", ["flashd", "flashd_pallas"])
def test_launcher_trainer_sharded_matches_single_device(attn_impl):
    """`launch.train.build_trainer` on the plan_mesh(4) mesh == one device:
    the launcher's sharded init and two steps, the second fed the state the
    first returned (as the train loop does), with the Pallas kernels run
    per batch shard inside a shard_map (GSPMD cannot partition them)."""
    _run_in_subprocess(f"""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_trainer
    from repro.runtime.resilience import plan_mesh
    from repro.train.train_step import TrainConfig

    cfg = dataclasses.replace(configs.get_smoke_config("qwen2-1.5b"),
                              attn_impl="{attn_impl}")
    tc = TrainConfig()
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4))
    batch = jax.tree.map(jnp.asarray, data.batch(0))

    init1, step1 = build_trainer(cfg, tc, seed=0)
    plan = plan_mesh(4)
    mesh = make_mesh(plan.mesh_shape, plan.axis_names, devices=jax.devices()[:4])
    init4, step4 = build_trainer(cfg, tc, seed=0, mesh=mesh)

    sref, s4 = init1(), init4()
    for _ in range(2):
        sref, mref = step1(sref, batch)
        s4, m4 = step4(s4, batch)
        np.testing.assert_allclose(float(mref["loss"]), float(m4["loss"]), rtol=1e-4)
    # the step hands back the state in the layout it takes it in
    for a, b in zip(jax.tree.leaves(init4()), jax.tree.leaves(s4)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (a.sharding, b.sharding)
    for a, b in zip(jax.tree.leaves(sref.params), jax.tree.leaves(s4.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
    print("launcher sharded == single OK")
    """)


def test_moe_expert_parallel_matches():
    _run_in_subprocess("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro import configs
    from repro.distributed import sharding as shd
    from repro.models import moe as m

    cfg = configs.get_smoke_config("qwen3-moe-235b-a22b")
    params = m.init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, cfg.d_model)), jnp.float32)
    y0, _ = m.apply_moe(params, x, cfg)

    mesh = make_mesh((2, 4), ("data", "model"))
    ctx = shd.ShardingCtx(mesh)
    with shd.activate(ctx), jax.set_mesh(mesh):
        pspecs = shd.param_specs(params)
        f = jax.jit(lambda p, xx: m.apply_moe(p, xx, cfg)[0],
                    in_shardings=(pspecs, P(("data",), None, None)))
        y1 = f(params, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=3e-3, atol=3e-3)
    print("EP OK")
    """)


def test_param_rules_divisibility_fallback():
    """Rules engine never emits a spec whose axis product doesn't divide."""
    import jax.numpy as jnp
    from repro import configs
    from repro.models import get_model

    mesh_axes = {"data": 16, "model": 16, "pod": 2}

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        devices = np.empty((2, 16, 16), dtype=object)

    ctx = shd.ShardingCtx.__new__(shd.ShardingCtx)
    ctx.mesh = None
    ctx.axis_sizes = mesh_axes
    ctx.use_sp = True
    ctx.fsdp_axis = "data"
    ctx.has_pod = True

    with shd.activate(ctx):
        for arch in ["qwen2-1.5b", "yi-34b", "qwen3-moe-235b-a22b", "seamless-m4t-medium"]:
            cfg = configs.get_config(arch)
            api = get_model(cfg)
            shapes = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0), cfg))
            specs = shd.param_specs(shapes)
            flat_shapes = jax.tree.leaves(shapes)
            flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_shapes) == len(flat_specs)
            for shp, spec in zip(flat_shapes, flat_specs):
                for dim, ax in zip(shp.shape, tuple(spec) + (None,) * 8):
                    if ax is None:
                        continue
                    size = ctx.axis_size(ax)
                    assert dim % size == 0, (arch, shp.shape, spec)


def test_activation_rules_fallbacks():
    ctx = shd.ShardingCtx.__new__(shd.ShardingCtx)
    ctx.mesh = None
    ctx.axis_sizes = {"data": 16, "model": 16}
    ctx.use_sp = True
    ctx.fsdp_axis = "data"
    ctx.has_pod = False
    with shd.activate(ctx):
        # heads divide → TP over heads
        assert shd.spec_for("heads", (256, 4096, 32, 128)) == P(("data",), None, "model", None)
        # heads don't divide → full-DP attention over data×model
        s = shd.spec_for("heads", (256, 4096, 56, 128))
        assert s == P(("data", "model"), None, None, None)
        # batch=1 long context decode: KV cache context-parallel over data
        s = shd.spec_for("kv_cache", (1, 524288, 8, 128))
        assert s[1] == "data"
