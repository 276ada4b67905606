"""Multi-device tests: run in subprocesses with 8 fake host devices (the
main pytest process must keep the real single-device view)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path


SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(code: str, timeout=560) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_host_mesh
"""


def test_sharded_train_step_runs_and_matches_single_device():
    """A reduced arch trains one step on a 2x4 mesh; loss matches the
    single-device value (same math, different layout)."""
    code = HEADER + textwrap.dedent("""
        from repro.config import get_config, ShapeConfig, TrainConfig, MeshConfig
        from repro.models import api
        from repro.sharding import param_partition, batch_partition, named
        from repro.sharding.ctx import active_mesh
        from repro.train.step import make_train_step
        from repro.optim.adamw import adamw_init

        cfg = get_config("llama3-8b", reduced=True)
        mcfg = MeshConfig((2, 4), ("data", "model"))
        mesh = make_host_mesh((2, 4), ("data", "model"))
        shape = ShapeConfig("t", "train", 64, 4)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        batch = api.make_batch(cfg, shape, jax.random.PRNGKey(1))
        batch = jax.tree.map(lambda x: x % cfg.vocab_size
                             if x.dtype == jnp.int32 else x, batch)
        loss1, _ = jax.jit(lambda p, b: api.loss_fn(cfg, p, b, q_chunk=32))(
            params, batch)

        spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        pshard = named(mesh, param_partition(cfg, spec, mcfg))
        bshard = named(mesh, batch_partition(cfg, shape, mcfg, batch))
        with active_mesh(mesh, data_axes=("data",)):
            pp = jax.tree.map(jax.device_put, params, pshard)
            bb = jax.tree.map(jax.device_put, batch, bshard)
            loss2, _ = jax.jit(
                lambda p, b: api.loss_fn(cfg, p, b, q_chunk=32),
                in_shardings=(pshard, bshard))(pp, bb)
        print("LOSSES", float(loss1), float(loss2))
        assert abs(float(loss1) - float(loss2)) < 2e-2, (loss1, loss2)

        # one full sharded train step with donation
        from repro.config import TrainConfig
        opt = adamw_init(pp)
        step = make_train_step(cfg, TrainConfig(), q_chunk=32)
        with active_mesh(mesh, data_axes=("data",)):
            p2, o2, m = jax.jit(step, donate_argnums=(0, 1))(pp, opt, bb)
        assert np.isfinite(float(m["loss"]))
        print("OK")
    """)
    out = _run(code)
    assert "OK" in out


def test_psum_int8_collective():
    code = HEADER + textwrap.dedent("""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from repro.optim.grad_compress import psum_int8

        mesh = make_host_mesh((8,), ("dp",))
        g = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 32)),
                        jnp.float32)

        @partial(shard_map, mesh=mesh, in_specs=P("dp", None),
                 out_specs=P("dp", None))
        def reduce8(x):
            return psum_int8(x, "dp")

        out = reduce8(g)
        ref = jnp.broadcast_to(jnp.sum(g, 0, keepdims=True), g.shape)
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        print("ERR", err)
        assert err < 0.1, err  # int8 quantization error bound
        print("OK")
    """)
    assert "OK" in _run(code)


def test_elastic_resume_smaller_mesh(tmp_path):
    """Checkpoint on a (2,4) mesh, restore onto (1,4): the elastic-resume
    path after dropping a data replica / pod."""
    code = HEADER + textwrap.dedent(f"""
        from repro.config import get_config, ShapeConfig, MeshConfig
        from repro.models import api
        from repro.sharding import param_partition, named
        from repro.checkpoint import CheckpointStore

        cfg = get_config("llama3-8b", reduced=True)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            params)

        big = MeshConfig((2, 4), ("data", "model"))
        mesh_big = make_host_mesh((2, 4), ("data", "model"))
        pshard = named(mesh_big, param_partition(cfg, spec, big))
        pp = jax.tree.map(jax.device_put, params, pshard)

        store = CheckpointStore(r"{tmp_path}")
        store.save(3, pp)

        # "pod failure": resume on half the devices
        small = MeshConfig((1, 4), ("data", "model"))
        mesh_small = make_host_mesh((1, 4), ("data", "model"),
                                    devices=jax.devices()[:4])
        sshard = named(mesh_small, param_partition(cfg, spec, small))
        step, restored = store.restore(params, shardings=sshard)
        assert step == 3
        for a, b in zip(jax.tree.leaves(pp), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        devs = {{d.id for d in jax.tree.leaves(restored)[0].devices()}}
        assert devs <= set(range(4))
        print("OK")
    """)
    assert "OK" in _run(code)


def test_mini_dryrun_multi_pod_axes():
    """A 3-axis (pod, data, model) mesh lowers + compiles a reduced train
    step — the multi-pod path in miniature."""
    code = HEADER + textwrap.dedent("""
        from repro.config import get_config, ShapeConfig, TrainConfig, MeshConfig
        from repro.models import api
        from repro.sharding import param_partition, batch_partition, named
        from repro.sharding.ctx import active_mesh
        from repro.train.step import make_train_step
        from repro.optim.adamw import adamw_init_spec

        cfg = get_config("qwen2-moe-a2.7b", reduced=True)
        mcfg = MeshConfig((2, 2, 2), ("pod", "data", "model"))
        mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("t", "train", 32, 4)
        spec = api.param_spec(cfg, model_axis=2)
        pshard = named(mesh, param_partition(cfg, spec, mcfg))
        ins = api.input_specs(cfg, shape)
        bshard = named(mesh, batch_partition(cfg, shape, mcfg, ins))
        opt_spec = adamw_init_spec(spec)
        opt_shard = {"m": pshard, "v": pshard,
                     "count": named(mesh, P()),
                     "master": jax.tree.map(
                         lambda p, s: s if p.dtype == jnp.bfloat16 else None,
                         spec, pshard)}
        step = make_train_step(cfg, TrainConfig(), q_chunk=32)
        with active_mesh(mesh, data_axes=("pod", "data")):
            lowered = jax.jit(step, in_shardings=(pshard, opt_shard, bshard),
                              out_shardings=(pshard, opt_shard, None),
                              donate_argnums=(0, 1)).lower(spec, opt_spec, ins)
            compiled = lowered.compile()
        txt = compiled.as_text()
        assert "all-reduce" in txt or "reduce-scatter" in txt
        print("OK")
    """)
    assert "OK" in _run(code)
