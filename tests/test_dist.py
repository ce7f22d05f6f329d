"""Multi-device semantics, each in a subprocess with 8 host devices:
shard_map MoE == XLA MoE, sharded train step == single-device step,
compressed ring all-reduce == psum, elastic checkpoint restore across
mesh shapes."""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run8(body: str, timeout=420) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


def test_moe_shard_map_matches_xla_path():
    run8("""
        from repro.config import ModelConfig, MoEConfig, ParallelConfig
        from repro.dist import sharding as shlib
        from repro.launch.mesh import make_local_mesh, local_mesh_config
        from repro.models import moe as moe_lib
        from repro.models.param import init_params

        for mode in ("ep", "tp"):
            cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=16,
                              n_heads=2, n_kv_heads=2, d_ff=32, vocab=64,
                              moe=MoEConfig(num_experts=4, top_k=2,
                                            capacity_factor=8.0, sharding=mode))
            defs = moe_lib.moe_defs(cfg, 1)
            p = init_params(defs, jax.random.PRNGKey(0))
            p = jax.tree.map(lambda a: a[0], p)
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16)).astype(jnp.bfloat16)

            # jit both sides: eager XLA materializes bf16 intermediates
            # that jit fuses in f32, so eager-vs-jit differs by bf16 ULPs
            y_ref, aux_ref = jax.jit(
                lambda p, x: moe_lib.moe_apply_xla(p, x, cfg))(p, x)

            mesh = make_local_mesh(model=2, data=2, pod=2)
            with mesh, shlib.use_mesh(mesh, local_mesh_config(mesh), ParallelConfig()):
                y_sm, aux_sm = jax.jit(
                    lambda p, x: moe_lib.moe_apply_shard_map(p, x, cfg, mesh)
                )(p, x)
            err = np.abs(np.asarray(y_sm, np.float32) - np.asarray(y_ref, np.float32))
            scale = np.maximum(np.abs(np.asarray(y_ref, np.float32)), 1.0)
            assert float((err / scale).max()) < 0.05, float((err / scale).max())
            np.testing.assert_allclose(float(aux_sm), float(aux_ref), atol=1e-3)
            print(mode, "OK")
    """)


def test_sharded_train_step_matches_single_device():
    run8("""
        from repro.config import OptimizerConfig, ParallelConfig
        from repro.configs import get_arch
        from repro.dist import sharding as shlib
        from repro.launch.mesh import make_local_mesh, local_mesh_config
        from repro.models.model import build_model
        from repro.models.param import init_params
        from repro.train.step import init_opt_state, make_train_step

        cfg = get_arch("granite_8b").smoke
        model = build_model(cfg)
        params = init_params(model.param_defs(), jax.random.PRNGKey(0))
        ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=5)
        par = ParallelConfig(microbatches=1)
        opt = init_opt_state(params, ocfg, par)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
        batch = {"tokens": tokens}

        p1, o1, m1 = jax.jit(make_train_step(model, ocfg, par))(params, opt, batch)

        mesh = make_local_mesh(model=2, data=4)
        with mesh, shlib.use_mesh(mesh, local_mesh_config(mesh), par):
            step = jax.jit(make_train_step(model, ocfg, par, batch_shards=4))
            p2, o2, m2 = step(params, opt, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2, (m1, m2)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=3e-2, rtol=3e-2)
        print("train step parity OK")
    """)


def test_int8_ring_allreduce_close_to_psum():
    run8("""
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import ring_allreduce_int8
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model=1, data=8)
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 32), jnp.float32)

        def inner(xl):
            return ring_allreduce_int8(xl, "data")

        y = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data")))(x)
        # every shard ends with (approximately) the global sum
        exact = jnp.sum(x, axis=0, keepdims=True)
        got = y[0:1]
        rel = float(jnp.max(jnp.abs(got - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
        assert rel < 0.08, rel
        # and all shards agree with each other
        for i in range(1, 8):
            np.testing.assert_allclose(np.asarray(y[i]), np.asarray(y[0]),
                                       rtol=0.1, atol=0.3)
        print("ring allreduce OK rel", rel)
    """)


def test_elastic_checkpoint_restore_across_meshes():
    run8("""
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_arch
        from repro.dist import sharding as shlib
        from repro.launch.mesh import make_local_mesh, local_mesh_config
        from repro.launch import specs as S
        from repro.config import OptimizerConfig, ParallelConfig
        from repro.models.model import build_model
        from repro.models.param import init_params
        from repro.train.step import init_opt_state

        cfg = get_arch("stablelm_3b").smoke
        model = build_model(cfg)
        par = ParallelConfig()
        ocfg = OptimizerConfig()

        mesh_a = make_local_mesh(model=4, data=2)
        with mesh_a, shlib.use_mesh(mesh_a, local_mesh_config(mesh_a), par):
            _, specs_a, sh_a = S.param_shardings(model, mesh_a, par)
            params = init_params(model.param_defs(), jax.random.PRNGKey(0))
            params = jax.tree.map(jax.device_put, params, sh_a)
            opt = init_opt_state(params, ocfg, par)

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(5, params, opt)

            mesh_b = make_local_mesh(model=2, data=4)   # DIFFERENT mesh
            with mesh_b, shlib.use_mesh(mesh_b, local_mesh_config(mesh_b), par):
                _, specs_b, sh_b = S.param_shardings(model, mesh_b, par)
                o_structs, o_sh = S.opt_shardings(
                    jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params),
                    specs_b, mesh_b, ocfg, par)
                p2, o2, _, meta = mgr.restore(params, opt, shardings=(sh_b, o_sh))
            assert meta["step"] == 5
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))
        print("elastic restore OK")
    """)
