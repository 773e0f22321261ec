"""Multi-device tests run in a subprocess with a forced 8-device host
platform (keeping the main test process on 1 device, per the dry-run
isolation rule)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, timeout=420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The 8-device (4 data x 2 model) sharded train step must produce the
    same loss trajectory as the host run — GSPMD partitioning is
    numerics-preserving for our step."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.base import RuntimeConfig, ShapeConfig
        from repro.data import pipeline as data_mod
        from repro.distributed import sharding as shd
        from repro.launch import steps as steps_mod
        from repro.models import lm
        from repro.optim import adamw

        assert len(jax.devices()) == 8
        cfg = get_config('qwen2.5-14b').reduced()
        shape = ShapeConfig('t', 32, 4, 'train')
        rt = RuntimeConfig(mode='xla')
        rules = shd.ShardingRules()
        params, axes = lm.init(jax.random.PRNGKey(0), cfg)
        opt = adamw.init(params)
        step = steps_mod.make_train_step(
            cfg, rt, adamw.AdamWConfig(lr=1e-3))

        losses = {}
        for name, mesh_shape in (('sharded', (4, 2)), ('single', (1, 1))):
            devs = np.array(jax.devices()[: mesh_shape[0] * mesh_shape[1]])
            mesh = Mesh(devs.reshape(mesh_shape), ('data', 'model'))
            pspecs = shd.repair_specs(
                params, shd.param_specs(axes, rules, mesh), mesh)
            ospecs = shd.opt_state_specs(pspecs, mesh)
            bspecs = steps_mod._maybe_batch_spec(
                steps_mod.input_specs(cfg, shape), mesh)
            to_sh = lambda t: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda x: isinstance(x, P))
            with mesh:
                fn = jax.jit(step,
                             in_shardings=(to_sh(pspecs), to_sh(ospecs),
                                           to_sh(bspecs)),
                             out_shardings=(to_sh(pspecs), to_sh(ospecs),
                                            None))
                p, o = params, opt
                ls = []
                for i in range(3):
                    batch = jax.tree_util.tree_map(
                        jnp.asarray,
                        data_mod.synth_batch(cfg, shape, i, 7))
                    p, o, m = fn(p, o, batch)
                    ls.append(float(m['loss']))
            losses[name] = ls
        np.testing.assert_allclose(losses['sharded'], losses['single'],
                                   rtol=2e-4, atol=2e-4)
        print('OK', losses['sharded'])
    """)


def test_pipeline_parallel_matches_sequential():
    """GPipe ppermute schedule over a 4-stage mesh == sequential apply."""
    _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed import pipeline_parallel as pp

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs.reshape(4), ('stage',))

        def block_fn(params, x):
            return jnp.tanh(x @ params['w'])

        rng = np.random.default_rng(0)
        stage_params = {'w': jnp.asarray(
            rng.standard_normal((4, 16, 16), np.float32) * 0.5)}
        x = jnp.asarray(rng.standard_normal((8, 16), np.float32))

        with mesh:
            y = pp.pipeline_apply(block_fn, stage_params, x, mesh=mesh,
                                  n_microbatches=4)
        want = x
        for i in range(4):
            want = block_fn({'w': stage_params['w'][i]}, want)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print('OK pipeline')
    """)


def test_hierarchical_psum_and_reduce_scatter():
    _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.distributed import collectives as coll

        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ('pod', 'data'))
        x = jnp.arange(8.0).reshape(8, 1)

        f = coll.shard_map(
            lambda v: coll.psum_hierarchical(v, pod_axis='pod',
                                             data_axis='data'),
            mesh=mesh, in_specs=P(('pod', 'data'), None),
            out_specs=P(('pod', 'data'), None))
        y = f(x)
        np.testing.assert_allclose(np.asarray(y), 28.0)

        g = coll.shard_map(
            lambda v: coll.reduce_scatter_mean(v, 'data', split_dim=1),
            mesh=mesh, in_specs=P('pod', None),
            out_specs=P('pod', 'data'))
        z = g(jnp.ones((2, 8)))
        np.testing.assert_allclose(np.asarray(z), 1.0)
        print('OK collectives')
    """)


def test_dryrun_cell_on_small_mesh():
    """plan_cell lower+compile on a reduced config over a real 8-device
    mesh — the same path the 512-device production dry-run takes."""
    _run("""
        import dataclasses
        import jax, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.configs.base import RuntimeConfig, ShapeConfig
        from repro.distributed import sharding as shd
        from repro.launch import dryrun, steps as steps_mod

        devs = np.array(jax.devices()).reshape(4, 2)
        mesh = Mesh(devs, ('data', 'model'))
        rt = RuntimeConfig(mode='xla', loss_unroll=True,
                           fused_loss_chunk=8)
        for arch, kind in (('zamba2-7b', 'train'),
                           ('granite-moe-3b-a800m', 'decode'),
                           ('paligemma-3b', 'prefill')):
            cfg = get_config(arch).reduced()
            shape = ShapeConfig('t', 64, 8, kind)
            cell = steps_mod.plan_cell(cfg, shape, mesh, rt)
            with mesh:
                fn = jax.jit(cell.step,
                             in_shardings=dryrun._to_shardings(
                                 cell.in_shardings, mesh),
                             out_shardings=dryrun._to_shardings(
                                 cell.out_shardings, mesh),
                             donate_argnums=cell.donate_argnums)
                compiled = fn.lower(*cell.args).compile()
            cost = compiled.cost_analysis()
            assert cost.get('flops', 0) > 0
            coll = dryrun.parse_collective_bytes(compiled.as_text())
            assert sum(coll['bytes'].values()) > 0, arch
            print('OK', arch, kind, cost.get('flops'))
    """)


def test_elastic_reshard_resume_identical():
    """Large-scale recovery contract: train on an 8-device (4,2) mesh,
    checkpoint, 'lose' half the devices, re-plan the mesh with
    fault_tolerance.plan_mesh, restore the checkpoint under the new
    shardings, and continue — the loss trajectory must be identical to an
    uninterrupted run (global batch and math are mesh-independent)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpointer as ckpt
        from repro.configs import get_config
        from repro.configs.base import RuntimeConfig, ShapeConfig
        from repro.data import pipeline as data_mod
        from repro.distributed import fault_tolerance as ft
        from repro.distributed import sharding as shd
        from repro.launch import steps as steps_mod
        from repro.models import lm
        from repro.optim import adamw

        cfg = get_config('qwen2.5-14b').reduced()
        shape = ShapeConfig('t', 32, 4, 'train')
        rt = RuntimeConfig(mode='xla')
        rules = shd.ShardingRules()
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        step = steps_mod.make_train_step(cfg, rt, opt_cfg)
        params0, axes = lm.init(jax.random.PRNGKey(0), cfg)
        opt0 = adamw.init(params0)

        def build(mesh_shape, n_devices):
            devs = np.array(jax.devices()[:n_devices]).reshape(mesh_shape)
            mesh = Mesh(devs, ('data', 'model'))
            pspecs = shd.repair_specs(
                params0, shd.param_specs(axes, rules, mesh), mesh)
            ospecs = shd.opt_state_specs(pspecs, mesh)
            bspecs = steps_mod._maybe_batch_spec(
                steps_mod.input_specs(cfg, shape), mesh)
            to_sh = lambda t: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda x: isinstance(x, P))
            fn = jax.jit(step,
                         in_shardings=(to_sh(pspecs), to_sh(ospecs),
                                       to_sh(bspecs)),
                         out_shardings=(to_sh(pspecs), to_sh(ospecs), None))
            return mesh, fn

        def run_steps(fn, mesh, p, o, start, n):
            losses = []
            with mesh:
                for i in range(start, start + n):
                    batch = jax.tree_util.tree_map(
                        jnp.asarray, data_mod.synth_batch(cfg, shape, i, 7))
                    p, o, m = fn(p, o, batch)
                    losses.append(float(m['loss']))
            return p, o, losses

        # uninterrupted 8-device run
        mesh8, fn8 = build((4, 2), 8)
        _, _, full = run_steps(fn8, mesh8, params0, opt0, 0, 8)

        # interrupted: 4 steps on 8 devices, checkpoint, lose 4 devices
        p, o, first = run_steps(fn8, mesh8, params0, opt0, 0, 4)
        d = tempfile.mkdtemp()
        ckpt.save(d, 4, {'params': jax.tree_util.tree_map(np.asarray, p),
                         'opt': jax.tree_util.tree_map(np.asarray, o)})

        plan = ft.plan_mesh(4, model_parallel=2)      # survivors -> (2, 2)
        assert plan.shape == (2, 2), plan
        mesh4, fn4 = build(plan.shape, 4)
        tree, _ = ckpt.restore(d, 4, {'params': params0, 'opt': opt0})
        _, _, rest = run_steps(fn4, mesh4, tree['params'], tree['opt'], 4, 4)

        np.testing.assert_allclose(first + rest, full, rtol=2e-4, atol=2e-5)
        print('OK elastic reshard', full[-1])
    """)
