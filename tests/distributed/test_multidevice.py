"""Multi-device distribution tests.

These run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(jax pins the device count at first init, so the main test process — which
must see 1 device for everything else — cannot host them).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def _run(code: str, timeout=420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\n" \
                                 f"STDERR:\n{proc.stderr[-4000:]}"
    return proc.stdout


def test_sharded_train_step_and_elastic_remesh(tmp_path):
    _run(f"""
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == 8
        from repro.configs import get_config
        from repro.models import transformer as T
        from repro.training import optimizer as opt
        from repro.training.train import make_train_step
        from repro.distributed import sharding as S
        from repro.checkpoint import CheckpointManager

        cfg = get_config('internlm2-1.8b', smoke=True)
        ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=0)
        from repro.launch.mesh import make_mesh
        mesh42 = make_mesh((4, 2), ('data', 'model'))
        mesh24 = make_mesh((2, 4), ('data', 'model'))

        params = T.init_params(jax.random.PRNGKey(0), cfg)
        state = opt.init(params)
        batch = {{'tokens': jnp.ones((8, 32), jnp.int32)}}

        def run_on(mesh, params, state):
            ps = S.param_shardings(mesh, params)
            os_ = S.opt_state_shardings(mesh, state, params)
            bs = S.batch_shardings(mesh, batch)
            params = jax.device_put(params, ps)
            state = jax.device_put(state, os_)
            b = jax.device_put(batch, bs)
            step = jax.jit(make_train_step(cfg, ocfg),
                           in_shardings=(ps, os_, bs))
            return step(params, state, b)

        p1, s1, m1 = run_on(mesh42, params, state)
        assert np.isfinite(float(m1['loss']))

        # elastic remesh: checkpoint under (4,2), restore+step under (2,4)
        mgr = CheckpointManager({str(tmp_path)!r}, async_write=False)
        mgr.save(1, {{'params': p1, 'opt': s1}})
        like = {{'params': p1, 'opt': s1}}
        ps24 = S.param_shardings(mesh24, params)
        os24 = S.opt_state_shardings(mesh24, state, params)
        restored, _ = mgr.restore(1, like,
                                  shardings={{'params': ps24, 'opt': os24}})
        p2, s2, m2 = run_on(mesh24, restored['params'], restored['opt'])
        assert np.isfinite(float(m2['loss']))

        # same math on both meshes: one more step on mesh42 from p1
        p3, s3, m3 = run_on(mesh42, p1, s1)
        assert abs(float(m2['loss']) - float(m3['loss'])) < 1e-3
        print('elastic remesh OK', float(m2['loss']), float(m3['loss']))
    """)


def test_compressed_allreduce_and_pipeline():
    _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compress import make_compressed_allreduce
        from repro.distributed.pipeline import make_pipeline

        mesh = jax.make_mesh((8,), ('data',))
        rng = np.random.RandomState(0)
        local = jnp.asarray(rng.randn(8, 64, 32).astype(np.float32))
        err = jnp.zeros_like(local)
        fn = make_compressed_allreduce(mesh, {'g': local})
        out, new_err = fn({'g': local}, {'g': err})
        want = np.mean(np.asarray(local), axis=0)
        got = np.asarray(out['g'])[0]
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 2e-2, rel
        # error feedback property: the *average* transmitted gradient over
        # rounds converges to the true mean (per-round error need not be
        # monotone)
        out2, _ = fn({'g': local}, new_err)
        got2 = np.asarray(out2['g'])[0]
        avg2 = (got + got2) / 2
        # L2 error of the running average roughly halves (compensation)
        assert np.linalg.norm(avg2 - want) <= \
            0.8 * np.linalg.norm(got - want)
        print('compressed allreduce OK', rel)

        # pipeline parallel: y = x @ W applied stage-by-stage == chained
        smesh = jax.make_mesh((8,), ('stage',))
        S, M, D = 8, 4, 16
        Ws = jnp.asarray(rng.randn(S, D, D).astype(np.float32) * 0.2)
        x = jnp.asarray(rng.randn(M, 4, D).astype(np.float32))

        def stage_fn(w, xb):
            return jnp.tanh(xb @ w)

        pipe = make_pipeline(smesh, stage_fn, Ws, n_micro=M)
        got = np.asarray(pipe(Ws, x))
        want = np.asarray(x)
        for s in range(S):
            want = np.tanh(want @ np.asarray(Ws[s]))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        print('pipeline parallel OK')
    """)


def test_dryrun_single_cell_multipod():
    """End-to-end proof that the dry-run machinery works inside the test
    suite (512 fake devices in a subprocess; smallest arch).

    Was xfail (33.6 GB of involuntary-full-remat temps): fixed by (a)
    `sharding.constrain_activation` pinning the layer/scan boundary to the
    canonical batch×model layout (only when the batch axis carries the
    full DP degree — a partial pin measurably made it worse), and (b)
    computing the CE label pick as an equality-mask sum instead of
    `take_along_axis`, which gathered along the model-sharded vocab axis
    and forced XLA to replicate the full f32 logits.  Temps: 1.44 GB,
    zero involuntary remats."""
    _run("""
        import os
        os.environ['XLA_FLAGS'] = \
            '--xla_force_host_platform_device_count=512'
        import jax
        from repro.launch.mesh import make_production_mesh
        from repro.launch.steps import build_cell
        mesh = make_production_mesh(multi_pod=True)
        assert mesh.devices.size == 512
        fn, aargs, meta = build_cell('internlm2-1.8b', 'train_4k', mesh)
        with mesh:
            compiled = fn.lower(*aargs).compile()
            ma = compiled.memory_analysis()
        print('multi-pod compile OK; temp bytes/device =',
              ma.temp_size_in_bytes)
        assert ma.temp_size_in_bytes < 16e9   # fits v5e HBM
    """, timeout=560)
