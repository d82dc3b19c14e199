"""Multi-device integration tests.

jax fixes its device count at first init, so these run in subprocesses
with XLA_FLAGS=--xla_force_host_platform_device_count=8 — the same
mechanism the production dry-run uses at 512.
"""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout, cwd=REPO)


def test_bittide_scheduled_pipeline_matches_sequential():
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.sched import pipeline_apply, plan
        from repro.core import ring, make_links
        from repro.core.latency import logical_latency
        from repro.core.schedule import LogicalSynchronyNetwork

        S, M, D = 4, 6, 16
        mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(0, 0.5, (S, D, D)).astype(np.float32))
        x = jnp.asarray(rng.normal(0, 1, (M, 2, D)).astype(np.float32))

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        out = pipeline_apply(stage_fn, ws, x, mesh, "stage", M)
        # sequential reference
        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        # the AOT timetable for the same chain is schedulable and bounded
        topo = ring(S)
        lsn = LogicalSynchronyNetwork(topo, logical_latency(topo, make_links(topo)))
        p = plan(lsn, list(range(S)), M, fwd_ticks=100, bwd_ticks=0,
                 activation_frames=8)
        assert p.bounded
        print("PIPELINE_OK", p.makespan_ticks, round(p.bubble_fraction, 3))
    """)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_elastic_remesh_and_resume():
    """Train on 8 devices, checkpoint, 'fail' 4, remesh to 4, resume: loss
    continues from the same value (resharding restore is exact)."""
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.models import ModelZoo
        from repro.models.layers import materialize, pspec_tree
        from repro.data import DataConfig, SyntheticPipeline
        from repro.optim import AdamWConfig, adamw_init, adamw_update
        from repro.checkpoint import CheckpointManager
        from repro.ft import remesh, plan_mesh

        cfg = get_config("smollm-135m").reduced()
        zoo = ModelZoo(cfg)
        opt = AdamWConfig(lr=1e-2)
        data = SyntheticPipeline(DataConfig(cfg.vocab_size, 32, 8, seed=1))

        def make_step():
            def step(params, opt_state, batch, n):
                loss, g = jax.value_and_grad(zoo.train_loss)(params, batch)
                params, opt_state, _ = adamw_update(g, opt_state, params, opt)
                return params, opt_state, loss
            return jax.jit(step)

        # -- phase 1: 8 devices (4 data x 2 model)
        mesh8 = remesh(jax.devices(), model_size=2)
        specs = pspec_tree(zoo.param_defs(), use_fsdp=False, dp_axes=("data",))
        params = materialize(zoo.param_defs(), jax.random.PRNGKey(0), jnp.float32)
        params = jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh8, s)),
                              params, specs)
        opt_state = adamw_init(params, opt)
        step = make_step()
        for n in range(3):
            params, opt_state, loss = step(params, opt_state, data.batch(n), n)
        ckdir = tempfile.mkdtemp()
        mgr = CheckpointManager(ckdir)
        mgr.save(3, {"params": params, "opt": opt_state})
        p8, o8, loss8 = step(params, opt_state, data.batch(3), 3)

        # -- phase 2: four devices "fail"; remesh survivors, restore, resume
        survivors = jax.devices()[:4]
        assert plan_mesh(len(survivors), 2) == (2, 2)
        mesh4 = remesh(survivors, model_size=2)
        shard4 = jax.tree.map(lambda s: NamedSharding(mesh4, s),
                              {"params": specs,
                               "opt": {"mu": specs, "nu": specs,
                                       "count": jax.sharding.PartitionSpec()}})
        n, state = mgr.restore_latest({"params": params, "opt": opt_state}, shard4)
        assert n == 3
        p4, o4, loss4 = step(state["params"], state["opt"], data.batch(3), 3)
        print("LOSS8", float(loss8), "LOSS4", float(loss4))
        # restore is exact, but the 4-device step reduces in a different
        # order than the 8-device one -> O(1e-4) float32 drift is expected
        assert abs(float(loss8) - float(loss4)) < 5e-4
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_mini_dryrun_8dev():
    """The dry-run machinery end-to-end on an 8-device (2 pod, 2 data,
    2 model) mesh with a reduced arch — fast sanity for CI."""
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.launch.train import make_train_step, abstract_train_args
        from repro.launch.hloanalysis import collective_stats, cost_analysis_dict

        cfg = get_config("internlm2-1.8b").reduced()
        shape = ShapeSpec("train", "train", 64, 8)
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                    ("pod", "data", "model"))
        args = abstract_train_args(cfg, shape, mesh, ("pod", "data"))
        lowered = jax.jit(make_train_step(cfg)).lower(*args)
        compiled = lowered.compile()
        ca = cost_analysis_dict(compiled)
        coll = collective_stats(compiled.as_text())
        assert ca.get("flops", 0) > 0
        assert coll["total"]["count"] > 0, "expected collectives on a 3-axis mesh"
        print("MINIDRYRUN_OK", int(coll["total"]["count"]))
    """)
    assert "MINIDRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


def test_compressed_psum_multidevice():
    r = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compression import compressed_psum

        mesh = Mesh(np.array(jax.devices()), ("dp",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 1, (8, 16)).astype(np.float32))
        e = jnp.zeros((8, 16), jnp.float32)

        fn = jax.shard_map(lambda g, e: compressed_psum(g, e, "dp"),
                           mesh=mesh, in_specs=(P("dp"), P("dp")),
                           out_specs=(P(), P("dp")), check_vma=False)
        mean, new_e = fn(g, e)
        ref = np.asarray(g).mean(axis=0)
        got = np.asarray(mean)[0]
        # int8 quantization error bound: scale/2 per shard, averaged
        assert np.abs(got - ref).max() < 0.05
        print("PSUM_OK", float(np.abs(got - ref).max()))
    """)
    assert "PSUM_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
