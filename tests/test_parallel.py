"""Multi-device tests (subprocess with forced host devices): sharding rules,
BFP collectives, pipeline parallelism, elastic reshard, small-mesh dry-run."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_bfp_psum_and_pipeline():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.parallel.collectives import bfp_psum
        from repro.parallel.pipeline import pipeline_apply
        mesh = jax.make_mesh((8,), ("data",),
                              axis_types=(AUTO,))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, 2048)), jnp.float32)
        out = shard_map(lambda xs: bfp_psum(xs[0], "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P(None),
                        check_vma=False)(x)
        rel = float(jnp.abs(out - x.sum(0)).max() / jnp.abs(x.sum(0)).max())
        assert rel < 0.05, rel
        out16 = shard_map(lambda xs: bfp_psum(xs[0], "data", bits=16),
                          mesh=mesh, in_specs=P("data"), out_specs=P(None),
                          check_vma=False)(x)
        rel16 = float(jnp.abs(out16 - x.sum(0)).max()/jnp.abs(x.sum(0)).max())
        assert rel16 < 3e-4, rel16
        mesh2 = jax.make_mesh((4, 2), ("pipe", "data"),
                              axis_types=(AUTO,) * 2)
        ws = jnp.asarray(rng.standard_normal((4, 16, 16)) * 0.3, jnp.float32)
        xs = jnp.asarray(rng.standard_normal((8, 2, 16)), jnp.float32)
        fn = lambda w, x: jnp.tanh(x @ w)
        out_p = pipeline_apply(fn, ws, xs, mesh=mesh2, axis="pipe")
        ref = xs
        for s in range(4): ref = fn(ws[s], ref)
        assert float(jnp.abs(out_p - ref).max()) < 1e-5
        print("OK")
    """)
    assert "OK" in out


def test_sharding_rules_divisibility():
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.parallel import sharding as sh
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(AUTO,) * 2)
        with sh.use_mesh_rules(mesh, None):
            # divisible: sharded on model
            s = sh.logical_sharding((16, 8), (None, "heads"), mesh)
            assert s.spec == jax.sharding.PartitionSpec(None, "model"), s.spec
            # indivisible: dropped
            s2 = sh.logical_sharding((16, 5), (None, "heads"), mesh)
            assert s2.spec == jax.sharding.PartitionSpec(None, None), s2.spec
            # one mesh axis never used twice
            s3 = sh.logical_sharding((8, 8), ("heads", "mlp"), mesh)
            assert list(s3.spec).count("model") == 1, s3.spec
        print("OK")
    """)
    assert "OK" in out


def test_elastic_reshard_and_training_step():
    """Train 5 steps on a (4,2) mesh, reshard to (2,2) (shrink), continue,
    and match the single-device trajectory."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.configs import get_config
        from repro.runtime import Trainer, TrainerConfig, reshard_state
        cfg = get_config("smollm-360m").reduced()
        tc = dict(steps=6, batch=4, seq_len=32, base_lr=1e-3, log_every=2)
        mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                              axis_types=(AUTO,) * 2)
        t1 = Trainer(cfg, TrainerConfig(**tc), mesh=mesh1)
        t1.run()
        # elastic shrink to 4 devices
        mesh2 = jax.make_mesh((2, 2), ("data", "model"),
                              axis_types=(AUTO,) * 2)
        st2 = reshard_state(t1.state, mesh2)
        t2 = Trainer(cfg, TrainerConfig(**dict(tc, steps=10)), mesh=mesh2)
        t2.state = st2
        t2.run()
        assert int(jax.device_get(t2.state["step"])) == 10
        # reference: uninterrupted single-mesh run
        t3 = Trainer(cfg, TrainerConfig(**dict(tc, steps=10)), mesh=mesh1)
        t3.run()
        for a, b in zip(jax.tree_util.tree_leaves(t2.state["params"]),
                        jax.tree_util.tree_leaves(t3.state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("OK")
    """, devices=8, timeout=900)
    assert "OK" in out


@pytest.mark.parametrize("arch", ["smollm-360m", "granite-moe-1b-a400m",
                                  "mamba2-2.7b"])
def test_small_mesh_dryrun_reduced(arch):
    """lower+compile a reduced config on a 2x4 host mesh: validates the
    sharding machinery end-to-end without the 512-device production run."""
    out = _run(f"""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        AUTO = AxisType.Auto
        from repro.configs import get_config
        from repro.parallel import sharding as shlib
        from repro.launch import specs as sp
        import dataclasses
        from repro.config import ShapeCfg
        cfg = get_config("{arch}").reduced()
        shape = ShapeCfg("t", 64, 8, "train")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(AUTO,) * 2)
        with shlib.use_mesh_rules(mesh, None):
            state_spec = sp.state_specs(cfg)
            batch_spec = sp.batch_specs(cfg, shape)
            in_sh = (sp.state_shardings(cfg, state_spec, mesh),
                     sp.batch_shardings(cfg, shape, mesh, batch_spec))
            step = sp.make_train_step(cfg)
            j = jax.jit(step, in_shardings=in_sh,
                        out_shardings=(in_sh[0], None), donate_argnums=(0,))
            c = j.lower(state_spec, batch_spec).compile()
        assert c.cost_analysis().get("flops", 0) > 0
        print("OK")
    """, devices=8, timeout=600)
    assert "OK" in out
