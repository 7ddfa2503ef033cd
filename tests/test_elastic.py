"""Elastic-scaling integration: a checkpoint saved on ONE device restores
onto an 8-device (2x2x2 pod/data/model) mesh with the production sharding
rules and trains a further step — the restart-after-topology-change path
(node failure -> replan_mesh -> restore -> continue)."""

import os
import subprocess
import sys
import textwrap

import jax

from repro.configs import registry as REG
from repro.train import checkpoint as CKPT
from repro.train import optimizer as OPT
from repro.train import train_step as TS


def test_elastic_restore_onto_8_device_mesh(tmp_path):
    # save on the single real device
    import jax.numpy as jnp
    cfg = REG.smoke_config("yi-9b")
    opt = OPT.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    state = TS.init_state(jax.random.key(0), cfg, opt)
    state.step = jnp.full((), 4, jnp.int32)
    CKPT.save(str(tmp_path), state, 4)

    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.configs import registry as REG
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.parallel import sharding as SH
        from repro.train import checkpoint as CKPT
        from repro.train import data as DATA
        from repro.train import optimizer as OPT
        from repro.train import train_step as TS

        # the elastic replan for 8 surviving chips, TP axis preserved at 2
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = REG.smoke_config("yi-9b")
        opt = OPT.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        ref = TS.init_state(jax.random.key(0), cfg, opt)
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), ref)
        sh = TS.TrainState(
            params=SH.param_shardings(mesh, ref.params),
            opt_state=SH.param_shardings(mesh, ref.opt_state),
            step=SH.scalar_sharding(mesh), err_state=None)
        state, manifest = CKPT.restore(r"{tmp_path}", target, shardings=sh)
        assert manifest["step"] == 4
        assert int(state.step) == 4
        # every leaf landed with its production sharding
        flat = jax.tree.leaves(state.params)
        assert all(len(x.sharding.device_set) >= 1 for x in flat)

        # one more step on the new mesh
        shape = ShapeConfig("t", 32, 8, "train")
        batch = DATA.SyntheticLM(cfg, shape,
                                 act_dtype=jnp.float32).batch(4)
        bs = SH.batch_shardings(mesh, batch)
        batch = jax.tree.map(jax.device_put, batch, bs)
        with mesh:
            step = jax.jit(TS.make_train_step(cfg, opt))
            state, metrics = step(state, batch)
        assert int(state.step) == 5
        assert bool(jnp.isfinite(metrics["loss"]))
        print("ELASTIC_OK", float(metrics["loss"]))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=480, cwd="/root/repo", env=env)
    assert "ELASTIC_OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
