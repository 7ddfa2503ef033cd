"""Checkpointing + fault tolerance: atomic saves, elastic restore,
crash-resume bit-exactness, heartbeats/stragglers, preemption, re-planning.
"""

import os
import signal
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.configs import registry as REG
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as CKPT
from repro.train import data as DATA
from repro.train import fault_tolerance as FT
from repro.train import optimizer as OPT
from repro.train import train_step as TS


def _tiny_setup(seed=0):
    cfg = REG.smoke_config("yi-9b")
    opt = OPT.OptConfig(lr=1e-3, warmup_steps=1, total_steps=50)
    state = TS.init_state(jax.random.key(seed), cfg, opt)
    shape = ShapeConfig("t", 32, 4, "train")
    ds = DATA.SyntheticLM(cfg, shape, seed=seed, act_dtype=jnp.float32)
    step = jax.jit(TS.make_train_step(cfg, opt))
    return cfg, state, ds, step


def _assert_state_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Checkpoint basics
# ---------------------------------------------------------------------------


def test_save_restore_roundtrip(tmp_path):
    _, state, _, _ = _tiny_setup()
    CKPT.save(str(tmp_path), state, 7)
    assert CKPT.latest_step(str(tmp_path)) == 7
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, manifest = CKPT.restore(str(tmp_path), target)
    assert manifest["step"] == 7
    _assert_state_equal(state, restored)


def test_restore_ignores_partial_tmp(tmp_path):
    _, state, _, _ = _tiny_setup()
    CKPT.save(str(tmp_path), state, 5)
    # simulate a crash mid-save: stale .tmp directory beside the good one
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert CKPT.latest_step(str(tmp_path)) == 5


def test_manager_gc_keeps_last_k(tmp_path):
    _, state, _, _ = _tiny_setup()
    mgr = CKPT.CheckpointManager(str(tmp_path), every=1, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_sync(state, s)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_00000003", "step_00000004"]


def test_manager_async(tmp_path):
    _, state, _, _ = _tiny_setup()
    mgr = CKPT.CheckpointManager(str(tmp_path), every=1, keep=3)
    mgr.save_async(state, 11)
    mgr.wait()
    assert CKPT.latest_step(str(tmp_path)) == 11


def test_elastic_restore_new_sharding(tmp_path):
    """Restore onto a (trivially different) sharding — the elastic path."""
    _, state, _, _ = _tiny_setup()
    CKPT.save(str(tmp_path), state, 1)
    mesh = make_mesh((1,), ("data",))
    from repro.parallel import sharding as SH
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    sh = TS.TrainState(
        params=SH.param_shardings(mesh, state.params),
        opt_state=SH.param_shardings(mesh, state.opt_state),
        step=SH.scalar_sharding(mesh), err_state=None)
    restored, _ = CKPT.restore(str(tmp_path), target, shardings=sh)
    _assert_state_equal(state, restored)


# ---------------------------------------------------------------------------
# Crash-resume bit-exactness (THE fault-tolerance invariant)
# ---------------------------------------------------------------------------


def test_crash_resume_bitexact(tmp_path):
    cfg, state0, ds, step = _tiny_setup()
    mgr = CKPT.CheckpointManager(str(tmp_path), every=3, keep=5)

    def step_fn(state, batch):
        return step(state, batch)

    # uninterrupted run to step 8
    ref_state, _ = FT.run_training(
        jax.tree.map(lambda x: x, state0), step_fn, ds.batch, 8)

    # interrupted run: dies at step 5, restores from the step-3 checkpoint
    with pytest.raises(FT.SimulatedFailure):
        FT.run_training(jax.tree.map(lambda x: x, state0), step_fn,
                        ds.batch, 8, manager=mgr, fail_at=5)
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state0)
    resumed, _ = CKPT.restore(str(tmp_path), target)
    assert int(resumed.step) == 3
    final, _ = FT.run_training(resumed, step_fn, ds.batch, 8, manager=mgr)
    assert int(final.step) == 8
    _assert_state_equal(ref_state.params, final.params)


# ---------------------------------------------------------------------------
# Heartbeats / stragglers / preemption / re-planning
# ---------------------------------------------------------------------------


def test_heartbeat_failure_detection():
    mon = FT.HeartbeatMonitor([0, 1, 2], timeout_s=10.0)
    mon.beat(0, 1, now=0.0)
    mon.beat(1, 1, now=0.0)
    mon.beat(2, 1, now=0.0)
    mon.beat(0, 2, now=8.0)
    mon.beat(1, 2, now=8.0)
    assert mon.failed(now=15.0) == {2}


def test_straggler_detection():
    mon = FT.HeartbeatMonitor([0, 1, 2, 3], straggler_factor=1.5)
    t = {w: 0.0 for w in range(4)}
    for step in range(1, 6):
        for w in range(4):
            dt = 1.0 if w != 3 else 2.5  # worker 3 is slow
            t[w] += dt
            mon.beat(w, step, now=t[w])
    assert mon.stragglers() == {3}


def test_preemption_guard_checkpoints_and_stops(tmp_path):
    cfg, state0, ds, step = _tiny_setup()
    mgr = CKPT.CheckpointManager(str(tmp_path), every=100, keep=2)

    with FT.PreemptionGuard(signals=(signal.SIGUSR1,)) as guard:
        def step_fn(state, batch):
            new_state, m = step(state, batch)
            if int(new_state.step) == 4:  # preempt mid-run
                os.kill(os.getpid(), signal.SIGUSR1)
            return new_state, m

        final, log = FT.run_training(state0, step_fn, ds.batch, 20,
                                     manager=mgr, guard=guard)
    assert guard.preempted
    assert int(final.step) < 20
    assert CKPT.latest_step(str(tmp_path)) == int(final.step)


@given(st.integers(16, 4096))
def test_replan_mesh_properties(n_chips):
    shape, axes = FT.replan_mesh(n_chips, model=16, pod_size=256)
    total = int(np.prod(shape))
    assert total <= n_chips                     # never oversubscribe
    assert shape[-1] in (16, 8, 4, 2, 1)        # TP axis preserved or halved
    assert total >= n_chips // 4                # uses most surviving chips
    assert len(shape) == len(axes)


def test_replan_keeps_tp_axis_when_possible():
    shape, axes = FT.replan_mesh(512, model=16, pod_size=256)
    assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
    shape, axes = FT.replan_mesh(496, model=16, pod_size=256)
    # one pod lost 16 chips: 1 pod of (15, 16) + remainder ignored
    assert shape[-1] == 16


def test_shard_assignment_deterministic_and_balanced():
    a1 = FT.shard_assignment(64, [0, 1, 2, 5])
    a2 = FT.shard_assignment(64, [5, 2, 1, 0])
    assert a1 == a2
    sizes = [len(v) for v in a1.values()]
    assert max(sizes) - min(sizes) <= 1
    assert sorted(s for v in a1.values() for s in v) == list(range(64))
