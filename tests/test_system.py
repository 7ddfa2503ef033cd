"""End-to-end system tests: the full public path (configs -> data -> train
-> checkpoint -> serve) plus a real single-cell dry-run in a subprocess."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry as REG
from repro.configs.base import ShapeConfig
from repro.models import model as MD
from repro.serve import decode as D
from repro.train import data as DATA
from repro.train import optimizer as OPT
from repro.train import train_step as TS


def test_train_then_generate_end_to_end():
    cfg = REG.smoke_config("mixtral-8x7b")  # MoE + SWA path
    opt = OPT.OptConfig(lr=3e-3, warmup_steps=2, total_steps=15)
    state = TS.init_state(jax.random.key(0), cfg, opt)
    shape = ShapeConfig("t", 64, 4, "train")
    ds = DATA.SyntheticLM(cfg, shape, act_dtype=jnp.float32)
    step = jax.jit(TS.make_train_step(cfg, opt), donate_argnums=(0,))
    first = last = None
    for i in range(15):
        state, m = step(state, ds.batch(i))
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    assert last < first

    cache = MD.init_cache(cfg, 2, 32, jnp.float32)
    toks, _, _ = D.generate(state.params, cfg, cache,
                            jnp.array([[1], [2]], jnp.int32),
                            jnp.zeros((2,), jnp.int32), 8)
    assert toks.shape == (2, 8)
    assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size)))


def test_train_driver_cli(tmp_path, monkeypatch):
    """The launch/train.py driver end-to-end, including restart."""
    from repro.launch import train as TR

    # keep the persistent compile cache off in the test process
    monkeypatch.setattr(TR, "place_compile_cache", lambda: "")
    state, log = TR.main(["--arch", "internvl2-1b", "--steps", "6",
                          "--batch", "2", "--seq", "48",
                          "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])
    assert int(state.step) == 6
    # restart picks up from the checkpoint and continues
    state2, log2 = TR.main(["--arch", "internvl2-1b", "--steps", "8",
                            "--batch", "2", "--seq", "48",
                            "--ckpt-dir", str(tmp_path),
                            "--ckpt-every", "3"])
    assert int(state2.step) == 8
    assert len(log2) == 2  # only steps 7..8 re-run


def test_dryrun_single_cell_subprocess():
    """One real dry-run cell on the 256-chip mesh (the full sweep runs via
    `python -m repro.launch.dryrun --all`; this guards the machinery)."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-moe-3b-a800m", "--shape", "decode_32k", "--mesh",
         "single", "--out", "/tmp/dryrun_test", "--force"],
        capture_output=True, text=True, timeout=540, cwd="/root/repo",
        env=env)
    assert " ok " in r.stdout, r.stdout + r.stderr[-2000:]
    rec = json.load(open(
        "/tmp/dryrun_test/granite-moe-3b-a800m__decode_32k__single.json"))
    assert rec["ok"]
    assert rec["n_chips"] == 256
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
    assert rec["analysis"]["memory"]["peak_bytes_per_device"] < 16e9


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own and the code sets
    nothing; otherwise the cache is the fixed .jax_cache/ of the checkout
    (never a temp, pid or time-derived path)."""
    from repro.launch import compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-outside")
    assert CC.place_compile_cache() == "cache-from-outside"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = CC.place_compile_cache()
        assert path == str(CC.CHECKOUT_ROOT / ".jax_cache")
        assert (CC.CHECKOUT_ROOT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_to_run_off_the_chip():
    """chip_smoke.py exits non-zero, and prints no result, without a TPU."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(root, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture()
def chip_smoke_on_cpu(monkeypatch):
    """chip_smoke.py's module, with the platform's attention choice set to
    the chip's (Pallas; interpreted here) so its phases rehearse the path
    the chip runs, at a tiny size."""
    import importlib.util

    from repro.kernels.tri_attn import ops as OPS
    from repro.launch import serve as SERVE

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(root, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(OPS, "platform_impl", lambda: "pallas")
    # keep the persistent compile cache off in the test process
    monkeypatch.setattr(SERVE, "place_compile_cache", lambda: "")
    return mod


def test_chip_smoke_kernel_phase_on_cpu(chip_smoke_on_cpu):
    chip_smoke_on_cpu.phase_kernels(h=4, hkv=2, d=16, blk=8, slots=8,
                                    s_cache=64)


@pytest.mark.parametrize("step_mode", ["split", "fused"])
def test_chip_smoke_serving_phase_on_cpu(chip_smoke_on_cpu, step_mode):
    cs = chip_smoke_on_cpu
    cs.phase_serving(step_mode, cs.serve_argv(
        step_mode, slots=4, max_len=128, prompts=(20, 100), max_new=4,
        requests=4, full=False), requests=4, max_new=4)


def test_chip_smoke_training_phase_on_cpu(chip_smoke_on_cpu):
    chip_smoke_on_cpu.phase_training(steps=2, layers=2, docs=(50, 30, 20),
                                     block=16, full=False)
