"""Training driver.

Runs REAL steps, by default on the reduced (smoke) configs — the same code
path the production mesh lowers in dryrun.py, with checkpointing,
preemption guard and deterministic restart. ``--full-config`` keeps the
published widths; ``--layers`` then cuts depth only. Attention takes the
Pallas kernels on a TPU and the scan mirror elsewhere.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 50 \
      --batch 8 --seq 128 --ckpt-dir ckpt

XLA latency-hiding / async-collective flags for real TPU runs are set here
(they are harmless no-ops on CPU).
"""

from __future__ import annotations

import os

os.environ.setdefault(
    "LIBTPU_INIT_ARGS",
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true "
    "--xla_tpu_data_parallel_opt_different_sized_ops=true "
    "--xla_tpu_overlap_compute_collective_tc=true",
)

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import time         # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import registry as REG        # noqa: E402
from repro.configs.base import ShapeConfig       # noqa: E402
from repro.kernels.tri_attn import ops as OPS    # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.train import checkpoint as CKPT       # noqa: E402
from repro.train import data as DATA             # noqa: E402
from repro.train import fault_tolerance as FT    # noqa: E402
from repro.train import optimizer as OPT         # noqa: E402
from repro.train import train_step as TS         # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b", choices=REG.ARCH_IDS)
    ap.add_argument("--full-config", action="store_true",
                    help="published widths (one chip: cut depth with "
                         "--layers)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --full-config: keep this many layers")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient all-reduce over the "
                         "local data mesh (parallel/compression.py)")
    args = ap.parse_args(argv)

    place_compile_cache()
    cfg = REG.launch_config(args.arch, args.full_config, args.layers)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    opt = OPT.OptConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                        total_steps=args.steps)

    state = TS.init_state(jax.random.key(args.seed), cfg, opt,
                          compression=args.compress_grads)
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} (reduced={not args.full_config}) "
          f"params={n_params/1e6:.2f}M steps={args.steps}"
          + (" [int8-EF grad AR]" if args.compress_grads else ""))

    compressed_ar = None
    if args.compress_grads:
        from repro.launch.mesh import make_local_mesh
        from repro.parallel.compression import make_compressed_allreduce
        compressed_ar = make_compressed_allreduce(make_local_mesh(), "data")

    ds = DATA.SyntheticLM(cfg, shape, seed=args.seed,
                          act_dtype=jnp.float32)
    step_fn = jax.jit(TS.make_train_step(
        cfg, opt, microbatches=args.microbatches,
        attn_impl=OPS.platform_impl(),
        remat=True, compressed_allreduce=compressed_ar),
        donate_argnums=(0,))

    manager = (CKPT.CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
               if args.ckpt_dir else None)
    if manager is not None and CKPT.latest_step(args.ckpt_dir) is not None:
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state, manifest = CKPT.restore(args.ckpt_dir, target)
        print(f"restored checkpoint at step {int(state.step)}")

    t0 = time.time()
    last = [t0]

    def batch_fn(step):
        return ds.batch(step)

    def logging_step(state, batch):
        state, metrics = step_fn(state, batch)
        s = int(state.step)
        if s % args.log_every == 0 or s == args.steps:
            dt = time.time() - last[0]
            last[0] = time.time()
            print(f"step {s:5d} loss={float(metrics['loss']):.4f} "
                  f"ce={float(metrics['ce']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({dt:.2f}s)", flush=True)
        return state, metrics

    with FT.PreemptionGuard() as guard:
        state, log = FT.run_training(
            state, logging_step, batch_fn, args.steps,
            manager=manager, guard=guard)
    if manager is not None:
        manager.save_sync(state, int(state.step))
        manager.wait()
    print(f"done: {int(state.step)} steps in {time.time()-t0:.1f}s; "
          f"final loss {log[-1]['loss']:.4f}" if log else "no steps run")
    return state, log


if __name__ == "__main__":
    main()
