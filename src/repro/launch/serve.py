"""Serving driver: the slot-based continuous-batching engine on random
prompts.

By default it runs the reduced (smoke) config, which decodes on a CPU in
seconds. ``--full-config`` keeps the architecture's published widths and
dtype; ``--layers`` then cuts depth only, so the weights fit one chip.
Attention takes the Pallas kernels on a TPU and the scan mirror elsewhere
(the Engine decides from the platform).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --requests 6
  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --full-config \\
      --layers 16 --slots 8 --max-len 2048 --min-prompt-len 200 \\
      --prompt-len 1500 --max-new 32 --requests 8      # one TPU v5e
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry as REG
from repro.launch.compile_cache import place_compile_cache
from repro.models import model as MD
from repro.serve.engine import Engine


def main(argv=None):
    """Serve --requests random prompts to completion; returns the Engine
    (its finished requests, stats and params) for callers that check it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b", choices=REG.ARCH_IDS)
    ap.add_argument("--full-config", action="store_true",
                    help="published widths and dtype (one chip: cut depth "
                         "with --layers)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --full-config: keep this many layers")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--min-prompt-len", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-mode", default="packed",
                    choices=["packed", "sequential"],
                    help="packed = one ragged launch per admit round "
                         "(attention archs); sequential = per-token loop")
    ap.add_argument("--decode-mode", default="auto",
                    choices=["auto", "packed", "lockstep"],
                    help="auto = packed mixed-position decode on "
                         "position-skewed rounds, lockstep otherwise")
    ap.add_argument("--step-mode", default="split",
                    choices=["split", "fused"],
                    help="fused = admits and decode rows in one launch")
    args = ap.parse_args(argv)

    place_compile_cache()
    cfg = REG.launch_config(args.arch, args.full_config, args.layers)
    # prefill/decode tile edge: the MXU width at published widths, 16 on
    # the reduced widths' short prompts
    block = 128 if args.full_config else 16
    params = jax.jit(MD.init_params, static_argnums=1)(
        jax.random.key(args.seed), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"dtype={cfg.dtype} params={n_params / 1e9:.3f}B block={block}")
    engine = Engine(params, cfg, slots=args.slots, max_len=args.max_len,
                    temperature=args.temperature, seed=args.seed,
                    prefill_mode=args.prefill_mode, prefill_block=block,
                    decode_mode=args.decode_mode, decode_block=block,
                    step_mode=args.step_mode)

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=rng.integers(args.min_prompt_len,
                                                args.prompt_len + 1))
        engine.submit(prompt, max_new=args.max_new, uid=uid)
    results = engine.run()
    dt = time.time() - t0
    n_tok = sum(len(v) for v in results.values())
    for uid in sorted(results):
        print(f"req {uid}: {len(results[uid])} tokens -> "
              f"{results[uid][:8]}...")
    st = engine.stats
    print(f"{len(results)}/{args.requests} requests, {n_tok} tokens "
          f"in {dt:.1f}s ({n_tok/dt:.1f} tok/s, {args.slots} slots, "
          f"compiles included)")
    print(f"prefill[{engine.prefill_mode}/{engine.prefill_impl}]: "
          f"{st['prefill_launches']} launches for {st['prefill_requests']} "
          f"requests / {st['prefill_tokens']} tokens over "
          f"{st['admit_rounds']} admit rounds")
    print(f"decode[{engine.decode_mode}/{engine.decode_impl}]: "
          f"{st['decode_rounds']} rounds ({st['decode_packed_launches']} "
          f"packed / {st['decode_lockstep_launches']} lockstep), tiles "
          f"{st['decode_tiles_packed']} packed vs "
          f"{st['decode_tiles_padded']} pad-to-max; step={engine.step_mode} "
          f"fused rounds {st['fused_rounds']}")
    return engine


if __name__ == "__main__":
    main()
