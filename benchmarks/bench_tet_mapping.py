"""BB-3D vs tetrahedral launch — the paper's Fig. 3 methodology in 3D.

Structural columns (hardware-independent): blocks launched by the 3D
bounding box (n^3) vs the tetrahedral map (n(n+1)(n+2)/6) and the waste
fraction, which grows to 5/6 — the reason an exact lambda -> (i,j,k) map
pays off even more in 3D than g(lambda) did in 2D (Navarro et al.,
arXiv 1606.08881).

Wall-clock columns (CPU analogue of the dummy kernel): a jitted vectorized
tet_map over every launched tet lambda vs the BB-3D div/mod + simplex
guard over every launched cube lambda, plus the 3-body triplet kernel
(scan impls) at small scale.

--accelerator times the REAL Pallas tet kernel (compiled, block=128,
production scale) against the BB-3D Pallas baseline instead of the
scan-at-toy-scale stand-ins. It needs a TPU: without one it exits non-zero
rather than time a stand-in under the kernel's name.

  PYTHONPATH=src python -m benchmarks.bench_tet_mapping [--accelerator]
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmarks._util import best_of as _time
from repro.core import mapping as M

RHO = 8  # assumed block edge (rho^3-point tiles) for the N column


@jax.jit
def _tet_dummy(lams):
    i, j, k = M.tet_map(lams)
    return i + j + k


@jax.jit
def _bb3_dummy(lams_n):
    lams, n = lams_n
    i, j, k = M.bb3_map(lams, n)
    return jnp.where(M.bb3_active(i, j, k), i + j + k, -1)


def run(n_values=None, out_path: str | None = None) -> list:
    if n_values is None:
        n_values = [16, 32, 64, 128, 256]
    rows = []
    for n in n_values:
        t3 = M.tet(n)
        bb3 = M.bb3_blocks(n)
        lam_tet = jnp.arange(t3, dtype=jnp.int32)
        lam_bb3 = jnp.arange(bb3, dtype=jnp.int32)
        t_tet = _time(_tet_dummy, lam_tet)
        t_bb3 = _time(_bb3_dummy, (lam_bb3, jnp.int32(n)))
        rows.append({
            "N": n * RHO, "n": n,
            "launched_tet": t3,
            "launched_bb3": bb3,
            "wasted_bb3": M.wasted_blocks_bb3(n),
            "waste_fraction_bb3": M.wasted_blocks_bb3(n) / bb3,
            "launch_reduction": bb3 / t3,
            "times_ms": {"tet": t_tet * 1e3, "bb3": t_bb3 * 1e3},
            "improvement_I_vs_bb3": t_bb3 / t_tet,
        })
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1)
    return rows


def kernel_run(n_rows: int = 32, block: int = 8, d: int = 4, *,
               accelerator: bool = False) -> dict:
    """3-body triplet reduction wall-clock.

    Default: tet scan vs BB-3D scan at toy scale (CPU-friendly).
    accelerator=True: the real Pallas kernels, compiled, with block=128 at
    production tile counts — the numbers that actually validate the
    launch-reduction claim on hardware. Raises SystemExit off a TPU.
    """
    from repro.kernels.tri_3body import ops as OPS

    backend = jax.default_backend()
    if accelerator:
        if backend != "tpu":
            raise SystemExit(f"--accelerator needs a TPU; JAX found "
                             f"{backend!r}")
        block = 128
        n_rows = 16 * block  # n = 16 tiles/side: tet 816 vs bb3 4096 tiles
        d = max(d, 64)
        impls = ("pallas", "bb3")
    else:
        impls = ("scan", "bb3_scan")

    x = jax.random.normal(jax.random.PRNGKey(0), (n_rows, d), jnp.float32)
    tet_fn = jax.jit(lambda v: OPS.three_body(v, block, impl=impls[0]))
    bb3_fn = jax.jit(lambda v: OPS.three_body(v, block, impl=impls[1]))
    t_tet = _time(tet_fn, x)
    t_bb3 = _time(bb3_fn, x)
    n = n_rows // block
    return {"n_rows": n_rows, "block": block, "d": d,
            "backend": backend, "impls": impls,
            "tiles_tet": M.tet(n), "tiles_bb3": n ** 3,
            "t_tet_ms": t_tet * 1e3, "t_bb3_ms": t_bb3 * 1e3,
            "I_wallclock": t_bb3 / t_tet}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--accelerator", action="store_true",
                    help="time the compiled Pallas tet kernel with "
                         "block=128 (needs a TPU; exits non-zero without)")
    args = ap.parse_args(argv)
    rows = run(out_path="artifacts/bench_tet_mapping.json")
    print(f"{'N':>6} {'tet':>10} {'bb3':>11} {'waste%':>7} {'reduce':>7} "
          f"{'I(map)':>7}")
    for r in rows:
        print(f"{r['N']:6d} {r['launched_tet']:10d} {r['launched_bb3']:11d} "
              f"{100 * r['waste_fraction_bb3']:6.1f}% "
              f"{r['launch_reduction']:6.2f}x "
              f"{r['improvement_I_vs_bb3']:7.3f}")
    k = kernel_run(accelerator=args.accelerator)
    print(f"3-body kernel (N={k['n_rows']}, b={k['block']}, "
          f"{k['impls'][0]}/{k['impls'][1]} on {k['backend']}): "
          f"tiles {k['tiles_tet']}/{k['tiles_bb3']} "
          f"tet={k['t_tet_ms']:.1f}ms bb3={k['t_bb3_ms']:.1f}ms "
          f"I={k['I_wallclock']:.3f}")


if __name__ == "__main__":
    main()
