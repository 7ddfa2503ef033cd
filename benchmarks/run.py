"""Benchmark harness entry point: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast | --smoke]

  bench_mapping     — paper Fig. 3 (dummy kernel / strategy cost + waste)
  bench_tet_mapping — the 3D analogue: BB-3D (n^3) vs tetrahedral launch
  bench_edm         — paper Fig. 5 (EDM, d = 1..4 features, LTM vs BB)
  bench_attention   — the technique on causal flash attention (tiles/FLOPs/I)
  bench_packed      — packed ragged batch vs per-request vs padded launches,
                      plus --decode: packed mixed-position decode rounds vs
                      lockstep pad-to-max at skew {1x, 4x, 16x}, and
                      --train: packed ragged-document fwd+bwd vs pad-to-max
                      training at document-length skew {1x, 4x, 16x}
  bench_continuous  — continuous batching: the FUSED engine-step launch
                      (admits + live decode slots, one mixed member table)
                      vs the split prefill + decode pair at skew {1,4,16}
  bench_fleet       — replicated engines: tri(n) tile-cost routing balance
                      under skewed arrivals, and failover determinism
                      (migrated requests token-identical) under engine death
  bench_roofline    — §Roofline table from the dry-run artifacts (if present)

--smoke is the CI tier: tiny n, scan impls only, seconds not minutes —
scripts/check.sh runs it so the benchmark scripts cannot rot offline.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller N ranges (CI-sized)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny n, scan impls: execution check only "
                         "(scripts/check.sh tier)")
    args = ap.parse_args(argv)
    os.makedirs("artifacts", exist_ok=True)
    from repro.launch.compile_cache import place_compile_cache

    place_compile_cache()

    from repro.obs import launch as OBS_LAUNCH
    from repro.obs import sinks as SK

    trace_path = SK.enable(trace_dir="artifacts/trace",
                           metrics_path="artifacts/metrics.json")
    print(f"obs: trace -> {trace_path}")

    from benchmarks import bench_mapping, bench_tet_mapping, bench_edm, \
        bench_attention, bench_packed, bench_continuous, bench_fleet, \
        bench_roofline

    t0 = time.time()
    print("=" * 72)
    print("bench_mapping (paper Fig. 3)")
    print("=" * 72)
    rows = bench_mapping.run(
        n_values=[16, 64] if args.smoke
        else [64, 256, 1024] if args.fast else None,
        out_path="artifacts/bench_mapping.json")
    for r in rows:
        ii = r["improvement_I_vs_bb"]
        print(f"  N={r['N']:6d} I(ltm)={ii['ltm']:.3f} I(rb)={ii['rb']:.3f} "
              f"I(utm)={ii['utm']:.3f} wasted bb={r['blocks']['bb']['wasted']}"
              f" ltm={r['blocks']['ltm']['wasted']}")
    print("  LTM-R exactness:", bench_mapping.exactness_check(
        256 if args.smoke else 1024 if args.fast else 4096))

    print("=" * 72)
    print("bench_tet_mapping (BB-3D vs tetrahedral launch)")
    print("=" * 72)
    rows = bench_tet_mapping.run(
        n_values=[8, 16] if args.smoke else [16, 64] if args.fast else None,
        out_path="artifacts/bench_tet_mapping.json")
    for r in rows:
        print(f"  N={r['N']:6d} tet={r['launched_tet']} "
              f"bb3={r['launched_bb3']} "
              f"waste={100 * r['waste_fraction_bb3']:.1f}% "
              f"I(map)={r['improvement_I_vs_bb3']:.3f}")

    print("=" * 72)
    print("bench_edm (paper Fig. 5)")
    print("=" * 72)
    rows = bench_edm.run(
        n_values=(256,) if args.smoke else (1024,) if args.fast
        else (1024, 2048, 4096),
        features=(1,) if args.smoke else (1, 4) if args.fast
        else (1, 2, 3, 4),
        out_path="artifacts/bench_edm.json")
    for r in rows:
        print(f"  N={r['N']:6d} d={r['features']} I={r['I']:.3f} "
              f"ltm={r['t_ltm_ms']:.1f}ms bb={r['t_bb_ms']:.1f}ms "
              f"err={r['max_err_vs_oracle']}")

    print("=" * 72)
    print("bench_attention (LTM flash attention vs BB)")
    print("=" * 72)
    rows = bench_attention.run(
        seqs=(256,) if args.smoke else (512,) if args.fast
        else (1024, 2048),
        block=64 if args.smoke else 128,
        out_path="artifacts/bench_attention.json")
    for r in rows:
        print(f"  seq={r['seq']:5d} tiles={r['tiles_ltm']}/{r['tiles_bb']} "
              f"I_wall={r['I_wallclock']:.3f} I_flops={r['I_flops']:.3f}")

    print("=" * 72)
    print("bench_packed (packed ragged batch vs per-request vs padded)")
    print("=" * 72)
    rec = bench_packed.run(
        lens=(64, 16, 96) if args.smoke else (192, 48, 320, 96),
        block=8 if args.smoke else 16,
        out_path="artifacts/bench_packed.json")
    b, t = rec["blocks"], rec["times_ms"]
    print(f"  lens={rec['lens']} blocks packed={b['packed']} "
          f"padded-bb={b['padded_bb']} padded-ltm={b['padded_ltm']} "
          f"t_packed={t['packed']:.1f}ms t_per={t['per_request']:.1f}ms "
          f"t_padded={t['padded_ltm_batch']:.1f}ms")

    print("=" * 72)
    print("bench_packed --decode (packed mixed-position vs lockstep decode)")
    print("=" * 72)
    bench_packed.main_decode(
        smoke=args.smoke or args.fast,
        out_path="artifacts/bench_packed_decode.json")

    print("=" * 72)
    print("bench_packed --train (packed ragged-doc fwd+bwd vs pad-to-max)")
    print("=" * 72)
    bench_packed.main_train(
        smoke=args.smoke or args.fast,
        out_path="artifacts/bench_packed_train.json")

    print("=" * 72)
    print("bench_continuous (fused engine-step launch vs split pair)")
    print("=" * 72)
    bench_continuous.main(
        smoke=args.smoke or args.fast,
        out_path="artifacts/bench_continuous.json")

    print("=" * 72)
    print("bench_fleet (replicated engines: routing balance + failover)")
    print("=" * 72)
    bench_fleet.main(
        smoke=args.smoke or args.fast,
        out_path="artifacts/bench_fleet.json")

    print("=" * 72)
    print("bench_roofline (dry-run artifacts)")
    print("=" * 72)
    recs = bench_roofline.load()
    if recs:
        print(" ", bench_roofline.summary(recs))
    else:
        print("  no dry-run artifacts yet "
              "(run: python -m repro.launch.dryrun --all --mesh both)")

    print("=" * 72)
    print("contract lint (static invariants backing the numbers above)")
    print("=" * 72)
    lint_path = "artifacts/lint_report.json"
    if os.path.exists(lint_path):
        with open(lint_path) as f:
            rep = json.load(f)
        per = ", ".join(f"{k}={v['checks'] - v['failures']}/{v['checks']}"
                        for k, v in rep["passes"].items())
        print(f"  {rep['total_checks']} checks, "
              f"{rep['total_failures']} failures ({per})")
    else:
        print("  no lint report yet "
              "(run: python -m repro.analysis.lint --json)")

    print("=" * 72)
    print("obs: metrics + trajectory")
    print("=" * 72)
    kernels = OBS_LAUNCH.kernel_summary()
    metrics_path = SK.flush_metrics()
    record = {
        "schema": SK.SCHEMA_VERSION,
        "kind": "bench_trajectory",
        "created_unix": time.time(),
        "run_id": SK.run_id(),
        "mode": ("smoke" if args.smoke else "fast" if args.fast else "full"),
        "wall_s": time.time() - t0,
        "kernels": kernels,
    }
    traj_path = "BENCH_trajectory.json"
    traj = []
    if os.path.exists(traj_path):
        try:
            with open(traj_path) as f:
                traj = json.load(f)
            assert isinstance(traj, list)
        except Exception:
            traj = []
    traj.append(record)
    with open(traj_path + ".tmp", "w") as f:
        json.dump(traj, f, indent=1)
    os.replace(traj_path + ".tmp", traj_path)
    for name in sorted(kernels):
        k = kernels[name]
        print(f"  {name:28s} launched={k['tiles_launched']:>9d} "
              f"bb={k['tiles_bb']:>9d} util={k['utilization']:.3f} "
              f"I={k['improvement_vs_bb']:.3f}")
    print(f"  metrics -> {metrics_path}; trajectory -> {traj_path} "
          f"({len(traj)} records)")
    SK.disable()
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
