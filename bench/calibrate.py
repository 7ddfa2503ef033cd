"""Readings that a cell's limits are set from, in one process on the chip.

  python3 bench/calibrate.py --workload granite-34b.serve.decode \\
      --seeds 11,12,13 --control 3 --seconds 20

For each seed: the cell's set-up and a window of ``--seconds`` at its
own load, then the numbers that decide ``correct`` for what the timed
path produced (the sound reading). For the first ``--control`` seeds
also the control: the scaled-fp8 reference in the program's place, scored the
same way. Training cells need no window; with ``--faults`` they also
read the program with half of each batch left out (the mean taken over
the rest). Prints one JSON line per seed, with what ``correct`` would
say of each reading under the cell's current limits, and, last, the
largest sound and the smallest control and fault reading of each
number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, log, place_cache

from bench.harness import checks as CK
from bench.harness import device as DV
from bench.harness import generate as G
from bench.harness import spec as SP


def serve_seed(config, traffic, seed, seconds, control, warm):
    from bench.harness import serving as SV

    sc = SV.ServeCell(config, traffic, seed, seconds)
    if warm:
        sc.setup()
    else:  # the programs are this process's already
        sc.params = SV.make_params(sc.cfg, seed)
        from repro.serve.engine import Engine
        e = traffic["engine"]
        sc.engine = Engine(sc.params, sc.cfg, slots=e["slots"],
                           max_len=e["max_len"],
                           prefill_block=e["prefill_block"],
                           prefill_bucket=e["prefill_bucket"],
                           decode_block=e["decode_block"], seed=seed)
    sc.window()
    finished = sc.finished()
    sc.release()
    out = {}
    out["sound"] = {"served_gap_max": CK.served_gap(config, traffic, seed,
                                                    finished)[0]}
    if control:
        out["control"] = {"served_gap_max": CK.control_gap(
            config, traffic, seed, finished)[0]}
    out["compiles_in_window"] = sc.compiles.count
    return out


def train_seed(config, traffic, seed, seconds, control, faults):
    from bench.harness import training as TRN

    def readings(feed=None):
        tc = TRN.TrainCell(config, traffic, seed, feed=feed)
        tc.setup()
        r = tc.readings
        tc.release()
        return r

    sound = readings()
    t0 = time.perf_counter()
    ref = TRN.reference_readings(config, traffic, seed)
    out = {"reference_s": time.perf_counter() - t0,
           "sound": TRN.compare(sound, ref)["values"],
           "detail": TRN.compare(sound, ref)["detail"]}
    if control:
        ctl = TRN.reference_readings(config, traffic, seed, prec="fp8")
        out["control"] = TRN.compare(ctl, ref)["values"]
    if faults:
        def half(step):
            b = G.train_batch(traffic, config["vocab_size"], seed, step)
            b["mask"][0, b["mask"].shape[1] // 2:] = 0.0
            return b
        out["fault_half_batch"] = TRN.compare(readings(half), ref)["values"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    bench = SP.load_benchmark()
    cell = SP.workload(bench, args.workload)
    err = DV.chip_error(cell["chips"])
    if err:
        log(f"calibrate: {err}")
        return 2
    place_cache(ROOT)
    config = SP.load_config(bench, cell["config"])
    traffic = SP.load_traffic(cell["traffic"])
    limits = SP.load_limits(args.workload)
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if traffic["kind"] == "serve":
            row = serve_seed(config, traffic, seed, args.seconds,
                             i < args.control, warm=(i == 0))
        else:
            row = train_seed(config, traffic, seed, args.seconds,
                             i < args.control, i < args.faults)
        # what ``correct`` would say of each reading under the cell's limits
        for kind in ("sound", "control", "fault_half_batch"):
            if kind in row:
                row[kind + "_correct"] = CK.verdict(row[kind], limits)[0]
        row.update(seed=seed, seconds=time.perf_counter() - t0)
        log(json.dumps(row))
        rows.append(row)
    summary = {}
    for kind, pick in (("sound", max), ("control", min),
                       ("fault_half_batch", min)):
        vals = [r[kind] for r in rows if kind in r]
        if vals:
            summary[kind] = {k: pick(v[k] for v in vals) for k in vals[0]}
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
