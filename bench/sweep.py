"""Find the highest open-loop rate a serving cell sustains: one set-up, then
one window per offered rate, in one process.

  python3 bench/sweep.py --workload <open-loop cell> --rates 3,4,5,6,7 \\
      --seconds 20 --seed 11

For each rate it prints the time to first token (median, p95), the token
gap p95, requests admitted per second against those offered, and the
backlog left at the window's close. A rate is sustained while admissions
keep up with arrivals and the backlog does not grow; the cell's traffic
file then fixes its rate at about four fifths of the highest such rate.
"""

from __future__ import annotations

import argparse
import json
import sys

from run import ROOT, log, place_cache

from bench.harness import device as DV
from bench.harness import generate as G
from bench.harness import serving as SV
from bench.harness import spec as SP
from bench.harness.serving import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    bench = SP.load_benchmark()
    cell = SP.workload(bench, args.workload)
    err = DV.chip_error(cell["chips"])
    if err:
        log(f"sweep: {err}")
        return 2
    place_cache(ROOT)
    config = SP.load_config(bench, cell["config"])
    traffic = SP.load_traffic(cell["traffic"])
    # warm for the largest pool the sweep will offer
    sc = SV.ServeCell(config, dict(traffic, rate_per_s=max(rates)),
                      args.seed, args.seconds)
    sc.setup()
    out = []
    for rate in rates:
        tr = dict(traffic, rate_per_s=rate)
        sc.traffic = tr
        sc.items = G.serve_items(tr, args.seed, args.seconds,
                                 config["vocab_size"])
        sc.served, sc.rounds = {}, []
        t0, t1 = sc.window()
        due = [s for s in sc.served.values() if t0 <= s.due_at < t1]
        ttft = [(s.times[0] if s.times else t1) - s.due_at for s in due]
        ttft += [t1 - (sc.clock0 + it.due) for it in sc.unsent_items
                 if t0 <= sc.clock0 + it.due < t1]
        gaps = []
        for s in sc.served.values():
            ts = [t for t in s.times if t0 <= t <= t1]
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        admitted = sum(len(r.admitted) for r in sc.rounds
                       if t0 <= r.t0 < t1)
        row = {"rate": rate, "due": len(ttft),
               "admitted_per_s": admitted / (t1 - t0),
               "backlog": len(sc.unsent_items) + len(sc.engine.queue),
               "ttft_p50_ms": percentile(ttft, 50) * 1e3,
               "ttft_p95_ms": percentile(ttft, 95) * 1e3,
               "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
               "compiles": sc.compiles.count}
        log(json.dumps(row))
        out.append(row)
        sc.compiles.count = 0
        sc._drain([], per_round=1)
    print(json.dumps({"sweep": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
