"""Run one benchmark cell on the chips of this machine and print its result.

  python3 bench/run.py --workload granite-34b.serve.decode --seed 7 \\
      --seconds 51 --trace 0

Every run is a fresh process: it makes the weights from the seed, warms
every program the cell's traffic uses (set-up, reported as ``setup_s``),
measures for ``--seconds``, frees the program's state, checks what the
timed path produced against the plain reference, and prints one JSON line
as the last line of standard output:

  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
   "checks"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
profiler over the end of the window and reports its per-layer metrics,
the device's busy and window seconds, and a ``breakdown``. A traffic
file's ``kind`` picks the harness: ``serve`` (harness/serving.py) or
``train`` (harness/training.py).
Off a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. JAX's compilation cache lives in ``.jax_cache/`` at
the checkout root unless ``JAX_COMPILATION_CACHE_DIR`` is set.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import pathlib    # noqa: E402
import sys        # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax        # noqa: E402

from bench.harness import checks as CK      # noqa: E402
from bench.harness import device as DV      # noqa: E402
from bench.harness import spec as SP        # noqa: E402
from bench.harness import trace_reduce as TRC  # noqa: E402


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def place_cache(root: pathlib.Path) -> str:
    """Persistent compilation cache at a fixed path inside the checkout;
    every program is cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def load_peak(kind: str, base: pathlib.Path = SP.BENCH_DIR) -> dict:
    peaks = json.loads((base / "peaks.json").read_text())
    if kind not in peaks:
        raise SP.SpecError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def cell_runner(kind: str):
    """The harness of a traffic kind: ``serve`` or ``train``."""
    if kind == "serve":
        from bench.harness.serving import serve_cell
        return serve_cell
    if kind == "train":
        from bench.harness.training import train_cell
        return train_cell
    raise SP.SpecError(f"unknown traffic kind {kind!r}")


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = SP.ROOT, base: pathlib.Path = SP.BENCH_DIR,
             t_process: float = None) -> dict:
    """One run of cell ``name`` on the default devices; the result line."""
    t_process = time.perf_counter() if t_process is None else t_process
    bench = SP.load_benchmark(root)
    cell = SP.workload(bench, name)
    config = SP.load_config(bench, cell["config"], root)
    traffic = SP.load_traffic(cell["traffic"], base)
    limits = SP.load_limits(name, base)
    tracer = DV.Tracer(SP.kernel_families(base)) if trace else None
    out = cell_runner(traffic["kind"])(
        cell, config, traffic, limits, seed, seconds, trace, t_process,
        cell["chips"], base, tracer=tracer, log=log)

    correct, checks = CK.verdict(out["compared"], limits)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": out["device"]}
    if trace:
        out["rec"]["peak"] = load_peak(out["device"]["kind"], base)
        red = out["rec"]["trace"]
        result["device"]["busy_s"] = red["busy_ns"] / 1e9
        result["device"]["window_s"] = red["window_ns"] / 1e9
        for m in SP.per_layer_metrics(bench, name):
            value = SP.metric_reader(m["name"], base)(out["rec"])
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = TRC.breakdown(red)
        log(f"trace: {red['marks']} rounds, kernels {red['kernel_ns']}, "
            f"busy in spans {red['busy_in_span_ns']}, longest gaps "
            f"{red['longest_gaps'][:5]}")
    else:
        for m in SP.end_to_end_metrics(bench, name):
            if m["name"] not in out["values"]:
                raise RuntimeError(f"cell {name} did not measure "
                                   f"{m['name']}")
            result["metrics"][m["name"]] = {"value": out["values"][m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = checks
    for line in CK.check_lines(checks):
        log(line)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = SP.workload(SP.load_benchmark(), args.workload)
    err = DV.chip_error(cell["chips"])
    if err:
        log(f"bench: {err}")
        return 2
    log(f"compile cache {place_cache(ROOT)}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
