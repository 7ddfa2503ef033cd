"""Record the small TPU traces that test_bench_trace.py reduces.

  python3 bench/tests/record_trace.py OUT.xplane.pb [--phases]

On one chip, under the profiler with the same options as a traced run,
three rounds, each a ``bench.round`` span holding an
``engine.admit_batch`` span (the packed prefill attention kernel on three
prompts) and an ``engine.decode_round`` span (a matmul), a host sleep
inside the round (an idle gap the round owns) and one after it (a gap the
harness owns). With ``--phases`` (trace_phases.xplane.pb) each round also
opens the engine's phase spans as ``Engine.round`` nests them: an
``engine.round`` around the whole, host sleeps under ``engine.schedule``,
``engine.admit_commit``, ``engine.decode_plan``, ``engine.guard`` and
``engine.sample``, one blocking read of the matmul's result under
``engine.to_host`` inside ``engine.guard``, and ``engine.commit``. Writes
the .xplane.pb.
"""

from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax              # noqa: E402
import jax.numpy as jnp  # noqa: E402


span = jax.profiler.TraceAnnotation


def _round(attn, mm):
    with span("engine.admit_batch"):
        jax.block_until_ready(attn())
    time.sleep(0.002)
    with span("engine.decode_round"):
        jax.block_until_ready(mm())


def _phase_round(attn, mm):
    """As ``Engine.round`` nests its phases."""
    with span("engine.round"):
        with span("engine.schedule"):
            time.sleep(0.0005)
        with span("engine.admit_batch"):
            o = attn()
        with span("engine.admit_commit"):
            jax.block_until_ready(o)
            time.sleep(0.0005)
        with span("engine.decode_plan"):
            time.sleep(0.0005)
        with span("engine.decode_round"):
            y = mm()
        with span("engine.guard"):
            with span("engine.to_host"):
                jax.device_get(y[:8, :8])
            time.sleep(0.001)
        with span("engine.sample"):
            time.sleep(0.002)
        with span("engine.commit"):
            time.sleep(0.0005)


def main(out: str, phases: bool = False) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro.kernels.tri_attn import ops as OPS

    sched = OPS.make_packed_sched([256, 512, 128], block=128)
    s = sched.s_total
    key = jax.random.key(0)
    q = jax.random.normal(key, (1, 8, s, 128), jnp.bfloat16)
    k = jax.random.normal(key, (1, 2, s, 128), jnp.bfloat16)
    attn = jax.jit(lambda a, b, c: OPS.packed_prefill_attention(
        a, b, c, sched, impl="pallas"))
    x = jax.random.normal(key, (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    jax.block_until_ready((attn(q, k, k), mm(x)))   # compile outside
    jax.device_get(mm(x)[:8, :8])

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with span("bench.round"):
            (_phase_round if phases else _round)(lambda: attn(q, k, k),
                                                 lambda: mm(x))
        time.sleep(0.003)
    jax.profiler.stop_trace()
    src = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))[-1]
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], "--phases" in sys.argv[2:]))
