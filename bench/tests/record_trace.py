"""Record the small TPU trace that test_bench_trace.py reduces.

  python3 bench/tests/record_trace.py OUT.xplane.pb

On one chip, under the profiler with the same options as a traced run:
a ``bench.round`` span holding an ``engine.admit_batch`` span (the packed
prefill attention kernel on three prompts) and an ``engine.decode_round``
span (a matmul), a host sleep inside the round (an idle gap the round
owns) and one after it (a gap the harness owns). Writes the .xplane.pb
and prints the host-clock length of each part.
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


def main(out: str) -> int:
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

    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("engine.admit_batch"):
                jax.block_until_ready(attn(q, k, k))
            time.sleep(0.002)
            with jax.profiler.TraceAnnotation("engine.decode_round"):
                jax.block_until_ready(mm(x))
        time.sleep(0.003)
    jax.profiler.stop_trace()
    src = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))[-1]
    shutil.copy(src, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"wrote {out}: {pathlib.Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
