"""The chip a run is on: what it is, its memory peak, and its profiler.

A run that finds no TPU, or fewer chips than its cell asks for, stops
before it makes anything (``chip_error``). ``Tracer`` records part of a
window with the JAX profiler and reduces it (trace_reduce.py) over the
interval of the harness span that marks the measured work.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, Optional

import jax

from bench.harness import trace_reduce as TRC


def chip_error(chips: int) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"needs a TPU; JAX found {devices[0].platform}"
    if len(devices) < chips:
        return f"needs {chips} chips; JAX found {len(devices)}"
    return None


def device_block(chips: int) -> dict:
    """The result line's ``device``: as JAX reports it, with the peak of
    the fullest chip the cell used."""
    devices = jax.devices()[:chips]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class Tracer:
    """The profiler over part of the window, host Python tracing off. Its
    directory is a temporary one under ``TMPDIR``, removed after."""

    def __init__(self, families: Dict[str, dict]):
        self.families = families
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.started_at = None

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started_at = time.perf_counter()

    def stop(self):
        jax.profiler.stop_trace()

    def reduce(self, span: str) -> dict:
        """Reduce the traced window: from the start of the first ``span``
        to the end of the last."""
        try:
            trace = TRC.load(TRC.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        marks = [(a, b) for n, a, b in trace.spans if n == span]
        if not marks or not trace.ops:
            raise RuntimeError(f"trace holds no {span} span or no device "
                               f"operation ({len(trace.ops)} ops)")
        lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
        red = TRC.reduce(trace, self.families, lo, hi)
        red["devices"] = trace.devices
        red["marks"] = len(marks)
        red["clock_offset_ns"] = trace.offset_ns
        return red
