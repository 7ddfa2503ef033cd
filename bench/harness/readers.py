"""Shared arithmetic of the per-layer metric readers in bench/metrics/.

Each reader takes the run record that ``bench/run.py`` assembles:

  rec["shape"]          the ``Shape`` of the configuration's family
                        (work.Shape for the dense one)
  rec["peak"]           the device's row of bench/peaks.json
  rec["trace"]          trace_reduce.reduce() of the traced window, or None;
                        its ``marks`` are the traced rounds (serving) or
                        windows (training)
  rec["rounds"]         serving rounds in the window (serving.Round, with
                        the change of each engine counter over the round)
  rec["traced_rounds"]  the rounds run while the profiler traced
  rec["traced_steps"]   training: doc lengths of each step in the trace
  rec["compiles"]       programs built inside the window

and returns a number, or None when the run gave it nothing to read. A
share of a roofline or of the peak is never 0 for want of data.
"""

from __future__ import annotations

from typing import Optional

from bench.harness import work as WK


def _pct(num: float, den: float) -> Optional[float]:
    return 100.0 * num / den if num > 0 and den > 0 else None


def compiles(rec) -> Optional[float]:
    return float(rec["compiles"]) if rec.get("compiles") is not None \
        else None


def decode_packed_share(rec) -> Optional[float]:
    rounds = [r for r in rec.get("rounds") or [] if r.kv_lens]
    if not rounds:
        return None
    return 100.0 * sum(r.packed for r in rounds) / len(rounds)


def idle_share(rec) -> Optional[float]:
    red = rec.get("trace")
    if not red or red["busy_ns"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])


def _engine_trace(rec) -> Optional[dict]:
    """The reduced trace, where the engine's round span is in it."""
    red = rec.get("trace")
    if not red or not red["span_count"].get("engine.round") or \
            not red.get("marks"):
        return None
    return red


def engine_host_ms_per_round(rec) -> Optional[float]:
    """Device idle under the engine's phase spans, per traced round, ms."""
    red = _engine_trace(rec)
    if red is None:
        return None
    idle = sum(ns for label, ns in red["idle_by_label"].items()
               if label.startswith("engine."))
    return idle / red["marks"] / 1e6


def host_syncs_per_round(rec) -> Optional[float]:
    """Blocking device-to-host reads (``engine.to_host`` spans) per traced
    round."""
    red = _engine_trace(rec)
    if red is None:
        return None
    return red["span_count"].get("engine.to_host", 0) / red["marks"]


def _span_s(rec, name: str) -> float:
    red = rec.get("trace") or {}
    return red.get("busy_in_span_ns", {}).get(name, 0) / 1e9


def _kernel_s(rec, family: str) -> float:
    red = rec.get("trace") or {}
    return red.get("kernel_ns", {}).get(family, {}).get("ns", 0) / 1e9


def decode_mfu(rec) -> Optional[float]:
    flops = sum(WK.decode_step(rec["shape"], r.kv_lens)["flops"]
                for r in rec.get("traced_rounds") or [] if r.kv_lens)
    return _pct(flops, _span_s(rec, "engine.decode_round")
                * rec["peak"]["bf16_flops_per_s"])


def decode_hbm_share(rec) -> Optional[float]:
    nbytes = sum(WK.decode_step(rec["shape"], r.kv_lens)["bytes"]
                 for r in rec.get("traced_rounds") or [] if r.kv_lens)
    return _pct(nbytes, _span_s(rec, "engine.decode_round")
                * rec["peak"]["hbm_bytes_per_s"])


def train_step_mfu(rec) -> Optional[float]:
    """Required FLOPs of the steps completed in the traced window over the
    window at the bf16 peak."""
    red = rec.get("trace")
    steps = rec.get("traced_steps") or []
    if not red or not steps:
        return None
    flops = sum(WK.train_step(rec["shape"], lens)["flops"] for lens in steps)
    return _pct(flops, red["window_ns"] / 1e9
                * rec["peak"]["bf16_flops_per_s"])


def packed_train_roofline(rec, family: str, work_fn) -> Optional[float]:
    shape = rec["shape"]
    least = sum(WK.least_seconds(work_fn(shape, lens), rec["peak"])
                * shape.layers for lens in rec.get("traced_steps") or [])
    return _pct(least, _kernel_s(rec, family))
