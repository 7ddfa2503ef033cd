"""The trace reduction on a small trace recorded on one v5e
(record_trace.py) and on hand-made events; no chip needed."""

from __future__ import annotations

import json
import pathlib

import pytest

from bench.harness import spec as SP
from bench.harness import trace_reduce as TRC

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def small():
    return TRC.load(DATA / "trace_small.xplane.pb")


def _window(trace, span="bench.round"):
    marks = [(a, b) for n, a, b in trace.spans if n == span]
    return min(a for a, _ in marks), max(b for _, b in marks)


def test_device_clock_is_moved_onto_the_host_clock(small):
    """Each program starts on the device after the host launched it and
    inside the span that launched it: the kernel in engine.admit_batch,
    the matmul in engine.decode_round."""
    assert 0.5e6 < small.offset_ns < 3e6
    fam = SP.kernel_families()
    spans = {n: [(a, b) for m, a, b in small.spans if m == n]
             for n in ("engine.admit_batch", "engine.decode_round")}
    kernels = [o for o in small.ops if TRC.family_of(o, fam)]
    fusions = [o for o in small.ops if "fusion(" in o.name]
    assert len(kernels) == len(fusions) == 3
    for ops, name in ((kernels, "engine.admit_batch"),
                      (fusions, "engine.decode_round")):
        for o in ops:
            assert any(a <= o.start and o.end <= b for a, b in spans[name])


def test_reduce_small_trace(small):
    lo, hi = _window(small)
    red = TRC.reduce(small, SP.kernel_families(), lo, hi)
    k = red["kernel_ns"]["packed_fwd"]
    assert k["count"] == 3 and 0.6e6 < k["ns"] < 0.8e6
    assert red["busy_in_span_ns"]["engine.admit_batch"] == k["ns"]
    assert red["busy_in_span_ns"]["bench.round"] == red["busy_ns"]
    assert red["busy_ns"] + sum(red["idle_by_label"].values()) == \
        red["window_ns"]
    # the host sleeps between rounds (3 ms) and inside them (2 ms)
    assert red["idle_by_label"]["harness"] > 6e6
    assert red["idle_by_label"]["bench.round"] > 4e6
    assert red["top_ops"][0][0] == "packed_fwd"
    bd = TRC.breakdown(red)
    assert bd["device_ops"][0] == ["packed_fwd", k["ns"] / 1e9]
    assert {n for n, _ in bd["idle_gaps"]} >= {"harness", "bench.round"}


def test_kernel_families_match_their_ops_and_nothing_else():
    """Op texts of the chip's programs: the serving trace and a described
    v5e compile of the packed train step."""
    fam = SP.kernel_families()
    rows = [json.loads(line) for line in
            (DATA / "kernel_ops.jsonl").read_text().splitlines()]
    assert {r["family"] for r in rows} == {"packed_fwd", "packed_bwd",
                                           "packed_decode_fwd", None}
    for r in rows:
        assert TRC.family_of(TRC.Op(r["op"], 0, 1), fam) == r["family"], \
            r["op"][:120]
    for f in fam.values():
        assert callable(getattr(__import__("bench.harness.work",
                                           fromlist=["x"]), f["work"]))


def test_self_time_of_nested_ops():
    ops = [TRC.Op("%while.1 = s32[] while(...)", 0, 100),
           TRC.Op("%a = f32[] fusion(...)", 10, 30),
           TRC.Op("%b = f32[] custom-call(...)", 40, 90),
           TRC.Op("%c = f32[] fusion(...)", 120, 130)]
    assert TRC.self_times(ops, 0, 200) == [30, 20, 50, 10]
    assert TRC.self_times(ops, 50, 125) == [10, 0, 40, 5]
    assert TRC.op_label(ops[0]) == "while (while)"


def test_union_overlap_and_gap_labels():
    assert TRC.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)]) == \
        [(0, 4), (5, 12)]
    assert TRC.overlap_ns([(0, 4), (5, 12)], [(3, 6), (11, 20)]) == 3
    spans = [("bench.round", 0, 100), ("engine.decode_round", 10, 50)]
    assert TRC.gap_label(20, spans) == "engine.decode_round"
    assert TRC.gap_label(70, spans) == "bench.round"
    assert TRC.gap_label(150, spans) == "harness"


def test_clock_offset_pairs_programs_from_the_end():
    # the trace opened mid-way through a program: one device start more
    starts = [50, 1000, 2000, 3000]
    launches = [700, 1800, 2900]
    assert TRC.clock_offset(starts, launches) == 0
    assert TRC.clock_offset([100, 1100], [1500, 2600]) == 1500
    assert TRC.clock_offset([], [5]) == 0
