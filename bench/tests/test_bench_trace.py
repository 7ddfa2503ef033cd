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
        assert callable(SP.work_function(f["work"]))


def test_work_function_takes_a_bare_name_or_a_module_path():
    from bench.harness import readers
    from bench.harness import work as WK

    assert SP.work_function("packed_fwd") is WK.packed_fwd
    assert SP.work_function("bench.harness.work:decode_attn") is \
        WK.decode_attn
    assert SP.work_function("bench.harness.readers:idle_share") is \
        readers.idle_share
    for bad in ("no_such_count", "bench.harness.work:Shape.nothing",
                "bench.harness.no_such_module:f"):
        with pytest.raises(SP.SpecError):
            SP.work_function(bad)


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


def _as_before(trace):
    """The trace as the reduction read it when only ``SPANS`` were kept."""
    return TRC.Trace(trace.ops, [s for s in trace.spans
                                 if s[0] in TRC.SPANS], trace.devices,
                     trace.offset_ns)


def _unchanged(old: dict, new: dict):
    for key in ("window_ns", "busy_ns", "kernel_ns", "top_ops"):
        assert new[key] == old[key], key
    for name, ns in old["busy_in_span_ns"].items():
        assert new["busy_in_span_ns"][name] == ns
        assert new["span_count"][name] == old["span_count"][name]
    assert sum(new["idle_by_label"].values()) == \
        sum(old["idle_by_label"].values())


def test_spans_are_kept_by_prefix(small):
    """Every engine.* and bench.* host span is kept, so a span added to
    the engine is read with no edit; on the recorded trace, which opens
    only names of SPANS, nothing moves."""
    assert {n for n, _, _ in small.spans} == {
        "bench.round", "engine.admit_batch", "engine.decode_round"}
    lo, hi = _window(small)
    fam = SP.kernel_families()
    new = TRC.reduce(small, fam, lo, hi)
    assert new == TRC.reduce(_as_before(small), fam, lo, hi)
    rec = {"trace": dict(new, marks=3)}
    from bench.harness import readers

    assert readers.engine_host_ms_per_round(rec) is None
    assert readers.host_syncs_per_round(rec) is None


@pytest.fixture(scope="module")
def phases():
    return TRC.load(DATA / "trace_phases.xplane.pb")


def test_phase_spans_are_read_and_old_readings_stay(phases):
    """A v5e trace whose rounds open the engine's phase spans: they now
    reach the reduction, which gives the same window, busy, kernel and
    per-span readings of the old names as it did when it dropped them;
    only the idle labels get finer, and the two engine metrics read it."""
    from bench.harness import readers

    names = {n for n, _, _ in phases.spans}
    assert names >= {"engine.round", "engine.schedule", "engine.guard",
                     "engine.sample", "engine.commit", "engine.to_host",
                     "engine.admit_commit", "engine.decode_plan"}
    assert names <= set(TRC.SPANS) | {
        n for n in names if n.startswith(TRC.PREFIXES)}
    lo, hi = _window(phases)
    fam = SP.kernel_families()
    old = TRC.reduce(_as_before(phases), fam, lo, hi)
    new = TRC.reduce(phases, fam, lo, hi)
    _unchanged(old, new)
    assert new["kernel_ns"]["packed_fwd"]["count"] == 3
    assert new["span_count"]["engine.round"] == 3
    assert new["span_count"]["engine.to_host"] == 3
    assert old["idle_by_label"].get("engine.sample", 0) == 0
    # the round's host sleeps (5 ms of the 6 ms each round's phases
    # sleep) fall under the phases that slept
    assert new["idle_by_label"]["engine.sample"] > 3 * 1.5e6
    engine_idle = sum(ns for n, ns in new["idle_by_label"].items()
                      if n.startswith("engine."))
    rec = {"trace": dict(new, marks=3)}
    assert readers.engine_host_ms_per_round(rec) == engine_idle / 3 / 1e6
    assert readers.engine_host_ms_per_round(rec) > 4.0
    assert readers.host_syncs_per_round(rec) == 1.0
