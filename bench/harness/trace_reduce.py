"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into two
lists on one clock (nanoseconds): the device's operations (the "XLA Ops"
line of each ``/device:TPU:n`` plane) and the host spans that the
program and the harness open with ``TraceAnnotation``: every span whose
name starts with one of ``PREFIXES``, so a span the engine adds is read
with no edit here, and the names in ``SPANS``.

The device's timestamps run about a millisecond behind the host's on a
v5e. ``load`` moves them onto the host clock by the smallest shift that
puts no program's start on the device before the host enqueued it: the
n-th "XLA Modules" event of device 0 against the n-th host
``TpuLoadedExecutable::ExecuteLaunch``, counted from the end, since the
trace can open mid-way through a program but closes after the last.

Device ops nest (a ``while`` holds its body's ops), so an op's self time
is its time less that of the ops inside it. A kernel is found by its
HLO text: a Pallas call shows up as a ``tpu_custom_call`` named after
the call site, not the kernel, so each family (bench/kernels/) gives a
regular expression over its operand and result signature.

``reduce`` then gives, for a traced window:

  busy_ns           union of the intervals in which an operation ran
  kernel_ns         device time of each kernel family (bench/kernels/)
  busy_in_span_ns   device-busy time inside each host span name
  idle_by_label     idle time, by the innermost host span open in it
  top_ops           the operations with the most self time

The reduction works on plain tuples, so it is tested on a small recorded
trace and on hand-made events alike.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# host spans that label the device's time: those the metrics always read,
# innermost first when nested, and every span under one of the prefixes
SPANS = ("engine.admit_batch", "engine.decode_round", "engine.fused_step",
         "bench.round", "bench.window")
PREFIXES = ("engine.", "bench.")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
_SUFFIX = re.compile(r"[.:]\d+$")
_OP_KIND = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][\w-]*)\(")


@dataclasses.dataclass
class Op:
    name: str
    start: int
    end: int
    detail: str = ""  # the op's long name / source kernel, where given


@dataclasses.dataclass
class Trace:
    ops: List[Op]                       # device 0's operations
    spans: List[Tuple[str, int, int]]   # host spans (name, start, end)
    devices: int
    offset_ns: int = 0                  # added to every device timestamp


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _detail(ev) -> str:
    for key, value in ev.stats:
        if key in ("long_name", "tf_op", "hlo_op", "kernel_details"):
            return str(value)
    return ""


def _device_index(plane_name: str) -> Optional[int]:
    if not plane_name.startswith("/device:") or "TPU:" not in plane_name:
        return None
    tail = plane_name.split("TPU:")[1].split()[0]
    return int(tail) if tail.isdigit() else None


def clock_offset(modules: Sequence[int], launches: Sequence[int]) -> int:
    """Shift (ns) onto the host clock: the least that starts no program
    on the device before the host launched it. Programs and launches are
    paired from the end."""
    n = min(len(modules), len(launches))
    if n == 0:
        return 0
    pairs = zip(sorted(modules)[-n:], sorted(launches)[-n:])
    return max(0, max(launch - start for start, launch in pairs))


def load(path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, spans, devices = [], [], 0
    modules, launches = [], []
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is not None:
            devices += 1
            if dev != 0:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        ops.append(Op(ev.name, int(ev.start_ns),
                                      int(ev.end_ns), _detail(ev)))
                elif line.name == MODULES_LINE:
                    modules.extend(int(ev.start_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS or ev.name.startswith(PREFIXES):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
                    elif ev.name == LAUNCH:
                        launches.append(int(ev.start_ns))
    offset = clock_offset(modules, launches)
    for o in ops:
        o.start += offset
        o.end += offset
    ops.sort(key=lambda o: (o.start, -o.end))
    return Trace(ops, spans, devices, offset)


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def overlap_ns(merged: Sequence[Tuple[int, int]],
               others: Sequence[Tuple[int, int]]) -> int:
    """Length of merged ∩ union(others), both lists of intervals."""
    total = 0
    starts = [a for a, _ in merged]
    for a, b in union(others):
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(merged) and merged[i][0] < b:
            lo, hi = max(a, merged[i][0]), min(b, merged[i][1])
            if hi > lo:
                total += hi - lo
            i += 1
    return total


def family_of(op: Op, families: Dict[str, dict]) -> Optional[str]:
    """The kernel family whose ``pattern`` (a regular expression over the
    op's HLO text) the op matches."""
    text = op.name + " " + op.detail
    for fam, spec in families.items():
        if re.search(spec["pattern"], text):
            return fam
    return None


def op_label(op: Op) -> str:
    """A short name for the breakdown: the HLO op kind and its name."""
    head = op.name.split(" = ", 1)[0].lstrip("%")
    kind = _OP_KIND.search(op.name)
    return (_SUFFIX.sub("", head) + (f" ({kind.group(1)})" if kind else
                                     ""))[:80]


def self_times(ops: Sequence[Op], lo: int, hi: int) -> List[int]:
    """Each op's time in [lo, hi) less that of the ops nested in it (ops
    on one line nest: a parent starts no later and ends no earlier)."""
    out = [0] * len(ops)
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        dur = max(0, min(o.end, hi) - max(o.start, lo))
        out[i] += dur
        if stack:
            out[stack[-1]] -= dur
        stack.append(i)
    return out


def gap_label(mid: int, spans: Sequence[Tuple[str, int, int]]) -> str:
    """The innermost host span open at ``mid`` (the one that started
    last); "harness" when none is."""
    open_ = [(a, n) for n, a, b in spans if a <= mid < b]
    if not open_:
        return "harness"
    return max(open_)[1]


def reduce(trace: Trace, families: Dict[str, dict], lo: int, hi: int,
           top: int = 10) -> dict:
    """The window [lo, hi) of ``trace``, in nanoseconds on its clock."""
    ops = [o for o in trace.ops if o.end > lo and o.start < hi]
    busy = clip(union((o.start, o.end) for o in ops), lo, hi)
    busy_ns = sum(b - a for a, b in busy)
    spans = [(n, max(a, lo), min(b, hi)) for n, a, b in trace.spans
             if b > lo and a < hi]

    kernel = collections.defaultdict(lambda: {"ns": 0, "count": 0})
    per_op = collections.Counter()
    for o, own in zip(ops, self_times(ops, lo, hi)):
        fam = family_of(o, families)
        if fam is not None:
            kernel[fam]["ns"] += min(o.end, hi) - max(o.start, lo)
            kernel[fam]["count"] += 1
        per_op[fam or op_label(o)] += own

    by_span = collections.defaultdict(list)
    for n, a, b in spans:
        by_span[n].append((a, b))
    in_span = {n: overlap_ns(busy, iv) for n, iv in by_span.items()}

    idle = collections.Counter()
    gaps = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            label = gap_label((a + b) // 2, spans)
            idle[label] += b - a
            gaps.append((b - a, label))
    gaps.sort(reverse=True)
    return {"window_ns": hi - lo, "busy_ns": busy_ns,
            "kernel_ns": {k: dict(v) for k, v in kernel.items()},
            "busy_in_span_ns": in_span,
            "span_count": {n: len(iv) for n, iv in by_span.items()},
            "idle_by_label": dict(idle),
            "longest_gaps": [(label, ns) for ns, label in gaps[:top]],
            "top_ops": per_op.most_common(top)}


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: seconds, as measured."""
    return {"device_ops": [[n, ns / 1e9] for n, ns in red["top_ops"][:top]],
            "idle_gaps": [[n, ns / 1e9] for n, ns in sorted(
                red["idle_by_label"].items(), key=lambda kv: -kv[1])[:top]]}
