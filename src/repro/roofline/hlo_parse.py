"""Trip-count-aware analysis of optimized (post-SPMD) HLO text.

Why this exists: ``compiled.cost_analysis()`` counts every while-loop body
ONCE (verified on jax 0.8.2 / XLA CPU) — but this framework deliberately
wraps layers, microbatches and the triangular-attention tile enumeration in
``lax.scan``, so raw cost_analysis undercounts FLOPs by 2-4 orders of
magnitude. This module re-derives the three roofline inputs by walking the
compiled HLO with loop multipliers taken from XLA's own
``backend_config={"known_trip_count":{"n":...}}`` annotation (falling back
to the loop-condition constant, else 1 with a warning flag):

  * flops            — 2*M*N*K per dot (batch dims included), x trip counts.
  * hbm_bytes        — boundary-op traffic model: every op at the top level
                       of a non-fusion computation reads its operands and
                       writes its output once per execution; ops inside
                       fusions are free (they live in registers/VMEM).
                       Pure-layout ops (tuple plumbing, bitcast) are free.
  * collective_bytes — per-kind operand bytes of all-reduce / all-gather /
                       reduce-scatter / all-to-all / collective-permute
                       (+ async -start forms), x trip counts.

All shapes in a partitioned module are PER-DEVICE, so every figure this
module returns is per-device; roofline/model.py divides by per-chip peaks
directly (equivalent to the brief's global/(chips*peak) form).
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e4m3": 1, "f8e8m0fnu": 1,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|[a-z0-9]+\[[^\]]*\]"
    r"(?:\{[^}]*\})?)\s+([\w\-]+)\((.*)$")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

# ops that are pure plumbing/layout: no HBM traffic of their own
_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "add-dependency",
    "opt-barrier", "get-dimension-size", "domain",
    # -done halves of async pairs (bytes counted at -start)
    "all-reduce-done", "all-gather-done", "collective-permute-done",
    "async-done", "copy-done", "send-done", "recv-done",
}


def _shape_bytes(type_str: str) -> float:
    """Bytes of a (possibly tuple) HLO type string."""
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> Tuple[str, List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return "", []
    dt, dims = m.groups()
    return dt, [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Op:
    name: str
    out_type: str
    opcode: str
    rest: str  # operand list + attributes (unsplit tail of the line)

    @property
    def operands(self) -> List[str]:
        """Operand op names (strips nested call params; best effort)."""
        depth, buf, names = 0, "", []
        for ch in self.rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    buf and names.append(buf.strip())
                    break
                depth -= 1
            elif ch == "," and depth == 0:
                names.append(buf.strip())
                buf = ""
                continue
            buf += ch
        out = []
        for n in names:
            n = n.strip()
            # operands look like "%name" or "f32[..]{..} %name"
            m = re.search(r"%([\w.\-]+)\s*$", n)
            if m:
                out.append(m.group(1))
        return out

    def attr(self, key: str) -> Optional[str]:
        m = re.search(key + r"=%?([\w.\-]+)", self.rest)
        return m.group(1) if m else None

    def attr_list(self, key: str) -> List[str]:
        """e.g. branch_computations={%region_1, %region_2}."""
        m = re.search(key + r"=\{([^}]*)\}", self.rest)
        if not m:
            return []
        return re.findall(r"%([\w.\-]+)", m.group(1))


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    is_entry: bool = False

    def op_map(self) -> Dict[str, Op]:
        return {o.name: o for o in self.ops}


def parse_kernel_frames(hlo_text: str,
                        marker: str = "kernels/tri_attn") -> set:
    """DIAGNOSTIC: stack-frame ids whose file chain touches `marker`.

    Parses the HLO header's FileNames/FileLocations/StackFrames tables.
    NOT used for the kernel-adjusted memory term — custom_vjp re-staging
    collapses source info (measured: the attention interior's frames point
    at unrelated lines), so production detection is the `_KERNEL_REGION_RE`
    op-name match below. Kept for HLO spelunking."""
    file_ids = set()
    m = re.search(r"FileNames\n(.*?)\n\n", hlo_text, re.S)
    if m:
        for line in m.group(1).splitlines():
            fm = re.match(r"(\d+)\s+\"(.*)\"", line.strip())
            if fm and marker in fm.group(2):
                file_ids.add(int(fm.group(1)))
    if not file_ids:
        return set()
    loc_ids = set()
    m = re.search(r"FileLocations\n(.*?)\n\n", hlo_text, re.S)
    if m:
        for line in m.group(1).splitlines():
            lm = re.match(r"(\d+)\s+\{file_name_id=(\d+)", line.strip())
            if lm and int(lm.group(2)) in file_ids:
                loc_ids.add(int(lm.group(1)))
    # frames: frame id -> (file_location_id, parent)
    frames = {}
    m = re.search(r"StackFrames\n(.*?)\n\n", hlo_text, re.S)
    if m:
        for line in m.group(1).splitlines():
            sm = re.match(
                r"(\d+)\s+\{file_location_id=(\d+)"
                r"(?:\s+parent_frame_id=(\d+))?", line.strip())
            if sm:
                frames[int(sm.group(1))] = (
                    int(sm.group(2)),
                    int(sm.group(3)) if sm.group(3) else 0)
    marked = set()
    for fid in frames:
        cur = fid
        seen = set()
        while cur and cur not in seen:
            seen.add(cur)
            loc, parent = frames.get(cur, (0, 0))
            if loc in loc_ids:
                marked.add(fid)
                break
            cur = parent
    return marked


def parse_computations(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMP_RE.match(line)
            if m:
                cur = Computation(m.group(1), [],
                                  is_entry=line.startswith("ENTRY"))
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(line)
        if m:
            cur.ops.append(Op(*m.groups()))
    return comps


# ---------------------------------------------------------------------------
# Call-graph multipliers
# ---------------------------------------------------------------------------


def _trip_count(op: Op, comps: Dict[str, Computation]) -> Tuple[float, bool]:
    """(trips, known?) for a while op."""
    m = re.search(r'"known_trip_count":\{"n":"(\d+)"\}', op.rest)
    if m:
        return float(m.group(1)), True
    # fallback: find compare-with-constant in the condition computation
    cond_name = op.attr("condition")
    cond = comps.get(cond_name) if cond_name else None
    if cond is not None:
        consts = {o.name: o for o in cond.ops if o.opcode == "constant"}
        for o in cond.ops:
            if o.opcode == "compare":
                for operand in o.operands:
                    c = consts.get(operand)
                    if c is not None:
                        m2 = re.search(r"constant\((\d+)\)", "constant(" +
                                       c.rest)
                        if m2:
                            return float(m2.group(1)), True
    return 1.0, False


def computation_multipliers(comps: Dict[str, Computation]):
    """exec-count multiplier per computation, and which are fusion bodies.

    Walk from ENTRY; while body/cond multiply by trip count; fusion bodies
    inherit the caller's multiplier but are flagged (no HBM boundary)."""
    entry = next(c for c in comps.values() if c.is_entry)
    mult: Dict[str, float] = {}
    fusion_body: Dict[str, bool] = {}
    unknown_loops = [0]

    def visit(comp: Computation, m: float, in_fusion: bool):
        if mult.get(comp.name, 0) >= m and comp.name in mult and \
                fusion_body.get(comp.name, True) <= in_fusion:
            return  # already visited with >= multiplier and <= fusion flag
        mult[comp.name] = max(mult.get(comp.name, 0.0), m)
        fusion_body[comp.name] = fusion_body.get(comp.name, True) and in_fusion
        for op in comp.ops:
            if op.opcode == "while":
                trips, known = _trip_count(op, comps)
                if not known:
                    unknown_loops[0] += 1
                for key in ("condition", "body"):
                    sub = comps.get(op.attr(key))
                    if sub is not None:
                        visit(sub, m * trips, in_fusion)
            elif op.opcode == "fusion":
                sub = comps.get(op.attr("calls"))
                if sub is not None:
                    visit(sub, m, True)
            elif op.opcode in ("call", "custom-call", "async-start"):
                sub = comps.get(op.attr("to_apply") or op.attr("calls") or
                                op.attr("called_computation"))
                if sub is not None:
                    visit(sub, m, in_fusion)
            elif op.opcode == "conditional":
                branches = op.attr_list("branch_computations") or [
                    op.attr("true_computation"), op.attr("false_computation")]
                for name in branches:
                    sub = comps.get(name)
                    if sub is not None:
                        visit(sub, m, in_fusion)
            # map/reduce/sort/scatter to_apply bodies: scalar lambdas —
            # counted via the caller op's own cost, skip.

    visit(entry, 1.0, False)
    return mult, fusion_body, unknown_loops[0]


# ---------------------------------------------------------------------------
# FLOPs / bytes / collectives
# ---------------------------------------------------------------------------


def _dot_flops(op: Op, sym: Dict[str, Op]) -> float:
    """2 * prod(output dims) * prod(contracting dims of lhs)."""
    _, out_dims = _shape_dims(op.out_type)
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
    contract = 1
    if m:
        lhs_name = op.operands[0] if op.operands else None
        lhs = sym.get(lhs_name)
        if lhs is not None:
            _, lhs_dims = _shape_dims(lhs.out_type)
            for idx in (int(i) for i in m.group(1).split(",") if i):
                if idx < len(lhs_dims):
                    contract *= lhs_dims[idx]
    return 2.0 * out_elems * contract


_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")

# Kernel-fusable interiors, identified by op_name: the scan-attention cell
# (stack-frame tables are unreliable: custom_vjp re-staging collapses
# source info) — the only model code shaped as vmap(vmap(<cell with
# lax.scan>)), spelled "vmap(vmap())/.../while" — plus the explicit
# jax.named_scope markers around scan fallbacks with a Pallas twin. One
# tested table, so a JAX upgrade that reshuffles a spelling fails a
# pinned test instead of silently zeroing the interior-bytes column.
KERNEL_REGION_OP_NAME_SPELLINGS = (
    r"vmap\(vmap\(\)\)[^\"]*while",
    r"ssm_scan_kernel",
    r"wkv_scan_kernel",
    r"tri_attn_kernel",
)
_KERNEL_REGION_RE = re.compile("|".join(KERNEL_REGION_OP_NAME_SPELLINGS))


def _op_bytes(op: Op, sym: Dict[str, Op]) -> float:
    """HBM traffic of one boundary op."""
    b_out = _shape_bytes(op.out_type)
    if op.opcode in ("dynamic-slice", "gather", "slice"):
        return 2.0 * b_out  # reads only the sliced region, writes it
    if op.opcode in ("dynamic-update-slice", "scatter"):
        upd = sym.get(op.operands[1]) if len(op.operands) > 1 else None
        b_upd = _shape_bytes(upd.out_type) if upd is not None else b_out
        return 2.0 * min(b_upd, b_out)  # in-place: read update, write region
    b_in = sum(_shape_bytes(sym[o].out_type)
               for o in op.operands if o in sym)
    return b_in + b_out


def analyze(hlo_text: str) -> dict:
    comps = parse_computations(hlo_text)
    mult, fusion_body, unknown = computation_multipliers(comps)

    flops = 0.0
    hbm_bytes = 0.0
    hbm_kernel_interior = 0.0  # attention-scan interior (VMEM under Pallas)
    hbm_kernel_dma = 0.0       # tile loads/stores (the BlockSpec traffic)
    coll: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    coll_count: Dict[str, int] = {k: 0 for k in _COLLECTIVES}

    for comp in comps.values():
        m = mult.get(comp.name)
        if m is None:
            continue  # unreachable (dead computation)
        sym = comp.op_map()
        boundary = not fusion_body.get(comp.name, False)
        for op in comp.ops:
            base = op.opcode.replace("-start", "")
            # flops: dots count wherever they live (fused or not)
            if op.opcode == "dot":
                flops += m * _dot_flops(op, sym)
            elif op.opcode == "convolution":
                # rare here; approximate: 2 * out * (in_ch * k_spatial)
                flops += m * 2.0 * _shape_bytes(op.out_type)
            # collectives
            if base in _COLLECTIVES and not op.opcode.endswith("-done"):
                b = sum(_shape_bytes(sym[o].out_type) for o in op.operands
                        if o in sym)
                if b == 0.0:  # operand defined in another computation scope
                    b = _shape_bytes(op.out_type)
                coll[base] += m * b
                coll_count[base] += int(m)
            # HBM boundary traffic
            if boundary and op.opcode not in _FREE_OPS:
                b = m * _op_bytes(op, sym)
                hbm_bytes += b
                if _KERNEL_REGION_RE.search(op.rest):
                    if op.opcode in ("dynamic-slice",
                                     "dynamic-update-slice"):
                        hbm_kernel_dma += b
                    else:
                        hbm_kernel_interior += b

    return {
        "flops": flops,
        "hbm_bytes": hbm_bytes,
        # kernel-adjusted: on real TPU the Pallas tri_attn kernel keeps the
        # scan interior in VMEM; only the tile DMAs (dynamic-slice/update,
        # == the BlockSpec traffic) hit HBM. CPU cannot compile Pallas, so
        # the dry-run substitutes: adjusted = raw - interior.
        "hbm_bytes_kernel_adj": hbm_bytes - hbm_kernel_interior,
        "hbm_kernel_interior": hbm_kernel_interior,
        "hbm_kernel_dma": hbm_kernel_dma,
        "collective_bytes": {k: v for k, v in coll.items() if v},
        "collective_bytes_total": sum(coll.values()),
        "collective_counts": {k: v for k, v in coll_count.items() if v},
        "unknown_trip_loops": unknown,
        "n_computations": len(comps),
    }


def breakdown(hlo_text: str, top: int = 25) -> list:
    """Largest HBM/collective contributors: (bytes, opcode, comp, op, mult).

    The §Perf profiling probe: shows exactly which op x trip-count products
    drive the memory and collective roofline terms."""
    comps = parse_computations(hlo_text)
    mult, fusion_body, _ = computation_multipliers(comps)
    rows = []
    for comp in comps.values():
        m = mult.get(comp.name)
        if m is None or fusion_body.get(comp.name, False):
            continue
        sym = comp.op_map()
        for op in comp.ops:
            if op.opcode in _FREE_OPS:
                continue
            b_out = _shape_bytes(op.out_type)
            if op.opcode in ("dynamic-slice", "gather", "slice"):
                b = 2.0 * b_out
            elif op.opcode in ("dynamic-update-slice", "scatter"):
                upd = (sym.get(op.operands[1])
                       if len(op.operands) > 1 else None)
                b = 2.0 * min(_shape_bytes(upd.out_type) if upd else b_out,
                              b_out)
            else:
                b = b_out + sum(_shape_bytes(sym[o].out_type)
                                for o in op.operands if o in sym)
            rows.append((m * b, op.opcode, comp.name, op.name, m))
    rows.sort(reverse=True)
    return rows[:top]


def analyze_compiled(compiled) -> dict:
    """Full report for a jax compiled artifact: parser + XLA's own stats."""
    out = analyze(compiled.as_text())
    try:
        ca = compiled.cost_analysis()
        out["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", -1.0)),
            "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
            "transcendentals": float(ca.get("transcendentals", -1.0)),
        }
    except Exception as e:  # pragma: no cover
        out["xla_cost_analysis"] = {"error": str(e)}
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    return out
