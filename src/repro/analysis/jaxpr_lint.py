"""Jaxpr/HLO structural lint — pass 3 of the block-space checker.

Traces every public op (no kernel executes: jax.make_jaxpr only abstracts)
and enforces the launch-structure invariants the runtime tests cannot see:

  pallas-call counts   packed/triangular attention pallas forward = 1
                       launch, grad = exactly 3 (fwd + dq + dk/dv) with NO
                       scan/while in the pallas path — a silent fallback
                       to autodiff-through-scan would be numerically fine
                       and an order of magnitude slower, the worst kind of
                       regression; tri_edm / tri_3body entry points = 1.
  member tables        the scalar-prefetch tables are load-bearing ABI:
                       (7, R) int32 for packed prefill, (5, R) int32 for
                       decode rounds, (8, R) int32 for the fused
                       continuous-batching step (kind row partitioning
                       prefill columns before decode columns), cumulative
                       rows ascending from 0, and the decode/fused pad
                       member owning the garbage output row declared as
                       (cur, n_slots, DECODE_NO_EMIT, 0, 0).
  capacity bucketing   decode grids must be power-of-two capacities
                       (recompile-hazard detection) and the decode launch
                       must carry the b+1-row output (pad garbage row).
  platform resolution  every launch is a platform-dependent pair whose TPU
                       branch is the compiled kernel: no TPU program can
                       reach the Pallas interpreter.
  dtype hygiene        no f64/i64 avals anywhere in any traced jaxpr — an
                       accidental promotion doubles scalar-core latency
                       and memory traffic silently.
  HLO launch invariant the compiled scan path contains a while loop with
                       known trip count == the schedule's step count —
                       reusing the HLO walker from roofline/hlo_parse.py
                       (the scan mirror must enumerate exactly the
                       schedule, not a padded or fused variant).
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import CheckResult


def _res(rule, ok, detail=""):
    return CheckResult(pass_name="jaxpr", rule=rule, ok=ok, detail=detail)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _subjaxprs(value):
    """Duck-typed: yields any Jaxpr held by an eqn param (ClosedJaxpr,
    bare Jaxpr, or (possibly nested) sequences of either)."""
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr"):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _subjaxprs(v)


def _tpu_branch(eqn):
    """The branch a TPU runs of a ``jax.lax.platform_dependent`` cond
    (obs.launch wraps every Pallas launch in one), else None."""
    platforms = eqn.params.get("branches_platforms")
    if eqn.primitive.name != "cond" or platforms is None:
        return None
    for branch, ps in zip(eqn.params["branches"], platforms):
        if ps is None or "tpu" in ps:
            return branch.jaxpr
    return None


def iter_eqns(jaxpr):
    """Depth-first over all equations, descending into sub-jaxprs. Of a
    platform-dependent cond only the TPU branch is walked: counts and
    layouts are those of the program the chip runs (the interpreted
    twin of each launch is a CPU-only stand-in)."""
    for eqn in jaxpr.eqns:
        yield eqn
        tpu = _tpu_branch(eqn)
        if tpu is not None:
            yield from iter_eqns(tpu)
            continue
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def interpreted_on_tpu(jaxpr) -> int:
    """pallas_calls the TPU branch would run in interpret mode, plus any
    pallas_call outside a platform-dependent cond (which would run the
    same mode on every platform). Must be 0."""
    bad = 0
    for eqn in jaxpr.eqns:
        tpu = _tpu_branch(eqn)
        if tpu is not None:
            bad += sum(1 for e in iter_eqns(tpu)
                       if e.primitive.name == "pallas_call"
                       and e.params.get("interpret"))
            continue
        if eqn.primitive.name == "pallas_call":
            bad += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                bad += interpreted_on_tpu(sub)
    return bad


def count_primitive(jaxpr, name: str) -> int:
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def find_eqns(jaxpr, name: str):
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == name]


def wide_dtypes(jaxpr) -> List[str]:
    """Avals with f64/i64 dtypes anywhere in the jaxpr (should be none:
    the kernels are pinned to f32/int32 grid arithmetic)."""
    bad = []
    for eqn in iter_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt in (jnp.float64, jnp.int64):
                bad.append(f"{eqn.primitive.name}:{dt}")
    return bad


def _jaxpr_of(fn, *args):
    return jax.make_jaxpr(fn)(*args).jaxpr


# ---------------------------------------------------------------------------
# fixtures (tiny shapes; tracing only, nothing executes)
# ---------------------------------------------------------------------------


def _attn_fixture():
    from repro.kernels.tri_attn import ops as OPS

    psched = OPS.make_packed_sched([32, 16, 48], block=16,
                                   window=[None, 24, None],
                                   prefix=[0, 0, 16])
    b, h, d = 1, 2, 8
    q = np.zeros((b, h, psched.s_total, d), np.float32)
    return OPS, psched, q


def _decode_fixture():
    from repro.kernels.tri_attn import ops as OPS

    blk, s_cache, n_slots, n_members = 4, 16, 3, 4
    tbl, needed = OPS.make_decode_table([5, 9], [0, 1], blk=blk,
                                        n_members=n_members,
                                        n_slots=n_slots, s_cache=s_cache)
    from repro.serve import decode as D

    capacity = D.round_capacity(needed)
    spec = OPS.DecodeRoundSpec(n_members=n_members, capacity=capacity,
                               blk=blk, impl="pallas")
    q = np.zeros((n_slots, 2, 8), np.float32)
    kc = np.zeros((n_slots, s_cache, 2, 8), np.float32)
    return OPS, tbl, needed, spec, q, kc


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def lint_packed_prefill() -> List[CheckResult]:
    OPS, psched, q = _attn_fixture()
    out = []

    fwd = _jaxpr_of(
        lambda a, b, c: OPS.packed_prefill_attention(a, b, c, psched,
                                                     impl="pallas"),
        q, q, q)
    out.append(_res(
        "jaxpr.packed_prefill.fwd_pallas_calls",
        count_primitive(fwd, "pallas_call") == 1
        and count_primitive(fwd, "scan") == 0
        and count_primitive(fwd, "while") == 0,
        f"pallas fwd: {count_primitive(fwd, 'pallas_call')} pallas_call "
        f"(expect 1), {count_primitive(fwd, 'scan')} scan (expect 0)"))

    grad = _jaxpr_of(
        jax.grad(lambda a, b, c: jnp.sum(
            OPS.packed_prefill_attention(a, b, c, psched, impl="pallas")),
            argnums=(0, 1, 2)),
        q, q, q)
    n_pc = count_primitive(grad, "pallas_call")
    n_scan = count_primitive(grad, "scan") + count_primitive(grad, "while")
    out.append(_res(
        "jaxpr.packed_prefill.grad_pallas_calls",
        n_pc == 3 and n_scan == 0,
        f"pallas grad: {n_pc} pallas_call (expect exactly 3: fwd + dq + "
        f"dkv), {n_scan} scan/while (expect 0 — no silent autodiff "
        f"fallback)"))

    # per-row softmax stats travel as (B, H, S, 1) f32: a (1, 1, blk)
    # block of a (B, H, S) array is refused by Mosaic
    pcs = find_eqns(fwd, "pallas_call")
    b_, h_, s_, _ = q.shape
    stat_shapes = [tuple(v.aval.shape) for e in pcs for v in e.outvars]
    out.append(_res(
        "jaxpr.packed_prefill.row_stats_layout",
        (b_, h_, s_, 1) in stat_shapes,
        f"packed fwd out avals {stat_shapes} must include the lse column "
        f"{(b_, h_, s_, 1)}"))
    out.extend(_table_rules_packed(psched))
    out.append(_res(
        "jaxpr.packed_prefill.no_wide_dtypes", not wide_dtypes(grad),
        f"f64/i64 avals in grad jaxpr: {wide_dtypes(grad) or 'none'}"))
    return out


def _table_rules_packed(psched) -> List[CheckResult]:
    tbl = psched.table()
    r = len(psched.members)
    starts, rows = tbl[0], tbl[1]
    shape_ok = tbl.shape == (7, r) and tbl.dtype == np.int32
    asc_ok = (starts[0] == 0 and rows[0] == 0
              and bool((np.diff(starts) > 0).all())
              and bool((np.diff(rows) > 0).all())
              and bool((np.diff(starts)
                        == [m.rm_steps for m in psched.members[:-1]]).all()))
    return [_res(
        "jaxpr.packed_prefill.member_table",
        shape_ok and asc_ok,
        f"(7, R) int32 scalar-prefetch table: shape {tbl.shape} "
        f"{tbl.dtype}; cumulative rows ascending from 0: {asc_ok}")]


def lint_triangular_attention() -> List[CheckResult]:
    from repro.kernels.tri_attn import ops as OPS

    q = np.zeros((1, 2, 64, 8), np.float32)
    fwd = _jaxpr_of(
        lambda a, b, c: OPS.triangular_attention(a, b, c, impl="pallas",
                                                 block_q=16, block_k=16),
        q, q, q)
    grad = _jaxpr_of(
        jax.grad(lambda a, b, c: jnp.sum(
            OPS.triangular_attention(a, b, c, impl="pallas",
                                     block_q=16, block_k=16)),
            argnums=(0, 1, 2)),
        q, q, q)
    n_f, n_g = (count_primitive(fwd, "pallas_call"),
                count_primitive(grad, "pallas_call"))
    return [
        _res("jaxpr.tri_attn.fwd_pallas_calls", n_f == 1,
             f"pallas fwd: {n_f} pallas_call (expect 1)"),
        _res("jaxpr.tri_attn.grad_pallas_calls",
             n_g == 3 and count_primitive(grad, "scan") == 0,
             f"pallas grad: {n_g} pallas_call (expect 3), "
             f"{count_primitive(grad, 'scan')} scan (expect 0)"),
    ]


def lint_packed_decode() -> List[CheckResult]:
    from repro.core.mapping import INT32_MAX
    from repro.kernels.tri_attn import kernel as K

    OPS, tbl, needed, spec, q, kc = _decode_fixture()
    out = []

    jx = _jaxpr_of(
        lambda a, b, c, t: OPS.packed_decode_attention(a, b, c, t, spec),
        q, kc, kc, tbl)
    pcs = find_eqns(jx, "pallas_call")
    out.append(_res(
        "jaxpr.packed_decode.pallas_calls",
        len(pcs) == 1 and count_primitive(jx, "scan") == 0,
        f"pallas decode: {len(pcs)} pallas_call (expect 1), "
        f"{count_primitive(jx, 'scan')} scan (expect 0)"))

    # pad garbage row: the launch writes (b+1, h, 1, d) — a (1, d) tile
    # per query head, the layout Mosaic tiles; row b belongs to the pad
    # member and is dropped by the caller.
    b, h, d = q.shape
    pad_row_ok = bool(pcs) and any(
        tuple(v.aval.shape) == (b + 1, h, 1, d) for v in pcs[0].outvars)
    out.append(_res(
        "jaxpr.packed_decode.pad_garbage_row", pad_row_ok,
        f"decode launch out avals "
        f"{[tuple(v.aval.shape) for v in pcs[0].outvars] if pcs else []} "
        f"must include (b+1, h, 1, d) = {(b + 1, h, 1, d)}"))

    # capacity bucketing: static grid is a power of two >= needed
    cap = spec.capacity
    out.append(_res(
        "jaxpr.packed_decode.capacity_pow2",
        cap >= needed and cap & (cap - 1) == 0,
        f"capacity {cap} for {needed} live tiles (power-of-two bucket)"))

    # (5, R) int32 member table with the declared pad-member column
    n_live = 2
    pad_col = tuple(int(v) for v in tbl[:, -1])
    expect_pad = (int(tbl[0, n_live]), q.shape[0], K.DECODE_NO_EMIT, 0, 0)
    tbl_ok = (tbl.shape == (5, spec.n_members) and tbl.dtype == np.int32
              and int(tbl[0, 0]) == 0
              and bool((np.diff(tbl[0]) >= 0).all())
              and pad_col == expect_pad
              and K.DECODE_NO_EMIT == 2 ** 30
              and K.DECODE_NO_EMIT > INT32_MAX // (2 * spec.blk))
    out.append(_res(
        "jaxpr.packed_decode.member_table", tbl_ok,
        f"(5, R) int32 decode table; pad column {pad_col} vs declared "
        f"(cur, n_slots, DECODE_NO_EMIT, 0, 0) = {expect_pad}; "
        f"DECODE_NO_EMIT = 2**30 dominates any real tile count"))
    out.append(_res(
        "jaxpr.packed_decode.no_wide_dtypes", not wide_dtypes(jx),
        f"f64/i64 avals: {wide_dtypes(jx) or 'none'}"))
    return out


def lint_tri_kernels() -> List[CheckResult]:
    from repro.kernels.tri_3body import ops as O3
    from repro.kernels.tri_edm import ops as OE

    x = np.zeros((32, 4), np.float32)
    je = _jaxpr_of(lambda v: OE.edm(v, block=8, impl="pallas"), x)
    j3 = _jaxpr_of(lambda v: O3.three_body(v, block=8, impl="pallas"), x)
    ne, n3 = (count_primitive(je, "pallas_call"),
              count_primitive(j3, "pallas_call"))
    return [
        _res("jaxpr.tri_edm.pallas_calls", ne == 1,
             f"edm pallas: {ne} pallas_call (expect 1)"),
        _res("jaxpr.tri_3body.pallas_calls", n3 == 1,
             f"three_body pallas: {n3} pallas_call (expect 1)"),
        _res("jaxpr.tri_kernels.no_wide_dtypes",
             not wide_dtypes(je) and not wide_dtypes(j3),
             f"f64/i64 avals: "
             f"{(wide_dtypes(je) + wide_dtypes(j3)) or 'none'}"),
    ]


def lint_fused_step() -> List[CheckResult]:
    """Fused continuous-batching step: one mixed launch, (8, R) table ABI,
    power-of-two decode bucket, garbage output row/tile — and, traced
    through the whole model, exactly ONE pallas_call per engine step."""
    from repro.core.mapping import INT32_MAX
    from repro.kernels.tri_attn import kernel as K
    from repro.kernels.tri_attn import ops as OPS
    from repro.serve import decode as D

    out = []
    blk, s_cache, b = 4, 16, 3
    psched = OPS.make_packed_sched([8, 4], block=blk)
    r_p = len(psched.members)
    kv_lens, slots = [5, 9], [0, 1]
    n_members = r_p + b + 1
    tbl, needed = OPS.make_fused_table(psched, kv_lens, slots, blk=blk,
                                       n_members=n_members, n_slots=b,
                                       s_cache=s_cache)
    dec_cap = D.round_capacity(needed - psched.steps)
    spec = OPS.FusedStepSpec(n_members=n_members,
                             capacity=psched.steps + dec_cap, blk=blk,
                             impl="pallas")
    h, hkv, d = 4, 2, 8
    qp = np.zeros((1, h, psched.s_total, d), np.float32)
    kp = np.zeros((1, hkv, psched.s_total, d), np.float32)
    qd = np.zeros((b, h, d), np.float32)
    kc = np.zeros((b, s_cache, hkv, d), np.float32)
    jx = _jaxpr_of(
        lambda a, b_, c, e, f, g, t: OPS.fused_step_attention(
            a, b_, c, e, f, g, t, psched, spec), qp, kp, kp, qd, kc, kc,
        tbl)
    pcs = find_eqns(jx, "pallas_call")
    out.append(_res(
        "jaxpr.fused_step.pallas_calls",
        len(pcs) == 1 and count_primitive(jx, "scan") == 0,
        f"fused pallas step: {len(pcs)} pallas_call (expect 1 — prefill "
        f"AND decode members in one launch), "
        f"{count_primitive(jx, 'scan')} scan (expect 0)"))

    # per-kind garbage outputs: the pack output carries an extra garbage
    # TILE (row n_pack_tiles), the decode output the pad garbage ROW b.
    s_pack = psched.s_total
    shapes = ([tuple(v.aval.shape) for v in pcs[0].outvars] if pcs else [])
    out.append(_res(
        "jaxpr.fused_step.garbage_outputs",
        (1, h, s_pack + blk, d) in shapes and (b + 1, h, 1, d) in shapes,
        f"fused launch out avals {shapes} must include the pack buffer "
        f"with its garbage tile {(1, h, s_pack + blk, d)} AND the decode "
        f"buffer with its pad row {(b + 1, h, 1, d)}"))

    # (8, R) fused table ABI: kind row partitions prefill columns (0)
    # before decode columns (1); starts cumulative from 0; the shared pad
    # column is the decode pad member in fused row order.
    pad_col = tuple(int(v) for v in tbl[:, -1])
    expect_pad = (needed, 1, K.DECODE_NO_EMIT, 0, 0, b, 0, 0)
    tbl_ok = (tbl.shape == (8, n_members) and tbl.dtype == np.int32
              and int(tbl[0, 0]) == 0
              and bool((np.diff(tbl[0]) >= 0).all())
              and bool((tbl[1, :r_p] == 0).all())
              and bool((tbl[1, r_p:] == 1).all())
              and int(tbl[0, r_p]) == psched.steps
              and pad_col == expect_pad
              and K.DECODE_NO_EMIT > INT32_MAX // (2 * blk))
    out.append(_res(
        "jaxpr.fused_step.member_table", tbl_ok,
        f"(8, R) int32 fused table: shape {tbl.shape} {tbl.dtype}; kind "
        f"row {tbl[1].tolist()} partitions prefill|decode at {r_p}; pad "
        f"column {pad_col} vs declared {expect_pad}"))

    # decode half of the grid must stay power-of-two bucketed
    out.append(_res(
        "jaxpr.fused_step.capacity_pow2",
        dec_cap >= needed - psched.steps and dec_cap & (dec_cap - 1) == 0
        and spec.capacity == psched.steps + dec_cap,
        f"fused capacity {spec.capacity} = {psched.steps} prefill steps + "
        f"{dec_cap} decode bucket (power of two)"))
    out.append(_res(
        "jaxpr.fused_step.no_wide_dtypes", not wide_dtypes(jx),
        f"f64/i64 avals: {wide_dtypes(jx) or 'none'}"))

    # -- whole-model invariant: ONE pallas_call per engine step ---------
    from repro.configs import registry as REG
    from repro.models import model as MD

    cfg = REG.smoke_config("yi-9b")
    params = MD.init_params(jax.random.key(0), cfg)
    cache = MD.init_cache(cfg, b, s_cache, jnp.float32)
    pack_tokens = np.zeros((1, s_pack), np.int32)
    pack_positions = np.zeros((s_pack,), np.int32)
    dec_tokens = np.zeros((b, 1), np.int32)
    pos = np.zeros((b,), np.int32)
    admit_rows = np.asarray([7, 11], np.int32)
    mj = _jaxpr_of(
        lambda p_, c_, t: MD.fused_step(
            p_, cfg, c_, pack_tokens, pack_positions, dec_tokens, pos,
            psched, t, spec, admit_rows), params, cache, tbl)
    n_pc = count_primitive(mj, "pallas_call")
    out.append(_res(
        "jaxpr.fused_step.one_launch_per_engine_step", n_pc == 1,
        f"model fused_step jaxpr: {n_pc} pallas_call (expect exactly 1 — "
        f"the superlayer scan body carries the single fused launch every "
        f"engine step reuses)"))
    return out


def lint_compiled_on_tpu() -> List[CheckResult]:
    """Every Pallas launch of every public op is platform-resolved: the
    TPU branch runs the Mosaic kernel (interpret=False) and no launch
    runs one mode on every platform, so no TPU program can reach the
    interpreter."""
    from repro.kernels.ssm_scan import ops as OS
    from repro.kernels.tri_3body import ops as O3
    from repro.kernels.tri_edm import ops as OE
    from repro.kernels.wkv_scan import ops as OW

    OPS, psched, q = _attn_fixture()
    _, tbl, _, spec, qd, kc = _decode_fixture()
    x = np.zeros((32, 4), np.float32)
    f32 = lambda *shape: np.zeros(shape, np.float32)
    traced = {
        "packed_prefill_grad": _jaxpr_of(jax.grad(
            lambda a, b, c: jnp.sum(OPS.packed_prefill_attention(
                a, b, c, psched, impl="pallas")), argnums=(0, 1, 2)),
            q, q, q),
        "tri_attn_grad": _jaxpr_of(jax.grad(
            lambda a: jnp.sum(OPS.triangular_attention(
                a, a, a, impl="pallas", block_q=16, block_k=16))),
            f32(1, 2, 64, 8)),
        "packed_decode": _jaxpr_of(
            lambda a, b, c, t: OPS.packed_decode_attention(a, b, c, t, spec),
            qd, kc, kc, tbl),
        "tri_edm": _jaxpr_of(lambda v: OE.edm(v, block=8, impl="pallas"), x),
        "tri_3body": _jaxpr_of(
            lambda v: O3.three_body(v, block=8, impl="pallas", strict=True),
            x),
        "ssm_scan": _jaxpr_of(
            lambda a, b, c, d: OS.selective_scan(a, b, c, d, d, block_d=8,
                                                 block_l=8),
            f32(1, 16, 8), f32(1, 16, 8), f32(8, 4), f32(1, 16, 4)),
        "wkv_scan": _jaxpr_of(
            lambda a, u: OW.wkv(a, a, a, a, u, block_l=8),
            f32(1, 16, 2, 4), f32(2, 4)),
    }
    out = []
    for name, jx in traced.items():
        n_pc = count_primitive(jx, "pallas_call")
        bad = interpreted_on_tpu(jx)
        out.append(_res(
            f"jaxpr.compiled_on_tpu.{name}", n_pc >= 1 and bad == 0,
            f"{n_pc} launch(es) on the TPU branch, {bad} that a TPU would "
            f"interpret (expect >= 1 and 0)"))
    return out


def lint_hlo_scan_invariant() -> List[CheckResult]:
    """Compiled scan-path attention: the while loop's known trip count
    must equal the schedule's step count (reuses roofline/hlo_parse)."""
    from repro.kernels.tri_attn import ops as OPS
    from repro.roofline import hlo_parse as HLO

    psched = OPS.make_packed_sched([32, 16], block=16)
    q = np.zeros((1, 2, psched.s_total, 8), np.float32)
    compiled = (
        jax.jit(lambda a, b, c: OPS.packed_prefill_attention(
            a, b, c, psched, impl="scan"))
        .lower(q, q, q).compile())
    comps = HLO.parse_computations(compiled.as_text())
    trips = []
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "while":
                t, known = HLO._trip_count(op, comps)
                if known:
                    trips.append(int(t))
    ok = psched.steps in trips
    return [_res(
        "jaxpr.hlo.scan_trip_count", ok,
        f"compiled scan path while trip counts {trips} must include "
        f"schedule steps {psched.steps} (exact block-space enumeration, "
        f"no pad/fuse drift)")]


def run() -> List[CheckResult]:
    out = []
    for rule_fn in (lint_packed_prefill, lint_triangular_attention,
                    lint_packed_decode, lint_fused_step, lint_tri_kernels,
                    lint_compiled_on_tpu, lint_hlo_scan_invariant):
        try:
            out.extend(rule_fn())
        except Exception as e:  # a trace crash IS a lint failure
            out.append(_res(f"jaxpr.{rule_fn.__name__}", False,
                            f"exception: {type(e).__name__}: {e}"))
    return out
