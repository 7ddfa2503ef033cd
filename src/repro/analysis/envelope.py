"""Envelope certification — pass 1 of the block-space contract checker.

core/mapping.py DECLARES traced-exactness envelopes as named constants
(ISQRT_TRACED_MAX_X, LTM_TRACED_MAX_LAM, TET_TRACED_MAX_LAM, probe
counts). This pass DERIVES each bound from first principles and fails if
declaration and derivation disagree:

  * float-error interval analysis over the correction-probe logic — a
    float32 op chain of length L has relative error < L * u + O(u^2)
    (u = 2^-24); the derived absolute error at the top of the envelope
    bounds how far the floor()ed candidate can sit from the true root,
    which lower-bounds the number of integer probes each direction;
  * int32 overflow analysis of every intermediate (8*lam + 1, the probe
    squares/cubes, tri(i) in the j computation) — the binding constraint
    for both the 2D and 3D envelopes;
  * empirical certification at the closed-form boundary points (x = r^2,
    lam = tri(i), lam = tet(i) and their predecessors) where float
    rounding actually bites — vectorized, one jit per map, no kernels.

The derivations are deliberately conservative (candidate error rounded up
to whole integers): a DECLARED probe count below the DERIVED requirement
fails the check, which is exactly how the mutated-probe-count test in
tests/test_analysis_lint.py breaks the contract on purpose.

History note: this pass is what exposed the pre-clamp bug where
``_isqrt_traced``'s up-probe squared 46341 into int32 wrap-around,
silently corrupting ltm_map for ~11k lambdas below the then-claimed
``lam < 2**31`` envelope. The probes are now clamped at ISQRT_MAX_R and
the declared envelope is the honest, certified one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import CheckResult
from repro.core import mapping as M

U32 = 2.0 ** -24  # float32 unit roundoff

# Conservative op-chain lengths (each op correctly rounded or better):
# isqrt: int->f32 conversion + sqrt. cube root: conversion + multiply +
# exp(log(x) / 3). log's few-ulp RELATIVE error is an ABSOLUTE error on a
# log of at most ln(2**32) ~ 22.2, which /3 and exp turn into a relative
# error of ~7.4 times that many ulp; with ~3 ulp for log and exp each and
# one step each for conversion, multiply and divide, 32 steps bound it.
_SQRT_CHAIN_OPS = 2
_CBRT_CHAIN_OPS = 32


def _res(rule, ok, detail=""):
    return CheckResult(pass_name="envelope", rule=rule, ok=ok,
                       detail=detail)


# ---------------------------------------------------------------------------
# isqrt
# ---------------------------------------------------------------------------


def derive_isqrt():
    """Derived facts about _isqrt_traced over int32 inputs."""
    r_cap = math.isqrt(M.INT32_MAX)
    # |sqrt_f32(f32(x)) - sqrt(x)| <= sqrt(x) * (chain * u); at the top of
    # the int32 range that is < 1, so floor() lands within one of truth.
    abs_err = math.sqrt(M.INT32_MAX) * (_SQRT_CHAIN_OPS * U32)
    probes_required = max(1, math.ceil(abs_err))
    # With probes clamped at r_cap, no intermediate square can exceed
    # r_cap^2 <= INT32_MAX, so the envelope is the full int32 range.
    envelope = M.INT32_MAX
    return {"r_cap": r_cap, "abs_err": abs_err,
            "probes_required": probes_required, "envelope": envelope}


def certify_isqrt():
    d = derive_isqrt()
    out = [
        _res("isqrt.float_error",
             d["abs_err"] < 1.0,
             f"derived |err| <= {d['abs_err']:.2e} over int32 (< 1 keeps "
             f"the candidate within one of floor(sqrt))"),
        _res("isqrt.probes",
             M.ISQRT_PROBES >= d["probes_required"],
             f"declared ISQRT_PROBES={M.ISQRT_PROBES}, derived "
             f"requirement {d['probes_required']}"),
        _res("isqrt.probe_clamp",
             M.ISQRT_MAX_R == d["r_cap"]
             and M.ISQRT_MAX_R ** 2 <= M.INT32_MAX
             and (M.ISQRT_MAX_R + 1) ** 2 > M.INT32_MAX,
             f"declared clamp {M.ISQRT_MAX_R}, derived isqrt(INT32_MAX) "
             f"= {d['r_cap']} (squares above it wrap int32)"),
        _res("isqrt.envelope",
             M.ISQRT_TRACED_MAX_X == d["envelope"],
             f"declared {M.ISQRT_TRACED_MAX_X}, derived {d['envelope']}"),
    ]
    # Empirical boundary certification: x = r^2 - 1, r^2, r^2 + 1 — every
    # point where floor(sqrt) changes value, i.e. where a candidate off by
    # one float ulp flips the answer.
    xs = sorted({r * r + dd for r in range(1, d["r_cap"] + 1)
                 for dd in (-1, 0, 1) if 0 <= r * r + dd <= d["envelope"]}
                | {0, 1, 2, d["envelope"]})
    xs = np.asarray(xs, np.int32)
    got = np.asarray(jax.jit(M._isqrt_traced)(jnp.asarray(xs)))
    want = np.asarray([math.isqrt(int(x)) for x in xs])
    bad = int((got != want).sum())
    out.append(_res(
        "isqrt.boundaries", bad == 0,
        f"{len(xs)} floor-boundary probes over [0, {d['envelope']}], "
        f"{bad} mismatches"))
    return out


# ---------------------------------------------------------------------------
# ltm (2D)
# ---------------------------------------------------------------------------


def derive_ltm():
    """Derived facts about traced ltm_map (int32 grid indices)."""
    # Binding constraint: 8*lam + 1 computed in int32.
    max_lam = (M.INT32_MAX - 1) // 8
    max_i = (math.isqrt(8 * max_lam + 1) - 1) // 2
    # tri(i) in the j computation must also fit int32.
    tri_fits = max_i * (max_i + 1) <= M.INT32_MAX
    return {"max_lam": max_lam, "max_i": max_i, "tri_fits": tri_fits}


def certify_ltm():
    d = derive_ltm()
    out = [
        _res("ltm.envelope",
             M.LTM_TRACED_MAX_LAM == d["max_lam"] and d["tri_fits"],
             f"declared {M.LTM_TRACED_MAX_LAM}, derived {d['max_lam']} "
             f"(8*lam+1 int32 bound; tri(i) fits: {d['tri_fits']})"),
        _res("ltm.max_row",
             M.LTM_TRACED_MAX_I == d["max_i"],
             f"declared {M.LTM_TRACED_MAX_I}, derived {d['max_i']}"),
    ]
    # Boundary probes: row starts tri(i) -> (i, 0) and row ends
    # tri(i) - 1 -> (i-1, i-1), for every traced row, plus the envelope lam.
    lams = sorted({t for i in range(1, d["max_i"] + 1)
                   for t in (i * (i + 1) // 2 - 1, i * (i + 1) // 2)}
                  | {0, d["max_lam"]})
    lams = np.asarray(lams, np.int32)
    gi, gj = jax.jit(M.ltm_map)(jnp.asarray(lams))
    wi = np.asarray([(math.isqrt(8 * int(l) + 1) - 1) // 2 for l in lams])
    wj = lams.astype(np.int64) - wi * (wi + 1) // 2
    bad = int(((np.asarray(gi) != wi) | (np.asarray(gj) != wj)).sum())
    out.append(_res(
        "ltm.boundaries", bad == 0,
        f"{len(lams)} row-boundary probes up to lam={d['max_lam']}, "
        f"{bad} mismatches"))
    return out


# ---------------------------------------------------------------------------
# tet (3D)
# ---------------------------------------------------------------------------


def derive_tet():
    """Derived facts about the traced tetrahedral row-finder."""
    # Binding constraint: tet(i)'s int32 intermediate tri(i)*(i+2).
    i = 1
    while (i + 1) * (i + 2) // 2 * (i + 3) <= M.INT32_MAX:
        i += 1
    max_i = i  # largest i with tri(i)*(i+2) <= INT32_MAX
    # Real-arithmetic candidate: for lam in [tet(i), tet(i+1)),
    # i^3 < 6*lam < (i+2)^3 (since i(i+1)(i+2) > i^3 and
    # (i+1)(i+2)(i+3) < (i+2)^3), so floor(cbrt(6 lam)) is i or i+1 —
    # real candidate error in [0, +1].
    real_err_lo, real_err_hi = 0, 1
    # float32 adds at most abs_err, which rounds the floor()ed candidate
    # at most one further step either way.
    abs_err = (max_i + 2) * (_CBRT_CHAIN_OPS * U32)
    float_step = max(1, math.ceil(abs_err)) if abs_err < 1 else None
    probes_up = -real_err_lo + 1    # candidate as low as i - 1
    probes_down = real_err_hi + 1   # candidate as high as i + 2
    return {"max_i": max_i, "abs_err": abs_err,
            "probes_up_required": probes_up,
            "probes_down_required": probes_down,
            "exact_planes": max_i - 1,
            "max_lam": max_i * (max_i + 1) * (max_i + 2) // 6 - 1,
            "float_step_ok": float_step == 1}


def certify_tet():
    d = derive_tet()
    out = [
        _res("tet.float_error",
             d["abs_err"] < 1.0 and d["float_step_ok"],
             f"derived cbrt-chain |err| <= {d['abs_err']:.2e} at "
             f"i={d['max_i']} (< 1 adds at most one floor step)"),
        _res("tet.probes_up",
             M.TET_PROBES_UP >= d["probes_up_required"],
             f"declared TET_PROBES_UP={M.TET_PROBES_UP}, derived "
             f"requirement {d['probes_up_required']}"),
        _res("tet.probes_down",
             M.TET_PROBES_DOWN >= d["probes_down_required"],
             f"declared TET_PROBES_DOWN={M.TET_PROBES_DOWN}, derived "
             f"requirement {d['probes_down_required']} (real candidate "
             f"reaches +1, float rounding one more)"),
        _res("tet.clamp",
             M.TET_TRACED_MAX_I == d["max_i"],
             f"declared clamp {M.TET_TRACED_MAX_I}, derived largest i "
             f"with tri(i)*(i+2) <= INT32_MAX = {d['max_i']}"),
        _res("tet.envelope",
             M.TET_TRACED_EXACT_PLANES == d["exact_planes"]
             and M.TET_TRACED_MAX_LAM == d["max_lam"],
             f"declared planes<={M.TET_TRACED_EXACT_PLANES} / "
             f"lam<={M.TET_TRACED_MAX_LAM}, derived "
             f"{d['exact_planes']} / {d['max_lam']}"),
    ]
    # Boundary probes: plane starts tet(i) -> (i, 0, 0) and plane ends
    # tet(i) - 1 -> (i-1, i-1, i-1) for every exact plane + the envelope.
    tets = [i * (i + 1) * (i + 2) // 6
            for i in range(d["exact_planes"] + 1)]
    lams = sorted({t + dd for t in tets[1:] for dd in (-1, 0)}
                  | {0, d["max_lam"]})
    lams = np.asarray(lams, np.int32)
    gi, gj, gk = jax.jit(M.tet_map)(jnp.asarray(lams))
    want = [M.tet_map(int(l)) for l in lams]
    wi = np.asarray([w[0] for w in want])
    wj = np.asarray([w[1] for w in want])
    wk = np.asarray([w[2] for w in want])
    bad = int(((np.asarray(gi) != wi) | (np.asarray(gj) != wj)
               | (np.asarray(gk) != wk)).sum())
    out.append(_res(
        "tet.boundaries", bad == 0,
        f"{len(lams)} plane-boundary probes up to lam={d['max_lam']}, "
        f"{bad} mismatches"))
    # Tightness: one past the envelope the traced map MUST diverge from
    # host (the final clamp pins it to the last exact plane). If it did
    # not, the declared envelope would be needlessly conservative.
    past = d["max_lam"] + 1  # == tet(TET_TRACED_MAX_I), still fits int32
    t = jax.jit(M.tet_map)(jnp.asarray(past, jnp.int32))
    traced_past = tuple(int(v) for v in t)
    host_past = M.tet_map(past)
    out.append(_res(
        "tet.envelope_tight", traced_past != host_past,
        f"lam={past}: traced {traced_past} vs host {host_past} "
        f"(clamped to plane {M.TET_TRACED_MAX_I - 1} as declared)"))
    return out


def run():
    """All envelope certifications -> list[CheckResult]."""
    return certify_isqrt() + certify_ltm() + certify_tet()
