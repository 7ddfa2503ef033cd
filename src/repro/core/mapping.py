"""Block-mapping functions for triangular-domain problems (the paper's core).

Implements the paper's g(lambda) (LTM) plus every competitor strategy it
benchmarks (BB, UTM, RB, REC), as pure functions usable both:

  * traced inside Pallas ``BlockSpec.index_map`` / kernel bodies (jnp scalar ops
    on the TPU scalar core), and
  * eagerly on host (numpy ints) for schedule construction and analysis.

Conventions
-----------
The triangular domain is the *lower* triangle of an ``n x n`` block grid:
blocks ``(i, j)`` with ``0 <= j <= i < n`` (diagonal included unless stated).
``T(n) = n(n+1)/2`` is the number of useful blocks. ``lambda`` (``lam``) is a
linear block index in ``[0, T)`` enumerated row-major: ``lam = i(i+1)/2 + j``.

Exactness: the paper's LTM-R uses ``x*rsqrtf(x) + eps`` and is exact only for
``N < 30,720``. On TPU the map runs once per grid step on the scalar core, so
we use float sqrt followed by integer corrections (the paper's own
"e <= 1 fixable by conditionals" observation) with overflow-clamped probes.
The traced envelopes are DECLARED as named module constants below
(``ISQRT_TRACED_MAX_X``, ``LTM_TRACED_MAX_LAM``, ``TET_TRACED_MAX_LAM``, ...)
and CERTIFIED against derived float-error bounds by
``repro.analysis.envelope`` — do not restate the numbers in prose; import
the constants. Host ints are exact unboundedly (math.isqrt / python ints).

The 2D/3D map zoo
-----------------
Row-major lower-triangle maps (launch-index -> tile coords):
  ``ltm_map``        g(lambda) -> (i, j), diagonal included  (paper eq. 2)
  ``ltm_map_nodiag`` strictly-lower variant                  (paper eq. 10)
  ``band_map``       sliding-window trapezoid (beyond-paper)
  ``prefix_full_map`` causal triangle + bidirectional prefix rectangle
  ``tet_map``        lambda -> (i, j, k) over the discrete TETRAHEDRON
                     ``0 <= k <= j <= i < n`` (3D simplex; beyond-paper,
                     after Navarro et al. arXiv 1606.08881 / 1610.07394).
                     BB-3D waste grows O(n^3) so the exact map pays off
                     even more than in 2D.
Column-major variants (backward-pass enumerations): ``cm_map``,
``band_cm_map``, ``prefix_cm_map``.
Competitors at block level: ``utm_map`` (Avril), ``rb_map`` (Jung fold),
``rec_schedule`` (Ries recursive), ``bb_map`` (bounding box).

The 3D row-finder uses the same repair pattern as ``_isqrt_traced``: a
float32 ``cbrt`` candidate followed by ``TET_PROBES_UP``/``TET_PROBES_DOWN``
integer corrections (overflow-clamped probes). Traced exactness envelope:
int32 intermediates of ``tet(i) = tri(i)*(i+2)/3`` fit below 2**31 for
``i <= TET_TRACED_MAX_I``, so the map is exact for planes
``i <= TET_TRACED_EXACT_PLANES`` (``lam <= TET_TRACED_MAX_LAM``); host ints
are exact unboundedly.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

# ---------------------------------------------------------------------------
# Triangular numbers
# ---------------------------------------------------------------------------


def tri(n):
    """T(n) = n(n+1)/2, the n-th triangular number (works traced or host)."""
    return (n * (n + 1)) // 2


def tri_blocks(n: int) -> int:
    """Number of blocks LTM launches for an n-block-per-side domain."""
    return tri(n)


def bb_blocks(n: int) -> int:
    """Number of blocks the bounding-box strategy launches."""
    return n * n


def wasted_blocks_bb(n: int) -> int:
    """Paper: BB wastes n(n-1)/2 (strictly-upper) blocks."""
    return (n * (n - 1)) // 2


def wasted_blocks_ltm(n: int) -> int:
    """Paper: LTM wastes only the intra-diagonal-block upper halves => O(n).

    At block granularity no whole block is wasted; the n diagonal blocks each
    run half-masked, so the *block-equivalent* waste is n/2 (we report n to
    stay integer, matching the paper's O(n) claim).
    """
    return n


# ---------------------------------------------------------------------------
# Tetrahedral numbers (3D simplex)
# ---------------------------------------------------------------------------


def tet(i):
    """T3(i) = i(i+1)(i+2)/6, the i-th tetrahedral number (traced or host).

    Computed as (tri(i) * (i+2)) // 3 — each division is exact (i(i+1)/2 is
    an integer; i(i+1)(i+2)/2 is divisible by 3 since one of three
    consecutive integers is) and the int32 intermediate tri(i)*(i+2) stays
    below 2**31 for i <= TET_TRACED_MAX_I, the traced exactness envelope.
    """
    return (tri(i) * (i + 2)) // 3


def tet_blocks(n: int) -> int:
    """Blocks the tetrahedral map launches: exactly the domain size."""
    return tet(n)


def bb3_blocks(n: int) -> int:
    """Blocks the 3D bounding-box strategy launches (full n^3 cube)."""
    return n * n * n


def wasted_blocks_bb3(n: int) -> int:
    """BB-3D waste: n^3 - n(n+1)(n+2)/6 -> (5/6) n^3, i.e. O(n^3).

    In 2D the bounding box wastes ~half the launch; in 3D it wastes ~5/6 of
    it, which is why the exact simplex map pays off even more here.
    """
    return n * n * n - tet(n)


# ---------------------------------------------------------------------------
# Traced-exactness envelopes — DECLARED here, CERTIFIED by repro.analysis
# ---------------------------------------------------------------------------
#
# Single source of truth for every "exact up to ..." claim in this module.
# The static verifier (repro.analysis.envelope) re-derives each bound from
# float32 error analysis of the correction-probe logic and fails the lint
# tier if a declared constant drifts from the derived one, so edits to the
# probe code below must keep these in sync (the checker tells you how).

INT32_MAX = 2**31 - 1

# floor(sqrt(INT32_MAX)): the largest root whose square fits int32.
# Correction probes clamp at this value so the repair itself cannot
# overflow (probing (r+1)^2 for r = 46340 would wrap negative and accept
# a too-large root — the failure mode the clamp exists to prevent).
ISQRT_MAX_R = 46340

# Integer correction probes applied in each direction after the float32
# sqrt candidate. The float error bound derived by the verifier is < 1,
# so one probe each way suffices.
ISQRT_PROBES = 1

# _isqrt_traced(x) == floor(sqrt(x)) for all 0 <= x <= ISQRT_TRACED_MAX_X.
ISQRT_TRACED_MAX_X = INT32_MAX

# ltm_map computes 8*lam + 1 in the index dtype; int32 caps lam here.
# Largest exactly-mapped traced lambda and the row it lands in.
LTM_TRACED_MAX_LAM = (INT32_MAX - 1) // 8  # 268,435,455
LTM_TRACED_MAX_I = 23169  # row of LTM_TRACED_MAX_LAM

# 3D row-finder: float32 cbrt candidate error spans [-1, +2] relative to
# the true plane (real-arithmetic candidate sits in [i, i+1]; float
# rounding adds at most one more either way), so two probes up and two
# down repair it with margin.
TET_PROBES_UP = 2
TET_PROBES_DOWN = 2

# Largest argument whose tet() int32 intermediate tri(i)*(i+2) fits in
# 2**31. Correction probes clamp here, so the traced map is exact for
# planes i <= TET_TRACED_EXACT_PLANES, i.e. lam <= TET_TRACED_MAX_LAM.
TET_TRACED_MAX_I = 1624
TET_TRACED_EXACT_PLANES = TET_TRACED_MAX_I - 1  # 1623
TET_TRACED_MAX_LAM = tet(TET_TRACED_MAX_I) - 1  # 715,168,999

# Kept for callers that predate the public names.
_TET_TRACED_MAX_I = TET_TRACED_MAX_I


# ---------------------------------------------------------------------------
# Exact integer sqrt usable in traced code
# ---------------------------------------------------------------------------


def _isqrt_traced(x: Array) -> Array:
    """floor(sqrt(x)) for non-negative int32/int64 scalars, traced.

    float32 sqrt gives a candidate within +-1 of the true root over the
    whole int32 range (paper's observation); ISQRT_PROBES where-corrections
    in each direction make it exact. Branch-free on the TPU scalar core.
    Probes are overflow-clamped at ISQRT_MAX_R: without the clamp,
    (r+1)^2 wraps negative for r >= ISQRT_MAX_R and the up-probe accepts a
    too-large root, which is exactly what happened for
    x >= 2,147,395,599 before the clamp existed.
    """
    xf = x.astype(jnp.float32)
    r = jnp.floor(jnp.sqrt(xf)).astype(x.dtype)
    r = jnp.minimum(r, ISQRT_MAX_R)
    # r may be off by one in either direction after float rounding.
    for _ in range(ISQRT_PROBES):
        up = jnp.minimum(r + 1, ISQRT_MAX_R)
        r = jnp.where((up * up <= x) & (up == r + 1), r + 1, r)
    for _ in range(ISQRT_PROBES):
        r = jnp.where(r * r > x, r - 1, r)
    return r


def isqrt(x):
    """Exact floor-sqrt: host ints use math.isqrt, traced arrays use repair."""
    if isinstance(x, (int, np.integer)):
        return math.isqrt(int(x))
    return _isqrt_traced(x)


# ---------------------------------------------------------------------------
# LTM — the paper's g(lambda)  (eq. 2)
# ---------------------------------------------------------------------------


def ltm_map(lam):
    """g(lambda) -> (i, j), lower-triangular row-major, diagonal included.

    i = floor(sqrt(1/4 + 2 lam) - 1/2)  computed exactly as
    i = floor((isqrt(8 lam + 1) - 1) / 2), j = lam - i(i+1)/2.
    """
    if isinstance(lam, (int, np.integer)):
        i = (math.isqrt(8 * int(lam) + 1) - 1) // 2
        return i, int(lam) - tri(i)
    lam = lam.astype(jnp.int32) if lam.dtype not in (jnp.int32, jnp.int64) else lam
    i = (isqrt(8 * lam + 1) - 1) // 2
    j = lam - (i * (i + 1)) // 2
    return i, j


def ltm_map_nodiag(lam):
    """Paper eq. (10): strictly-lower triangle (diagonal excluded).

    Equivalent to mapping into row i+1: i = floor(sqrt(1/4+2lam) + 1/2),
    j = lam - i(i-1)/2 with the returned row shifted so (i, j) satisfies
    j < i.
    """
    if isinstance(lam, (int, np.integer)):
        i = (math.isqrt(8 * int(lam) + 1) + 1) // 2
        return i, int(lam) - tri(i - 1)
    i = (isqrt(8 * lam + 1) + 1) // 2
    j = lam - (i * (i - 1)) // 2
    return i, j


def ltm_inverse(i, j):
    """(i, j) -> lambda for the row-major lower-tri enumeration."""
    return tri(i) + j


def ltm_map_float_r(lam, eps: float = 1e-4):
    """Paper's LTM-R: sqrt via x*rsqrt(x) + eps repair (faithful reproduction).

    Exactness only guaranteed for lam within the paper's envelope
    (N < 30,720 with rho=16 => lam < ~1.8M). Kept for the faithful benchmark;
    production code uses ltm_map.
    """
    lamf = jnp.asarray(lam, jnp.float32)
    x = 0.25 + 2.0 * lamf
    sq = x * jax_rsqrt(x)
    i = jnp.floor(sq - 0.5 + eps).astype(jnp.int32)
    j = jnp.asarray(lam, jnp.int32) - (i * (i + 1)) // 2
    return i, j


def jax_rsqrt(x: Array) -> Array:
    return jnp.asarray(1.0, x.dtype) / jnp.sqrt(x)  # lowered to rsqrt on TPU


# ---------------------------------------------------------------------------
# TET — tetrahedral map over the discrete 3D simplex (beyond-paper)
# ---------------------------------------------------------------------------
#
# Domain: {(i, j, k): 0 <= k <= j <= i < n}, |domain| = tet(n).
# Enumeration is "row-major" in the outermost coordinate: all tiles of
# plane i precede plane i+1, and within plane i the (j, k) sub-triangle is
# enumerated by g(mu) with mu = lam - tet(i). Hence
#     lam = tet(i) + tri(j) + k.
# Plane boundaries are contiguous (lam in [tet(i), tet(i+1))), the property
# per-plane accumulation kernels rely on — the 3D analogue of LTM's
# row-major contiguity.


def _tet_row_traced(lam: Array) -> Array:
    """Largest i with tet(i) <= lam, traced (the 3D analogue of the sqrt
    row-finder).

    A float32 cube root of 6 lam gives a candidate within [-1, +2] of the
    true plane over the whole int32 envelope; TET_PROBES_UP/TET_PROBES_DOWN
    branch-free corrections make it exact with margin, mirroring
    ``_isqrt_traced``.
    Probe arguments are clamped to TET_TRACED_MAX_I so the repair itself
    cannot overflow.
    """
    probe = lambda x: tet(jnp.minimum(x, TET_TRACED_MAX_I))
    # cube root as exp(log(x) / 3): Mosaic lowers exp/log on the TPU
    # scalar core but has no cbrt or pow. The float error this adds is
    # certified by repro.analysis.envelope; max(., 1) keeps log off 0.
    x = jnp.maximum(6.0 * lam.astype(jnp.float32), 1.0)
    c = jnp.floor(jnp.exp(jnp.log(x) / 3.0)).astype(lam.dtype)
    for _ in range(TET_PROBES_UP):
        c = jnp.where(probe(c + 1) <= lam, c + 1, c)
    for _ in range(TET_PROBES_DOWN):
        c = jnp.where(probe(c) > lam, c - 1, c)
    return jnp.minimum(c, TET_TRACED_MAX_I - 1)


def tet_map(lam):
    """lambda -> (i, j, k) over the discrete tetrahedron k <= j <= i < n.

    i = the unique plane with tet(i) <= lam < tet(i+1), found by
    integer-corrected cube root; (j, k) = g(lam - tet(i)) reuses the 2D map.
    Exact: host unboundedly (python ints), traced for planes
    i <= TET_TRACED_EXACT_PLANES (lam <= TET_TRACED_MAX_LAM, int32).
    """
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        # host: float cbrt seeds, integer loop repairs (exact for any lam)
        i = round((6 * lam) ** (1.0 / 3.0))
        while tet(i + 1) <= lam:
            i += 1
        while i > 0 and tet(i) > lam:
            i -= 1
        j, k = ltm_map(lam - tet(i))
        return i, j, k
    lam = lam.astype(jnp.int32) if lam.dtype not in (jnp.int32, jnp.int64) else lam
    i = _tet_row_traced(lam)
    j, k = ltm_map(lam - tet(i))
    return i, j, k


def tet_inverse(i, j, k):
    """(i, j, k) -> lambda for the plane-major tetrahedral enumeration."""
    return tet(i) + tri(j) + k


def bb3_map(lam, n):
    """BB-3D: row-major linear index over the full n^3 cube -> (i, j, k).

    The 3D bounding-box baseline's decode (traced or host); the single
    definition shared by Dense3DSchedule, the bb3 scan baseline, and the
    benchmarks. Block (i,j,k) is useful iff k <= j <= i (see bb3_active).
    """
    return lam // (n * n), (lam // n) % n, lam % n


def bb3_active(i, j, k):
    """Whether a BB-3D block lies inside the simplex (traced or host)."""
    if isinstance(i, (int, np.integer)):
        return k <= j <= i
    return (k <= j) & (j <= i)


# ---------------------------------------------------------------------------
# UTM — Avril et al. thread-level upper-triangular map (competitor)
# ---------------------------------------------------------------------------


def utm_map(k, n):
    """UTM: thread index k -> (a, b) in the strictly-upper triangle of n x n.

    a = floor((-(2n+1) + sqrt(4n^2 - 4n - 8k + 1)) / -2), 1-based rows;
    b = (a+1) + k - (a-1)(2n-a)/2.  We return 0-based (a-1, b-1).
    Exact via integer sqrt + repair (the original uses float sqrt + two
    conditionals).
    """
    if isinstance(k, (int, np.integer)):
        k = int(k)
        disc = 4 * n * n - 4 * n - 8 * k + 1
        s = math.isqrt(disc)
        a = int(math.floor((-(2 * n + 1) + s) / -2.0))
        # repair (paper: two conditionals)
        while (a - 1) * (2 * n - a) // 2 > k:
            a -= 1
        while a * (2 * n - a - 1) // 2 <= k:
            a += 1
        b = (a + 1) + k - (a - 1) * (2 * n - a) // 2
        return a - 1, b - 1
    disc = 4 * n * n - 4 * n - 8 * k + 1
    s = isqrt(disc)
    a = (2 * n + 1 - s) // 2
    # repair in both directions (e <= 1)
    lo = lambda a: ((a - 1) * (2 * n - a)) // 2  # first k of row a
    a = jnp.where(lo(a) > k, a - 1, a)
    a = jnp.where(lo(a + 1) <= k, a + 1, a)
    b = (a + 1) + k - lo(a)
    return a - 1, b - 1


def utm_inverse(a, b, n):
    """0-based (a,b), b>a -> k."""
    a1, b1 = a + 1, b + 1
    return (a1 - 1) * (2 * n - a1) // 2 + (b1 - a1 - 1)


# ---------------------------------------------------------------------------
# RB — Jung et al. rectangular-box fold (competitor)
# ---------------------------------------------------------------------------


def rb_grid_shape(n: int) -> Tuple[int, int]:
    """RB folds the triangle into a (n+1)//2 x (n+1) rectangle (even n shown
    in the paper; odd n partitions at floor(n/2)). We use ceil(n/2) rows by
    (n+1) cols which covers both parities with n(n+1)/2 <= rows*cols."""
    return ((n + 1) // 2, n + 1)


def rb_map(x, y, n):
    """RB: folded-rectangle coords (x=col in [0, n], y=row in [0, H)) ->
    lower-tri (i, j), with H = ceil(n/2).

    Jung et al. fold the triangle into a half-size rectangle with O(1) index
    arithmetic (the paper reimplements it arithmetically, no texture). We use
    a coverage-equivalent fold:
      x >  y : (i, j) = (x - 1, y)          -- the complete columns j < H
      x <= y : (i, j) = (H + y, H + x)      -- residual triangle, folded in
    Even n: zero waste (H*(n+1) == T(n)). Odd n: H cells fall outside and are
    filtered at runtime -- O(n) waste, exactly the paper's odd-N partition.
    """
    H = (n + 1) // 2
    below = x > y
    i_b, j_b = x - 1, y
    i_a, j_a = H + y, H + x
    if isinstance(x, (int, np.integer)):
        return (i_b, j_b) if below else (i_a, j_a)
    i = jnp.where(below, i_b, i_a)
    j = jnp.where(below, j_b, j_a)
    return i, j


def rb_valid(x, y, n):
    """Whether rectangle cell maps inside the lower triangle (odd-n edge)."""
    i, j = rb_map(x, y, n)
    if isinstance(x, (int, np.integer)):
        return 0 <= j <= i < n
    return (j >= 0) & (j <= i) & (i < n)


# ---------------------------------------------------------------------------
# REC — Ries et al. recursive partition (competitor)
# ---------------------------------------------------------------------------


def rec_levels(n: int, m: int) -> int:
    """n = m * 2**k; returns k (requires n divisible by m and n/m a pow2)."""
    assert m >= 1 and n >= m and n % m == 0, (
        f"REC needs n = m*2^k with m >= 1, got n={n} m={m}")
    q = n // m
    assert q & (q - 1) == 0, (
        f"REC needs n = m*2^k, got n={n} m={m} (n/m={q} is not a power of 2)")
    return q.bit_length() - 1


def rec_schedule(n: int, m: int):
    """REC: list of passes [(edge_blocks, origins, is_diag)].

    Pass 0 covers the n/m diagonal sub-triangles of side m with BB-style
    m x m squares (Ries's extra diagonal pass; upper halves masked =>
    O(n*m) waste). Level l in [1, k] launches 2**(k-l) square grids of edge
    m*2**(l-1) fully inside the domain (zero waste).
    """
    k = rec_levels(n, m)
    passes = [(m, [(d * m, d * m) for d in range(n // m)], True)]
    for lvl in range(1, k + 1):
        edge = m * (1 << (lvl - 1))
        step = 2 * edge
        origins = [(s * step + edge, s * step) for s in range(n // step)]
        passes.append((edge, origins, False))
    return passes


def rec_total_blocks(n: int, m: int) -> int:
    """Tiles LAUNCHED by REC (diagonal squares count fully: masked waste)."""
    total = 0
    for edge, origins, is_diag in rec_schedule(n, m):
        total += len(origins) * edge * edge
    return total


def rec_useful_blocks(n: int, m: int) -> int:
    return tri(n)


# ---------------------------------------------------------------------------
# BB — bounding box (baseline)
# ---------------------------------------------------------------------------


def bb_map(x, y):
    """BB: identity map; block (x, y) used iff y >= x (lower triangle).

    Paper's optimized BB: discard by *block* coordinates (B_x > B_y => return)
    before any thread-level work."""
    return y, x  # (i, j) = (row=y, col=x)


def bb_active(x, y):
    return y >= x


# ---------------------------------------------------------------------------
# Band (sliding-window) mapping — beyond-paper extension
# ---------------------------------------------------------------------------


def band_blocks(n: int, w: int) -> int:
    """Blocks in the banded lower triangle: rows i keep j in [max(0,i-w+1), i].

    Rows 0..w-2 are triangular (i+1 blocks), rows >= w-1 have w blocks.
    """
    w = min(w, n)
    return tri(w - 1) + (n - (w - 1)) * w


def band_map(lam, w):
    """lambda -> (i, j) for the banded lower triangle, row-major.

    Triangular head for lam < T(w-1) reuses g(lambda); the parallelogram tail
    is a closed-form div/mod. Exact; traced-friendly.
    """
    head = tri(w - 1)
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        if lam < head:
            return ltm_map(lam)
        r, c = divmod(lam - head, w)
        i = (w - 1) + r
        return i, i - (w - 1) + c
    i_t, j_t = ltm_map(lam)
    q = (lam - head) // w
    c = (lam - head) - q * w
    i_b = (w - 1) + q
    j_b = i_b - (w - 1) + c
    in_head = lam < head
    return jnp.where(in_head, i_t, i_b), jnp.where(in_head, j_t, j_b)


def band_inverse(i, j, w):
    if i < w - 1:
        return ltm_inverse(i, j)
    return tri(w - 1) + (i - (w - 1)) * w + (j - (i - (w - 1)))


# ---------------------------------------------------------------------------
# Prefix-causal mapping (rectangle ∪ triangle) — beyond-paper, for VLM
# ---------------------------------------------------------------------------


# Prefix-causal (PrefixLM / VLM image-prefix) domain: cells (i, j) with
# (j <= i) OR (j < p) — the full causal lower triangle plus the rectangle of
# bidirectional-prefix columns above the diagonal. Count = T(n) + T(p-1).
def prefix_full_blocks(n: int, p: int) -> int:
    p = min(p, n)
    return tri(n) + tri(p - 1)


def prefix_full_map(lam, n, p):
    """Row-major enumeration of {(i,j): j <= i or j < p}. Row i has
    width(i) = max(i+1, p). Closed form: rows < p-? have width p (flat),
    rows >= p-1 have i+1 (triangular tail). Flat head: rows 0..p-1 width p
    => lam < p*p? No: width(i) = p for i <= p-1, else i+1.
    head = p*p for rows [0, p). For lam >= head: triangular with offset.
    """
    head = p * p
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        if lam < head:
            return lam // p, lam % p
        rem = lam - head
        # rows i >= p, width i+1; rem indexes triangle rows shifted by p:
        # sum over rows p..i-1 of (r+1) = T(i) - T(p)
        i = (math.isqrt(8 * (rem + tri(p)) + 1) - 1) // 2
        j = rem + tri(p) - tri(i)
        return i, j
    in_head = lam < head
    i_h, j_h = lam // p, lam % p
    rem = lam - head + tri(p)
    i_t = (isqrt(8 * rem + 1) - 1) // 2
    j_t = rem - (i_t * (i_t + 1)) // 2
    return jnp.where(in_head, i_h, i_t), jnp.where(in_head, j_h, j_t)


# ---------------------------------------------------------------------------
# Column-major triangular maps (for attention BACKWARD dk/dv accumulation)
# ---------------------------------------------------------------------------


def cm_map(lam, n):
    """Column-major lower-tri (diag incl): column j holds rows i in [j, n).

    off(j) = j(2n+1-j)/2; j = floor(((2n+1) - sqrt((2n+1)^2 - 8 lam)) / 2)
    with <=2 integer corrections; i = j + lam - off(j). Needed so backward
    kernels visit all lambdas of a k-column contiguously (dk/dv scratch).
    """
    off = lambda j: (j * (2 * n + 1 - j)) // 2
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        disc = (2 * n + 1) ** 2 - 8 * lam
        j = (2 * n + 1 - math.isqrt(disc)) // 2
        while off(j + 1) <= lam:
            j += 1
        while off(j) > lam:
            j -= 1
        return j + lam - off(j), j
    disc = (2 * n + 1) ** 2 - 8 * lam
    j = (2 * n + 1 - isqrt(disc)) // 2
    j = jnp.where(off(j + 1) <= lam, j + 1, j)
    j = jnp.where(off(j) > lam, j - 1, j)
    return j + lam - off(j), j


def cm_inverse(i, j, n):
    return (j * (2 * n + 1 - j)) // 2 + (i - j)


def band_cm_map(lam, n, w):
    """Column-major banded lower-tri: column j holds rows [j, min(j+w, n)).

    Full columns j <= n - w (w rows each) form a flat head; the shrinking
    tail (cols n-w+1 .. n-1) is a reversed triangle mapped via ltm_map on the
    mirrored index. Exact; zero waste.
    """
    # min must stay traced-friendly: the packed backward gathers (n, w)
    # from a runtime member table, so they may be traced scalars here.
    w = min(w, n) if isinstance(w, (int, np.integer)) and \
        isinstance(n, (int, np.integer)) else jnp.minimum(w, n)
    head_cols = n - w + 1
    head = head_cols * w
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        if lam < head:
            j, r = divmod(lam, w)
            return j + r, j
        mu = tri(w - 1) - 1 - (lam - head)
        a, b = ltm_map(mu)
        c = (w - 2) - a
        j = head_cols + c
        return j + a - b, j
    j_h = lam // w
    i_h = j_h + (lam - j_h * w)
    mu = tri(w - 1) - 1 - (lam - head)
    a, b = ltm_map(jnp.maximum(mu, 0))
    c = (w - 2) - a
    j_t = head_cols + c
    i_t = j_t + a - b
    in_head = lam < head
    return jnp.where(in_head, i_h, i_t), jnp.where(in_head, j_h, j_t)


def prefix_cm_map(lam, n, p):
    """Column-major prefix-causal: cols j < p hold all n rows; cols j >= p
    hold rows [j, n) (delegates to cm_map on the shifted triangle)."""
    head = p * n
    if isinstance(lam, (int, np.integer)):
        lam = int(lam)
        if lam < head:
            return lam % n, lam // n
        i, j = cm_map(lam - head, n - p)
        return i + p, j + p
    i_h, j_h = lam % n, lam // n
    i_t, j_t = cm_map(jnp.maximum(lam - head, 0), n - p)
    in_head = lam < head
    return (
        jnp.where(in_head, i_h, i_t + p),
        jnp.where(in_head, j_h, j_t + p),
    )
