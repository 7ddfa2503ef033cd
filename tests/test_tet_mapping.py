"""Property + unit tests for the tetrahedral (3D simplex) mapping.

The 3D analogue of the paper's central claim: tet_map is a bijection from
[0, T3(n)) onto {(i,j,k): 0 <= k <= j <= i < n}, exact on host and traced,
with plane-contiguous enumeration (the property per-plane accumulation
kernels rely on).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import mapping as M
from repro.core import schedule as S


# ---------------------------------------------------------------------------
# tet_map bijection / round-trip
# ---------------------------------------------------------------------------


def test_tet_numbers():
    assert [M.tet(i) for i in range(6)] == [0, 1, 4, 10, 20, 35]
    for n in range(200):
        assert M.tet(n) == n * (n + 1) * (n + 2) // 6
        assert M.bb3_blocks(n) - M.wasted_blocks_bb3(n) == M.tet_blocks(n)


@pytest.mark.parametrize("n", [1, 2, 3, 8, 17, 64])
def test_tet_enumerates_tetrahedron_exactly(n):
    """Every lambda < T3(n) hits a unique in-domain (i, j, k)."""
    seen = {M.tet_map(l) for l in range(M.tet(n))}
    expect = {(i, j, k) for i in range(n) for j in range(i + 1)
              for k in range(j + 1)}
    assert seen == expect


@pytest.mark.parametrize("n", [1, 2, 3, 8, 17, 64])
def test_tet_roundtrip_exhaustive(n):
    for lam in range(M.tet(n)):
        i, j, k = M.tet_map(lam)
        assert 0 <= k <= j <= i < n
        assert M.tet_inverse(i, j, k) == lam


@given(st.integers(min_value=0, max_value=2**52))
def test_tet_host_roundtrip_large(lam):
    i, j, k = M.tet_map(lam)
    assert 0 <= k <= j <= i
    assert M.tet_inverse(i, j, k) == lam


def test_tet_plane_major_contiguity():
    # Plane i occupies lambdas [tet(i), tet(i+1)), enumerated by g(mu):
    # the 3D analogue of LTM's row-major contiguity.
    for i in range(30):
        lams = [M.tet_inverse(i, j, k) for j in range(i + 1)
                for k in range(j + 1)]
        assert lams == list(range(M.tet(i), M.tet(i + 1)))


# ---------------------------------------------------------------------------
# Traced == host
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 5, 16, 64])
def test_tet_traced_matches_host_exhaustive(n):
    lams = jnp.arange(M.tet(n), dtype=jnp.int32)
    it, jt, kt = jax.jit(jax.vmap(M.tet_map))(lams)
    for l in range(M.tet(n)):
        assert (int(it[l]), int(jt[l]), int(kt[l])) == M.tet_map(l), l


# Traced exactness envelope: tet() int32 intermediates fit for arguments
# up to TET_TRACED_MAX_I, so planes i <= TET_TRACED_EXACT_PLANES
# (lam <= TET_TRACED_MAX_LAM ~ 7.15e8) are exact. The constants live in
# core/mapping.py and are certified from derived float error bounds by
# repro.analysis.envelope.
@given(st.integers(min_value=0, max_value=M.TET_TRACED_MAX_LAM))
@settings(max_examples=200)
def test_tet_traced_matches_host_envelope(lam):
    i_h, j_h, k_h = M.tet_map(lam)
    i_t, j_t, k_t = M.tet_map(jnp.asarray(lam, jnp.int32))
    assert (int(i_t), int(j_t), int(k_t)) == (i_h, j_h, k_h)


def test_tet_traced_exact_at_plane_boundaries():
    """Plane boundaries are where the cbrt repair earns its keep."""
    edges = []
    for i in [1, 2, 3, 100, 500, 1000, M.TET_TRACED_EXACT_PLANES]:
        t = M.tet(i)
        edges += [t - 1, t, t + 1]
    edges = [e for e in set(edges) if 0 <= e <= M.TET_TRACED_MAX_LAM]
    lams = jnp.asarray(sorted(edges), jnp.int32)
    it, jt, kt = jax.jit(jax.vmap(M.tet_map))(lams)
    for idx, l in enumerate(sorted(edges)):
        assert (int(it[idx]), int(jt[idx]), int(kt[idx])) == M.tet_map(l), l


# ---------------------------------------------------------------------------
# Schedules: TetrahedralSchedule vs Dense3DSchedule (BB-3D)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 2, 5, 12])
def test_tet_schedule_covers_domain(n):
    sched = S.TetrahedralSchedule(n=n)
    seen = sched.enumerate_host()
    assert len(seen) == len(set(seen)) == M.tet(n) == sched.num_blocks
    assert sched.domain_blocks == sched.num_blocks
    assert sched.waste_fraction == 0.0


@pytest.mark.parametrize("n", [1, 2, 5, 8])
def test_bb3_schedule_guard_matches_domain(n):
    sched = S.Dense3DSchedule(n=n)
    assert sched.num_blocks == n ** 3
    active = [sched.host_map(l) for l in range(sched.num_blocks)
              if bool(sched.active(l))]
    assert len(active) == M.tet(n) == sched.domain_blocks
    assert set(active) == set(S.TetrahedralSchedule(n=n).enumerate_host())


def test_launch_reduction_vs_bb3():
    """The acceptance claim: tet launches n(n+1)(n+2)/6 of BB-3D's n^3,
    an asymptotic 6x reduction (5/6 of the cube is waste)."""
    for n in (8, 64, 512):
        frac = S.Dense3DSchedule(n=n).waste_fraction
        assert frac > 5 / 6 - 3 / n
        assert M.tet_blocks(n) * 6 >= M.bb3_blocks(n)
        assert M.tet_blocks(n) <= M.bb3_blocks(n) // 6 + n * n


@pytest.mark.parametrize("kind", ["tet", "bb3"])
def test_tet_traced_index_map_matches_host(kind):
    n = 9
    sched = S.make_schedule(kind, n)
    lams = jnp.arange(sched.num_blocks)
    it, jt, kt = jax.jit(jax.vmap(sched.index_map))(lams)
    for l in range(sched.num_blocks):
        got = (int(it[l]), int(jt[l]), int(kt[l]))
        assert got == tuple(sched.host_map(l)), (kind, l)


@pytest.mark.parametrize("n", [1, 3, 7])
def test_tet_segment_bookkeeping(n):
    """seg_start/seg_end fire exactly at plane boundaries (shared 2D/3D
    segment machinery)."""
    sched = S.TetrahedralSchedule(n=n)
    for lam in range(sched.num_blocks):
        i = sched.host_map(lam)[0]
        assert bool(sched.seg_start(lam)) == (lam == M.tet(i))
        assert bool(sched.seg_end(lam)) == (lam == M.tet(i + 1) - 1)


def test_2d_segment_origin_consistent_with_rows():
    """The shared segment bookkeeping agrees with the 2D row structure for
    every segment-contiguous schedule kind."""
    for sched in [S.TriangularSchedule(n=9),
                  S.TriangularSchedule(n=9, include_diagonal=False),
                  S.DenseSchedule(n=7),
                  S.BandSchedule(n=11, w=4),
                  S.PrefixSchedule(n=9, p=3),
                  S.TetrahedralSchedule(n=6),
                  S.Dense3DSchedule(n=4)]:
        prev_outer = None
        for lam in range(sched.num_blocks):
            outer = sched.host_map(lam)[0]
            assert bool(sched.seg_start(lam)) == (outer != prev_outer)
            last = (lam == sched.num_blocks - 1
                    or sched.host_map(lam + 1)[0] != outer)
            assert bool(sched.seg_end(lam)) == last
            prev_outer = outer


# ---------------------------------------------------------------------------
# rec_levels regression (malformed-assert bugfix)
# ---------------------------------------------------------------------------


def test_rec_levels_accepts_power_of_two_ratios():
    assert M.rec_levels(8, 1) == 3
    assert M.rec_levels(16, 4) == 2
    assert M.rec_levels(3, 3) == 0
    assert M.rec_levels(24, 3) == 3


@pytest.mark.parametrize("n,m", [(12, 5), (12, 8), (24, 9), (0, 1), (6, 4),
                                 (10, 2), (12, 4)])
def test_rec_levels_rejects_non_power_of_two(n, m):
    """Regression: the old first assert was vacuous whenever m divided n,
    silently relying on a later check; non-pow2 ratios and indivisible m
    must raise with a clear message."""
    with pytest.raises(AssertionError, match="REC needs n = m\\*2\\^k"):
        M.rec_levels(n, m)
