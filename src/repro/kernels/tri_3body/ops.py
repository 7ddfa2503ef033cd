"""Public 3-body op: packed tetrahedral triplet-interaction reduction.

impl='pallas'   — tet-grid Pallas kernel (interpreted off the TPU).
impl='scan'     — pure-XLA scan over the tet enumeration (fast CPU path).
impl='bb3_scan' — bounding-box baseline as a scan: n^3 steps, simplex
                  guard; wasted steps emit zeros (for benchmarks).
impl='bb3'      — bounding-box Pallas baseline ((n, n, n) output).
impl='ref'      — numpy oracle.

``three_body_total`` reduces the packed values to the total over all
ordered point triples using the multiset permutation weights — the
correctness anchor against the dense einsum oracle.

strict=True (all impls) masks non-strictly-ordered point triples INSIDE
the kernel (a > b > c over global indices; only diagonal tiles i==j or
j==k are affected) so each unordered triple of distinct points is counted
exactly once — the physics-kernel semantics (e.g. Axilrod–Teller). The
strict total is then the plain sum of the packed values, checked against
ref.three_body_total_strict_ref.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mapping as M
from repro.kernels.tri_3body import kernel as K
from repro.kernels.tri_3body import ref as R
from repro.obs import launch as OBS


def _tile_body(xi, xj, xk, i, j, k, block: int, strict: bool):
    a, b, c = xi @ xj.T, xj @ xk.T, xi @ xk.T
    if strict:
        m_ab, m_bc = K._strict_masks(i, j, k, block)
        a = jnp.where(m_ab, a, 0.0)
        b = jnp.where(m_bc, b, 0.0)
    return jnp.sum((a @ b) * c)


def _three_body_scan(x, block: int, strict: bool = False):
    """lax.scan over lambda with tet_map dynamic slicing (packed out)."""
    n_rows, d = x.shape
    n = n_rows // block
    t3 = M.tet(n)
    OBS.record_launch(
        OBS.meta_exact("tri_3body.tet", "tri_3body", impl="scan",
                       kind="tet", steps=t3,
                       block_shape=(block, block, block),
                       bb_bound=n * n * n), (x,))
    xf = x.astype(jnp.float32)

    def step(_, lam):
        i, j, k = M.tet_map(lam)
        sl = lambda t: jax.lax.dynamic_slice(xf, (t * block, 0), (block, d))
        return None, _tile_body(sl(i), sl(j), sl(k), i, j, k, block, strict)

    _, vals = jax.lax.scan(step, None, jnp.arange(t3, dtype=jnp.int32))
    return vals[:, None]


def _three_body_scan_bb3(x, block: int, strict: bool = False):
    """BB-3D baseline as a scan: n^3 lambda steps, simplex steps guarded by
    the block-coordinate predicate; same packing semantics as tri_edm's
    bb_scan (dead steps emit zeros)."""
    n_rows, d = x.shape
    n = n_rows // block
    OBS.record_launch(
        OBS.meta_dense("tri_3body.bb3", "tri_3body", impl="scan",
                       grid=(n, n, n), block_shape=(block, block, block),
                       tiles_domain=M.tet(n), kind="bb3"), (x,))
    xf = x.astype(jnp.float32)

    def step(_, lam):
        i, j, k = M.bb3_map(lam, n)

        def active():
            sl = lambda t: jax.lax.dynamic_slice(
                xf, (t * block, 0), (block, d))
            return _tile_body(sl(i), sl(j), sl(k), i, j, k, block, strict)

        return None, jax.lax.cond(M.bb3_active(i, j, k), active,
                                  lambda: 0.0)

    _, vals = jax.lax.scan(step, None,
                           jnp.arange(n * n * n, dtype=jnp.int32))
    return vals[:, None]


def three_body(x, block: int = 128, *, impl: str = "pallas",
               strict: bool = False):
    """x: (N, d) points -> per-tile-triple reductions.

    Packed impls return (T3, 1); 'bb3' returns (n, n, n) with the simplex
    guard applied ('bb3_scan' returns (n^3, 1) with zeroed dead steps).
    strict=True masks to a > b > c in-kernel (distinct-point semantics).
    """
    assert x.shape[0] % block == 0, (
        f"n_rows={x.shape[0]} must be a multiple of block={block}")
    if impl == "pallas":
        return K.three_body_tet(x, block, strict=strict)
    if impl == "scan":
        return _three_body_scan(x, block, strict)
    if impl == "bb3_scan":
        return _three_body_scan_bb3(x, block, strict)
    if impl == "bb3":
        return K.three_body_bb3(x, block, strict=strict)
    if impl == "ref":
        return R.three_body_packed_ref(x, block, strict=strict)
    raise ValueError(f"unknown impl {impl!r}")


def three_body_total(x, block: int = 128, *, impl: str = "pallas",
                     strict: bool = False):
    """Total triplet interaction, from the packed unique-tile launch.

    strict=False: multiset-permutation-weighted total over ALL ordered
    point triples — equals ref.three_body_total_ref. strict=True: each
    unordered triple of distinct points once (in-kernel a > b > c masking),
    so the total is the plain SUM of the packed values — equals
    ref.three_body_total_strict_ref. No post-hoc diagonal correction in
    either case.

    Works for every impl: the BB-3D layouts ((n,n,n) cube / (n^3, 1) flat)
    are gathered down to the packed (T3, 1) order first, so the baseline
    totals are comparable to the tet launch. The host-side coords table is
    enumerated once and shared with the multiplicity weights."""
    n = x.shape[0] // block
    out = three_body(x, block, impl=impl, strict=strict)
    coords = R.tet_coords(n)
    if impl == "bb3":
        packed = out[coords[:, 0], coords[:, 1], coords[:, 2]][:, None]
    elif impl == "bb3_scan":
        lin = (coords[:, 0] * n + coords[:, 1]) * n + coords[:, 2]
        packed = out[lin]
    else:
        packed = out
    if strict:
        return jnp.sum(packed[:, 0])
    return R.combine_packed(packed, n, coords)
