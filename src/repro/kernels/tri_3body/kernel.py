"""Pallas kernels for the 3-body triplet reduction over the tetrahedron.

Three kernels, mirroring tri_edm's LTM/BB/dummy trio one dimension up:
  three_body_tet — 1-D grid of T3 = tet(n) steps, tet_map index_map,
                   packed (T3, 1) output: one reduction per unique tile
                   triple k <= j <= i. The exact-map strategy.
  three_body_bb3 — n x n x n bounding-box grid with the block-coordinate
                   simplex guard; (n, n, n) output, ~5/6 of tiles dead.
  dummy_tet      — computes only the mapping and writes i+j+k, isolating
                   the cube-root map cost from the problem (the paper's
                   'dummy kernel' methodology in 3D).

Per tile triple the body is three (b, d) x (d, b) MXU contractions plus a
(b, b) x (b, b) product-and-reduce:
  A = Xi Xj^T, B = Xj Xk^T, C = Xi Xk^T,  s = sum((A @ B) * C).

strict=True enforces a > b > c over GLOBAL point indices in-kernel (not
post-hoc): A is masked to a > b and B to b > c before the product-reduce,
so each unordered triple of DISTINCT points is counted exactly once and
the total is the plain sum of the packed values (no multiset weights).
Off-diagonal tile triples (i > j > k) are unaffected — their masks are
all-ones by construction — so strictness only changes the O(n^2) diagonal
tiles, exactly the paper's intra-diagonal-masking observation one
dimension up.

TPU notes: d is padded to the lane width by Mosaic; block should be a
multiple of 8 (sublane) and ideally 128, as for tri_edm.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import mapping as M
from repro.obs import launch as OBS


def _strict_masks(i, j, k, blk: int):
    """(a > b, b > c) masks over global point indices for tile (i, j, k).

    All-ones whenever the tiles are distinct (i > j implies a > b for every
    a in tile i, b in tile j), so applying them unconditionally is exact
    and branch-free — only diagonal tiles are actually masked."""
    row = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    m_ab = (i * blk + row) > (j * blk + col)
    m_bc = (j * blk + row) > (k * blk + col)
    return m_ab, m_bc


def _triplet_tile(xi, xj, xk, masks=None):
    xi = xi.astype(jnp.float32)
    xj = xj.astype(jnp.float32)
    xk = xk.astype(jnp.float32)
    dot = lambda u, v: jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    a = dot(xi, xj)  # (b, b) = G[I, J]
    b = dot(xj, xk)  # (b, b) = G[J, K]
    c = dot(xi, xk)  # (b, b) = G[I, K]
    if masks is not None:  # strict a > b > c (a > c follows)
        m_ab, m_bc = masks
        a = jnp.where(m_ab, a, 0.0)
        b = jnp.where(m_bc, b, 0.0)
    ab = jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return jnp.sum(ab * c, keepdims=True)  # (1, 1): a vector Mosaic stores


def _tet_kernel(x_i_ref, x_j_ref, x_k_ref, out_ref, *, block: int,
                strict: bool):
    lam = pl.program_id(0)
    i, j, k = M.tet_map(lam)
    masks = _strict_masks(i, j, k, block) if strict else None
    out_ref[0] = _triplet_tile(x_i_ref[...], x_j_ref[...], x_k_ref[...],
                               masks)


def three_body_tet(x, block: int, *, strict: bool = False):
    """x: (N, d) -> packed (T3, 1) unique-tile-triple reductions.

    Each step writes a (1, 1) tile of a (T3, 1, 1) output: Mosaic refuses
    a (1, 1) block of a (T3, 1) array (the second-minor dim must be 8-row
    tiled or whole)."""
    n_rows, d = x.shape
    assert n_rows % block == 0
    n = n_rows // block
    t3 = M.tet(n)
    # certified traced-cbrt envelope (repro.analysis.envelope derives it
    # from float error bounds; lint fails if the constant drifts)
    assert t3 - 1 <= M.TET_TRACED_MAX_LAM, (
        f"grid {t3} exceeds the certified tet_map int32 envelope "
        f"(max lam {M.TET_TRACED_MAX_LAM}); use a larger block")
    return OBS.instrumented_pallas_call(
        functools.partial(_tet_kernel, block=block, strict=strict),
        meta=OBS.meta_exact("tri_3body.tet", "tri_3body", impl="pallas",
                            kind="tet", steps=t3,
                            block_shape=(block, block, block),
                            bb_bound=n * n * n),
        grid=(t3,),
        in_specs=[
            pl.BlockSpec((block, d), lambda lam: (M.tet_map(lam)[0], 0)),
            pl.BlockSpec((block, d), lambda lam: (M.tet_map(lam)[1], 0)),
            pl.BlockSpec((block, d), lambda lam: (M.tet_map(lam)[2], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1), lambda lam: (lam, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t3, 1, 1), jnp.float32),
    )(x, x, x)[:, 0]


def _bb3_kernel(x_i_ref, x_j_ref, x_k_ref, out_ref, *, block: int,
                strict: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    inside = M.bb3_active(i, j, k)  # block-coordinate simplex guard

    @pl.when(inside)
    def _():
        masks = _strict_masks(i, j, k, block) if strict else None
        out_ref[0, 0, 0] = _triplet_tile(
            x_i_ref[...], x_j_ref[...], x_k_ref[...], masks)

    @pl.when(jnp.logical_not(inside))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def three_body_bb3(x, block: int, *, strict: bool = False):
    """BB-3D baseline: (n, n, n) output; tiles outside the simplex are
    launched and immediately guarded out — the O(n^3) waste the tet map
    eliminates."""
    n_rows, d = x.shape
    assert n_rows % block == 0
    n = n_rows // block
    return OBS.instrumented_pallas_call(
        functools.partial(_bb3_kernel, block=block, strict=strict),
        meta=OBS.meta_dense("tri_3body.bb3", "tri_3body", impl="pallas",
                            grid=(n, n, n),
                            block_shape=(block, block, block),
                            tiles_domain=M.tet(n), kind="bb3"),
        grid=(n, n, n),
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block, d), lambda i, j, k: (j, 0)),
            pl.BlockSpec((block, d), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1, 1),
                               lambda i, j, k: (i, j, k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n, n, 1, 1), jnp.float32),
    )(x, x, x)[..., 0, 0]


def _dummy_kernel(out_ref):
    lam = pl.program_id(0)
    i, j, k = M.tet_map(lam)
    out_ref[...] = jnp.full_like(out_ref, (i + j + k).astype(jnp.float32))


def dummy_tet(n: int):
    """3D dummy kernel: map lambda -> (i, j, k), write i+j+k. Pure mapping
    cost; one f32 per block."""
    t3 = M.tet(n)
    return OBS.instrumented_pallas_call(
        _dummy_kernel,
        meta=OBS.meta_exact("tri_3body.dummy_tet", "tri_3body",
                            impl="pallas", kind="tet", steps=t3,
                            block_shape=(1, 1), bb_bound=n * n * n),
        grid=(t3,),
        out_specs=pl.BlockSpec((1, 1, 1), lambda lam: (lam, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t3, 1, 1), jnp.float32),
    )()[:, 0]
