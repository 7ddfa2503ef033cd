"""Pallas kernels for the paper's EDM benchmark (§IV).

Three kernels:
  edm_ltm    — 1-D triangular grid of T = tri(n) steps, g(lambda) index_map,
               block-packed output (T, b, b). The paper's LTM strategy.
  edm_bb     — n x n bounding-box grid with the paper's optimized block-level
               guard; full (N, N) output, upper tiles dead. The BB baseline.
  dummy_ltm  — the paper's 'dummy kernel': computes only the mapping and
               writes i+j, isolating the mapping cost from the problem.

TPU notes: feature dim d is padded to the lane width by Mosaic (the paper
uses d in 1..4); block should be a multiple of 8 (sublane) and ideally 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import mapping as M
from repro.obs import launch as OBS


def _edm_tile(xi, xj, i, j, *, squared: bool):
    xi = xi.astype(jnp.float32)
    xj = xj.astype(jnp.float32)
    sqi = jnp.sum(xi * xi, axis=-1, keepdims=True)  # (b, 1)
    sqj = jnp.sum(xj * xj, axis=-1, keepdims=True)  # (b, 1)
    cross = jax.lax.dot_general(xi, xj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    d2 = jnp.maximum(sqi + sqj.T - 2.0 * cross, 0.0)
    # exact zero self-distance on diagonal tiles (a+b-2ab roundoff otherwise
    # survives the sqrt as ~sqrt(eps)*|x|)
    b = d2.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    d2 = jnp.where((i == j) & (r == c), 0.0, d2)
    return d2 if squared else jnp.sqrt(d2)


def _ltm_kernel(x_i_ref, x_j_ref, out_ref, *, squared: bool):
    lam = pl.program_id(0)
    i, j = M.ltm_map(lam)
    out_ref[0] = _edm_tile(x_i_ref[...], x_j_ref[...], i, j, squared=squared)


def edm_ltm(x, block: int, *, squared: bool = False):
    """x: (N, d) -> packed (T, block, block) lower-tri EDM blocks."""
    n_rows, d = x.shape
    assert n_rows % block == 0
    n = n_rows // block
    t = M.tri(n)
    # certified traced-isqrt envelope (see repro.analysis.envelope)
    assert t - 1 <= M.LTM_TRACED_MAX_LAM, (
        f"grid {t} exceeds the certified ltm_map int32 envelope "
        f"(max lam {M.LTM_TRACED_MAX_LAM}); use a larger block")
    return OBS.instrumented_pallas_call(
        functools.partial(_ltm_kernel, squared=squared),
        meta=OBS.meta_exact("tri_edm.ltm", "tri_edm", impl="pallas",
                            kind="ltm", steps=t, block_shape=(block, block),
                            bb_bound=n * n),
        grid=(t,),
        in_specs=[
            pl.BlockSpec((block, d), lambda lam: (M.ltm_map(lam)[0], 0)),
            pl.BlockSpec((block, d), lambda lam: (M.ltm_map(lam)[1], 0)),
        ],
        out_specs=pl.BlockSpec((1, block, block), lambda lam: (lam, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, block, block), jnp.float32),
    )(x, x)


def _bb_kernel(x_i_ref, x_j_ref, out_ref, *, squared: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j <= i)  # paper's optimized BB: block-coordinate guard
    def _():
        out_ref[...] = _edm_tile(x_i_ref[...], x_j_ref[...], i, j,
                                 squared=squared)

    @pl.when(j > i)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def edm_bb(x, block: int, *, squared: bool = False):
    """BB baseline: full (N, N) output; tiles with j > i are wasted work."""
    n_rows, d = x.shape
    assert n_rows % block == 0
    n = n_rows // block
    return OBS.instrumented_pallas_call(
        functools.partial(_bb_kernel, squared=squared),
        meta=OBS.meta_dense("tri_edm.bb", "tri_edm", impl="pallas",
                            grid=(n, n), block_shape=(block, block),
                            tiles_domain=M.tri(n)),
        grid=(n, n),
        in_specs=[
            pl.BlockSpec((block, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block, block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_rows, n_rows), jnp.float32),
    )(x, x)


def _dummy_kernel(out_ref):
    lam = pl.program_id(0)
    i, j = M.ltm_map(lam)
    out_ref[...] = jnp.full_like(out_ref, (i + j).astype(jnp.float32))


def dummy_ltm(n: int):
    """Paper's dummy kernel: map lambda -> (i, j), write i+j. Pure mapping
    cost; one f32 per block."""
    t = M.tri(n)
    return OBS.instrumented_pallas_call(
        _dummy_kernel,
        meta=OBS.meta_exact("tri_edm.dummy_ltm", "tri_edm", impl="pallas",
                            kind="ltm", steps=t, block_shape=(1, 1),
                            bb_bound=n * n),
        grid=(t,),
        out_specs=pl.BlockSpec((1, 1, 1), lambda lam: (lam, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
    )()[:, 0]
