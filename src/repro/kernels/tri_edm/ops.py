"""Public EDM op: packed triangular Euclidean distance matrix.

impl='pallas' — LTM Pallas kernel (interpreted off the TPU).
impl='scan'   — pure-XLA scan over the LTM enumeration (fast CPU path used
                by the paper-reproduction benchmarks at large N).
impl='bb'     — bounding-box Pallas baseline (full output).
impl='ref'    — oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import mapping as M
from repro.kernels.tri_edm import kernel as K
from repro.kernels.tri_edm import ref as R
from repro.obs import launch as OBS


def _edm_scan(x, block: int, *, squared: bool = False):
    """lax.scan over lambda with g(lambda) dynamic slicing (packed out)."""
    n_rows, d = x.shape
    n = n_rows // block
    t = M.tri(n)
    OBS.record_launch(
        OBS.meta_exact("tri_edm.ltm", "tri_edm", impl="scan", kind="ltm",
                       steps=t, block_shape=(block, block),
                       bb_bound=n * n), (x,))
    xf = x.astype(jnp.float32)
    sq = jnp.sum(xf * xf, axis=-1)

    def step(_, lam):
        i, j = M.ltm_map(lam)
        xi = jax.lax.dynamic_slice(xf, (i * block, 0), (block, d))
        xj = jax.lax.dynamic_slice(xf, (j * block, 0), (block, d))
        si = jax.lax.dynamic_slice(sq, (i * block,), (block,))
        sj = jax.lax.dynamic_slice(sq, (j * block,), (block,))
        d2 = jnp.maximum(si[:, None] + sj[None, :] - 2.0 * (xi @ xj.T), 0.0)
        r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        d2 = jnp.where((i == j) & (r == c), 0.0, d2)  # exact self-distance
        return None, (d2 if squared else jnp.sqrt(d2))

    _, blocks = jax.lax.scan(step, None, jnp.arange(t, dtype=jnp.int32))
    return blocks


def _edm_scan_bb(x, block: int, *, squared: bool = False):
    """Bounding-box baseline as a scan: n*n lambda steps, upper-triangle
    steps guarded out by a block-coordinate predicate (the paper's optimized
    BB). Same output packing as LTM for a fair comparison: wasted steps
    emit zeros."""
    n_rows, d = x.shape
    n = n_rows // block
    OBS.record_launch(
        OBS.meta_dense("tri_edm.bb", "tri_edm", impl="scan", grid=(n, n),
                       block_shape=(block, block), tiles_domain=M.tri(n)),
        (x,))
    xf = x.astype(jnp.float32)
    sq = jnp.sum(xf * xf, axis=-1)

    def step(_, lam):
        i, j = lam // n, lam % n

        def active():
            xi = jax.lax.dynamic_slice(xf, (i * block, 0), (block, d))
            xj = jax.lax.dynamic_slice(xf, (j * block, 0), (block, d))
            si = jax.lax.dynamic_slice(sq, (i * block,), (block,))
            sj = jax.lax.dynamic_slice(sq, (j * block,), (block,))
            d2 = jnp.maximum(si[:, None] + sj[None, :] - 2.0 * (xi @ xj.T),
                             0.0)
            r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
            d2_ = jnp.where((i == j) & (r == c), 0.0, d2)
            return d2_ if squared else jnp.sqrt(d2_)

        # paper's optimized BB: discard by block coords before thread work
        return None, jax.lax.cond(
            j <= i, active, lambda: jnp.zeros((block, block), jnp.float32))

    _, blocks = jax.lax.scan(step, None,
                             jnp.arange(n * n, dtype=jnp.int32))
    return blocks


def edm(x, block: int = 128, *, squared: bool = False, impl: str = "pallas"):
    """x: (N, d) features -> EDM.

    Packed impls return (T, block, block); 'bb'/'ref' return full/guarded
    grids ('bb_scan' returns (n*n, block, block) with zeroed dead tiles).
    """
    if impl == "pallas":
        return K.edm_ltm(x, block, squared=squared)
    if impl == "scan":
        return _edm_scan(x, block, squared=squared)
    if impl == "bb_scan":
        return _edm_scan_bb(x, block, squared=squared)
    if impl == "bb":
        return K.edm_bb(x, block, squared=squared)
    if impl == "ref":
        return R.edm_full(x, squared=squared)
    raise ValueError(f"unknown impl {impl!r}")


pack_tri = R.pack_tri
unpack_tri = R.unpack_tri
