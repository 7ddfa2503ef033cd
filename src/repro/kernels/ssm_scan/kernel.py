"""Pallas TPU kernel: fused selective scan (Mamba-1 recurrence).

Why a kernel (the paper-analogous hot-spot): the pure-XLA chunked scan
materializes (B, L, D, N)-shaped decay/injection tensors in HBM — for
jamba-1.5 train_4k that alone is a 1215 s memory roofline term (§Roofline).
This kernel keeps the (D, N) state AND all (D, N)-shaped intermediates in
VMEM, streaming only the O(L·(D+N)) inputs/outputs through HBM — the same
reduction the original CUDA selective-scan kernel achieves, re-tiled for
TPU: D is blocked to `block_d` lanes (multiple of 128 for VPU lanes), the
time axis is blocked to `block_l` VMEM-resident chunks, and the recurrence
runs as a fori_loop over the chunk with (block_d, N) vector ops.

Grid: (B, D/block_d, L/block_l) — the L axis iterates INNERMOST so the
state scratch carries across chunk steps without HBM round-trips (the same
revisit-friendly ordering argument as the LTM row-major schedule).

HBM traffic per (b, d-block): L·(x + dt + y) + L·(B + C) vs the XLA path's
L·D·N — a ~N/3 ≈ 5x reduction at N=16, and it removes the (B,L,D,N)
temporaries entirely.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import launch as OBS


def _ssm_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
                h_s, *, block_l: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_s[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)          # (block_d, N)
    x = x_ref[0].astype(jnp.float32)            # (block_l, block_d)
    dt = dt_ref[0].astype(jnp.float32)          # (block_l, block_d)
    bt = b_ref[0].astype(jnp.float32)           # (block_l, N)
    ct = c_ref[0].astype(jnp.float32)           # (block_l, N)

    def step(t, carry):
        h, ys = carry
        dtt = dt[t][:, None]                    # (block_d, 1)
        decay = jnp.exp(dtt * a)                # (block_d, N)
        h = decay * h + (dtt * x[t][:, None]) * bt[t][None, :]
        y_t = jnp.sum(h * ct[t][None, :], axis=1)   # (block_d,)
        ys = jax.lax.dynamic_update_slice(ys, y_t[None, :], (t, 0))
        return h, ys

    ys0 = jnp.zeros_like(x)
    h, ys = jax.lax.fori_loop(0, block_l, step, (h_s[...], ys0))
    h_s[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan(x, dt, A, Bt, Ct, h0=None, *, block_d: int = 256,
                   block_l: int = 128):
    """x, dt: (B, L, D); A: (D, N); Bt, Ct: (B, L, N); h0: (B, D, N).

    Returns (y (B, L, D) in x.dtype, h_L (B, D, N) f32).
    """
    b, l, d = x.shape
    n = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((b, d, n), jnp.float32)
    block_d = min(block_d, d)
    block_l = min(block_l, l)
    assert d % block_d == 0 and l % block_l == 0, (d, block_d, l, block_l)
    n_chunks = l // block_l
    grid = (b, d // block_d, n_chunks)

    y, h_out = OBS.instrumented_pallas_call(
        functools.partial(_ssm_kernel, block_l=block_l, n_chunks=n_chunks),
        meta=OBS.meta_dense("ssm_scan.selective_scan", "ssm_scan",
                            impl="pallas", grid=(n_chunks,),
                            block_shape=(block_l, block_d),
                            tiles_domain=n_chunks, kind="chunked",
                            cells=b * (d // block_d)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, ci: (bi, ci, di)),   # x
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, ci: (bi, ci, di)),   # dt
            pl.BlockSpec((block_d, n), lambda bi, di, ci: (di, 0)),  # A
            pl.BlockSpec((1, block_l, n),
                         lambda bi, di, ci: (bi, ci, 0)),    # Bt
            pl.BlockSpec((1, block_l, n),
                         lambda bi, di, ci: (bi, ci, 0)),    # Ct
            pl.BlockSpec((1, block_d, n),
                         lambda bi, di, ci: (bi, di, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, ci: (bi, ci, di)),   # y
            pl.BlockSpec((1, block_d, n),
                         lambda bi, di, ci: (bi, di, 0)),    # h_L
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, d), x.dtype),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
    )(x, dt, A, Bt, Ct, h0)
    return y, h_out
