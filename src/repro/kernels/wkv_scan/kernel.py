"""Pallas TPU kernel: fused WKV6 (RWKV-6) linear-attention scan.

The XLA chunked formulation (models/rwkv6._wkv_chunk) materializes the
(B, t, s, H, hd) intra-chunk decay tensor in HBM — the strictly-lower-
triangular intra-chunk domain the framework's schedule accounting covers.
This kernel keeps the (hd, hd) state and every chunk intermediate in VMEM
and streams only r/k/v/lw in and out through HBM.

Grid: (B, H, L/block_l), time innermost so the state scratch carries across
chunks (the same revisit-friendly ordering as the LTM row-major schedule
and the ssm_scan kernel). Per step: one outer product, one vec-mat and one
per-row decay on (hd, hd) — hd = 64 pads VPU lanes to 128; acceptable for
the state-resident formulation (noted for the roofline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import launch as OBS


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, out_ref,
                sout_ref, s_s, *, block_l: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_s[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)    # (block_l, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)
    lw = lw_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (hd,)

    def step(t, carry):
        s, outs = carry
        kt, vt, rt, lwt = k[t], v[t], r[t], lw[t]   # (hd,)
        kv = kt[:, None] * vt[None, :]              # (hd, hd)
        out_t = jnp.sum(rt[:, None] * (s + u[:, None] * kv), axis=0)
        s = jnp.exp(lwt)[:, None] * s + kv
        outs = jax.lax.dynamic_update_slice(outs, out_t[None, :], (t, 0))
        return s, outs

    outs0 = jnp.zeros_like(r)
    s, outs = jax.lax.fori_loop(0, block_l, step, (s_s[...], outs0))
    s_s[...] = s
    out_ref[0, :, 0] = outs.astype(out_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        sout_ref[0, 0] = s.astype(sout_ref.dtype)


def wkv(r, k, v, lw, u, s0=None, *, block_l: int = 64):
    """r, k, v, lw: (B, L, H, hd); u: (H, hd); s0: (B, H, hd, hd).

    Returns (out (B, L, H, hd) in r.dtype, s_L (B, H, hd, hd) f32)."""
    b, l, h, hd = r.shape
    if s0 is None:
        s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    block_l = min(block_l, l)
    assert l % block_l == 0, (l, block_l)
    n_chunks = l // block_l
    grid = (b, h, n_chunks)

    seq_spec = pl.BlockSpec((1, block_l, 1, hd),
                            lambda bi, hi, ci: (bi, ci, hi, 0))
    out, s_out = OBS.instrumented_pallas_call(
        functools.partial(_wkv_kernel, block_l=block_l, n_chunks=n_chunks),
        meta=OBS.meta_dense("wkv_scan.wkv", "wkv_scan", impl="pallas",
                            grid=(n_chunks,), block_shape=(block_l, hd),
                            tiles_domain=n_chunks, kind="chunked",
                            cells=b * h),
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,           # r, k, v, lw
            pl.BlockSpec((1, hd), lambda bi, hi, ci: (hi, 0)),  # u
            pl.BlockSpec((1, 1, hd, hd),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),  # s0
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, hd, hd),
                         lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, h, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
    )(r, k, v, lw, u, s0)
    return out, s_out
