"""Public fused-selective-scan op.

impl='pallas' — the TPU kernel (interpreted off the TPU) — serving/forward.
impl='ref'    — the sequential jnp oracle (tests).
The training path keeps the chunked associative-scan in models/mamba.py
(measured LOWER traffic than a sequential XLA scan — EXPERIMENTS §Perf);
the kernel is what replaces both on real TPU, and the roofline's
ssm-kernel adjustment is backed by it.
"""

from __future__ import annotations

from repro.kernels.ssm_scan import kernel as K
from repro.kernels.ssm_scan import ref as R


def selective_scan(x, dt, A, Bt, Ct, h0=None, *, impl: str = "pallas",
                   block_d: int = 256, block_l: int = 128):
    if impl == "pallas":
        return K.selective_scan(x, dt, A, Bt, Ct, h0, block_d=block_d,
                                block_l=block_l)
    if impl == "ref":
        return R.selective_scan_ref(x, dt, A, Bt, Ct, h0)
    raise ValueError(f"unknown impl {impl!r}")
