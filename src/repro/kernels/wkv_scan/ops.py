"""Public fused WKV6 op. impl='pallas' (TPU kernel; interpreted off the TPU) or
'ref' (sequential oracle). The training path keeps the chunked
exp-argument formulation in models/rwkv6.py (numerically matched — see
tests); the kernel is the TPU-native replacement the roofline's
wkv-kernel adjustment is backed by."""

from __future__ import annotations

from repro.kernels.wkv_scan import kernel as K
from repro.kernels.wkv_scan import ref as R


def wkv(r, k, v, lw, u, s0=None, *, impl: str = "pallas",
        block_l: int = 64):
    if impl == "pallas":
        return K.wkv(r, k, v, lw, u, s0, block_l=block_l)
    if impl == "ref":
        return R.wkv_ref(r, k, v, lw, u, s0)
    raise ValueError(f"unknown impl {impl!r}")
