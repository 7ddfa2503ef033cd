"""Three-term roofline model, with per-chip peaks keyed by device kind.

  compute_s    = flops_per_device / peak FLOP/s
  memory_s     = hbm_bytes_per_device / HBM bytes/s
  collective_s = collective_bytes_per_device / ICI bytes/s per link

(All inputs from roofline/hlo_parse.py are per-device, so dividing by
per-chip peaks equals the brief's global/(chips*peak) formulation.)

The dominant term is the bottleneck; step time ~ max(terms) under perfect
overlap, sum(terms) with none. MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D
(MoE) measures how much of the compiled compute is "useful" — remat
recompute, padded vocab and dead masked tiles all show up as ratio < 1.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float    # bf16 FLOP/s
    hbm_bw: float   # HBM bytes/s
    ici_bw: float   # interconnect bytes/s per link
    source: str


# Keyed by jax.Device.device_kind. A chip missing here is an error, never a
# default: its numbers would be read against another chip's peaks.
V5E = "TPU v5 lite"
PEAKS = {
    V5E: ChipPeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s; 50 GB/s per ICI link is the "
               "dry-run brief's per-link figure"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Published per-chip peaks of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to roofline.model.PEAKS "
                       f"with their source") from None


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    peak_flops: float
    model_flops: float = 0.0
    hlo_flops_per_dev: float = 0.0
    n_chips: int = 1
    # memory term with the attention-scan interior treated as VMEM-resident
    # (what the Pallas tri_attn kernel achieves on real TPU)
    memory_kernel_adj_s: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def dominant_kernel_adj(self) -> str:
        terms = {"compute": self.compute_s,
                 "memory": self.memory_kernel_adj_s or self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops summed over chips)."""
        total = self.hlo_flops_per_dev * self.n_chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_at_bound(self) -> float:
        """Model FLOPs utilization if the step ran at the dominant term."""
        if self.bound_s == 0:
            return 0.0
        return (self.model_flops / (self.n_chips * self.peak_flops)) \
            / self.bound_s

    @property
    def bound_kernel_adj_s(self) -> float:
        return max(self.compute_s,
                   self.memory_kernel_adj_s or self.memory_s,
                   self.collective_s)

    @property
    def mfu_at_bound_kernel_adj(self) -> float:
        if self.bound_kernel_adj_s == 0:
            return 0.0
        return (self.model_flops / (self.n_chips * self.peak_flops)) \
            / self.bound_kernel_adj_s

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "memory_kernel_adj_s": self.memory_kernel_adj_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "dominant_kernel_adj": self.dominant_kernel_adj,
            "model_flops": self.model_flops,
            "hlo_flops_per_dev": self.hlo_flops_per_dev,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_at_bound": self.mfu_at_bound,
            "mfu_at_bound_kernel_adj": self.mfu_at_bound_kernel_adj,
            "n_chips": self.n_chips,
        }


def terms_from_analysis(an: dict, *, n_chips: int, device_kind: str,
                        model_flops: float = 0.0) -> RooflineTerms:
    pk = peaks(device_kind)
    return RooflineTerms(
        compute_s=an["flops"] / pk.flops,
        memory_s=an["hbm_bytes"] / pk.hbm_bw,
        memory_kernel_adj_s=an.get("hbm_bytes_kernel_adj",
                                   an["hbm_bytes"]) / pk.hbm_bw,
        collective_s=an["collective_bytes_total"] / pk.ici_bw,
        peak_flops=pk.flops,
        model_flops=model_flops,
        hlo_flops_per_dev=an["flops"],
        n_chips=n_chips,
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS
# ---------------------------------------------------------------------------


def model_flops(cfg, shape, *, include_backward: Optional[bool] = None
                ) -> float:
    """6*N*D for training (fwd 2ND + bwd 4ND); 2*N*D for inference steps.

    N = active params (MoE counts routed experts only); D = tokens processed
    in the step (decode: one per sequence)."""
    n_active = cfg.param_counts()["active"]
    d_tokens = shape.tokens_per_step
    train = shape.kind == "train" if include_backward is None \
        else include_backward
    return (6.0 if train else 2.0) * n_active * d_tokens
