"""Operations and bytes each piece of work requires, from logical inputs.

Counts use the real tokens, the causal pairs within each prompt or
document, and the valid KV prefix of each live slot: no padding, no
bucket tail, no tile a bounding box would launch, no recompute. So one
input gets one count whatever implements it (LTM or BB tiles, a packed or
fused kernel, the lockstep einsum), a later change of implementation
cannot move the yardstick, and a share of a roofline or of the peak that
reads over 100% can only mean a wrong count or a wrong time.

FLOPs count a multiply-add as 2. Attention: QK^T and PV are 2 * hd FLOPs
per (query, key) pair and head each, so the forward is 4 * hd * H per
pair; the backward needs dV, dP, dQ and dK, twice the forward. Matmul
parameters N count every weight matrix of the layers and the output head,
not the embedding table (a lookup).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

BF16 = 2
F32 = 4


def causal_pairs(n: int) -> int:
    """(query, key) pairs of one causal sequence of n tokens."""
    return n * (n + 1) // 2


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the counts need, read from a configuration file."""
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    act: str
    bytes_per_param: int = BF16

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   layers=c["num_hidden_layers"], act=c["mlp"],
                   bytes_per_param={"bfloat16": BF16, "float32": F32}[
                       c["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d, self.head_dim
        attn = 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd
        mlp = (3 if self.act == "swiglu" else 2) * d * self.ff
        return attn + mlp

    @property
    def matmul_params(self) -> int:
        return self.layers * self.layer_matmul_params + self.d * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token in every layer, as cached (bf16)."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16


# -- attention kernels, per launch (one layer) --------------------------------


def packed_fwd(shape: Shape, lens: Sequence[int]) -> dict:
    """Causal self-attention over prompts/documents of ``lens`` tokens,
    each attending only itself: Q, K, V read and O written once (bf16)."""
    pairs = sum(causal_pairs(n) for n in lens)
    toks = sum(lens)
    h, hkv, hd = shape.heads, shape.kv_heads, shape.head_dim
    return {"flops": 4 * hd * h * pairs,
            "bytes": toks * hd * (2 * h + 2 * hkv) * BF16}


def packed_bwd(shape: Shape, lens: Sequence[int]) -> dict:
    """Its backward: dV, dP, dQ, dK (twice the forward's FLOPs); Q, K, V,
    O, dO read and dQ, dK, dV written once, bf16, plus the f32 row
    statistics."""
    pairs = sum(causal_pairs(n) for n in lens)
    toks = sum(lens)
    h, hkv, hd = shape.heads, shape.kv_heads, shape.head_dim
    return {"flops": 8 * hd * h * pairs,
            "bytes": toks * (hd * (5 * h + 4 * hkv) * BF16 + h * F32)}


def decode_attn(shape: Shape, kv_lens: Sequence[int]) -> dict:
    """One decode round's attention: each live slot's query against its
    valid KV prefix, read once."""
    kv = sum(kv_lens)
    h, hkv, hd = shape.heads, shape.kv_heads, shape.head_dim
    return {"flops": 4 * hd * h * kv,
            "bytes": kv * 2 * hkv * hd * BF16
            + len(kv_lens) * 2 * h * hd * BF16}


# -- whole model steps --------------------------------------------------------


def prefill_step(shape: Shape, lens: Sequence[int]) -> dict:
    """Forward over new prompts: 2 * N per token plus causal attention in
    every layer; weights read once."""
    toks = sum(lens)
    attn = packed_fwd(shape, lens)["flops"] * shape.layers
    return {"flops": 2 * shape.matmul_params * toks + attn,
            "bytes": shape.matmul_params * shape.bytes_per_param
            + toks * shape.kv_bytes_per_token}


def decode_step(shape: Shape, kv_lens: Sequence[int]) -> dict:
    """One token for each live slot: 2 * N per token plus attention over
    each valid prefix in every layer; the weights and the valid KV read
    once."""
    attn = decode_attn(shape, kv_lens)
    return {"flops": 2 * shape.matmul_params * len(kv_lens)
            + attn["flops"] * shape.layers,
            "bytes": shape.matmul_params * shape.bytes_per_param
            + sum(kv_lens) * shape.kv_bytes_per_token}


def train_step(shape: Shape, doc_lens: Iterable[int]) -> dict:
    """Forward and backward over packed documents: 6 * N per real token
    plus forward and backward causal attention within each document."""
    lens = list(doc_lens)
    toks = sum(lens)
    attn = (packed_fwd(shape, lens)["flops"]
            + packed_bwd(shape, lens)["flops"]) * shape.layers
    return {"flops": 6 * shape.matmul_params * toks + attn, "bytes": 0}


def least_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: compute- or bandwidth-bound,
    whichever is longer."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])


def bound_by(work: dict, peak: dict) -> str:
    return ("compute" if work["flops"] / peak["bf16_flops_per_s"]
            >= work["bytes"] / peak["hbm_bytes_per_s"] else "bandwidth")
