"""A model family that the discovery tests add to a benchmark tree as a
new file (families/probe_moe.py), to show that the harness runs a family
it has never seen: a decoder whose every MLP is a token-choice top-k
mixture of experts (SwiGLU experts, router in float32, the top-k router
logits renormalised by a softmax), served through the program's engine.

Its reference is plain float32: attention as the dense reference
computes it, then every expert over every token, each token's output the
routing-weighted sum of its top-k experts. It imports nothing of the
program. It has no ``train_block``: a training cell of this family is
refused.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from bench.harness import reference as REF

L = "layers/l0/"


def program_config(c: dict):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        n_experts=c["num_local_experts"],
        experts_per_token=c["num_experts_per_tok"], moe_every=1,
        mlp_activation="swiglu", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"],
        tie_embeddings=False, layer_pattern=("attn",))


@dataclasses.dataclass(frozen=True)
class Reference:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    experts: int
    top_k: int
    vocab: int
    layers: int
    rope_theta: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Reference":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], ff=c["intermediate_size"],
                   experts=c["num_local_experts"],
                   top_k=c["num_experts_per_tok"],
                   vocab=c["vocab_size"], layers=c["num_hidden_layers"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])

    @property
    def blocks(self) -> int:
        return self.layers

    def block_shapes(self) -> Dict[str, tuple]:
        d, hd, f, e = self.d, self.head_dim, self.ff, self.experts
        return {L + "norm1": (d,), L + "norm2": (d,),
                L + "mixer/wq": (d, self.heads * hd),
                L + "mixer/wk": (d, self.kv_heads * hd),
                L + "mixer/wv": (d, self.kv_heads * hd),
                L + "mixer/wo": (self.heads * hd, d),
                L + "ffn/router": (d, e), L + "ffn/wi": (e, d, f),
                L + "ffn/wg": (e, d, f), L + "ffn/wo": (e, f, d)}

    def served_dtypes(self) -> Dict[str, object]:
        mat = jnp.dtype(self.dtype)
        return {p: (jnp.float32 if len(s) == 1 or p.endswith("router")
                    else mat) for p, s in self.block_shapes().items()}

    def block(self, wts, x, prec: str):
        pos = jnp.arange(x.shape[1])

        def one(xs):
            s = xs.shape[0]
            h = REF._rms(xs, wts[L + "norm1"], self.eps)
            q = REF._mm(h, wts[L + "mixer/wq"], prec).reshape(
                s, self.heads, self.head_dim)
            k = REF._mm(h, wts[L + "mixer/wk"], prec).reshape(
                s, self.kv_heads, self.head_dim)
            v = REF._mm(h, wts[L + "mixer/wv"], prec).reshape(
                s, self.kv_heads, self.head_dim)
            q = REF._rope(q, pos, self.rope_theta)
            k = REF._rope(k, pos, self.rope_theta)
            xs = xs + REF._mm(REF._attention(q, k, v, self, prec),
                              wts[L + "mixer/wo"], prec)
            return xs + self._moe(wts, xs, prec)
        return jax.lax.map(one, x)

    def _moe(self, wts, x, prec: str):
        h = REF._rms(x, wts[L + "norm2"], self.eps)            # (S, d)
        top, idx = jax.lax.top_k(REF._mm(h, wts[L + "ffn/router"], prec),
                                 self.top_k)
        gate = jnp.sum(jax.nn.one_hot(idx, self.experts)
                       * jax.nn.softmax(top, -1)[..., None], 1)  # (S, E)
        out = jnp.zeros_like(x)
        for e in range(self.experts):
            a = (jax.nn.silu(REF._mm(h, wts[L + "ffn/wg"][e], prec))
                 * REF._mm(h, wts[L + "ffn/wi"][e], prec))
            out = out + gate[:, e:e + 1] * REF._mm(a, wts[L + "ffn/wo"][e],
                                                   prec)
        return out


@dataclasses.dataclass(frozen=True)
class Shape:
    """What the probe's work is counted from: the routed share of the
    expert weights, top_k of experts, each token reads."""
    d: int
    ff: int
    experts: int
    experts_per_token: int
    layers: int

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(d=c["hidden_size"], ff=c["intermediate_size"],
                   experts=c["num_local_experts"],
                   experts_per_token=c["num_experts_per_tok"],
                   layers=c["num_hidden_layers"])

    @property
    def routed_expert_params(self) -> int:
        return self.layers * self.experts_per_token * 3 * self.d * self.ff
