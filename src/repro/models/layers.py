"""Shared model layers: norms, RoPE, GQA attention, dense MLPs.

Parameters are plain pytrees (nested dicts of jnp arrays); layer stacks add
a leading superlayer dimension handled by the scan in transformer.py.
"""

from __future__ import annotations

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.kernels.tri_attn import ops as attn_ops
from repro.kernels.tri_attn import ref as attn_ref
from repro.parallel import hints


def dense_init(key, shape, in_axis=0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape, jnp.float32)
            / jnp.sqrt(fan_in)).astype(dtype)


def rms_norm(x, scale, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, n_heads, head_dim); positions: (S,) or (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA) — schedule-aware triangular kernels for train/prefill,
# plain einsum against the KV cache for decode.
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, h * hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype=dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype=dtype),
    }


def attention(params, x, cfg, *, positions, prefix: int = 0,
              attn_impl: str = "scan", block: int = 512, packed=None):
    """Full-sequence attention (training / prefill).

    x: (B, S, d). Returns (out (B, S, d), k, v) — k/v (B, S, Hkv, hd) already
    RoPE-rotated, ready to seed a decode cache.

    packed: optional PackedTriSched — S is then the concatenation of a
    ragged request batch and attention is block-diagonal per request (the
    batched ragged-prefill path AND the ragged document-batch training
    path: the packed attention carries a custom VJP, so jax.grad issues
    one packed launch per direction; ``positions`` must restart per
    request/document).
    """
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, hkv, hd)
    v = (x @ params["wv"]).reshape(b, s, hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qkv_sh = hints.get("attn_qkv")
    if qkv_sh is not None:
        # §Perf attention layouts. Head-TP (spec shards the head axis):
        # expand KV to the full head count and pin q/k/v to (dp, model-on-
        # heads, None, None) — per-device working set equals replicated-KV
        # GQA but the score/out einsums contract UNSHARDED dims (no
        # per-tile all-reduce). Replicated (attn_rep, spec=None on heads):
        # just pins q/k/v unsharded on model — redundant compute, zero
        # attention collectives (for archs whose heads don't divide TP).
        heads_sharded = getattr(qkv_sh, "spec", (None, None))[1] is not None
        g = h // hkv
        if heads_sharded and g > 1:
            kt = jnp.repeat(kt, g, axis=1)
            vt = jnp.repeat(vt, g, axis=1)
        qt = hints.constrain(qt, "attn_qkv")
        kt = hints.constrain(kt, "attn_qkv")
        vt = hints.constrain(vt, "attn_qkv")
    if packed is not None:
        # Ragged multi-request prefill: one launch over the packed grid;
        # member schedules carry each request's window/prefix.
        def attend(q_, k_, v_):
            return attn_ops.packed_prefill_attention(q_, k_, v_, packed,
                                                     impl=attn_impl)
    else:
        blk = block
        while s % blk:
            blk //= 2

        def attend(q_, k_, v_):
            return attn_ops.triangular_attention(
                q_, k_, v_, window=cfg.sliding_window, prefix=prefix,
                impl=attn_impl, block_q=blk, block_k=blk)
    if qkv_sh is not None and attn_impl == "pallas":
        # GSPMD cannot partition a Mosaic kernel: run it per shard of the
        # layout the hint pins (whole heads and batch rows per device —
        # attention never mixes either, so no collective is needed).
        attend = jax.shard_map(attend, mesh=qkv_sh.mesh,
                               in_specs=(qkv_sh.spec,) * 3,
                               out_specs=qkv_sh.spec, check_vma=False)
    ot = attend(qt, kt, vt)
    # one checkpoint name for both paths so the training-mode remat policy
    # can save the context across the attention VJP
    ctx = jax.ad_checkpoint.checkpoint_name(
        ot.transpose(0, 2, 1, 3).reshape(b, s, h * hd), "attn_out")
    return ctx @ params["wo"], k, v


def _decode_qkv(params, x, cfg, cache_k, cache_v, pos):
    """Shared decode front half: project + rotate the new token and write
    its k/v into each slot's cache (rolling slot pos % S_cache for SWA).
    Returns (q (B, 1, H, hd) rotated, new cache_k, new cache_v, pos (B,))."""
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s_cache = cache_k.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, hkv, hd)
    v = (x @ params["wv"]).reshape(b, 1, hkv, hd)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    slot = pos % s_cache  # rolling for SWA; identity while pos < s_cache
    bidx = jnp.arange(b)
    cache_k = cache_k.at[bidx, slot].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, slot].set(v[:, 0].astype(cache_v.dtype))
    return q, cache_k, cache_v, pos


def decode_attention(params, x, cfg, *, cache_k, cache_v, pos):
    """Single-token decode. x: (B, 1, d); cache_k/v: (B, S_cache, Hkv, hd)
    (rotated keys); pos: scalar or (B,) int32 — absolute position of each
    sequence's new token (per-slot positions enable continuous batching).

    For sliding-window configs the cache is a rolling buffer of W slots and
    slot s holds absolute position p_s = pos - ((pos - s) mod W).
    Returns (out (B, 1, d), new_cache_k, new_cache_v).
    """
    b, _, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s_cache = cache_k.shape[1]
    w = cfg.sliding_window
    q, cache_k, cache_v, pos = _decode_qkv(params, x, cfg, cache_k, cache_v,
                                           pos)

    slots = jnp.arange(s_cache)
    if w is not None:  # rolling buffer: recover absolute positions
        slot_pos = pos[:, None] - jnp.mod(pos[:, None] - slots, s_cache)
        valid = slot_pos >= 0  # (B, S_cache)
    else:
        valid = slots[None, :] <= pos[:, None]

    g = h // hkv
    qg = q.reshape(b, 1, hkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    scores = jnp.where(valid[:, None, None, None, :], scores,
                       attn_ref.NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, cache_v.astype(jnp.float32))
    out = o.reshape(b, 1, h * hd).astype(x.dtype) @ params["wo"]
    return out, cache_k, cache_v


def packed_decode_attention(params, x, cfg, *, cache_k, cache_v, pos,
                            decode_tbl, decode_spec):
    """Packed mixed-position decode: same projections/cache write as
    decode_attention, but attention runs over the packed decode grid —
    each live slot attends ONLY its own valid KV prefix
    (sum_r ceil(kv_len_r / blk) tiles in one launch instead of the
    lockstep einsum's B * S_cache pad-to-max work). decode_tbl is the
    round's traced (5, R) member table, decode_spec its static half
    (ops.DecodeRoundSpec). Slots without a live member get zero attention
    output (their k/v cache write still happens, matching lockstep)."""
    b, _, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    q, cache_k, cache_v, _ = _decode_qkv(params, x, cfg, cache_k, cache_v,
                                         pos)
    ot = attn_ops.packed_decode_attention(q[:, 0], cache_k, cache_v,
                                          decode_tbl, decode_spec)
    out = ot.reshape(b, 1, h * hd).astype(x.dtype) @ params["wo"]
    return out, cache_k, cache_v


def fused_attention(params, x_pack, x_dec, cfg, *, pack_positions, packed,
                    cache_k, cache_v, pos, fused_tbl, fused_spec):
    """Fused continuous-batching attention: ONE launch covers the round's
    newly admitted prompts (x_pack (1, S_pack, d), packed block-diagonal
    self-attention like ``attention(packed=...)``) AND every live decode
    slot (x_dec (B, 1, d), each attending its own valid KV prefix like
    ``packed_decode_attention``). The decode half's projections and cache
    write are byte-identical to the split path (_decode_qkv); fused_tbl /
    fused_spec route both kinds through ops.fused_step_attention.

    Returns (out_pack (1, S_pack, d), out_dec (B, 1, d),
    k_pack, v_pack (1, S_pack, Hkv, hd) rotated — the admit-splice seed,
    new cache_k, new cache_v)."""
    b = x_dec.shape[0]
    _, s, _ = x_pack.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x_pack @ params["wq"]).reshape(1, s, h, hd)
    k = (x_pack @ params["wk"]).reshape(1, s, hkv, hd)
    v = (x_pack @ params["wv"]).reshape(1, s, hkv, hd)
    q = apply_rope(q, pack_positions, cfg.rope_theta)
    k = apply_rope(k, pack_positions, cfg.rope_theta)

    q_dec, cache_k, cache_v, _ = _decode_qkv(params, x_dec, cfg, cache_k,
                                             cache_v, pos)
    op, od = attn_ops.fused_step_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_dec[:, 0], cache_k, cache_v,
        fused_tbl, packed, fused_spec)
    out_pack = (op.transpose(0, 2, 1, 3).reshape(1, s, h * hd)
                @ params["wo"])
    out_dec = (od.reshape(b, 1, h * hd).astype(x_dec.dtype)
               @ params["wo"])
    return out_pack, out_dec, k, v, cache_k, cache_v


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_activation == "swiglu":
        return {
            "wi": dense_init(ks[0], (d, f), dtype=dtype),
            "wg": dense_init(ks[1], (d, f), dtype=dtype),
            "wo": dense_init(ks[2], (f, d), dtype=dtype),
        }
    return {
        "wi": dense_init(ks[0], (d, f), dtype=dtype),
        "wo": dense_init(ks[2], (f, d), dtype=dtype),
    }


def mlp(params, x, cfg):
    if cfg.mlp_activation == "swiglu":
        return (jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])) @ params["wo"]
    if cfg.mlp_activation == "relu2":  # nemotron squared-ReLU
        h = jax.nn.relu(x @ params["wi"])
        return (h * h) @ params["wo"]
    return jax.nn.gelu(x @ params["wi"]) @ params["wo"]
