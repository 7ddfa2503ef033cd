"""Sampling, generation loops, and the batched ragged prefill.

`generate` drives models/model.decode_step over a fixed number of tokens
with per-sequence positions (a (B,) pos vector — sequences at different
offsets decode in the same batch, the substrate for continuous batching in
engine.py). The loop is a lax.scan so the whole generation compiles to one
program (no per-token dispatch overhead).

`packed_prefill` prefills a ragged batch of prompts in ONE packed forward:
prompts are padded to a tile multiple, concatenated along S, and attention
runs block-diagonally over the PackedSchedule grid (core/packing.py) —
sum_r tri(n_r) tiles instead of R separate launches or R * tri(n_max)
padded ones. The engine splices the returned per-layer KV states into its
slot caches (Engine._admit_batch).

`decode_step_packed` is the decode-time analogue: a position-skewed batch
advances one token per live slot in one packed launch per attention layer,
each slot attending only its own valid KV prefix (core/packing's
decode_round of RowSchedule members) — sum_r ceil(kv_len_r / blk) tiles
instead of the lockstep einsum's pad-to-max B * S_cache.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mapping as M
from repro.kernels.tri_attn import ops as attn_ops
from repro.models import model as MD


def sample_logits(key, logits, *, temperature: float = 1.0,
                  top_k: Optional[int] = None, vocab_size: int = 0):
    """logits: (B, Vp) f32 -> (B,) int32 tokens."""
    if vocab_size and logits.shape[-1] > vocab_size:
        neg = jnp.finfo(jnp.float32).min
        pad = jnp.arange(logits.shape[-1]) >= vocab_size
        logits = jnp.where(pad, neg, logits)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def generate(params, cfg, cache, first_tokens, start_pos, n_tokens: int, *,
             key=None, temperature: float = 0.0, top_k: Optional[int] = None,
             active=None):
    """Decode n_tokens greedily/sampled.

    first_tokens: (B, 1) int32 — the first input token of each sequence.
    start_pos: (B,) int32 — absolute position of that token.
    active: optional (B,) bool — inactive slots keep emitting pad(0) and do
    not advance their cache (engine slot-masking).
    Returns (tokens (B, n_tokens), final cache, final pos).
    """
    b = first_tokens.shape[0]
    key = jax.random.key(0) if key is None else key
    start_pos = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (b,))
    act = jnp.ones((b,), bool) if active is None else active

    def step(carry, k):
        cache, tok, pos = carry
        logits, new_cache = MD.decode_step(params, cfg, cache, tok, pos)
        nxt = sample_logits(k, logits[:, 0], temperature=temperature,
                            top_k=top_k, vocab_size=cfg.vocab_size)
        nxt = jnp.where(act, nxt, 0)
        # inactive slots: keep old cache values
        new_cache = jax.tree.map(
            lambda n, o: jnp.where(
                act.reshape((1, b) + (1,) * (n.ndim - 2)), n, o),
            new_cache, cache)
        return (new_cache, nxt[:, None], pos + act.astype(jnp.int32)), nxt

    keys = jax.random.split(key, n_tokens)
    (cache, _, pos), toks = jax.lax.scan(
        step, (cache, first_tokens, start_pos), keys)
    return toks.T, cache, pos  # (B, n_tokens)


@functools.partial(jax.jit, static_argnames=("cfg", "n_tokens",
                                             "temperature", "top_k"))
def jit_generate(params, cfg, cache, first_tokens, start_pos, n_tokens,
                 key, temperature=0.0, top_k=None):
    return generate(params, cfg, cache, first_tokens, start_pos, n_tokens,
                    key=key, temperature=temperature, top_k=top_k)


# ---------------------------------------------------------------------------
# Output guards + traced-envelope check (request lifecycle hardening)
# ---------------------------------------------------------------------------


def poisoned_slots(logits_np: np.ndarray, live: Sequence[int]) -> List[int]:
    """Cheap host-side NaN/Inf guard on a decode round's emitted logits:
    the live batch rows whose logit vector contains a non-finite value
    (a poisoned output tile). logits_np: (B, V) after squeezing the
    length-1 axis. O(B*V) numpy — the detection cost the engine pays per
    round so corruption becomes a quarantine instead of a silent garbage
    token stream."""
    return [s for s in live
            if not bool(np.isfinite(logits_np[s]).all())]


def states_finite(states, to_host) -> bool:
    """NaN/Inf guard over packed prefill state leaves (float leaves only;
    token/table int leaves can't be poisoned by arithmetic). Each float
    leaf costs one blocking read, made as ``to_host(x, what, bool)``
    (``Engine._to_host``, which counts it)."""
    for leaf in jax.tree.leaves(states):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if not to_host(jnp.isfinite(leaf).all(), "states_finite", bool):
            return False
    return True


def traced_prefill_ok(lens: Sequence[int], block: int,
                      max_lam: Optional[int] = None) -> bool:
    """True iff every member of a packed admit round stays inside the
    certified traced-isqrt envelope: the member's largest lambda is
    tri(ceil(S_r / block)) - 1, which must be <= LTM_TRACED_MAX_LAM for
    the traced block mapping to be exact. Beyond it the engine must take
    the host-map (sequential) path — the traced -> host rung of the
    degradation ladder."""
    cap = M.LTM_TRACED_MAX_LAM if max_lam is None else max_lam
    return all(M.tri(-(-int(s) // block)) - 1 <= cap for s in lens)


# ---------------------------------------------------------------------------
# Packed mixed-position decode (one launch per decode round)
# ---------------------------------------------------------------------------


def round_capacity(needed: int, floor: int = 8) -> int:
    """Bucket a round's live tile count to a static grid size (next power
    of two, floored) so position skew does not recompile every round: at
    most log2(B * S_cache / blk) distinct programs per engine."""
    return max(floor, 1 << max(0, int(needed) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("cfg", "spec"))
def _packed_decode_forward(params, cfg, cache, tokens, pos, tbl, spec):
    return MD.decode_step(params, cfg, cache, tokens, pos, decode_tbl=tbl,
                          decode_spec=spec)


def make_decode_table(kv_lens, slots, *, blk: int, n_members: int,
                      n_slots: int, s_cache: int = 0, window=None):
    """Serve-side decode-table builder — the band-limited variant.

    Delegates to ops.make_decode_table; ``window`` (tokens, scalar or
    per-slot) caps each slot's attended region at its LAST w tokens, so
    per-slot kv_tiles stays near ceil(w / blk) however deep the position —
    the decode-round analogue of the band member (a RowSchedule that keeps
    only its rightmost tiles). Only valid when cache row index == absolute
    position (a non-rolling cache, e.g. a max_len prefix cache serving a
    windowed policy); rolling SWA buffers are already window-sized and
    must keep window=None.
    """
    return attn_ops.make_decode_table(
        kv_lens, slots, blk=blk, n_members=n_members, n_slots=n_slots,
        s_cache=s_cache, window=window)


def decode_step_packed(params, cfg, cache, tokens, pos, kv_lens, slots, *,
                       block: int = 16, impl: str = "scan",
                       n_members: int = 0, capacity: int = 0, window=None):
    """One PACKED decode round: every live slot advances one token in ONE
    launch per attention layer, each attending only its own valid KV
    prefix — sum_r ceil(kv_len_r / blk) tiles instead of the lockstep
    pad-to-max B * S_cache.

    tokens: (B, 1) int32; pos: (B,) int32 (stale entries for retired slots
    are fine — they are not in ``slots``). kv_lens/slots: host lists — live
    slots' valid KV token counts (min(pos + 1, S_cache)) and batch rows.
    n_members/capacity pin the table width / grid bucket (0 = derive:
    B + 1 members, power-of-two capacity). window band-limits each slot to
    its last w tokens (see make_decode_table — non-rolling caches only).
    Returns (logits, new_cache, info) with info the round's tile
    accounting: {"tiles": live tiles, "tiles_padded": n_live * max tiles,
     "capacity": static grid size}.

    Only attention layers change behavior; recurrent mixers decode their
    own slot's state independently either way. Retired slots still run the
    (idempotent) k/v cache rewrite and get zero attention output — the
    engine discards their sampled tokens, so token streams are unaffected.
    """
    b = tokens.shape[0]
    n_members = n_members or b + 1
    # Band-limiting assumes cache row index == absolute position; a
    # rolling SWA cache (layers._decode_qkv writes slot pos % S_cache)
    # breaks that once any slot wraps, silently attending the wrong
    # token subset — reject here, where cfg is known.
    assert cfg.sliding_window is None or window is None, (
        "window= band-limiting is invalid over a rolling sliding-window "
        "cache (rows alias positions mod S_cache); the rolling buffer is "
        "already window-sized — keep window=None")
    # every attention layer shares one cache geometry (cfg-global S_cache)
    s_cache = _attn_cache_len(cfg, cache)
    blk = min(block, s_cache)
    while s_cache % blk:
        blk //= 2
    tbl, needed = attn_ops.make_decode_table(
        kv_lens, slots, blk=blk, n_members=n_members, n_slots=b,
        s_cache=s_cache, window=window)
    capacity = capacity or round_capacity(needed)
    rebucketed = False
    if capacity < needed:
        # A pinned capacity the round outgrew is a RECOVERABLE sizing
        # miss, not a crash: rebucket to the canonical power-of-two grid
        # (one extra compile) and report it so the engine can emit the
        # registered capacity: requested -> rebucketed degrade event.
        capacity = round_capacity(needed)
        rebucketed = True
    spec = attn_ops.DecodeRoundSpec(n_members=n_members, capacity=capacity,
                                    blk=blk, impl=impl)
    logits, new_cache = _packed_decode_forward(
        params, cfg, cache, tokens, jnp.asarray(pos, jnp.int32),
        jnp.asarray(tbl), spec)
    tiles_max = int(np.max(tbl[2, :len(list(kv_lens))])) if kv_lens else 0
    info = {"tiles": needed, "tiles_padded": len(list(kv_lens)) * tiles_max,
            "capacity": capacity, "blk": blk, "rebucketed": rebucketed}
    return logits, new_cache, info


def _attn_cache_len(cfg, cache):
    """S_cache shared by every attention layer's KV leaves — identified by
    the (n_sl, B, S, Hkv, hd) shape signature so recurrent-state leaves of
    the same rank can never be mistaken for KV; cfg.sliding_window caps
    it. This is the single source of truth for the decode-round geometry
    (the engine reads it too, so kv_len clamps cannot drift from the
    actual cache sizing)."""
    for leaf in jax.tree.leaves(cache):
        if leaf.ndim == 5 and leaf.shape[3:] == (cfg.n_kv_heads,
                                                 cfg.head_dim):
            return leaf.shape[2]
    raise ValueError("no attention KV leaves in cache (recurrent-only "
                     "arch cannot take the packed decode path)")


# ---------------------------------------------------------------------------
# Batched ragged prefill (one packed launch for R prompts)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "psched", "attn_impl"))
def _packed_forward(params, cfg, tokens, positions, psched, attn_impl):
    """Jitted packed forward: (1, S_total) tokens + per-request-restarting
    positions -> (hidden, per-layer states). Compiled once per distinct
    packing (psched is static); MoE runs drop-free (serving semantics)."""
    hidden, _, states = MD.forward(
        params, cfg, {"tokens": tokens}, attn_impl=attn_impl, remat=False,
        collect_state=True, positions=positions, packed=psched,
        full_capacity=True)
    return hidden, states


def packed_prefill(params, cfg, prompts, *, block: int = 16,
                   attn_impl: str = "scan", bucket: int = 0):
    """Prefill a ragged prompt batch in ONE packed launch.

    prompts: list of (S_r,) int token arrays (arbitrary mixed lengths).
    Each is zero-padded to a multiple of ``block`` (padding sits at the
    request's causal tail: real tokens never attend to it and its rows are
    never spliced out). Returns (psched, starts, lens, hidden, states):
    request r's tokens occupy packed rows [starts[r], starts[r] + lens[r])
    of hidden and of every (n_sl, 1, S_total, ...) KV state leaf.

    The forward is jitted with the packing STATIC, so every distinct tuple
    of padded lengths compiles (and caches) a new program. ``bucket`` > 0
    rounds each padded length up to a multiple of it, trading a bounded
    amount of extra (inert) tail padding for far fewer distinct shapes —
    set it under compile-bound serving traffic (e.g. bucket = 4 * block).

    Only valid for attention token mixers: recurrent state (mamba/rwkv)
    carries across the packed concatenation and would leak between
    requests — Engine gates on cfg.layer_kinds before calling this.
    """
    assert all(k == "attn" for k in cfg.layer_kinds), (
        "packed_prefill requires attention-only token mixers; recurrent "
        "state would leak across the packed request boundary")
    lens = [int(len(p)) for p in prompts]
    quantum = max(block, -(-bucket // block) * block if bucket else block)
    pads = [-(-s // quantum) * quantum for s in lens]
    starts = list(np.cumsum([0] + pads[:-1]))
    s_total = sum(pads)
    tokens = np.zeros((1, s_total), np.int32)
    positions = np.zeros((s_total,), np.int32)
    for st, pad, p in zip(starts, pads, prompts):
        tokens[0, st:st + len(p)] = np.asarray(p, np.int32)
        positions[st:st + pad] = np.arange(pad)
    psched = attn_ops.make_packed_sched(pads, block=block,
                                        window=cfg.sliding_window)
    hidden, states = _packed_forward(params, cfg, jnp.asarray(tokens),
                                     jnp.asarray(positions), psched,
                                     attn_impl)
    return psched, starts, lens, hidden, states


# ---------------------------------------------------------------------------
# Fused continuous-batching step (admits + live decode slots, one launch)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg", "psched", "spec"))
def _fused_forward(params, cfg, cache, pack_tokens, pack_positions,
                   dec_tokens, pos, tbl, admit_rows, psched, spec):
    """Jitted fused step: compiled once per (packing template, decode
    capacity bucket) — the fused table rides as a traced array so
    positions advancing every round never recompile."""
    return MD.fused_step(params, cfg, cache, pack_tokens, pack_positions,
                         dec_tokens, pos, psched, tbl, spec, admit_rows)


def fused_step(params, cfg, cache, prompts, tokens, pos, kv_lens, slots, *,
               block: int = 16, impl: str = "scan", bucket: int = 0,
               capacity: int = 0):
    """ONE fused engine round: prefill the newly admitted ``prompts``
    (packed block-diagonal members) AND advance every live decode slot
    (row members over its own valid KV prefix) in a single mixed launch
    per attention layer.

    prompts: list of (S_r,) int token feeds to admit (>= 1 — decode-only
    rounds take decode_step_packed instead). tokens: (B, 1) int32 last
    tokens; pos: (B,) int32 (stale entries for slots being admitted /
    retired are fine); kv_lens/slots: host lists for the LIVE decode
    slots, exactly as decode_step_packed takes them. ``bucket`` rounds
    each padded prompt length up to a multiple of it — the length-bucketed
    packing templates that bound the number of distinct compiled programs.
    ``capacity`` optionally pins the total grid; a pin the round outgrew
    is rebucketed (info["rebucketed"]) rather than crashing.

    Returns (logits_admit (A, Vp) f32 — one row per admitted prompt, from
    its last real token; logits_dec (B, Vp) f32 — live slots only, others
    garbage; new_cache — decode KV writes applied, admit KV NOT yet
    spliced; states — per-layer pack k/v for kv_cache.splice_slot;
    psched, starts, lens, info).
    """
    assert all(k == "attn" for k in cfg.layer_kinds), (
        "fused_step requires attention-only token mixers; recurrent state "
        "has no packed-member notion")
    assert len(prompts) >= 1, "fused_step needs at least one admit"
    b = tokens.shape[0]
    s_cache = _attn_cache_len(cfg, cache)
    blk = min(block, s_cache)
    while s_cache % blk:
        blk //= 2
    lens = [int(len(p)) for p in prompts]
    quantum = max(blk, -(-bucket // blk) * blk if bucket else blk)
    pads = [-(-s // quantum) * quantum for s in lens]
    starts = list(np.cumsum([0] + pads[:-1]))
    s_total = sum(pads)
    pack_tokens = np.zeros((1, s_total), np.int32)
    pack_positions = np.zeros((s_total,), np.int32)
    for st, pad, p in zip(starts, pads, prompts):
        pack_tokens[0, st:st + len(p)] = np.asarray(p, np.int32)
        pack_positions[st:st + pad] = np.arange(pad)
    psched = attn_ops.make_packed_sched(pads, block=blk,
                                        window=cfg.sliding_window)
    admit_rows = np.asarray([st + ln - 1 for st, ln in zip(starts, lens)],
                            np.int32)
    n_members = len(pads) + b + 1
    tbl, needed = attn_ops.make_fused_table(
        psched, kv_lens, slots, blk=blk, n_members=n_members, n_slots=b,
        s_cache=s_cache)
    needed_dec = needed - psched.steps
    dec_capacity = round_capacity(needed_dec) if len(list(kv_lens)) else 0
    rebucketed = False
    if capacity:
        if capacity < psched.steps + needed_dec:
            rebucketed = True  # same graceful rebucket as decode_step_packed
        else:
            dec_capacity = capacity - psched.steps
    spec = attn_ops.FusedStepSpec(
        n_members=n_members, capacity=psched.steps + dec_capacity,
        blk=blk, impl=impl)
    logits_admit, logits_dec, new_cache, states = _fused_forward(
        params, cfg, cache, jnp.asarray(pack_tokens),
        jnp.asarray(pack_positions), tokens,
        jnp.asarray(pos, jnp.int32), jnp.asarray(tbl),
        jnp.asarray(admit_rows), psched, spec)
    tiles_max = int(np.max(tbl[2, len(pads):len(pads) + len(list(kv_lens))])
                    ) if len(list(kv_lens)) else 0
    info = {"tiles": needed, "capacity": spec.capacity, "blk": blk,
            "s_pack": s_total, "rebucketed": rebucketed,
            "tiles_padded": psched.steps + len(list(kv_lens)) * tiles_max,
            # the length-bucketed packing template this round compiled
            # under: the padded prompt lengths that, with the capacity,
            # pin the fused program's static shapes. The engine records
            # the distinct set (compile-footprint accounting, persisted
            # across snapshot/restore).
            "template": tuple(int(p) for p in pads)}
    return (logits_admit[0], logits_dec[:, 0], new_cache, states, psched,
            starts, lens, info)
