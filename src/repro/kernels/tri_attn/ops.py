"""Public attention op: schedule-aware triangular-domain flash attention.

``triangular_attention`` is the single entry point the models use. It picks
the schedule kind from the mask parameters, and dispatches between:

  impl='pallas' — the Pallas kernels (kernel.py): compiled on a TPU,
                  interpreted elsewhere (decided at obs.launch's one
                  pallas_call site from the lowering platform).
  impl='scan'   — the pure-XLA LTM scan (scan_impl.py); the dry-run / CPU
                  training path. Differentiable via custom VJP.
  impl='ref'    — the O(S^2)-memory oracle (ref.py); tests only.
  impl='bb'     — the paper's bounding-box baseline Pallas kernel (fwd only).

``packed_prefill_attention`` + ``make_packed_sched`` are the ragged-batch
variant: R requests of mixed lengths concatenated along S, attended
block-diagonally in ONE launch over the core/packing PackedSchedule grid.
It serves the engine's bulk-admission prefill AND — via custom VJP over
the packed dq / dk/dv kernels — ragged document-batch training: jax.grad
issues one packed launch per direction on both the pallas and scan paths.

``packed_decode_attention`` + ``make_decode_table`` + ``DecodeRoundSpec``
are the DECODE-time analogue: one mixed-position decode round per launch,
each live slot attending only its own valid KV prefix. Unlike the prefill
pack the member table is runtime data (positions advance every round), so
it rides as a traced array / scalar-prefetch SMEM operand over a
statically bucketed grid capacity.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tri_attn import kernel as K
from repro.kernels.tri_attn import ref as R
from repro.kernels.tri_attn import scan_impl as SC
from repro.kernels.tri_attn.kernel import (DECODE_NO_EMIT, PackedTriSched,
                                           TriSched)


def platform_impl() -> str:
    """The attention impl for the platform this process computes on:
    the Pallas kernels on a TPU, the XLA scan mirror elsewhere (the CPU
    test suite, where the Pallas interpreter would be far slower)."""
    return "pallas" if jax.default_backend() == "tpu" else "scan"


def make_sched(s_len: int, *, block_q: int, block_k: int, window=None,
               prefix: int = 0) -> TriSched:
    bq = min(block_q, s_len)
    bk = min(block_k, s_len)
    if window is not None or prefix:
        bk = bq = min(bq, bk)  # square tiles for band/prefix domains
    assert s_len % bq == 0 and s_len % bk == 0, (
        f"seq {s_len} not divisible by blocks ({bq}, {bk})")
    if window is not None:
        kind = "band"
    elif prefix:
        kind = "prefix"
    else:
        kind = "ltm"
        bk = bq = min(bq, bk)  # triangular domain also needs square tiles
    return TriSched(kind=kind, n=s_len // bq, bq=bq, bk=bk,
                    window=window, prefix=prefix)


def make_packed_sched(seq_lens, *, block: int, window=None,
                      prefix=0) -> PackedTriSched:
    """Packed ragged-batch schedule for per-request sequence lengths.

    seq_lens: token lengths, each already padded to a multiple of ``block``
    (the engine pads prompts; the packed operand is their concatenation).
    window / prefix may be scalars (applied to every member) or
    per-request sequences. Members degrade exactly like make_sched:
    window -> band, prefix -> prefix-causal, else ltm.
    """
    seq_lens = tuple(int(s) for s in seq_lens)
    r = len(seq_lens)
    windows = tuple(window) if isinstance(window, (list, tuple)) \
        else (window,) * r
    prefixes = tuple(prefix) if isinstance(prefix, (list, tuple)) \
        else (prefix,) * r
    assert len(windows) == r and len(prefixes) == r, (
        f"per-request window/prefix lists must match the batch: "
        f"{len(windows)} windows / {len(prefixes)} prefixes for {r} "
        f"requests")
    members = []
    for s_len, w, p in zip(seq_lens, windows, prefixes):
        assert s_len % block == 0, (
            f"member seq {s_len} not padded to block {block}")
        if w is not None:
            kind = "band"
        elif p:
            kind = "prefix"
        else:
            kind = "ltm"
        members.append(TriSched(kind=kind, n=s_len // block, bq=block,
                                bk=block, window=w, prefix=p))
    return PackedTriSched(members=tuple(members))


@functools.lru_cache(maxsize=None)
def _packed_pallas_attention(psched: PackedTriSched, scale: float):
    """Custom-VJP packed Pallas attention for static (psched, scale):
    jax.grad issues ONE packed_bwd launch per direction (dq row-major,
    dk/dv column-major) over the forward's (7, R) member table — no
    fallback to autodiff through the forward, no pad-to-max."""

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = K.packed_fwd(q, k, v, psched, sm_scale=scale)
        return out

    def attn_fwd(q, k, v):
        out, lse = K.packed_fwd(q, k, v, psched, sm_scale=scale)
        return out, (q, k, v, out, lse)

    def attn_bwd(res, do):
        q, k, v, out, lse = res
        return K.packed_bwd(q, k, v, out, lse, do, psched, sm_scale=scale)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def packed_prefill_attention(q, k, v, psched: PackedTriSched, *,
                             sm_scale=None, impl: str = "scan"):
    """Ragged batched attention over the packed layout (prefill AND train).

    q: (B, H, S_total, D); k, v: (B, Hkv, S_total, D) — every batch row
    shares the same packing (the engine uses B=1). One launch covers all
    requests: sum_r blocks_r grid steps, zero cross-request tiles.
    Differentiable on the 'pallas' and 'scan' paths via custom VJP (packed
    dq / dk/dv launches over the same member table — the ragged
    document-batch training fast path). Returns (B, H, S_total, D).
    """
    b, h, s_len, d = q.shape
    assert s_len == psched.s_total, (
        f"packed operand has {s_len} rows but the schedule covers "
        f"{psched.s_total}")
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    if impl == "pallas":
        return _packed_pallas_attention(psched, scale)(q, k, v)
    if impl == "scan":
        return SC.make_packed_scan_attention(psched, scale)(q, k, v)
    if impl == "ref":
        outs = []
        base = 0
        for m in psched.members:
            s_r = m.n * m.bq
            seg = slice(base, base + s_r)
            outs.append(R.mha_reference(q[:, :, seg], k[:, :, seg],
                                        v[:, :, seg], sm_scale=scale,
                                        window=m.window, prefix=m.prefix))
            base += s_r
        return jnp.concatenate(outs, axis=2)
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# Packed mixed-position decode (one launch per decode round)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodeRoundSpec:
    """STATIC half of a packed decode round (hashable — it is a jit static
    arg). The dynamic half — which slots are live, at which KV lengths —
    is the (5, R) member table built fresh each round by
    ``make_decode_table`` and passed as a traced array, so positions can
    advance every round without recompiling; only a change of capacity
    bucket (or batch geometry) compiles a new program."""

    n_members: int  # table width R: max live slots + 1 (the pad member)
    capacity: int   # static grid size; >= the round's live tiles
    blk: int        # KV tile edge (divides S_cache)
    impl: str = "scan"

    # the dynamic half is the (5, R) member table (make_decode_table):
    # starts / slot / kv_tiles / kv_len / kv_first


def make_decode_table(kv_lens, slots, *, blk: int, n_members: int,
                      n_slots: int, s_cache: int = 0, window=None):
    """Build one decode round's (5, n_members) int32 member table.

    kv_lens[i] is live slot ``slots[i]``'s valid KV prefix in TOKENS
    (min(pos + 1, S_cache) — for rolling sliding-window buffers the valid
    region is always a prefix of the buffer, so one length describes it).
    Unused member columns are empty (0 tiles, skipped by the lambda
    search); the last column is the pad member (slot == n_slots, the
    garbage output row; kv_tiles == DECODE_NO_EMIT so it never emits).

    window (scalar or per-slot sequence, tokens) BAND-limits each member:
    the slot attends only KV tokens [max(0, kv_len - w), kv_len), i.e.
    cache tiles [kv_first // blk, ceil(kv_len / blk)) — at most
    ceil(w / blk) + 1 tiles however deep the position, instead of the full
    ceil(kv_len / blk)-tile prefix. Only valid when cache row index ==
    absolute token position (a NON-rolling cache: a rolling SWA buffer is
    already window-sized and its rows alias positions mod S_cache, so its
    members must keep window=None).

    Returns (table, needed) with ``needed`` the live tile count —
    sum_r member tiles, the number the lockstep pad-to-max round would
    inflate to n_live * max_r tiles.
    """
    kv_lens = [int(s) for s in kv_lens]
    slots = [int(s) for s in slots]
    windows = list(window) if isinstance(window, (list, tuple)) \
        else [window] * len(kv_lens)
    assert len(windows) == len(kv_lens), (
        f"per-slot window list must match the round: {len(windows)} "
        f"windows for {len(kv_lens)} live slots")
    assert len(kv_lens) == len(slots) <= n_members - 1, (
        f"{len(kv_lens)} live members need table width >= "
        f"{len(kv_lens) + 1}, got {n_members}")
    assert all(s >= 1 for s in kv_lens), "live slots attend >= 1 token"
    assert all(w is None or w >= 1 for w in windows), (
        "band-limited slots attend >= 1 token windows")
    # A kv_len beyond the cache would be silently corrupted downstream
    # (the kernel clamps the tile INDEX in-bounds but the token mask
    # would keep admitting the phantom tail) — reject it here, where the
    # lengths are still host ints. Callers with a rolling SWA buffer must
    # pre-clamp to min(pos + 1, S_cache).
    if s_cache:
        assert max(kv_lens) <= s_cache, (
            f"kv_lens {kv_lens} exceed the KV cache ({s_cache} rows); "
            f"clamp to min(pos + 1, S_cache)")
    cols, cur = [], 0
    for kl, sl, w in zip(kv_lens, slots, windows):
        first = 0 if w is None else max(0, kl - int(w))
        t = -(-kl // blk) - first // blk
        cols.append((cur, sl, t, kl, first))
        cur += t
    while len(cols) < n_members - 1:
        cols.append((cur, 0, 0, 0, 0))
    cols.append((cur, n_slots, DECODE_NO_EMIT, 0, 0))
    return np.asarray(cols, np.int32).T.copy(), cur


def packed_decode_attention(q, k_cache, v_cache, tbl,
                            spec: DecodeRoundSpec, *, sm_scale=None):
    """Single-token attention for a whole mixed-position decode round.

    q: (B, H, D) rotated queries (one new token per slot); k_cache,
    v_cache: (B, S_cache, Hkv, D) native cache layout with the new token
    already written. Each live slot attends ONLY its own valid KV prefix:
    sum_r ceil(kv_len_r / blk) tiles in ONE launch, vs the lockstep
    einsum's B * S_cache pad-to-max. Slots without a live member return
    zeros. impl: 'pallas' (member table via scalar-prefetch SMEM),
    'scan' (CPU lax.scan mirror), 'ref' (masked-einsum oracle).
    """
    b, h, d = q.shape
    s_cache = k_cache.shape[1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    assert tbl.shape == (5, spec.n_members), (tbl.shape, spec.n_members)
    assert s_cache % spec.blk == 0, (s_cache, spec.blk)
    assert spec.capacity >= 1
    if spec.impl == "pallas":
        full = K.packed_decode_fwd(q, k_cache, v_cache, tbl,
                                   capacity=spec.capacity, blk=spec.blk,
                                   sm_scale=scale)
        covered = _covered_slots(tbl, b)
        return jnp.where(covered[:, None, None], full[:b], 0)
    if spec.impl == "scan":
        return SC.packed_decode_scan(q, k_cache, v_cache, tbl,
                                     capacity=spec.capacity, blk=spec.blk,
                                     n_members=spec.n_members, scale=scale)
    if spec.impl == "ref":
        return _decode_ref(q, k_cache, v_cache, tbl, scale)
    raise ValueError(f"unknown impl {spec.impl!r}")


def _decode_ref(q, k_cache, v_cache, tbl, scale):
    """Masked-einsum oracle of a decode round over its (5, R) table."""
    b = q.shape[0]
    kv_len = _slot_kv_lens(tbl, b)
    kv_first = _slot_kv_firsts(tbl, b)
    srng = jnp.arange(k_cache.shape[1])[None, :]
    valid = (srng >= kv_first[:, None]) & (srng < kv_len[:, None])
    out = _masked_decode_einsum(q, k_cache, v_cache, valid, scale)
    return jnp.where(kv_len[:, None, None] > 0, out, 0)


def _covered_slots(tbl, b):
    """(B,) bool: slots owned by some live member (scatter-max over the
    table; the pad member's slot == B lands in the dropped extra row)."""
    return jnp.zeros((b + 1,), bool).at[tbl[1]].max(tbl[3] > 0)[:b]


def _slot_kv_lens(tbl, b):
    """(B,) int32 valid KV end per slot (0 where no live member)."""
    return jnp.zeros((b + 1,), jnp.int32).at[tbl[1]].max(tbl[3])[:b]


def _slot_kv_firsts(tbl, b):
    """(B,) int32 valid KV start per slot (band-limited members; 0 when
    the member attends its whole prefix or the slot has no member)."""
    return jnp.zeros((b + 1,), jnp.int32).at[tbl[1]].max(tbl[4])[:b]


def _masked_decode_einsum(q, k_cache, v_cache, valid, scale):
    """Lockstep-style full-cache masked attention (the decode oracle):
    q (B, H, D), caches (B, S, Hkv, D), valid (B, S) -> (B, H, D)."""
    b, h, d = q.shape
    hkv = k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   k_cache.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :], s, R.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused continuous-batching step (one launch: prefill members + decode rows)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedStepSpec:
    """STATIC half of a fused continuous-batching step (hashable jit
    static arg). One launch carries BOTH the round's newly admitted
    prompts (triangular/band/prefix members over the packed operand) and
    every live decode slot (single-row members over the KV cache). The
    dynamic half is the (8, n_members) fused table from
    ``make_fused_table``; capacity = psched.steps + the bucketed decode
    capacity, so rounds sharing a packing template and a decode bucket
    share one compiled program."""

    n_members: int  # fused table width: prefill members + decode + pad
    capacity: int   # static grid size >= prefill steps + live decode tiles
    blk: int        # tile edge (divides S_pack and S_cache)
    impl: str = "scan"


def make_fused_table(psched: PackedTriSched, kv_lens, slots, *, blk: int,
                     n_members: int, n_slots: int, s_cache: int = 0,
                     window=None):
    """Build one fused step's (8, n_members) int32 member table.

    Prefill columns come first (one per psched member, translated from
    the (7, R) packed-prefill table), then the decode columns
    (make_decode_table rebased by psched.steps), then the shared pad
    member. Row ABI (kernel.py `_fused_step_kernel`):

      0 starts | 1 kind (0=prefill, 1=decode/pad) | 2 n|kv_tiles |
      3 w_b|kv_len | 4 p_b|kv_first | 5 q_off|slot | 6 win|0 | 7 pre|0

    Returns (table, needed_total) with needed_total = psched.steps +
    live decode tiles — the minimum grid the round actually uses.
    """
    pt = np.asarray(psched.table())
    r_p = pt.shape[1]
    assert r_p >= 1, "fused step needs at least one prefill member"
    assert all(m.bq == blk and m.bk == blk for m in psched.members), (
        "fused step requires uniform square tiles == blk")
    dt, needed_dec = make_decode_table(
        list(kv_lens), list(slots), blk=blk, n_members=n_members - r_p,
        n_slots=n_slots, s_cache=s_cache if len(list(kv_lens)) else 0,
        window=window)
    cols = []
    for c in range(r_p):
        t = pt[:, c]
        cols.append((t[0], 0, t[2], t[3], t[4], t[1], t[5], t[6]))
    for c in range(dt.shape[1]):
        dc = dt[:, c]
        cols.append((psched.steps + dc[0], 1, dc[2], dc[3], dc[4],
                     dc[1], 0, 0))
    return np.asarray(cols, np.int32).T.copy(), psched.steps + needed_dec


def fused_step_attention(q_pack, k_pack, v_pack, q_dec, k_cache, v_cache,
                         tbl, psched: PackedTriSched, spec: FusedStepSpec,
                         *, sm_scale=None):
    """One attention launch for a whole continuous-batching engine step.

    q_pack: (1, H, S_pack, D) packed admitted prompts (k_pack/v_pack
    (1, Hkv, S_pack, D) their rotated keys/values); q_dec: (B, H, D) one
    new token per slot; k_cache/v_cache: (B, S_cache, Hkv, D) with the
    decode tokens already written. Prefill members attend
    block-diagonally within the pack; decode members attend their own
    valid cache prefix — all from ONE member table in one launch.
    Returns (out_pack (1, H, S_pack, D), out_dec (B, H, D)); slots
    without a live decode member return zeros.
    """
    b, h, d = q_dec.shape
    s_pack = q_pack.shape[2]
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    assert tbl.shape == (8, spec.n_members), (tbl.shape, spec.n_members)
    assert s_pack == psched.s_total, (s_pack, psched.s_total)
    assert k_cache.shape[1] % spec.blk == 0, (k_cache.shape, spec.blk)
    assert spec.capacity >= psched.steps, (spec.capacity, psched.steps)
    if spec.impl == "pallas":
        o_pack, o_dec = K.fused_step_fwd(
            q_pack, k_pack, v_pack, q_dec, k_cache, v_cache, tbl,
            capacity=spec.capacity, blk=spec.blk,
            n_pack_tiles=s_pack // spec.blk, sm_scale=scale)
        covered = _fused_covered_slots(tbl, b)
        return (o_pack[:, :, :s_pack],
                jnp.where(covered[:, None, None], o_dec[:b], 0))
    if spec.impl == "scan":
        return SC.fused_step_scan(
            q_pack, k_pack, v_pack, q_dec, k_cache, v_cache, tbl,
            capacity=spec.capacity, blk=spec.blk,
            n_members=spec.n_members, scale=scale)
    if spec.impl == "ref":
        # the two halves' oracles: per-member prefill and, over the decode
        # columns re-read as a (5, R) decode table, the masked einsum
        r_p = len(psched.members)
        dec_tbl = jnp.stack([tbl[0], tbl[5], tbl[2], tbl[3], tbl[4]])[:, r_p:]
        return (packed_prefill_attention(q_pack, k_pack, v_pack, psched,
                                         sm_scale=scale, impl="ref"),
                _decode_ref(q_dec, k_cache, v_cache, dec_tbl, scale))
    raise ValueError(f"unknown impl {spec.impl!r}")


def _fused_covered_slots(tbl, b):
    """(B,) bool: slots owned by a live DECODE member of the fused table
    (prefill columns scatter into the dropped extra row)."""
    return jnp.zeros((b + 1,), bool).at[
        jnp.where(tbl[1] == 1, tbl[5], b)].max(tbl[3] > 0)[:b]


@functools.lru_cache(maxsize=None)
def _pallas_attention(sched: TriSched, scale: float):
    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = K.fwd(q, k, v, sched, sm_scale=scale)
        return out

    def attn_fwd(q, k, v):
        out, lse = K.fwd(q, k, v, sched, sm_scale=scale)
        return out, (q, k, v, out, lse)

    def attn_bwd(res, do):
        q, k, v, out, lse = res
        return K.bwd(q, k, v, out, lse, do, sched, sm_scale=scale)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def triangular_attention(q, k, v, *, window=None, prefix: int = 0,
                         sm_scale=None, impl: str = "scan",
                         block_q: int = 256, block_k: int = 256):
    """Causal (optionally windowed / prefix-causal) attention.

    q: (B, H, S, D); k, v: (B, Hkv, S, D), H % Hkv == 0. Returns (B, H, S, D).
    """
    b, h, s_len, d = q.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    if impl == "ref":
        return R.mha_reference(q, k, v, sm_scale=scale, window=window,
                               prefix=prefix)
    sched = make_sched(s_len, block_q=block_q, block_k=block_k,
                       window=window, prefix=prefix)
    if impl == "pallas":
        return _pallas_attention(sched, scale)(q, k, v)
    if impl == "scan":
        return SC.make_scan_attention(sched, scale)(q, k, v)
    if impl == "bb":
        out, _ = K.fwd_bb(q, k, v, sched, sm_scale=scale)
        return out
    raise ValueError(f"unknown impl {impl!r}")
