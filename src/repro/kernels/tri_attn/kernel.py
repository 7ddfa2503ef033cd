"""Pallas TPU kernels: flash attention over triangular-domain 1-D grids.

The paper's g(lambda) becomes the BlockSpec index_map: the forward (and dq
backward) iterate a 1-D grid of T = tri(n) steps enumerated ROW-major (the
LTM order), the dk/dv backward iterates COLUMN-major (cm_map) so per-column
accumulators stay resident in VMEM scratch. Wasted tiles: zero off-diagonal
(vs. the BB baseline's n(n-1)/2), only intra-tile masking on boundary tiles
remains — exactly the paper's O(n^2) -> O(n) claim at tile granularity.

Schedules: 'ltm' (causal), 'band' (sliding window, beyond-paper), 'prefix'
(VLM prefix-causal, beyond-paper). 'bb' is the paper's bounding-box baseline
(2-D grid + block-level guard). PackedTriSched/packed_fwd extend the same
machinery to the CONCATENATION of R ragged requests: one 1-D grid of
sum_r blocks_r steps whose (7, R) member table rides in scalar-prefetch
SMEM (core/packing.py supplies the O(log R) request search).
packed_fwd's training counterpart packed_bwd walks the SAME member table
twice (dq row-major, dk/dv column-major) so jax.grad through a ragged
document batch is one launch per direction. packed_decode_fwd is the
single-token variant — one mixed-position decode round per launch, the
(5, R) RUNTIME member table (incl. band-limited kv_first) in
scalar-prefetch SMEM over a bucketed static capacity.

All kernels accumulate in f32 VMEM scratch and are validated in interpret
mode against ref.py (tests/test_kernels_tri_attn.py);
tests/test_chip_compile.py compiles them for a described v5e. TPU notes:
block_q and block_k should be multiples of 128 (MXU). Mosaic needs the
last two dims of every block to be multiples of (8, 128) or the array's
own, so per-row softmax stats travel as (B, H, S, 1), decode queries as
(B, H, 1, D), and the decode KV cache is read through a free
(B, S_cache, Hkv * D) view whose blocks are one head wide: decode and the
fused step need head_dim % 128 == 0 on the chip.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mapping as M
from repro.obs import launch as OBS

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Schedule parameterization shared by fwd / dq / dkv kernels
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TriSched:
    """Static schedule metadata for one attention call (bq == bk required
    for triangular/band kinds so the tile domain is square)."""

    kind: str  # 'ltm' | 'band' | 'prefix'
    n: int  # tiles per side
    bq: int
    bk: int
    window: Optional[int] = None  # tokens (band)
    prefix: int = 0  # tokens (prefix)

    def __post_init__(self):
        assert self.kind in ("ltm", "band", "prefix")
        if self.kind == "band":
            assert self.window is not None and self.window >= 1
            assert self.bq == self.bk

    @property
    def w_b(self) -> int:
        """Band width in tiles: tile j needed iff exists q,k in tiles with
        0 <= q-k < window  =>  j >= i - ((window-2)//bk + 1)."""
        if self.window is None:
            return self.n
        return min((self.window - 2) // self.bk + 2, self.n)

    @property
    def p_b(self) -> int:
        return -(-self.prefix // self.bk) if self.prefix else 0

    # ---- row-major enumeration (forward, dq) -----------------------------
    @property
    def rm_steps(self) -> int:
        if self.kind == "ltm":
            return M.tri(self.n)
        if self.kind == "band":
            return M.band_blocks(self.n, self.w_b)
        return M.prefix_full_blocks(self.n, self.p_b)

    def rm_map(self, lam):
        if self.kind == "ltm":
            return M.ltm_map(lam)
        if self.kind == "band":
            return M.band_map(lam, self.w_b)
        return M.prefix_full_map(lam, self.n, self.p_b)

    def rm_first_col(self, i):
        if self.kind == "band":
            return jnp.maximum(0, i - self.w_b + 1)
        return i * 0

    def rm_last_col(self, i):
        if self.kind == "prefix":
            return jnp.maximum(i, self.p_b - 1)
        return i

    # ---- column-major enumeration (dk/dv) --------------------------------
    @property
    def cm_steps(self) -> int:
        return self.rm_steps  # same domain, different order

    def cm_map(self, lam):
        if self.kind == "ltm":
            return M.cm_map(lam, self.n)
        if self.kind == "band":
            return M.band_cm_map(lam, self.n, self.w_b)
        return M.prefix_cm_map(lam, self.n, self.p_b)

    def cm_first_row(self, j):
        if self.kind == "prefix":
            return jnp.where(j < self.p_b, 0, j)
        return j

    def cm_last_row(self, j):
        if self.kind == "band":
            return jnp.minimum(j + self.w_b - 1, self.n - 1)
        return jnp.full_like(j, self.n - 1) if not isinstance(j, int) else self.n - 1


def _token_mask(sched: TriSched, i, j, bq, bk):
    """(bq, bk) boolean mask for tile (i, j): True = attend."""
    qp = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kp = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = kp <= qp
    if sched.window is not None:
        m &= (qp - kp) < sched.window
    if sched.prefix:
        m |= kp < sched.prefix
    return m


# ---------------------------------------------------------------------------
# Packed multi-request schedule (ragged prefill) — core/packing.py lifted to
# token-mask level. All members share one square block edge; the packed
# operand is the concatenation of the members' sequences along S.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackedTriSched:
    """Static metadata for ONE packed ragged-attention launch.

    members[r] describes request r's own domain (kind/n/window/prefix, all
    in that request's local coordinates). Request r's tokens occupy packed
    rows [tok_offsets[r], tok_offsets[r+1]); its tiles occupy packed grid
    steps [offsets[r], offsets[r+1]) of the single 1-D lambda grid.
    """

    members: tuple  # Tuple[TriSched, ...]

    def __post_init__(self):
        assert self.members, "packed schedule needs at least one member"
        blk = self.members[0].bq
        for m in self.members:
            assert m.bq == m.bk == blk, (
                "packed members must share one square block edge")

    @property
    def blk(self) -> int:
        return self.members[0].bq

    @property
    def steps(self) -> int:
        return sum(m.rm_steps for m in self.members)

    @property
    def total_tiles(self) -> int:
        return sum(m.n for m in self.members)

    @property
    def s_total(self) -> int:
        return self.total_tiles * self.blk

    @property
    def windows(self) -> tuple:
        """Per-request window in TOKENS; 0 = unwindowed."""
        return tuple(m.window or 0 for m in self.members)

    @property
    def prefixes(self) -> tuple:
        """Per-request bidirectional prefix in TOKENS; 0 = none."""
        return tuple(m.prefix for m in self.members)

    def table(self):
        """(7, R) int32 member table — the ONLY dynamic state the packed
        kernel needs, shipped to SMEM via scalar prefetch (index_maps must
        not capture constants). Rows 0/1 are the kernel-layer mirror of
        core PackedSchedule.offsets/row_offsets (same cumulative layout,
        see core/packing.py); rows 5/6 add the token-level mask params the
        block-coordinate core has no business knowing. Rows:
          0 starts   cumulative block offsets (offsets[:-1])
          1 rows     cumulative tile-row offsets into the packed operand
          2 n        member tiles per side
          3 w_b      band-family width in tiles (== n for unbanded)
          4 p_b      prefix width in tiles (0 = band family)
          5 win      window in tokens (0 = unwindowed)
          6 pre      prefix in tokens (0 = none)
        """
        import numpy as np

        starts, rows = [0], [0]
        for m in self.members:
            starts.append(starts[-1] + m.rm_steps)
            rows.append(rows[-1] + m.n)
        cols = [(s, t, m.n, m.w_b, m.p_b, w, p)
                for s, t, m, w, p in zip(starts[:-1], rows[:-1], self.members,
                                         self.windows, self.prefixes)]
        return np.asarray(cols, np.int32).T.copy()


class _TableRow:
    """Scalar-indexable view of one row of the member table; adapts both a
    (7, R) array and a Pallas SMEM Ref to packing's ``starts[mid]`` API."""

    def __init__(self, tbl, row: int):
        self._tbl, self._row = tbl, row

    def __getitem__(self, idx):
        return self._tbl[self._row, idx]


def _packed_decode(lam, tbl, n_requests: int):
    """lambda + member table -> (r, i, j, q_row, k_row); tbl is the (7, R)
    table as array or SMEM ref. O(log R) search + O(1) map (core/packing)."""
    from repro.core import packing as PK

    r = PK.request_from_starts(lam, _TableRow(tbl, 0), n_requests)
    local = lam - tbl[0, r]
    i, j = PK.member_map_params(local, tbl[2, r], tbl[3, r], tbl[4, r])
    return r, i, j, tbl[1, r] + i, tbl[1, r] + j


def _packed_token_mask(i, j, blk, win, pre):
    """(blk, blk) mask for one member tile (i, j): causal + the member's
    window/prefix (request-LOCAL token positions; win/pre traced scalars)."""
    qp = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
    kp = j * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    m = kp <= qp
    m &= (qp - kp) < jnp.where(win > 0, win, jnp.int32(2 ** 30))
    m |= kp < pre
    return m


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                sched: TriSched, scale: float):
    lam = pl.program_id(2)
    i, j = sched.rm_map(lam)

    @pl.when(j == sched.rm_first_col(i))
    def _init():
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_token_mask(sched, i, j, sched.bq, sched.bk), s, MASK_VALUE)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == sched.rm_last_col(i))
    def _emit():
        l = l_s[...]
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def fwd(q, k, v, sched: TriSched, *, sm_scale=None):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D). Returns (out, lse) with lse
    (B, H, S, 1): the trailing unit dim keeps each row block a (bq, 1)
    column, which Mosaic tiles (a (1, 1, bq) block is refused)."""
    b, h, s_len, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    bq, bk, n = sched.bq, sched.bk, sched.n
    assert n * bq == s_len and n * bk == s_len

    grid = (b, h, sched.rm_steps)
    rm_i = lambda lam: sched.rm_map(lam)[0]
    rm_j = lambda lam: sched.rm_map(lam)[1]
    kernel = functools.partial(_fwd_kernel, sched=sched, scale=scale)
    out, lse = OBS.instrumented_pallas_call(
        kernel,
        meta=OBS.meta_from_trisched("tri_attn.fwd", sched, impl="pallas",
                                    cells=b * h, grid=grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, rm_j(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, rm_j(lam), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Packed forward: ONE 1-D grid over the concatenation of R ragged requests.
# The per-request binary search + both closed-form member maps run on the
# scalar core each grid step (O(log R) + O(1)); on real TPU the offset
# tables could move to scalar-prefetch SMEM (PrefetchScalarGridSpec), but
# for R <= slot counts the baked-constant gathers are equivalent.
# ---------------------------------------------------------------------------


def _packed_fwd_kernel(tbl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s, *, psched: PackedTriSched,
                       scale: float):
    from repro.core import packing as PK

    lam = pl.program_id(2)
    r, i, j, _, _ = _packed_decode(lam, tbl_ref, len(psched.members))
    first_col = PK.first_col_params(i, tbl_ref[3, r])
    last_col = PK.last_col_params(i, tbl_ref[4, r])

    @pl.when(j == first_col)
    def _init():
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(
        _packed_token_mask(i, j, psched.blk, tbl_ref[5, r], tbl_ref[6, r]),
        s, MASK_VALUE)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == last_col)
    def _emit():
        l = l_s[...]
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def packed_fwd(q, k, v, psched: PackedTriSched, *, sm_scale=None):
    """Ragged batched prefill in ONE launch.

    q: (B, H, S_total, D); k, v: (B, Hkv, S_total, D) — all requests'
    sequences concatenated along S (each padded to a multiple of blk).
    Grid is (B, H, sum_r member_blocks): zero interior waste, no
    cross-request tiles. The (7, R) member table rides in via scalar
    prefetch (SMEM), so index_maps and body share one O(log R) decode.
    Returns (out, lse) in the packed layout.
    """
    import numpy as np

    b, h, s_len, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    assert s_len == psched.s_total, (s_len, psched.s_total)
    blk = psched.blk
    n_req = len(psched.members)
    tbl = np.ascontiguousarray(psched.table())

    def q_spec(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    def kv_spec(b_, h_, lam, tbl_):
        _, _, _, _, k_row = _packed_decode(lam, tbl_, n_req)
        return (b_, h_ // g, k_row, 0)

    def lse_spec(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    kernel = functools.partial(_packed_fwd_kernel, psched=psched, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, psched.steps),
        in_specs=[
            pl.BlockSpec((1, 1, blk, d), q_spec),
            pl.BlockSpec((1, 1, blk, d), kv_spec),
            pl.BlockSpec((1, 1, blk, d), kv_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, d), q_spec),
            pl.BlockSpec((1, 1, blk, 1), lse_spec),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
    )
    out, lse = OBS.instrumented_pallas_call(
        kernel,
        meta=OBS.meta_from_packed("tri_attn.packed_fwd", psched,
                                  impl="pallas", cells=b * h,
                                  grid=(b, h, psched.steps)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s_len, 1), jnp.float32),
        ],
    )(tbl, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Packed backward: the training-side counterpart of packed_fwd. dq re-walks
# the ROW-major packed grid (same enumeration as the forward, per-row dq
# accumulator); dk/dv walk the COLUMN-major enumeration of every member
# (core/packing.member_cm_map_params) so per-column accumulators stay
# resident in VMEM scratch across the member's rows. Both directions share
# the forward's (7, R) member table — rm_steps == cm_steps per member, so
# the cumulative ``starts`` row delegates identically.
# ---------------------------------------------------------------------------


def _packed_decode_cm(lam, tbl, n_requests: int):
    """Column-major packed decode: lambda + (7, R) table ->
    (r, i, j, q_row, k_row). Same O(log R) search as _packed_decode; the
    member map is the column-major two-family closed form."""
    from repro.core import packing as PK

    r = PK.request_from_starts(lam, _TableRow(tbl, 0), n_requests)
    local = lam - tbl[0, r]
    i, j = PK.member_cm_map_params(local, tbl[2, r], tbl[3, r], tbl[4, r])
    return r, i, j, tbl[1, r] + i, tbl[1, r] + j


def _packed_dq_kernel(tbl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_s, *, n_requests: int, blk: int,
                      scale: float):
    from repro.core import packing as PK

    lam = pl.program_id(2)
    r, i, j, _, _ = _packed_decode(lam, tbl_ref, n_requests)

    @pl.when(j == PK.first_col_params(i, tbl_ref[3, r]))
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_packed_token_mask(i, j, blk, tbl_ref[5, r], tbl_ref[6, r]),
                  s, MASK_VALUE)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_s[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(j == PK.last_col_params(i, tbl_ref[4, r]))
    def _emit():
        dq_ref[0, 0] = dq_s[...].astype(dq_ref.dtype)


def _packed_dkv_kernel(tbl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                       n_requests: int, blk: int, scale: float):
    from repro.core import packing as PK

    lam = pl.program_id(2)
    r, i, j, _, _ = _packed_decode_cm(lam, tbl_ref, n_requests)

    @pl.when(i == PK.cm_first_row_params(j, tbl_ref[4, r]))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_packed_token_mask(i, j, blk, tbl_ref[5, r], tbl_ref[6, r]),
                  s, MASK_VALUE)
    p = jnp.exp(s - lse)  # (blk, blk)
    dv_s[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == PK.cm_last_row_params(j, tbl_ref[2, r], tbl_ref[3, r]))
    def _emit():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def packed_bwd(q, k, v, out, lse, do, psched: PackedTriSched, *,
               sm_scale=None):
    """Packed ragged backward: (dq, dk, dv) for a whole mixed-length batch
    in ONE launch per direction (dq row-major, dk/dv column-major — the
    same two 1-D grids the per-domain ``bwd`` uses, lifted to the packed
    member table). dk/dv are group-summed to k/v's kv-head count. Replaces
    R per-document pad-to-max backward launches: sum_r blocks_r grid steps
    per direction, zero cross-request tiles."""
    import numpy as np

    b, h, s_len, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    assert s_len == psched.s_total, (s_len, psched.s_total)
    blk = psched.blk
    n_req = len(psched.members)
    tbl = np.ascontiguousarray(psched.table())
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def rm_q(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    def rm_kv(b_, h_, lam, tbl_):
        _, _, _, _, k_row = _packed_decode(lam, tbl_, n_req)
        return (b_, h_ // g, k_row, 0)

    def rm_row(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    dq = OBS.instrumented_pallas_call(
        functools.partial(_packed_dq_kernel, n_requests=n_req, blk=blk,
                          scale=scale),
        meta=OBS.meta_from_packed("tri_attn.packed_bwd_dq", psched,
                                  impl="pallas", cells=b * h,
                                  grid=(b, h, psched.steps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, psched.steps),
            in_specs=[
                pl.BlockSpec((1, 1, blk, d), rm_q),
                pl.BlockSpec((1, 1, blk, d), rm_kv),
                pl.BlockSpec((1, 1, blk, d), rm_kv),
                pl.BlockSpec((1, 1, blk, d), rm_q),
                pl.BlockSpec((1, 1, blk, 1), rm_row),
                pl.BlockSpec((1, 1, blk, 1), rm_row),
            ],
            out_specs=pl.BlockSpec((1, 1, blk, d), rm_q),
            scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(tbl, q, k, v, do, lse, delta)

    def cm_q(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode_cm(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    def cm_kv(b_, h_, lam, tbl_):
        _, _, _, _, k_row = _packed_decode_cm(lam, tbl_, n_req)
        return (b_, h_ // g, k_row, 0)

    def cm_row(b_, h_, lam, tbl_):
        _, _, _, q_row, _ = _packed_decode_cm(lam, tbl_, n_req)
        return (b_, h_, q_row, 0)

    def cm_out(b_, h_, lam, tbl_):
        _, _, _, _, k_row = _packed_decode_cm(lam, tbl_, n_req)
        return (b_, h_, k_row, 0)

    dk_ph, dv_ph = OBS.instrumented_pallas_call(
        functools.partial(_packed_dkv_kernel, n_requests=n_req, blk=blk,
                          scale=scale),
        meta=OBS.meta_from_packed("tri_attn.packed_bwd_dkv", psched,
                                  impl="pallas", cells=b * h,
                                  grid=(b, h, psched.steps)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, psched.steps),
            in_specs=[
                pl.BlockSpec((1, 1, blk, d), cm_q),
                pl.BlockSpec((1, 1, blk, d), cm_kv),
                pl.BlockSpec((1, 1, blk, d), cm_kv),
                pl.BlockSpec((1, 1, blk, d), cm_q),
                pl.BlockSpec((1, 1, blk, 1), cm_row),
                pl.BlockSpec((1, 1, blk, 1), cm_row),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, blk, d), cm_out),
                pl.BlockSpec((1, 1, blk, d), cm_out),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, d), jnp.float32),
                pltpu.VMEM((blk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_len, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s_len, d), v.dtype),
        ],
    )(tbl, q, k, v, do, lse, delta)

    if g > 1:  # sum per-q-head partials into kv heads
        dk = dk_ph.reshape(b, hkv, g, s_len, d).sum(axis=2).astype(k.dtype)
        dv = dv_ph.reshape(b, hkv, g, s_len, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_ph, dv_ph
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Packed mixed-position DECODE: one 1-D grid per decode round over the
# concatenation of every active slot's valid KV region (core/packing's
# decode_round lifted to the kernel). Unlike the prefill table (baked
# constants — the packing is static per compile), the decode table is
# RUNTIME data: positions advance every round, so the (5, R) member table
# rides in as a scalar-prefetch SMEM operand and the grid is padded to a
# static bucketed capacity. Rows:
#   0 starts    cumulative tile offsets per member (ascending, starts[0]=0)
#   1 slot      batch row of the member's KV cache / query / output
#   2 kv_tiles  member tiles (emit at j == kv_tiles - 1); empty members
#               (retired slots) carry 0, the pad member DECODE_NO_EMIT
#   3 kv_len    valid KV END in tokens (token mask kpos < kv_len); 0 = pad
#   4 kv_first  valid KV START in tokens (0 = attend the whole prefix; a
#               BAND-limited member attends cache tiles
#               [kv_first // blk, ceil(kv_len / blk)) and tokens
#               [kv_first, kv_len) — the decode-round member of a sliding
#               window over a non-rolling cache, so per-slot kv_tiles is
#               capped near ceil(window / blk) however deep the position)
# Convention: the LAST member is always the pad member owning the grid
# steps [needed, capacity); its slot is n_slots (the virtual garbage row
# of the (B+1)-row output) and it never inits state destructively for a
# live slot nor emits (kv_tiles sentinel).
# ---------------------------------------------------------------------------


DECODE_NO_EMIT = 2 ** 30  # pad-member kv_tiles sentinel: emit never fires


def _decode_member(lam, tbl, n_members: int):
    """lambda + (5, R) decode table ->
    (r, slot, j, kv_tiles, kv_len, kv_first).

    j is the member-local KV tile (RowSchedule members are single rows, so
    the local lambda IS the column — no closed-form map needed); the cache
    tile it reads is kv_first // blk + j. tbl may be a jnp array or a
    Pallas SMEM ref."""
    from repro.core import packing as PK

    r = PK.request_from_starts(lam, _TableRow(tbl, 0), n_members)
    return (r, tbl[1, r], lam - tbl[0, r], tbl[2, r], tbl[3, r],
            tbl[4, r])


def _packed_decode_kernel(tbl_ref, q_ref, k_ref, v_ref, o_ref,
                          m_s, l_s, acc_s, *, n_members: int, blk: int,
                          scale: float):
    lam = pl.program_id(1)
    _, _, j, kv_tiles, kv_len, kv_first = _decode_member(lam, tbl_ref,
                                                         n_members)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)  # (1, d)
    k = k_ref[0].astype(jnp.float32)     # (blk, d): one kv head's columns
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = (kv_first // blk + j) * blk + jax.lax.broadcasted_iota(
        jnp.int32, (1, blk), 1)
    s = jnp.where((kpos >= kv_first) & (kpos < kv_len), s, MASK_VALUE)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(j == kv_tiles - 1)
    def _emit():
        o_ref[0, 0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def packed_decode_fwd(q, k, v, tbl, *, capacity: int, blk: int,
                      sm_scale=None):
    """One packed launch for a whole mixed-position decode round.

    q: (B, H, D) — each slot's single rotated query; k, v: (B, S_cache,
    Hkv, D) — the NATIVE decode-cache layout (no transposes on the hot
    path; the kernel reads it through the free (B, S_cache, Hkv * D)
    view, one head's D columns per block), new token already written.
    tbl: (5, R) runtime member table (ops.make_decode_table). Grid is
    (H, capacity): sum_r kv_tiles_r live
    steps + masked pad steps, vs the lockstep einsum's B * S_cache work.
    Band-limited members (kv_first > 0) read only cache tiles
    [kv_first // blk, ceil(kv_len / blk)). Returns (B + 1, H, D): row B is
    the pad member's garbage row — callers slice [:B] and mask by the
    member table's coverage.
    """
    b, h, d = q.shape
    s_cache, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    assert s_cache % blk == 0, (s_cache, blk)
    cache_tiles = s_cache // blk
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    n_members = tbl.shape[1]

    def q_spec(h_, lam, tbl_):
        _, slot, _, _, _, _ = _decode_member(lam, tbl_, n_members)
        return (jnp.minimum(slot, b - 1), h_, 0, 0)

    def kv_spec(h_, lam, tbl_):
        _, slot, j, _, _, kv_first = _decode_member(lam, tbl_, n_members)
        return (jnp.minimum(slot, b - 1),
                jnp.minimum(kv_first // blk + j, cache_tiles - 1),
                h_ // g)

    def o_spec(h_, lam, tbl_):
        # pad member's slot == b: the extra garbage row, so pad steps can
        # never flush stale VMEM over a live slot's emitted block.
        _, slot, _, _, _, _ = _decode_member(lam, tbl_, n_members)
        return (slot, h_, 0, 0)

    kernel = functools.partial(_packed_decode_kernel, n_members=n_members,
                               blk=blk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, capacity),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), q_spec),
            pl.BlockSpec((1, blk, d), kv_spec),
            pl.BlockSpec((1, blk, d), kv_spec),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), o_spec),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = OBS.instrumented_pallas_call(
        kernel,
        meta=OBS.meta_exact("tri_attn.packed_decode_fwd", "tri_attn",
                            impl="pallas", kind="decode_round",
                            steps=capacity, block_shape=(1, blk),
                            bb_bound=b * cache_tiles, cells=h,
                            extra=(("capacity", capacity),)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b + 1, h, 1, d), q.dtype),
    )(tbl, q[:, :, None], k.reshape(b, s_cache, hkv * d),
      v.reshape(b, s_cache, hkv * d))
    return out[:, :, 0]


# ---------------------------------------------------------------------------
# FUSED continuous-batching step: ONE 1-D grid carrying newly admitted
# prompts (prefill members over the packed operand) AND live decode slots
# (row members over the KV cache) — the admit round and the decode round
# collapse into a single launch (core/packing's mixed_step lifted to the
# kernel). The unified (8, R) member table is RUNTIME data (decode
# positions advance every round; the prefill columns are constants of the
# compile but ride along so the whole grid shares one delegation):
#   0 starts    cumulative grid-step offsets per member (ascending)
#   1 kind      0 = prefill member, 1 = decode row member (incl. the pad)
#   2 n         prefill: member tiles per side | decode: kv_tiles
#               (DECODE_NO_EMIT for the pad member)
#   3 w_b       prefill: band width in tiles  | decode: kv_len in tokens
#   4 p_b       prefill: prefix width in tiles| decode: kv_first in tokens
#   5 q_off     prefill: packed tile-row offset | decode: cache/query slot
#   6 win       prefill window in tokens (0 = none) | decode: 0
#   7 pre       prefill prefix in tokens (0 = none) | decode: 0
# Output routing is per member KIND: prefill members emit their packed
# hidden tiles into o_pack (whose last tile row is the garbage target of
# every decode step), decode members emit their slot's row into o_dec
# (whose row B is the garbage target of every prefill step and the pad).
# ---------------------------------------------------------------------------


def _fused_member(lam, tbl, n_members: int):
    """lambda + (8, R) fused table -> (r, is_p, local, i_p, j_p).

    One O(log R) search shared by body and index maps; the prefill
    closed-form map runs on CLAMPED params (n=1, w=1, p=0, local=0) when
    the member is a decode row, so rows 2-4 holding kv_{tiles,len,first}
    can never overflow or divide inside the band/prefix delegation."""
    from repro.core import packing as PK

    r = PK.request_from_starts(lam, _TableRow(tbl, 0), n_members)
    is_p = tbl[1, r] == 0
    local = lam - tbl[0, r]
    i_p, j_p = PK.member_map_params(
        jnp.where(is_p, local, 0), jnp.where(is_p, tbl[2, r], 1),
        jnp.where(is_p, tbl[3, r], 1), jnp.where(is_p, tbl[4, r], 0))
    return r, is_p, local, i_p, j_p


def _fused_step_kernel(tbl_ref, qp_ref, kp_ref, vp_ref, qd_ref, kc_ref,
                       vc_ref, op_ref, od_ref, m_s, l_s, acc_s, *,
                       n_members: int, blk: int, scale: float):
    from repro.core import packing as PK

    lam = pl.program_id(1)
    r, is_p, local, i_p, j_p = _fused_member(lam, tbl_ref, n_members)
    kv_tiles = tbl_ref[2, r]
    kv_len = tbl_ref[3, r]
    kv_first = jnp.where(is_p, 0, tbl_ref[4, r])
    j_eff = jnp.where(is_p, j_p, local)
    first = jnp.where(is_p, PK.first_col_params(i_p, tbl_ref[3, r]), 0)
    last = jnp.where(is_p, PK.last_col_params(i_p, tbl_ref[4, r]),
                     kv_tiles - 1)

    @pl.when(j_eff == first)
    def _init():
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Decode rows broadcast their single query over the block: every row
    # computes the same online softmax, and the emit takes row 0.
    qp = qp_ref[0, 0].astype(jnp.float32)           # (blk, d)
    qd = qd_ref[0, 0].astype(jnp.float32)           # (1, d)
    q = jnp.where(is_p, qp, jnp.broadcast_to(qd, qp.shape))
    k = jnp.where(is_p, kp_ref[0, 0].astype(jnp.float32),
                  kc_ref[0].astype(jnp.float32))
    v = jnp.where(is_p, vp_ref[0, 0].astype(jnp.float32),
                  vc_ref[0].astype(jnp.float32))
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pmask = _packed_token_mask(i_p, j_p, blk, tbl_ref[6, r], tbl_ref[7, r])
    kpos = (kv_first // blk + jnp.where(is_p, 0, local)) * blk \
        + jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
    dmask = (kpos >= kv_first) & (kpos < kv_len)
    # select between the two masked scores, not between the masks: Mosaic
    # cannot lower a select over boolean vectors
    s = jnp.where(is_p, jnp.where(pmask, s, MASK_VALUE),
                  jnp.where(dmask, s, MASK_VALUE))

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(is_p & (j_eff == last))
    def _emit_pack():
        op_ref[0, 0] = (acc_s[...] / l_s[...]).astype(op_ref.dtype)

    @pl.when(jnp.logical_not(is_p) & (j_eff == last))
    def _emit_dec():
        od_ref[0, 0] = (acc_s[0:1, :] / l_s[0:1, :]).astype(od_ref.dtype)


def fused_step_fwd(q_pack, k_pack, v_pack, q_dec, k_cache, v_cache, tbl, *,
                   capacity: int, blk: int, n_pack_tiles: int,
                   sm_scale=None):
    """One fused launch for a whole continuous-batching engine step.

    q_pack: (1, H, S_pack, D) with k_pack/v_pack (1, Hkv, S_pack, D) — the
    newly admitted prompts concatenated along S (the packed-prefill
    layout); q_dec: (B, H, D) with k_cache/v_cache (B, S_cache, Hkv, D) —
    the live slots' rotated queries against the native decode cache (read
    as in packed_decode_fwd), new token already written. tbl: the (8, R)
    fused member table
    (ops.make_fused_table). Grid is (H, capacity): prefill blocks + live
    decode tiles + masked pad steps — ONE pallas_call where the split
    engine paid an admit launch and a decode launch. Returns

      o_pack (1, H, S_pack + blk, D) — packed hidden tiles; the final blk
             rows are the decode/pad steps' garbage tile, sliced off by
             the caller;
      o_dec  (B + 1, H, D) — per-slot decode rows; row B is the prefill/
             pad steps' garbage row, masked by the caller via coverage.
    """
    _, h, s_pack, d = q_pack.shape
    b = q_dec.shape[0]
    s_cache, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    assert s_cache % blk == 0, (s_cache, blk)
    assert s_pack == n_pack_tiles * blk, (s_pack, n_pack_tiles, blk)
    cache_tiles = s_cache // blk
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    n_members = tbl.shape[1]

    def qp_spec(h_, lam, tbl_):
        r, is_p, _, i_p, _ = _fused_member(lam, tbl_, n_members)
        return (0, h_, jnp.where(is_p, tbl_[5, r] + i_p, 0), 0)

    def kp_spec(h_, lam, tbl_):
        r, is_p, _, _, j_p = _fused_member(lam, tbl_, n_members)
        return (0, h_ // g, jnp.where(is_p, tbl_[5, r] + j_p, 0), 0)

    def qd_spec(h_, lam, tbl_):
        r, is_p, _, _, _ = _fused_member(lam, tbl_, n_members)
        slot = jnp.where(is_p, 0, tbl_[5, r])
        return (jnp.minimum(slot, b - 1), h_, 0, 0)

    def kc_spec(h_, lam, tbl_):
        r, is_p, local, _, _ = _fused_member(lam, tbl_, n_members)
        slot = jnp.where(is_p, 0, tbl_[5, r])
        kv_first = jnp.where(is_p, 0, tbl_[4, r])
        j_d = jnp.where(is_p, 0, local)
        return (jnp.minimum(slot, b - 1),
                jnp.minimum(kv_first // blk + j_d, cache_tiles - 1),
                h_ // g)

    def op_spec(h_, lam, tbl_):
        # decode/pad steps park on the extra garbage tile row n_pack_tiles
        r, is_p, _, i_p, _ = _fused_member(lam, tbl_, n_members)
        return (0, h_, jnp.where(is_p, tbl_[5, r] + i_p, n_pack_tiles), 0)

    def od_spec(h_, lam, tbl_):
        # prefill steps (and the pad member, whose slot is n_slots) park on
        # the garbage row b of the (B + 1)-row decode output
        r, is_p, _, _, _ = _fused_member(lam, tbl_, n_members)
        return (jnp.where(is_p, b, jnp.minimum(tbl_[5, r], b)), h_, 0, 0)

    kernel = functools.partial(_fused_step_kernel, n_members=n_members,
                               blk=blk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, capacity),
        in_specs=[
            pl.BlockSpec((1, 1, blk, d), qp_spec),
            pl.BlockSpec((1, 1, blk, d), kp_spec),
            pl.BlockSpec((1, 1, blk, d), kp_spec),
            pl.BlockSpec((1, 1, 1, d), qd_spec),
            pl.BlockSpec((1, blk, d), kc_spec),
            pl.BlockSpec((1, blk, d), kc_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk, d), op_spec),
            pl.BlockSpec((1, 1, 1, d), od_spec),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, 1), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
    )
    o_pack, o_dec = OBS.instrumented_pallas_call(
        kernel,
        meta=OBS.meta_exact(
            "tri_attn.fused_step_fwd", "tri_attn", impl="pallas",
            kind="fused_step", steps=capacity, block_shape=(blk, blk),
            bb_bound=n_pack_tiles * n_pack_tiles + b * cache_tiles,
            cells=h, extra=(("capacity", capacity),
                            ("members", n_members))),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, h, s_pack + blk, d), q_pack.dtype),
            jax.ShapeDtypeStruct((b + 1, h, 1, d), q_dec.dtype),
        ],
    )(tbl, q_pack, k_pack, v_pack, q_dec[:, :, None],
      k_cache.reshape(b, s_cache, hkv * d),
      v_cache.reshape(b, s_cache, hkv * d))
    return o_pack, o_dec[:, :, 0]


# ---------------------------------------------------------------------------
# Backward: dq (row-major grid, same enumeration as forward)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_s, *, sched: TriSched, scale: float):
    lam = pl.program_id(2)
    i, j = sched.rm_map(lam)

    @pl.when(j == sched.rm_first_col(i))
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_token_mask(sched, i, j, sched.bq, sched.bk), s, MASK_VALUE)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_s[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(j == sched.rm_last_col(i))
    def _emit():
        dq_ref[0, 0] = dq_s[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dk/dv (column-major grid; per-q-head partials, group-summed in
# ops.py — output revisiting cannot accumulate across kv-head groups)
# ---------------------------------------------------------------------------


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, sched: TriSched, scale: float):
    lam = pl.program_id(2)
    i, j = sched.cm_map(lam)

    @pl.when(i == sched.cm_first_row(j))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_token_mask(sched, i, j, sched.bq, sched.bk), s, MASK_VALUE)
    p = jnp.exp(s - lse)  # (bq, bk)
    dv_s[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    @pl.when(i == sched.cm_last_row(j))
    def _emit():
        dk_ref[0, 0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[...].astype(dv_ref.dtype)


def bwd(q, k, v, out, lse, do, sched: TriSched, *, sm_scale=None):
    """Returns (dq, dk, dv) with dk/dv shaped like k/v (group-summed)."""
    b, h, s_len, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    bq, bk = sched.bq, sched.bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    rm_i = lambda lam: sched.rm_map(lam)[0]
    rm_j = lambda lam: sched.rm_map(lam)[1]
    grid = (b, h, sched.rm_steps)
    dq = OBS.instrumented_pallas_call(
        functools.partial(_dq_kernel, sched=sched, scale=scale),
        meta=OBS.meta_from_trisched("tri_attn.bwd_dq", sched, impl="pallas",
                                    cells=b * h, grid=grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, rm_j(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, rm_j(lam), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, lam: (b_, h_, rm_i(lam), 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )(q, k, v, do, lse, delta)

    cm_i = lambda lam: sched.cm_map(lam)[0]
    cm_j = lambda lam: sched.cm_map(lam)[1]
    grid_cm = (b, h, sched.cm_steps)
    dk_ph, dv_ph = OBS.instrumented_pallas_call(
        functools.partial(_dkv_kernel, sched=sched, scale=scale),
        meta=OBS.meta_from_trisched("tri_attn.bwd_dkv", sched,
                                    impl="pallas", cells=b * h,
                                    grid=grid_cm),
        grid=grid_cm,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, cm_i(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, cm_j(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_ // g, cm_j(lam), 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, lam: (b_, h_, cm_i(lam), 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, lam: (b_, h_, cm_i(lam), 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, lam: (b_, h_, cm_i(lam), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_, cm_j(lam), 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, lam: (b_, h_, cm_j(lam), 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_len, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
    )(q, k, v, do, lse, delta)

    if g > 1:  # sum per-q-head partials into kv heads
        dk = dk_ph.reshape(b, hkv, g, s_len, d).sum(axis=2).astype(k.dtype)
        dv = dv_ph.reshape(b, hkv, g, s_len, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_ph, dv_ph
    return dq, dk, dv


# ---------------------------------------------------------------------------
# BB baseline (paper's bounding-box strategy): 2-D grid + block-level guard
# ---------------------------------------------------------------------------


def _bb_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                   sched: TriSched, scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, MASK_VALUE)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Paper's optimized BB: whole tile discarded by *block* coordinates.
    @pl.when(j <= i)
    def _active():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(_token_mask(sched, i, j, sched.bq, sched.bk), s,
                      MASK_VALUE)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(j == sched.n - 1)
    def _emit():
        l = l_s[...]
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)


def fwd_bb(q, k, v, sched: TriSched, *, sm_scale=None):
    """Bounding-box baseline: n x n grid, upper tiles guarded (dead DMA +
    dead grid steps — the cost the paper eliminates)."""
    b, h, s_len, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    bq, bk, n = sched.bq, sched.bk, sched.n
    kernel = functools.partial(_bb_fwd_kernel, sched=sched, scale=scale)
    out, lse = OBS.instrumented_pallas_call(
        kernel,
        meta=OBS.meta_dense("tri_attn.fwd_bb", "tri_attn", impl="pallas",
                            grid=(n, n), block_shape=(bq, bk),
                            tiles_domain=M.tri(n), cells=b * h),
        grid=(b, h, n, n),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )(q, k, v)
    return out, lse
