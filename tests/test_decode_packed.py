"""Packed mixed-position decode == sequential per-slot decode.

Property-tests (hypothesis) the tentpole claim end to end:

  * ops level — packed_decode_attention (scan / pallas / ref impls) equals
    the isolated per-slot oracle for arbitrary skewed KV lengths, retired
    slots, and rolling sliding-window prefixes;
  * engine level — an Engine decoding with the packed path emits
    TOKEN-IDENTICAL streams to the lockstep engine and to an isolated
    per-request greedy reference, across position skew, SWA configs, and
    mid-round slot retirement (mixed max_new);
  * stats — packed-prefill launches and packed-decode launches are counted
    apart (a single shared counter would conflate the two claims).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import oracles as O
from repro.configs import registry as REG
from repro.core.packing import PackedSchedule
from repro.kernels.tri_attn import ops as OPS
from repro.models import model as MD
from repro.serve import decode as D
from repro.serve.engine import Engine

# ---------------------------------------------------------------------------
# ops level
# ---------------------------------------------------------------------------


def _round(kv_lens, slots, b, blk, s_cache, seed=0, h=4, hkv=2, d=8):
    q, kc, vc = O.rand_decode_state(seed, b, h, hkv, s_cache, d)
    tbl, needed = OPS.make_decode_table(kv_lens, slots, blk=blk,
                                       n_members=b + 1, n_slots=b)
    cap = D.round_capacity(needed)
    per_slot = np.zeros((b,), np.int64)
    for kl, sl in zip(kv_lens, slots):
        per_slot[sl] = kl
    want = O.decode_round_oracle(q, kc, vc, per_slot)
    return q, kc, vc, tbl, cap, want


@pytest.mark.parametrize("impl", ["scan", "pallas", "ref"])
def test_skewed_round_matches_per_slot_oracle(impl):
    b, blk, s_cache = 5, 8, 64
    kv_lens, slots = [64, 3, 17], [0, 2, 4]  # slots 1 and 3 retired
    q, kc, vc, tbl, cap, want = _round(kv_lens, slots, b, blk, s_cache)
    spec = OPS.DecodeRoundSpec(n_members=b + 1, capacity=cap, blk=blk,
                               impl=impl)
    got = OPS.packed_decode_attention(q, kc, vc, jnp.asarray(tbl), spec)
    O.assert_close(got, want, "attn", err_msg=impl)
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(got[3]), 0.0)


@given(st.data())
@settings(max_examples=12)
def test_property_random_rounds_match_oracle(data):
    """Random live subsets x skewed lengths x tile edges: scan and pallas
    both equal the isolated per-slot oracle (mid-round retirement is the
    'absent from the table' case)."""
    b = data.draw(st.integers(min_value=1, max_value=5))
    blk = data.draw(st.integers(min_value=1, max_value=3)) * 4
    s_cache = blk * data.draw(st.integers(min_value=1, max_value=4))
    n_live = data.draw(st.integers(min_value=1, max_value=b))
    slots = sorted(np.random.RandomState(
        data.draw(st.integers(min_value=0, max_value=999))).permutation(
        b)[:n_live].tolist())
    kv_lens = [data.draw(st.integers(min_value=1, max_value=s_cache))
               for _ in slots]
    seed = data.draw(st.integers(min_value=0, max_value=99))
    q, kc, vc, tbl, cap, want = _round(kv_lens, slots, b, blk, s_cache,
                                       seed=seed)
    for impl in ("scan", "pallas"):
        spec = OPS.DecodeRoundSpec(n_members=b + 1, capacity=cap, blk=blk,
                                   impl=impl)
        got = OPS.packed_decode_attention(q, kc, vc, jnp.asarray(tbl), spec)
        O.assert_close(got, want, "attn",
                       err_msg=f"{impl} {kv_lens} {slots} blk={blk}")


def test_capacity_padding_is_inert():
    """Bigger static capacity buckets only add masked pad steps: output
    identical across capacities (the recompile-avoidance contract).

    The contract is exact WITHIN each implementation; scan and Pallas
    accumulate in different orders, so across them it is the attention
    tolerance."""
    b, blk, s_cache = 3, 4, 32
    kv_lens, slots = [9, 30], [0, 2]
    q, kc, vc, tbl, cap, want = _round(kv_lens, slots, b, blk, s_cache)
    outs = {}
    for impl in ("scan", "pallas"):
        for capacity in (cap, cap + 5, 4 * cap):
            spec = OPS.DecodeRoundSpec(n_members=b + 1, capacity=capacity,
                                       blk=blk, impl=impl)
            outs.setdefault(impl, []).append(np.asarray(
                OPS.packed_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                            spec)))
    for per_cap in outs.values():
        for o in per_cap[1:]:
            np.testing.assert_array_equal(per_cap[0], o)
    O.assert_close(outs["pallas"][0], outs["scan"][0], "attn")
    O.assert_close(outs["scan"][0], want, "attn")


def test_decode_table_layout():
    tbl, needed = OPS.make_decode_table([9, 1, 16], [0, 1, 3], blk=4,
                                        n_members=6, n_slots=5)
    assert tbl.shape == (5, 6)
    np.testing.assert_array_equal(tbl[0], [0, 3, 4, 8, 8, 8])  # starts
    np.testing.assert_array_equal(tbl[1, :4], [0, 1, 3, 0])    # slots
    np.testing.assert_array_equal(tbl[2, :4], [3, 1, 4, 0])    # kv_tiles
    np.testing.assert_array_equal(tbl[3], [9, 1, 16, 0, 0, 0])  # kv_len
    np.testing.assert_array_equal(tbl[4], 0)  # unbanded: whole prefix
    assert tbl[1, 5] == 5 and tbl[2, 5] == OPS.DECODE_NO_EMIT
    assert needed == 8
    # the table IS core/packing's decode_round: same offsets
    pk = PackedSchedule.decode_round([3, 1, 4])
    assert tuple(tbl[0, :3]) == pk.offsets[:-1]
    assert pk.num_blocks == needed


def test_decode_pad_member_declared_contract():
    """The pad-member ABI is a declared contract (also enforced by
    ``repro.analysis.jaxpr_lint``): the final column is exactly
    (cur, n_slots, DECODE_NO_EMIT, 0, 0) — it owns the garbage output
    row b and never emits — and DECODE_NO_EMIT is a fixed sentinel that
    dominates any representable tile count so the lambda search can
    never land past it."""
    assert OPS.DECODE_NO_EMIT == 2 ** 30
    for kv_lens, slots, n_members, n_slots in [
            ([9, 1, 16], [0, 1, 3], 6, 5),
            ([5], [2], 2, 4),
            ([7, 7, 7], [0, 1, 2], 4, 3)]:
        tbl, needed = OPS.make_decode_table(kv_lens, slots, blk=4,
                                            n_members=n_members,
                                            n_slots=n_slots)
        assert tbl.shape == (5, n_members) and tbl.dtype == np.int32
        pad = tuple(int(v) for v in tbl[:, -1])
        assert pad == (needed, n_slots, OPS.DECODE_NO_EMIT, 0, 0)
        # unused interior columns are zero-tile, never the pad sentinel
        for j in range(len(kv_lens), n_members - 1):
            assert tuple(int(v) for v in tbl[:, j]) == (needed, 0, 0, 0, 0)
        # the sentinel dominates any real cumulative tile count by far
        assert needed < OPS.DECODE_NO_EMIT // 2


def test_banded_decode_table_layout_and_tile_cap():
    """window=w trims each member to its LAST w tokens: kv_first row set,
    per-slot kv_tiles capped at ceil(w / blk) (+1 when kv_len is not
    tile-aligned), however deep the position."""
    from repro.serve import decode as D

    w, blk = 8, 4
    tbl, needed = D.make_decode_table([64, 9, 3], [0, 1, 2], blk=blk,
                                      n_members=5, n_slots=4, s_cache=64,
                                      window=w)
    assert tbl.shape == (5, 5)
    np.testing.assert_array_equal(tbl[3, :3], [64, 9, 3])      # kv_len
    np.testing.assert_array_equal(tbl[4, :3], [56, 1, 0])      # kv_first
    np.testing.assert_array_equal(tbl[2, :3], [2, 3, 1])       # kv_tiles
    assert needed == 6  # vs 16 + 3 + 1 unbanded
    cap = -(-w // blk) + 1
    assert max(tbl[2, :3]) <= cap
    # per-slot windows
    tbl2, _ = D.make_decode_table([64, 64], [0, 1], blk=blk, n_members=3,
                                  n_slots=2, window=[4, None])
    np.testing.assert_array_equal(tbl2[2, :2], [1, 16])
    with pytest.raises(AssertionError, match="window list"):
        D.make_decode_table([8, 8], [0, 1], blk=blk, n_members=3,
                            n_slots=2, window=[4])


@pytest.mark.parametrize("impl", ["scan", "pallas", "ref"])
def test_banded_decode_round_token_identical(impl):
    """Band-limited members equal the full-prefix WINDOWED oracle: the
    trimmed head tiles were entirely outside the window, so the packed
    banded round loses no information (token identity of the satellite)."""
    from repro.serve import decode as D

    b, blk, s_cache, w = 4, 8, 64, 16
    kv_lens, slots = [61, 17, 9], [0, 1, 3]
    q, kc, vc = O.rand_decode_state(7, b, 4, 2, s_cache, 8)
    tbl, needed = D.make_decode_table(kv_lens, slots, blk=blk,
                                      n_members=b + 1, n_slots=b,
                                      s_cache=s_cache, window=w)
    cap = D.round_capacity(needed)
    want = np.zeros((b, 4, 8), np.float32)
    for kl, sl in zip(kv_lens, slots):
        o = O.attention_oracle(
            np.asarray(q[sl])[None, :, None, :],
            np.asarray(kc[sl, :kl]).transpose(1, 0, 2)[None],
            np.asarray(vc[sl, :kl]).transpose(1, 0, 2)[None],
            window=w, q_offset=kl - 1)
        want[sl] = o[0, :, 0, :]
    spec = OPS.DecodeRoundSpec(n_members=b + 1, capacity=cap, blk=blk,
                               impl=impl)
    got = OPS.packed_decode_attention(q, kc, vc, jnp.asarray(tbl), spec)
    O.assert_close(got, want, "attn", err_msg=f"banded {impl}")
    # and the band actually trimmed tiles vs the unbanded round
    _, full = OPS.make_decode_table(kv_lens, slots, blk=blk,
                                    n_members=b + 1, n_slots=b)
    assert needed < full


def test_decode_table_rejects_overfull_and_empty():
    with pytest.raises(AssertionError, match="live members"):
        OPS.make_decode_table([1, 1, 1], [0, 1, 2], blk=4, n_members=3,
                              n_slots=4)
    with pytest.raises(AssertionError, match="attend"):
        OPS.make_decode_table([0], [0], blk=4, n_members=3, n_slots=4)
    # kv_len beyond the cache would silently re-attend the clamped last
    # tile downstream; the builder rejects it while lengths are host ints
    with pytest.raises(AssertionError, match="exceed the KV cache"):
        OPS.make_decode_table([33], [0], blk=4, n_members=3, n_slots=4,
                              s_cache=32)


# ---------------------------------------------------------------------------
# engine level (token-identical, incl. SWA + mid-round retirement)
# ---------------------------------------------------------------------------


def _setup(arch="yi-9b", seed=0):
    cfg = REG.smoke_config(arch)
    params = MD.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _run_engine(cfg, params, prompts, max_news, decode_mode, **kw):
    eng = Engine(params, cfg, slots=2, max_len=48, temperature=0.0,
                 prefill_block=4, decode_mode=decode_mode, decode_block=8,
                 **kw)
    for uid, (p, mn) in enumerate(zip(prompts, max_news)):
        eng.submit(p, max_new=mn, uid=uid)
    return eng.run(), eng.stats


def _greedy_reference(params, cfg, prompt, max_new, max_len=48):
    cache = MD.init_cache(cfg, 1, max_len, jnp.float32)
    for t, p in enumerate(prompt):
        logits, cache = MD.decode_step(
            params, cfg, cache, jnp.array([[p]], jnp.int32), jnp.int32(t))
    out, pos = [], len(prompt) - 1
    nxt = int(jnp.argmax(logits[0, 0, :cfg.vocab_size]))
    for _ in range(max_new):
        out.append(nxt)
        pos += 1
        logits, cache = MD.decode_step(
            params, cfg, cache, jnp.array([[nxt]], jnp.int32),
            jnp.int32(pos))
        nxt = int(jnp.argmax(logits[0, 0, :cfg.vocab_size]))
    return out


@pytest.mark.parametrize("arch", ["yi-9b", "mixtral-8x7b"])
def test_engine_packed_decode_token_identical(arch):
    """Skewed prompts + mixed max_new (mid-round retirement): the packed
    decode engine, the lockstep engine, and the isolated per-request
    reference all emit the same tokens. mixtral exercises the rolling
    sliding-window cache."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (11, 2, 19, 5)]
    max_news = [3, 7, 2, 5]  # slots retire mid-round at different times
    res_packed, st_packed = _run_engine(cfg, params, prompts, max_news,
                                        "packed")
    res_lock, st_lock = _run_engine(cfg, params, prompts, max_news,
                                    "lockstep")
    assert res_packed == res_lock
    for uid, (p, mn) in enumerate(zip(prompts, max_news)):
        assert res_packed[uid] == _greedy_reference(params, cfg, list(p), mn)
    assert st_packed["decode_packed_launches"] == st_packed["decode_rounds"]
    assert st_packed["decode_lockstep_launches"] == 0
    assert st_lock["decode_packed_launches"] == 0
    # position skew means the packed grid beats pad-to-max
    assert st_packed["decode_tiles_packed"] < st_packed["decode_tiles_padded"]


def test_engine_auto_mode_prefers_lockstep_when_uniform():
    """decode_mode='auto' is a COST crossover, not a skew test: packed wins
    only when PACKED_TILE_COST_RATIO * sum(tiles) < B * max(tiles). Uniform
    all-live rounds stay lockstep (the regression: skew=1 must be
    lockstep); one deep straggler among short slots flips to packed."""
    from repro.serve import engine as E

    cfg, params = _setup()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=7).astype(np.int32)
               for _ in range(2)]
    res, st = _run_engine(cfg, params, prompts, [4, 4], "auto")
    # equal-length prompts, equal max_new, slots == requests: sum(tiles)
    # == B * max(tiles), so the ratio-discounted packed cost never wins
    assert st["decode_packed_launches"] == 0
    assert st["decode_lockstep_launches"] == st["decode_rounds"] > 0
    # mild skew is NOT enough any more: at B=2 even tiles [1, 2] give
    # ratio*sum = 2.3*3 > 2*2 = B*max — lockstep is genuinely cheaper
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (3, 13)]
    res, st = _run_engine(cfg, params, prompts, [4, 4], "auto")
    assert st["decode_packed_launches"] == 0
    # one deep straggler among short slots: tiles [1, 1, 1, 5] ->
    # 2.3 * 8 = 18.4 < 4 * 5 = 20 -> packed rounds appear
    assert E.PACKED_TILE_COST_RATIO * 8 < 4 * 5
    eng = Engine(params, cfg, slots=4, max_len=48, temperature=0.0,
                 prefill_block=4, decode_mode="auto", decode_block=8)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (3, 3, 3, 37)]
    for uid, p in enumerate(prompts):
        eng.submit(p, max_new=4, uid=uid)
    eng.run()
    assert eng.stats["decode_packed_launches"] > 0


def test_engine_recurrent_arch_falls_back_to_lockstep_decode():
    cfg, params = _setup("rwkv6-1.6b")
    eng = Engine(params, cfg, slots=2, max_len=32, decode_mode="packed")
    assert eng.decode_mode == "lockstep"


def test_engine_counts_prefill_and_decode_launches_apart():
    """The satellite claim: packed-prefill launches and packed-decode
    launches are separate counters (one shared counter would conflate
    'one launch per admit round' with 'one launch per decode round')."""
    cfg, params = _setup()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (9, 3, 12)]
    res, st = _run_engine(cfg, params, prompts, [4, 4, 4], "packed")
    assert st["prefill_launches"] == st["admit_rounds"] == 2  # 2+1 over 2 slots
    assert st["decode_packed_launches"] == st["decode_rounds"]
    assert st["decode_packed_launches"] > 0
    assert (st["decode_packed_launches"] + st["decode_lockstep_launches"]
            == st["decode_rounds"])
    # tile accounting exists per round and is packed <= padded
    assert 0 < st["decode_tiles_packed"] <= st["decode_tiles_padded"]


@given(st.data())
@settings(max_examples=3)
def test_property_engine_token_identical_random_queues(data):
    """Random skewed queues (hypothesis-driven): packed == lockstep token
    streams, decode counters consistent."""
    cfg, params = _setup()
    rng = np.random.default_rng(data.draw(st.integers(0, 99)))
    n_req = data.draw(st.integers(min_value=1, max_value=4))
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=data.draw(st.integers(1, 20))).astype(
        np.int32) for _ in range(n_req)]
    max_news = [data.draw(st.integers(1, 6)) for _ in range(n_req)]
    res_p, st_p = _run_engine(cfg, params, prompts, max_news, "packed")
    res_l, _ = _run_engine(cfg, params, prompts, max_news, "lockstep")
    assert res_p == res_l
    assert st_p["decode_packed_launches"] == st_p["decode_rounds"]
