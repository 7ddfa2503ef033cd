"""Fused continuous-batching step == split admit + decode rounds.

The tentpole claim, verified at every layer:

  * ops level — fused_step_attention (scan and pallas, one mixed member
    table) equals the split halves: packed_prefill_attention over the
    pack AND packed_decode_attention over the live slots' cache prefixes;
  * driver level — serve.decode.fused_step emits the same admit logits,
    decode logits and cache as packed_prefill + decode_step_packed;
  * engine level — step_mode="fused" is TOKEN-IDENTICAL to the split
    engine and to the isolated greedy reference, including under a fault
    matrix (launch errors, poison, OOM): the fused -> split ladder rung
    absorbs every fused-attempt failure without changing the streams;
  * capacity — a pinned grid the round outgrew rebuckets (schema-valid
    degrade, satellite of PR 8's bare-assert bugfix) instead of crashing;
  * the HLO kernel-region op_name spellings live in ONE tested table
    (roofline/hlo_parse).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracles as O
from repro.configs import registry as REG
from repro.kernels.tri_attn import ops as OPS
from repro.models import model as MD
from repro.resilience import faults as F
from repro.serve import decode as D
from repro.serve import engine as E
from repro.serve.engine import Engine

# ---------------------------------------------------------------------------
# ops level: one mixed launch == the two split launches
# ---------------------------------------------------------------------------


def _fused_round(seed=0, h=4, hkv=2, d=8, blk=4, s_cache=32, b=3,
                 pads=(8, 4), kv_lens=(7, 18), slots=(0, 2)):
    qp, kp, vp = O.rand_qkv(seed, 1, h, hkv, sum(pads), d)
    qd, kc, vc = O.rand_decode_state(seed + 1, b, h, hkv, s_cache, d)
    psched = OPS.make_packed_sched(list(pads), block=blk)
    n_members = len(pads) + b + 1
    tbl, needed = OPS.make_fused_table(psched, list(kv_lens), list(slots),
                                       blk=blk, n_members=n_members,
                                       n_slots=b, s_cache=s_cache)
    return (qp, kp, vp, qd, kc, vc, psched, tbl, needed, n_members)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_fused_round_matches_split_halves(impl):
    blk, s_cache, b = 4, 32, 3
    kv_lens, slots = [7, 18], [0, 2]  # slot 1 has no live decode member
    (qp, kp, vp, qd, kc, vc, psched, tbl, needed,
     n_members) = _fused_round(blk=blk, s_cache=s_cache, b=b,
                               kv_lens=kv_lens, slots=slots)
    spec = OPS.FusedStepSpec(n_members=n_members,
                             capacity=psched.steps + D.round_capacity(
                                 needed - psched.steps),
                             blk=blk, impl=impl)
    out_p, out_d = OPS.fused_step_attention(qp, kp, vp, qd, kc, vc,
                                            jnp.asarray(tbl), psched, spec)
    want_p = OPS.packed_prefill_attention(qp, kp, vp, psched, impl="ref")
    dtbl, dneeded = OPS.make_decode_table(kv_lens, slots, blk=blk,
                                          n_members=b + 1, n_slots=b,
                                          s_cache=s_cache)
    dspec = OPS.DecodeRoundSpec(n_members=b + 1,
                                capacity=D.round_capacity(dneeded),
                                blk=blk, impl="ref")
    want_d = OPS.packed_decode_attention(qd, kc, vc, jnp.asarray(dtbl),
                                         dspec)
    O.assert_close(out_p, want_p, "attn", err_msg=f"pack half {impl}")
    O.assert_close(out_d, want_d, "attn", err_msg=f"decode half {impl}")
    # uncovered slot: no live member -> exact zeros, not garbage
    np.testing.assert_array_equal(np.asarray(out_d[1]), 0.0)


def test_fused_capacity_padding_is_inert():
    """Bigger fused capacity buckets only add masked pad steps — the
    recompile-avoidance contract the length-bucketed templates rely on."""
    (qp, kp, vp, qd, kc, vc, psched, tbl, needed,
     n_members) = _fused_round()
    outs = []
    for extra in (0, 5, 3 * needed):
        for impl in ("scan", "pallas"):
            spec = OPS.FusedStepSpec(n_members=n_members,
                                     capacity=needed + extra, blk=4,
                                     impl=impl)
            o_p, o_d = OPS.fused_step_attention(
                qp, kp, vp, qd, kc, vc, jnp.asarray(tbl), psched, spec)
            outs.append((np.asarray(o_p), np.asarray(o_d)))
    for o_p, o_d in outs[1:]:
        np.testing.assert_array_equal(outs[0][0], o_p)
        np.testing.assert_array_equal(outs[0][1], o_d)


def test_fused_table_layout_and_pad_member():
    """The (8, R) fused-table ABI is a declared contract (also pinned by
    analysis/jaxpr_lint + analysis/contracts "mixed"): prefill columns
    first (kind 0), decode columns rebased by psched.steps (kind 1), then
    the shared pad member owning the garbage outputs."""
    psched = OPS.make_packed_sched([8, 4], block=4)
    tbl, needed = OPS.make_fused_table(psched, [7, 18], [0, 2], blk=4,
                                       n_members=6, n_slots=3, s_cache=32)
    assert tbl.shape == (8, 6) and tbl.dtype == np.int32
    np.testing.assert_array_equal(tbl[0], [0, 3, 4, 6, 11, 11])  # starts
    np.testing.assert_array_equal(tbl[1], [0, 0, 1, 1, 1, 1])    # kinds
    assert int(tbl[0, 2]) == psched.steps  # decode half starts after pack
    np.testing.assert_array_equal(tbl[2, 2:4], [2, 5])   # kv tiles
    np.testing.assert_array_equal(tbl[3, 2:4], [7, 18])  # kv_len
    np.testing.assert_array_equal(tbl[5, 2:4], [0, 2])   # slots
    pad = tuple(int(v) for v in tbl[:, -1])
    assert pad == (needed, 1, OPS.DECODE_NO_EMIT, 0, 0, 3, 0, 0)
    assert needed == 11 == psched.steps + 7


# ---------------------------------------------------------------------------
# driver level: decode.fused_step == packed_prefill + decode_step_packed
# ---------------------------------------------------------------------------


def _setup(arch="yi-9b", seed=0):
    cfg = REG.smoke_config(arch)
    params = MD.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _filled_cache(params, cfg, b, max_len, depth, seed=1):
    """A decode cache with ``depth`` tokens of shared history per slot."""
    rng = np.random.default_rng(seed)
    hist = rng.integers(1, cfg.vocab_size, size=(b, depth)).astype(np.int32)
    cache = MD.init_cache(cfg, b, max_len, jnp.float32)
    for t in range(depth):
        _, cache = MD.decode_step(params, cfg, cache,
                                  jnp.asarray(hist[:, t:t + 1]),
                                  jnp.int32(t))
    return cache


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_driver_fused_step_equals_split_round(impl):
    cfg, params = _setup()
    b, max_len, depth = 3, 32, 9
    cache = _filled_cache(params, cfg, b, max_len, depth)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (6, 3)]
    live, pos_np = [0, 2], np.array([4, 0, 8], np.int32)
    kv_lens = [int(pos_np[s]) + 1 for s in live]
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      size=(b, 1)).astype(np.int32))
    pos = jnp.asarray(pos_np)
    # split: one decode launch + one admit launch
    lg_dec, cache_dec, _ = D.decode_step_packed(
        params, cfg, cache, tokens, pos, kv_lens, live, block=8, impl=impl)
    psched, starts, lens, hidden, _ = D.packed_prefill(
        params, cfg, prompts, block=8, attn_impl=impl)
    rows = [st + ln - 1 for st, ln in zip(starts, lens)]
    lg_adm = MD.logits_from_hidden(params, cfg, hidden)[0, rows]
    # fused: ONE launch
    la, ld, cache_f, states, psched_f, starts_f, lens_f, info = D.fused_step(
        params, cfg, cache, prompts, tokens, pos, kv_lens, live,
        block=8, impl=impl)
    assert (starts_f, lens_f) == (starts, lens)
    assert psched_f.steps == psched.steps
    assert info["tiles"] == psched.steps + sum(-(-kl // info["blk"])
                                               for kl in kv_lens)
    rows_live = np.asarray(live)
    O.assert_close(la, lg_adm, "attn", err_msg=f"admit logits {impl}")
    O.assert_close(np.asarray(ld)[rows_live],
                   np.asarray(lg_dec)[rows_live, 0], "attn",
                   err_msg=f"decode logits {impl}")
    for got, want in zip(jax.tree.leaves(cache_f),
                         jax.tree.leaves(cache_dec)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)
    # greedy decisions identical, not just close
    assert (np.argmax(np.asarray(la)[:, :cfg.vocab_size], -1).tolist()
            == np.argmax(np.asarray(lg_adm)[:, :cfg.vocab_size],
                         -1).tolist())


def test_driver_fused_capacity_pin_rebuckets():
    """Satellite: a pinned capacity the round outgrew is a RECOVERABLE
    sizing miss — both decode_step_packed and fused_step rebucket to the
    canonical grid (reported via info) instead of tripping an assert."""
    cfg, params = _setup()
    b, max_len = 2, 32
    cache = _filled_cache(params, cfg, b, max_len, 9)
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      size=(b, 1)).astype(np.int32))
    pos = jnp.asarray(np.array([8, 8], np.int32))
    kv_lens, live = [9, 9], [0, 1]
    base, _, info0 = D.decode_step_packed(params, cfg, cache, tokens, pos,
                                          kv_lens, live, block=4)
    assert not info0["rebucketed"]
    pinned, _, info1 = D.decode_step_packed(params, cfg, cache, tokens,
                                            pos, kv_lens, live, block=4,
                                            capacity=1)
    assert info1["rebucketed"] and info1["capacity"] >= info1["tiles"]
    np.testing.assert_array_equal(np.asarray(base), np.asarray(pinned))
    # same audit on the fused-step capacity path
    prompts = [rng.integers(1, cfg.vocab_size, size=5).astype(np.int32)]
    out0 = D.fused_step(params, cfg, cache, prompts, tokens, pos, kv_lens,
                        live, block=4)
    out1 = D.fused_step(params, cfg, cache, prompts, tokens, pos, kv_lens,
                        live, block=4, capacity=1)
    assert not out0[-1]["rebucketed"] and out1[-1]["rebucketed"]
    np.testing.assert_array_equal(np.asarray(out0[0]), np.asarray(out1[0]))
    np.testing.assert_array_equal(np.asarray(out0[1]), np.asarray(out1[1]))


# ---------------------------------------------------------------------------
# engine level: fused == split token streams (incl. the fault matrix)
# ---------------------------------------------------------------------------


def _run(cfg, params, prompts, max_news, *, step_mode, fault_plan=None,
         slots=2, **kw):
    eng = Engine(params, cfg, slots=slots, max_len=48, temperature=0.0,
                 prefill_block=4, decode_mode="packed", decode_block=8,
                 step_mode=step_mode, fault_plan=fault_plan, **kw)
    for uid, (p, mn) in enumerate(zip(prompts, max_news)):
        eng.submit(p, max_new=mn, uid=uid)
    return eng.run(), eng.stats


def _queue(cfg, seed=3, n=5):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (11, 2, 19, 5, 8)[:n]]
    return prompts, [3, 7, 2, 5, 4][:n]


def test_engine_fused_token_identical_to_split():
    """step_mode='fused' emits byte-identical streams to the split engine
    while paying ONE launch per admit-carrying round (fused_launches),
    with the round's tile accounting recorded."""
    cfg, params = _setup()
    prompts, max_news = _queue(cfg)
    res_f, st_f = _run(cfg, params, prompts, max_news, step_mode="fused")
    res_s, st_s = _run(cfg, params, prompts, max_news, step_mode="split")
    assert res_f == res_s
    assert st_f["fused_rounds"] == st_f["fused_launches"] > 0
    assert st_f["fused_fallbacks"] == 0
    assert st_f["fused_tiles"] > 0
    assert st_f["prefill_requests"] == st_s["prefill_requests"] == 5
    # the fused engine never pays a separate packed-prefill launch for
    # rounds it fused (split pays one per admit round)
    assert st_f["prefill_launches"] < st_s["prefill_launches"] + 1


def test_engine_fused_matches_isolated_greedy_reference():
    cfg, params = _setup()
    prompts, max_news = _queue(cfg, n=3)
    res, _ = _run(cfg, params, prompts, max_news, step_mode="fused")
    from test_decode_packed import _greedy_reference
    for uid, (p, mn) in enumerate(zip(prompts, max_news)):
        assert res[uid] == _greedy_reference(params, cfg, list(p), mn)


@pytest.mark.parametrize("kind,phase,rnd,times", [
    ("launch_error", "admit", 0, 1),
    ("launch_error", "decode", 1, 1),
    ("poison", "admit", 0, 1),
    ("poison", "decode", 1, 1),
    ("admit_oom", "admit", 0, 5),
])
def test_engine_fused_fault_matrix_token_identical(kind, phase, rnd, times):
    """The fused attempt is NOT retried: any strike inside it takes the
    registered step: fused -> split rung (requeue admits, re-run through
    the split ladders) — or, for decode poison, the shared quarantine
    machinery. Either way the streams equal the fault-free baseline."""
    cfg, params = _setup()
    prompts, max_news = _queue(cfg, n=4)
    base, _ = _run(cfg, params, prompts, max_news, step_mode="fused")
    plan = F.FaultPlan([F.Fault(kind=kind, phase=phase, round=rnd,
                                times=times)])
    res_f, st_f = _run(cfg, params, prompts, max_news, step_mode="fused",
                       fault_plan=plan)
    assert res_f == base, (kind, phase)
    plan.reset()
    res_s, _ = _run(cfg, params, prompts, max_news, step_mode="split",
                    fault_plan=plan)
    assert res_s == base, (kind, phase)
    if phase == "admit":  # strikes the fused attempt -> ladder rung taken
        assert st_f["fused_fallbacks"] >= 1
        assert st_f["launches_degraded_total"] >= 1


def test_engine_fused_requires_attention_only():
    """Recurrent mixers have no packed-member notion: the ctor falls back
    to split mode rather than letting fused_step leak state."""
    cfg, params = _setup("rwkv6-1.6b")
    eng = Engine(params, cfg, slots=2, max_len=32, step_mode="fused")
    assert eng.step_mode == "split"


# ---------------------------------------------------------------------------
# kernel-region op_name spellings live in ONE tested table
# ---------------------------------------------------------------------------


def test_kernel_region_spellings_pinned():
    """The installed JAX's spelling of the scan-attention cell,
    "vmap(vmap())/.../while", and the scan-fallback markers are in
    roofline/hlo_parse's table, and its regex is built from that table
    (no ad-hoc copy to drift)."""
    from repro.roofline import hlo_parse as H

    r = H._KERNEL_REGION_RE
    assert r.search('op_name="jit(f)/vmap(vmap())/while/body/add"')
    for marker in ("ssm_scan_kernel", "wkv_scan_kernel",
                   "tri_attn_kernel"):
        assert any(marker in s for s in H.KERNEL_REGION_OP_NAME_SPELLINGS)
        assert r.search(marker)
    # near-misses must NOT match (a plain while loop is not a kernel cell),
    # nor does JAX 0.4.x's collapsed spelling, which no supported JAX emits
    assert not r.search('op_name="jit(f)/while/body/add"')
    assert not r.search('op_name="vmap(while)"')
    assert not r.search('op_name="vmap(vmap(while))"')
    assert r.pattern == "|".join(H.KERNEL_REGION_OP_NAME_SPELLINGS)
