"""Serving-layer tests: generation loop, engine continuous batching ==
isolated sequential decode, cache accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as REG
from repro.models import model as MD
from repro.serve import decode as D
from repro.serve import kv_cache as KV
from repro.serve.engine import Engine


def _setup(arch="yi-9b", seed=0):
    cfg = REG.smoke_config(arch)
    params = MD.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _greedy_reference(params, cfg, prompt, max_new, max_len):
    """Single-sequence greedy decode, token by token."""
    cache = MD.init_cache(cfg, 1, max_len, jnp.float32)
    tok = None
    for t, p in enumerate(prompt):
        logits, cache = MD.decode_step(
            params, cfg, cache, jnp.array([[p]], jnp.int32), jnp.int32(t))
    out = []
    pos = len(prompt) - 1
    nxt = int(jnp.argmax(logits[0, 0, :cfg.vocab_size]))
    for _ in range(max_new):
        out.append(nxt)
        pos += 1
        logits, cache = MD.decode_step(
            params, cfg, cache, jnp.array([[nxt]], jnp.int32),
            jnp.int32(pos))
        nxt = int(jnp.argmax(logits[0, 0, :cfg.vocab_size]))
    return out


def test_engine_matches_sequential_decode():
    cfg, params = _setup()
    prompts = [np.array([3, 1, 4, 1, 5], np.int32),
               np.array([2, 7, 1], np.int32),
               np.array([9, 9, 8, 2, 6, 5], np.int32)]
    eng = Engine(params, cfg, slots=2, max_len=48, temperature=0.0)
    for uid, p in enumerate(prompts):
        eng.submit(p, max_new=6, uid=uid)
    results = eng.run()
    assert set(results) == {0, 1, 2}
    for uid, p in enumerate(prompts):
        ref = _greedy_reference(params, cfg, list(p), 6, 48)
        assert results[uid] == ref, (uid, results[uid], ref)


@pytest.mark.parametrize("step_mode,prefill_mode", [
    ("split", "packed"), ("fused", "packed"), ("split", "sequential")])
def test_engine_slot_positions_live_on_the_host(step_mode, prefill_mode):
    """``Engine.pos`` is a host int32 vector that every round keeps at
    prompt length + tokens emitted - 1 for each live slot; requests end by
    ``max_new`` or at ``max_len - 1``, greedy tokens match the sequential
    reference, and a mid-run snapshot holds a copy that restores to the
    same tokens."""
    cfg, params = _setup()
    max_len = 16
    prompts = [np.array([3, 1, 4, 1, 5], np.int32),
               np.array([2, 7, 1], np.int32),
               np.arange(1, 11, dtype=np.int32)]
    max_new = [4, 5, 20]  # the last stops at max_len - 1, after 6 tokens

    def make():
        eng = Engine(params, cfg, slots=2, max_len=max_len,
                     temperature=0.0, step_mode=step_mode,
                     prefill_mode=prefill_mode)
        for uid, (p, n) in enumerate(zip(prompts, max_new)):
            eng.submit(p, max_new=n, uid=uid)
        return eng

    eng, snap, rounds = make(), None, 0
    while not eng.idle():
        eng.round()
        rounds += 1
        assert type(eng.pos) is np.ndarray
        assert eng.pos.dtype == np.int32 and eng.pos.shape == (2,)
        for slot, req in enumerate(eng.slot_req):
            if req is not None:
                assert eng.pos[slot] == len(req.prompt) + len(req.out) - 1
        if rounds == 2:
            snap = eng.snapshot()
            pos_at_snap = eng.pos.copy()
    assert snap is not None and rounds > 3
    # the engine moved on; the snapshot's positions did not move with it
    np.testing.assert_array_equal(snap.pos, pos_at_snap)
    results = {r.uid: r.out for r in eng.finished}
    assert [len(results[u]) for u in range(3)] == [4, 5, max_len - 10]
    for uid, p in enumerate(prompts):
        ref = _greedy_reference(params, cfg, list(p), len(results[uid]),
                                max_len)
        assert results[uid] == ref, (uid, results[uid], ref)
    restored = Engine.restore(snap)
    assert type(restored.pos) is np.ndarray
    assert restored.pos.dtype == np.int32
    np.testing.assert_array_equal(restored.pos, pos_at_snap)
    assert restored.run() == results


def test_engine_packed_prefill_matches_sequential():
    """The batched ragged prefill must be token-for-token identical to the
    sequential per-request prefill on a mixed-length queue, while issuing
    exactly ONE packed launch per admit round."""
    cfg, params = _setup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (9, 3, 17, 5, 12)]

    def run(mode, bucket=0):
        eng = Engine(params, cfg, slots=2, max_len=48, temperature=0.0,
                     prefill_mode=mode, prefill_block=4,
                     prefill_bucket=bucket)
        for uid, p in enumerate(prompts):
            eng.submit(p, max_new=4, uid=uid)
        return eng.run(), eng.stats

    res_packed, st_packed = run("packed")
    res_seq, st_seq = run("sequential")
    assert res_packed == res_seq
    # length bucketing only adds inert tail padding: same tokens out
    res_bucket, _ = run("packed", bucket=16)
    assert res_bucket == res_seq
    # one packed launch per admit round vs one decode step per prompt token
    assert st_packed["prefill_launches"] == st_packed["admit_rounds"]
    assert st_seq["prefill_launches"] == sum(len(p) for p in prompts)
    assert st_packed["prefill_requests"] == len(prompts)
    # prefill launches are counted APART from decode launches: decode
    # rounds ran (tokens were generated) without touching the prefill
    # counter, and every decode round landed in exactly one decode bucket.
    assert st_packed["decode_rounds"] > 0
    assert (st_packed["decode_packed_launches"]
            + st_packed["decode_lockstep_launches"]
            == st_packed["decode_rounds"])


def test_engine_cost_ordered_admission_equalizes_rounds():
    """admit_order="cost" (default) admits the oldest request each round
    (aging), then alternates light/heavy so successive packed admit
    rounds get near-equal tile totals; "fifo" keeps arrival order. Token
    streams stay identical per uid either way, and the chosen order is
    exposed in stats."""
    cfg, params = _setup()
    rng = np.random.default_rng(11)
    # arrival order deliberately lumpy: two long then two short prompts
    lens = (17, 16, 2, 3)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in lens]

    def run(order):
        eng = Engine(params, cfg, slots=2, max_len=48, temperature=0.0,
                     prefill_block=4, admit_order=order)
        for uid, p in enumerate(prompts):
            eng.submit(p, max_new=4, uid=uid)
        return eng.run(), eng.stats

    res_cost, st_cost = run("cost")
    res_fifo, st_fifo = run("fifo")
    assert res_cost == res_fifo  # ordering never changes any token stream
    assert st_fifo["admit_round_tiles"] == [15 + 10, 1 + 1]  # lumpy
    assert st_cost["admit_round_tiles"] == [15 + 1, 10 + 1]  # equalized
    spread = lambda ts: max(ts) - min(ts)
    assert spread(st_cost["admit_round_tiles"]) < \
        spread(st_fifo["admit_round_tiles"])
    # the per-round order log names (uid, tiles) in launch order
    assert st_cost["admit_order_log"][0] == [(0, 15), (2, 1)]
    assert st_fifo["admit_order_log"][0] == [(0, 15), (1, 10)]


def test_engine_recurrent_arch_falls_back_to_sequential():
    """Recurrent token mixers cannot splice packed state across request
    boundaries; the engine must silently keep the sequential path."""
    cfg, params = _setup("rwkv6-1.6b")
    eng = Engine(params, cfg, slots=2, max_len=32, prefill_mode="packed")
    assert eng.prefill_mode == "sequential"


def test_engine_more_requests_than_slots_refills():
    cfg, params = _setup("rwkv6-1.6b")  # recurrent-state engine path
    eng = Engine(params, cfg, slots=2, max_len=32, temperature=0.0)
    for uid in range(5):
        eng.submit(np.array([uid + 1, 2, 3], np.int32), max_new=4, uid=uid)
    results = eng.run()
    assert len(results) == 5
    assert all(len(v) == 4 for v in results.values())


def test_generate_masks_inactive_slots():
    cfg, params = _setup()
    cache = MD.init_cache(cfg, 2, 16, jnp.float32)
    active = jnp.array([True, False])
    toks, cache2, pos = D.generate(
        params, cfg, cache, jnp.array([[1], [1]], jnp.int32),
        jnp.zeros((2,), jnp.int32), 5, active=active)
    assert toks.shape == (2, 5)
    assert jnp.all(toks[1] == 0)          # inactive slot emits pad
    assert int(pos[0]) == 5 and int(pos[1]) == 0  # pos frozen when inactive
    # inactive slot's cache untouched
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(cache2)):
        np.testing.assert_array_equal(np.asarray(a[:, 1]),
                                      np.asarray(b[:, 1]))


def test_sampling_temperature_and_topk():
    key = jax.random.key(0)
    logits = jnp.array([[0.0, 10.0, 0.0, 5.0]])
    assert int(D.sample_logits(key, logits, temperature=0.0)[0]) == 1
    t = D.sample_logits(key, logits, temperature=1.0, top_k=1)
    assert int(t[0]) == 1
    # padded-vocab positions never sampled
    s = D.sample_logits(key, jnp.array([[0.0, 0.0, 100.0]]),
                        temperature=0.0, vocab_size=2)
    assert int(s[0]) < 2


def test_cache_accounting():
    cfg = REG.get_config("yi-9b")
    per_tok = KV.cache_bytes_per_token(cfg)
    # 48 layers * 2 (k+v) * 4 kv heads * 128 hd * 2 bytes
    assert per_tok == 48 * 2 * 4 * 128 * 2
    swa = REG.get_config("mixtral-8x7b")
    assert KV.cache_bytes_per_token(swa) == 0  # rolling buffer

    cfg_r = REG.smoke_config("yi-9b")
    cache = KV.init_cache(cfg_r, 2, 16, jnp.bfloat16)
    got = KV.cache_bytes(cache)
    want = (cfg_r.n_layers * 2 * 2 * 16 * cfg_r.n_kv_heads
            * cfg_r.head_dim * 2)
    assert got == want
