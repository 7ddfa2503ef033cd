"""Packed triangular BACKWARD == per-document sequential backward.

Property-tests the training tentpole end to end:

  * kernel level — jax.grad through ops.packed_prefill_attention matches
    the numpy-f64 gradient oracle on BOTH the scan and Pallas custom-VJP
    paths, for mixed ltm/band/prefix members (no fallback: the Pallas
    grad jaxpr contains the packed fwd + dq + dkv pallas_calls and no
    scan loop);
  * property (hypothesis) — packed-batch grads equal per-document
    sequential grads for random member mixes;
  * train level — a packed ragged-document train step produces the SAME
    loss and parameter gradients as the pad-to-max padded batch over the
    identical documents, and make_train_step(packed=...) steps cleanly;
  * data level — pack_documents (first-fit decreasing) places every doc
    exactly once within capacity, and PackedDocsLM emits consistent
    tokens/labels/mask/positions for packed and padded layouts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import oracles as O
from repro.configs import registry as REG
from repro.kernels.tri_attn import ops as OPS
from repro.models import model as MD
from repro.train import data as DATA
from repro.train import optimizer as OPT
from repro.train import train_step as TS

# mixed member zoo: ltm + band + prefix in one packed launch
LENS = (32, 8, 16)
WINDOWS = (None, None, 8)
PREFIXES = (0, 4, 0)
BLK = 8


def _vjp_grads(impl, q, k, v, do, psched):
    f = lambda q_, k_, v_: OPS.packed_prefill_attention(q_, k_, v_, psched,
                                                        impl=impl)
    _, vjp = jax.vjp(f, q, k, v)
    return vjp(do)


@pytest.mark.parametrize("impl", ["scan", "pallas"])
def test_packed_grad_matches_f64_oracle(impl):
    s = sum(LENS)
    q, k, v = O.rand_qkv(0, 1, 4, 2, s, 16)
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    psched = OPS.make_packed_sched(LENS, block=BLK, window=list(WINDOWS),
                                   prefix=list(PREFIXES))
    dq, dk, dv = _vjp_grads(impl, q, k, v, do, psched)
    wq, wk, wv = O.packed_attention_grad_oracle(
        q, k, v, do, LENS, windows=WINDOWS, prefixes=PREFIXES)
    O.assert_close(dq, wq, "attn_grad", err_msg=f"dq {impl}")
    O.assert_close(dk, wk, "attn_grad", err_msg=f"dk {impl}")
    O.assert_close(dv, wv, "attn_grad", err_msg=f"dv {impl}")


def test_pallas_grad_runs_packed_bwd_not_fallback():
    """The Pallas path's backward is the packed dq + dk/dv kernels: the
    grad jaxpr carries three pallas_call launches (fwd, dq, dkv) on the
    TPU branch, none of them interpreted there, and no lax.scan fallback
    loop."""
    from repro.analysis import jaxpr_lint as JL

    s = sum(LENS)
    q, k, v = O.rand_qkv(1, 1, 2, 1, s, 8)
    psched = OPS.make_packed_sched(LENS, block=BLK)
    closed = jax.make_jaxpr(jax.grad(
        lambda q_: jnp.sum(OPS.packed_prefill_attention(
            q_, k, v, psched, impl="pallas"))))(q)
    n_pc = JL.count_primitive(closed.jaxpr, "pallas_call")
    assert n_pc == 3, n_pc
    assert JL.interpreted_on_tpu(closed.jaxpr) == 0
    assert "scan[" not in str(closed)


@given(st.data())
@settings(max_examples=6, deadline=None)
def test_property_packed_grads_equal_per_document(data):
    """Random member mixes (ltm/band/prefix, random tile counts): the ONE
    packed backward equals the per-document sequential backward exactly
    (same schedules, same op order per member)."""
    r = data.draw(st.integers(min_value=1, max_value=4))
    blk = 4 * data.draw(st.integers(min_value=1, max_value=2))
    lens, wins, pres = [], [], []
    for _ in range(r):
        n = data.draw(st.integers(min_value=1, max_value=4))
        kind = data.draw(st.sampled_from(["ltm", "band", "prefix"]))
        lens.append(n * blk)
        wins.append(data.draw(st.integers(1, n * blk))
                    if kind == "band" else None)
        pres.append(data.draw(st.integers(1, n * blk))
                    if kind == "prefix" and n > 1 else 0)
    s = sum(lens)
    q, k, v = O.rand_qkv(data.draw(st.integers(0, 99)), 1, 2, 1, s, 8)
    do = jax.random.normal(jax.random.PRNGKey(3), q.shape, jnp.float32)
    psched = OPS.make_packed_sched(lens, block=blk, window=wins,
                                   prefix=pres)
    got = _vjp_grads("scan", q, k, v, do, psched)

    base, want = 0, [[], [], []]
    for s_r, w, p in zip(lens, wins, pres):
        seg = slice(base, base + s_r)
        f = lambda q_, k_, v_: OPS.triangular_attention(
            q_, k_, v_, window=w, prefix=p, impl="scan", block_q=blk,
            block_k=blk)
        _, vjp = jax.vjp(f, q[:, :, seg], k[:, :, seg], v[:, :, seg])
        for acc, g in zip(want, vjp(do[:, :, seg])):
            acc.append(g)
        base += s_r
    for g, w_parts, nm in zip(got, want, "qkv"):
        O.assert_close(g, jnp.concatenate(w_parts, axis=2),
                       "attn_bitwise_pair",
                       err_msg=f"d{nm} {lens} {wins} {pres}")


# ---------------------------------------------------------------------------
# train level: packed ragged batch == pad-to-max batch, same documents
# ---------------------------------------------------------------------------


def test_train_step_packed_equals_padded():
    """Same documents, two layouts: the packed ragged row and the
    pad-to-max batch produce identical loss and parameter grads (the mask
    restricts both means to the same real-token set; packed attention is
    per-doc causal-isolated). Then make_train_step(packed=...) takes a
    full optimizer step on the packed batch."""
    cfg = REG.smoke_config("yi-9b")
    params = MD.init_params(jax.random.key(0), cfg)
    docs = DATA.PackedDocsLM(cfg, (13, 3, 7), block=4, seed=1)
    psched = OPS.make_packed_sched(docs.member_lens, block=4,
                                   window=cfg.sliding_window)
    batch, padded = docs.batch(0), docs.padded_batch(0)

    def packed_loss(p):
        return MD.loss_fn(p, cfg, batch, packed=psched, aux_weight=0.0,
                          block=4)[0]

    def padded_loss(p):
        return MD.loss_fn(p, cfg, padded, aux_weight=0.0, block=4)[0]

    (lp, gp) = jax.value_and_grad(packed_loss)(params)
    (ld, gd) = jax.value_and_grad(padded_loss)(params)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-6)
    err = jax.tree.reduce(max, jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gp, gd))
    assert err < 2e-6, err

    opt = OPT.OptConfig()
    state = TS.init_state(jax.random.key(0), cfg, opt)
    step = TS.make_train_step(cfg, opt, packed=psched, aux_weight=0.0,
                              block=4)
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1


# ---------------------------------------------------------------------------
# data level: bin packing + batch construction
# ---------------------------------------------------------------------------


@given(st.data())
@settings(max_examples=10, deadline=None)
def test_property_pack_documents_ffd(data):
    block = 4
    cap = block * data.draw(st.integers(min_value=2, max_value=8))
    n = data.draw(st.integers(min_value=1, max_value=12))
    lens = [data.draw(st.integers(min_value=1, max_value=cap))
            for _ in range(n)]
    bins = DATA.pack_documents(lens, cap, block=block)
    placed = sorted(i for b in bins for i in b)
    assert placed == list(range(n))  # every doc exactly once
    pad = lambda s: -(-s // block) * block
    for b in bins:
        assert sum(pad(lens[i]) for i in b) <= cap
        assert [pad(lens[i]) for i in b] == \
            sorted([pad(lens[i]) for i in b], reverse=True)


def test_packed_docs_batch_layout():
    cfg = REG.smoke_config("yi-9b")
    docs = DATA.PackedDocsLM(cfg, (5, 2, 9), block=4, seed=3)
    assert docs.member_lens == (8, 4, 12)
    b = docs.batch(2)
    assert b["tokens"].shape == (1, 24)
    # positions restart per document and run through the pad tail
    want_pos = np.concatenate([np.arange(8), np.arange(4), np.arange(12)])
    np.testing.assert_array_equal(np.asarray(b["positions"][0]), want_pos)
    # mask covers exactly the raw doc lengths, at each member's start
    mask = np.asarray(b["mask"][0])
    assert mask.sum() == 5 + 2 + 9
    np.testing.assert_array_equal(mask[:5], 1)
    np.testing.assert_array_equal(mask[5:8], 0)
    # same real tokens appear in the padded layout, row-aligned
    p = docs.padded_batch(2)
    assert p["tokens"].shape == (3, 12)
    np.testing.assert_array_equal(np.asarray(p["tokens"][0, :5]),
                                  np.asarray(b["tokens"][0, :5]))
    np.testing.assert_array_equal(np.asarray(p["labels"][2, :9]),
                                  np.asarray(b["labels"][0, 12:21]))
    # deterministic per (seed, step); different steps differ
    b2 = docs.batch(2)
    np.testing.assert_array_equal(np.asarray(b["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(docs.batch(3)["tokens"]),
                              np.asarray(b["tokens"]))
