"""The plain reference against the program's model at reduced width (CPU).

Both run in float32 at full matmul precision on the same seeded weights,
so they agree to float32 rounding; a wrong mask, RoPE pairing, head
grouping, norm or activation would miss by O(1)."""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as T
import dense_golden as DG
from bench.harness import generate as G
from bench.harness import reference as REF
from bench.harness import serving as SV
from bench.harness import spec as SP
from bench.harness import weights as WT
from repro.kernels.tri_attn import ops as OPS
from repro.models import model as MD

SEED = 2**32 + 17


def _configs():
    swiglu = dict(T.TINY_CONFIG, torch_dtype="float32")
    mqa_gelu = dict(swiglu, num_attention_heads=6, num_key_value_heads=1,
                    mlp="gelu", hidden_act="gelu_pytorch_tanh")
    return {"gqa_swiglu": swiglu, "mqa_gelu": mqa_gelu}


@pytest.fixture(params=sorted(_configs()))
def config(request):
    return _configs()[request.param]


def test_logits_match_the_program(config):
    cfg = SV.model_config(config)
    params = SV.make_params(cfg, SEED)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, config["vocab_size"], size=(2, 40)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        hidden, _, _ = MD.forward(params, cfg, {"tokens": tokens},
                                  attn_impl="ref", remat=False)
        want = MD.logits_from_hidden(params, cfg, hidden)
    m = REF.Model.from_config(config)
    got = REF.logits(REF.params(m, SEED), m, tokens)
    err = np.abs(np.asarray(got) - np.asarray(want)).max()
    assert err < 1e-4 * np.abs(np.asarray(want)).max()


def test_layer_by_layer_run_matches_whole_model(config):
    """The serving check's run() (weights made again inside each layer's
    program) reads the same gaps as the whole-model form."""
    m = REF.Model.from_config(config)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, m.vocab, size=n).astype(np.int32)
            for n in (30, 17)]
    rows = [np.arange(20, 30), np.arange(5, 17)]
    res = REF.run(m, SEED, seqs, rows, score=[[s[r] for s, r in
                                               zip(seqs, rows)]],
                  pad_to=48, batch=3)
    p = REF.params(m, SEED)
    for s, r, gap, am in zip(seqs, rows, res["gaps"][0], res["argmax"]):
        lg = np.asarray(REF.logits(p, m, jnp.asarray(s)[None]))[0][r]
        want = lg.max(-1) - lg[np.arange(len(r)), s[r]]
        np.testing.assert_allclose(gap, want, atol=1e-4)
        np.testing.assert_array_equal(am, lg.argmax(-1))


def test_packed_document_loss_matches_the_program():
    config = dict(T.TINY_CONFIG, torch_dtype="float32")
    cfg = SV.model_config(config)
    params = SV.make_params(cfg, SEED)
    traffic = {"doc_lens": [20, 7, 33], "block": 16}
    docs = G.train_tokens(traffic, config["vocab_size"], SEED, 0)
    pads = [-(-n // 16) * 16 for n in traffic["doc_lens"]]
    s = sum(pads)
    batch = {k: np.zeros((1, s), dt) for k, dt in (
        ("tokens", np.int32), ("labels", np.int32), ("mask", np.float32),
        ("positions", np.int32))}
    st = 0
    for n, pad, doc in zip(traffic["doc_lens"], pads, docs):
        batch["tokens"][0, st:st + n] = doc[:-1]
        batch["labels"][0, st:st + n] = doc[1:]
        batch["mask"][0, st:st + n] = 1.0
        batch["positions"][0, st:st + pad] = np.arange(pad)
        st += pad
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    psched = OPS.make_packed_sched(pads, block=16)
    with jax.default_matmul_precision("highest"):
        want, _ = MD.loss_fn(params, cfg, batch, attn_impl="scan",
                             remat=False, packed=psched)
    m = REF.Model.from_config(config)
    # one document at a time, over the real tokens only, the
    # cross-entropy in blocks of rows
    real = np.concatenate([d[:-1] for d in docs])
    labels = np.concatenate([d[1:] for d in docs])
    per_doc = REF.train_loss(REF.flat_params(m, SEED), jnp.asarray(real),
                             jnp.asarray(labels), m, "f32",
                             tuple(traffic["doc_lens"]))
    assert abs(float(per_doc) - float(want)) < 1e-5 * abs(float(want))


def test_weights_made_again_layer_by_layer():
    """The reference's one-layer weights are the program's layer slice."""
    config = T.TINY_CONFIG
    cfg = SV.model_config(config)
    params = SV.make_params(cfg, SEED)
    m = REF.Model.from_config(config)
    again = WT.layer_weights(WT.base_key(SEED), 1, m.block_shapes(),
                             m.served_dtypes())
    served = params["layers"]["l0"]
    for path, value in again.items():
        leaf = served
        for part in path.split("/")[2:]:
            leaf = leaf[part]
        # the same values up to where the two programs fuse the scale's
        # multiply-add: one float32 ulp, rarely one bf16 rounding apart
        a = np.asarray(leaf[1], np.float32)
        b = np.asarray(value)
        np.testing.assert_allclose(a, b, rtol=2.0 ** -8)
        assert np.mean(a != b) < 0.2


def _same(got, want, where=""):
    if isinstance(want, dict):
        assert sorted(got) == sorted(want), where
        for k in want:
            _same(got[k], want[k], f"{where}/{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), where
        for i, (g, w) in enumerate(zip(got, want)):
            _same(g, w, f"{where}[{i}]")
    elif isinstance(want, int):
        assert got == want, where
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=0,
                                   err_msg=where)


@pytest.mark.parametrize("name", sorted(DG.configs()))
def test_dense_family_reads_as_recorded(name):
    """Through the dense family, the reference's gaps and argmax, its
    training norms and the work counts are those recorded before the
    harness took families (bench/tests/data/dense_golden.json)."""
    want = json.loads((pathlib.Path(__file__).resolve().parent / "data"
                       / "dense_golden.json").read_text())[name]
    config = DG.configs()[name]
    fam = SP.load_family(config)
    assert fam.Reference is REF.Model
    _same(DG.readings(fam.Reference.from_config(config),
                      fam.Shape.from_config(config)), want)
