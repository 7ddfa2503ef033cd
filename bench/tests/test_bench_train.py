"""``correct`` for a training cell at a size a test run holds: a sound
run passes; the control (the fp8 reference in the program's place), a
step that returns its state unchanged and a step over half of the batch
(the mean taken over the rest) each fail a number."""

from __future__ import annotations

import pytest

import bench_tiny as T
from bench import run as R
from bench.harness import checks as CK
from bench.harness import generate as G
from bench.harness import training as TRN

SEED = 2**33 + 1


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_train")
    base = T.write_tree(root, {"tiny.train": ("tiny", "train")},
                        {"tiny": T.TINY_CONFIG}, {"train": T.TINY_TRAIN},
                        {"tiny.train": T.TRAIN_LIMITS}, metrics=())
    return root, base


@pytest.fixture(scope="module")
def reference():
    return TRN.reference_readings(T.TINY_CONFIG, T.TINY_TRAIN, SEED)


def _readings(feed=None):
    tc = TRN.TrainCell(T.TINY_CONFIG, T.TINY_TRAIN, SEED, feed=feed)
    tc.setup()
    out = tc.readings
    tc.release()
    return out


def _verdict(readings, reference):
    return CK.verdict(TRN.compare(readings, reference)["values"],
                      T.TRAIN_LIMITS)


def test_sound_run_is_correct(tree):
    root, base = tree
    res = R.run_cell("tiny.train", SEED, 1.0, False, root=root, base=base)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0


def test_control_fails(reference):
    control = TRN.reference_readings(T.TINY_CONFIG, T.TINY_TRAIN, SEED,
                                     prec="fp8")
    ok, checks = _verdict(control, reference)
    assert ok is False
    assert checks["grad_norm_gap_max"]["value"] > \
        checks["grad_norm_gap_max"]["limit"]


def test_state_left_unchanged_fails(reference):
    """A step that returns its state unchanged moves no parameter: every
    leaf's change reads 1."""
    sound = _readings()
    stale = dict(sound, change={k: 0.0 for k in sound["change"]})
    ok, checks = _verdict(stale, reference)
    assert ok is False and checks["change_norm_gap_max"]["value"] == 1.0
    assert _verdict(sound, reference)[0] is True


def test_half_of_the_batch_fails(reference):
    def half(step):
        b = G.train_batch(T.TINY_TRAIN, T.TINY_CONFIG["vocab_size"], SEED,
                          step)
        b["mask"][0, b["mask"].shape[1] // 2:] = 0.0
        return b

    ok, checks = _verdict(_readings(feed=half), reference)
    assert ok is False
    assert checks["grad_norm_gap_max"]["value"] > \
        checks["grad_norm_gap_max"]["limit"]


def test_packed_row_layout():
    b = G.train_batch(T.TINY_TRAIN, 256, 7, 0)
    lens = T.TINY_TRAIN["doc_lens"]
    assert b["tokens"].shape == (1, sum(-(-n // 16) * 16 for n in lens))
    assert b["mask"].sum() == sum(lens) == len(b["real_tokens"])
    assert (b["tokens"][b["mask"] == 1] == b["real_tokens"]).all()
    assert (b["labels"][b["mask"] == 1] == b["real_labels"]).all()
    assert list(b["positions"][0, 32:48]) == list(range(16))


def test_the_cells_row_was_drawn_by_fill_row():
    from bench.harness import spec as SP

    tr = SP.load_traffic("train_packed")
    lens = G.fill_row(tr["doc_len"], tr["row_tokens"], tr["block"],
                      tr["doc_seed"])
    assert lens == tr["doc_lens"]
    assert sum(-(-n // tr["block"]) * tr["block"] for n in lens) \
        == tr["row_tokens"]
    assert all(tr["doc_len"]["min"] <= n <= tr["doc_len"]["max"]
               for n in lens)
