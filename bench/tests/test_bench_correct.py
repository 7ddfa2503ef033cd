"""``correct`` at a size a test run holds: a sound run passes, the control
(the fp8 reference in the program's place) and a broken timed path fail.

Each test drives the rest of a run past the harness's look for a chip,
at toy widths on the CPU, with the program broken underneath where the
test says so."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import bench_tiny as T
from bench import run as R
from bench.harness import checks as CK
from bench.harness import serving as SV

SEED = 3 * 2**31 + 5


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_correct")
    base = T.write_tree(root, {"tiny.open": ("tiny", "open"),
                               "tiny.closed": ("tiny", "closed")},
                        {"tiny": T.TINY_CONFIG},
                        {"open": T.TINY_OPEN, "closed": T.TINY_CLOSED},
                        {"tiny.open": T.GAP_LIMIT,
                         "tiny.closed": T.GAP_LIMIT})
    return root, base


def _run(tree, cell, seconds=1.5):
    root, base = tree
    return R.run_cell(cell, SEED, seconds, False, root=root, base=base)


@pytest.mark.parametrize("cell", ["tiny.open", "tiny.closed"])
def test_sound_run_is_correct(tree, cell):
    res = _run(tree, cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_fails():
    """The scaled-fp8 reference, scored like served tokens on the same
    prompts, reads above the limit that sound runs stay under: ``correct``
    comes out false for it."""
    cell = SV.ServeCell(T.TINY_CONFIG, T.TINY_OPEN, SEED, 1.5)
    cell.setup()
    cell.window()
    finished = cell.finished()
    cell.release()
    sound, _ = CK.served_gap(T.TINY_CONFIG, T.TINY_OPEN, SEED, finished)
    control, _ = CK.control_gap(T.TINY_CONFIG, T.TINY_OPEN, SEED, finished)
    assert CK.verdict({"served_gap_max": sound}, T.GAP_LIMIT)[0] is True
    ok, checks = CK.verdict({"served_gap_max": control}, T.GAP_LIMIT)
    assert ok is False, checks


def test_altered_token_fails(tree, monkeypatch):
    """A token altered where it is produced: every third round's sampled
    tokens are shifted by one."""
    from repro.serve import decode as D

    real = D.sample_logits
    calls = {"n": 0}

    def altered(*a, **kw):
        tok = real(*a, **kw)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            tok = (tok + 1) % T.TINY_CONFIG["vocab_size"]
        return tok

    monkeypatch.setattr(D, "sample_logits", altered)
    assert _run(tree, "tiny.open")["correct"] is False


def test_state_left_unchanged_fails(tree, monkeypatch):
    """A decode step that returns its state unchanged: the new token's
    K/V never reach the cache."""
    from repro.models import model as MD

    real = MD.decode_step

    def stale(params, cfg, cache, *a, **kw):
        logits, _ = real(params, cfg, cache, *a, **kw)
        return logits, cache

    monkeypatch.setattr(MD, "decode_step", stale)
    jax.clear_caches()
    try:
        assert _run(tree, "tiny.closed")["correct"] is False
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_verdict_needs_every_number_under_its_limit():
    ok, checks = CK.verdict({"a": 0.1, "b": float("nan")},
                            {"a": {"limit": 0.2}, "b": {"limit": 1.0}})
    assert ok is False and checks["a"] == {"value": 0.1, "limit": 0.2}
    assert CK.verdict({"a": 0.2}, {"a": {"limit": 0.2}})[0] is True
    assert jnp.isinf(CK.served_gap(T.TINY_CONFIG, T.TINY_OPEN, 1, [])[0])
