"""Work counts: from logical inputs only, the same whatever implements them."""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from bench.harness import work as WK
from repro.core import analysis as AN
from repro.core import mapping as M
from repro.core import schedule as SCH
from repro.kernels.tri_attn import ref as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
YI = WK.Shape.from_config(json.loads(
    (BENCH / "configs" / "yi-9b.train.json").read_text()))


@pytest.mark.parametrize("n", [1, 2, 3, 7, 16, 33, 128])
def test_causal_pairs_match_closed_forms(n):
    """Pairs of a causal sequence are tri(n): the paper's triangle, the
    useful blocks of every strategy at one token a block, and the True
    entries of the ref oracle's mask."""
    assert WK.causal_pairs(n) == M.tri(n)
    stats = AN.strategy_stats(n)
    assert WK.causal_pairs(n) == stats["ltm"].useful == stats["bb"].useful
    assert WK.causal_pairs(n) == int(np.asarray(R.attention_mask(n, n)).sum())


@pytest.mark.parametrize("n_blocks,block", [(4, 128), (7, 128), (5, 16)])
def test_ltm_bb_ref_schedules_get_one_count(n_blocks, block):
    """LTM launches tri(n) tiles, BB n^2 and the ref oracle a dense mask:
    each is counted from the logical sequence it covers, so all three get
    that sequence's forward count, which is not the tiles launched."""
    s = n_blocks * block
    ltm = SCH.make_schedule("ltm", n_blocks)
    bb = SCH.make_schedule("bb", n_blocks)
    assert ltm.num_blocks == M.tri(n_blocks) < bb.num_blocks == n_blocks ** 2
    assert ltm.domain_blocks == bb.domain_blocks
    covered = {"ltm": ltm.n * block, "bb": bb.n * block,
               "ref": np.asarray(R.attention_mask(s, s)).shape[0]}
    counts = {k: WK.packed_fwd(YI, [n]) for k, n in covered.items()}
    assert counts["ltm"] == counts["bb"] == counts["ref"]
    assert counts["ltm"]["flops"] == 4 * 128 * 32 * M.tri(s)


def test_packing_and_padding_do_not_move_the_count():
    lens = [300, 1, 4095, 128]
    whole = WK.packed_fwd(YI, lens)
    parts = [WK.packed_fwd(YI, [n]) for n in lens]
    assert whole["flops"] == sum(p["flops"] for p in parts)
    assert whole["bytes"] == sum(p["bytes"] for p in parts)
    bwd = WK.packed_bwd(YI, lens)
    assert bwd["flops"] == 2 * whole["flops"]


def test_decode_counts_read_only_the_valid_prefix():
    """Lockstep reads every slot's whole cache, the packed kernel only the
    valid prefix; the count is the prefix either way."""
    kv = [1, 900, 4096, 17]
    one = WK.decode_attn(YI, kv)
    assert one["flops"] == 4 * 128 * 32 * sum(kv)
    assert one["bytes"] == sum(kv) * 2 * 4 * 128 * 2 + 4 * 2 * 32 * 128 * 2
    step = WK.decode_step(YI, kv)
    assert step["bytes"] == YI.matmul_params * 2 + sum(kv) * YI.kv_bytes_per_token


def test_yi_sizes_as_stated():
    """173.0M matmul parameters a layer; every layer and the head, not the
    embedding table; 2 KiB of KV a token in each layer."""
    assert YI.layer_matmul_params == 173_015_040
    assert YI.matmul_params == YI.layers * 173_015_040 + 4096 * 64000
    assert YI.kv_bytes_per_token == YI.layers * 2 * 1024


def test_train_step_is_six_n_plus_three_forward_attentions():
    docs = [512, 64, 4096]
    t = WK.train_step(YI, docs)
    fwd = WK.packed_fwd(YI, docs)["flops"] * YI.layers
    assert t["flops"] == 6 * YI.matmul_params * sum(docs) + 3 * fwd


def test_least_time_names_its_bound():
    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    assert WK.bound_by(WK.packed_fwd(YI, [4096]), peak) == "compute"
    assert WK.bound_by(WK.decode_attn(YI, [4096] * 32), peak) == "bandwidth"
    w = WK.decode_step(YI, [1000] * 32)
    assert WK.least_seconds(w, peak) == pytest.approx(
        w["bytes"] / peak["hbm_bytes_per_s"])
