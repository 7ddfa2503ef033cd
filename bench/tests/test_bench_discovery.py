"""The harness finds every part by name; the generator is seeded and
clipped as its files say."""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

import bench_tiny as T
from bench import run as R
from bench.harness import generate as G
from bench.harness import serving as SV
from bench.harness import spec as SP

BENCH = pathlib.Path(__file__).resolve().parents[1]
PROBE_SRC = (pathlib.Path(__file__).resolve().parent
             / "probe_family.py").read_text()
# a tiny mixture of experts (4 experts, top-2) of the probe family, in
# float32 so the program and the reference agree to rounding
PROBE_MOE = dict(T.TINY_CONFIG, family="probe_moe", num_local_experts=4,
                 num_experts_per_tok=2, torch_dtype="float32")


def test_repo_benchmark_resolves_every_name():
    bench = SP.load_benchmark()
    for cell in bench["workloads"]:
        config = SP.load_config(bench, cell["config"])
        assert config["num_hidden_layers"] > 0
        traffic = SP.load_traffic(cell["traffic"])
        assert traffic["kind"] in ("serve", "train")
        limits = SP.load_limits(cell["name"])
        assert all("limit" in v for v in limits.values())
        e2e = {m["name"] for m in SP.end_to_end_metrics(bench, cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = SP.per_layer_metrics(bench, cell["name"])
        assert layer, cell["name"]
        for m in layer:
            assert callable(SP.metric_reader(m["name"]))
            assert m["moves"] in e2e
    for m in bench["per_layer"]:
        assert m.get("workloads"), m["name"]
    assert set(SP.kernel_families()) >= {"packed_fwd", "packed_decode_fwd"}


def test_added_files_are_found_and_run(tmp_path):
    """A configuration, a traffic mix, a limit and a per-layer metric added
    as new files under new names run through the harness's loaders; no
    file the harness already has is edited."""
    before = {p: p.read_bytes() for p in BENCH.rglob("*.py")}
    base = T.write_tree(tmp_path, {"probe-model.probe_mix":
                                   ("probe-model", "probe_mix")},
                        {"probe-model": T.TINY_CONFIG},
                        {"probe_mix": T.TINY_OPEN},
                        {"probe-model.probe_mix": T.GAP_LIMIT})
    (base / "metrics" / "probe_metric.py").write_text(
        "def read(rec):\n    return 100.0 + len(rec['rounds'])\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "probe_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "probe",
        "moves": "itl_p95_ms", "workloads": ["probe-model.probe_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = SP.load_benchmark(tmp_path)
    cell = SP.workload(loaded, "probe-model.probe_mix")
    assert SP.load_config(loaded, cell["config"], tmp_path)["name"] == "tiny"
    assert SP.load_traffic(cell["traffic"], base)["loop"] == "open"
    names = [m["name"] for m in SP.per_layer_metrics(loaded, cell["name"])]
    assert "probe_metric" in names
    assert SP.metric_reader("probe_metric", base)({"rounds": [1, 2]}) == 102

    res = R.run_cell("probe-model.probe_mix", 12345, 1.0, False,
                     root=tmp_path, base=base)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
    assert list(res)[-1] == "checks"
    assert {p: p.read_bytes() for p in BENCH.rglob("*.py")} == before


def _probe_tree(root: pathlib.Path, family_src: str) -> pathlib.Path:
    """A tree whose one cell is of a family written only as new files:
    the family, a metric that reads its Shape and one that reads an
    engine counter."""
    base = T.write_tree(root, {"probe-moe.probe_closed":
                               ("probe-moe", "probe_closed")},
                        {"probe-moe": PROBE_MOE},
                        {"probe_closed": T.TINY_CLOSED},
                        {"probe-moe.probe_closed": T.GAP_LIMIT})
    (base / "families" / "probe_moe.py").write_text(family_src)
    (base / "metrics" / "probe_routed.py").write_text(
        "def read(rec):\n"
        "    return float(rec['shape'].routed_expert_params)\n")
    (base / "metrics" / "probe_prefill_tokens.py").write_text(
        "def read(rec):\n"
        "    return float(sum(r.counters.get('prefill_tokens', 0)\n"
        "                     for r in rec['rounds']))\n")
    return base


@pytest.mark.parametrize("top_k,correct", [("config", True), ("1", False)])
def test_a_family_added_as_files_runs(tmp_path, monkeypatch, top_k,
                                      correct):
    """A mixture-of-experts family, a new file under families/, runs
    through run_cell with no harness file edited: correct against its own
    float32 reference, and not correct when that reference routes each
    token to one expert where the program routes it to two."""
    before = {p: p.read_bytes() for p in BENCH.rglob("*.py")}
    src = PROBE_SRC
    if top_k != "config":
        old = 'top_k=c["num_experts_per_tok"]'
        assert src.count(old) == 1
        src = src.replace(old, f"top_k={top_k}")
    base = _probe_tree(tmp_path, src)
    seen = {}
    real = SV.serve_cell

    def keep(*a, **kw):
        seen.update(real(*a, **kw))
        return seen

    monkeypatch.setattr(SV, "serve_cell", keep)
    res = R.run_cell("probe-moe.probe_closed", 2**31 + 77, 1.0, False,
                     root=tmp_path, base=base)
    assert res["correct"] is correct, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    family = SP.load_family(PROBE_MOE, base)
    assert SV.ServeCell(PROBE_MOE, T.TINY_CLOSED, 1, 1.0,
                        base=base).cfg.n_experts == 4
    rec = seen["rec"]
    assert isinstance(rec["shape"], family.Shape)
    assert SP.metric_reader("probe_routed", base)(rec) == \
        2 * 2 * 3 * 64 * 96
    admitted = sum(sum(r.admitted) for r in rec["rounds"])
    assert SP.metric_reader("probe_prefill_tokens", base)(rec) >= admitted
    assert {p: p.read_bytes() for p in BENCH.rglob("*.py")} == before


def test_a_family_without_train_block_refuses_training(tmp_path):
    from bench.harness import training as TRN

    base = _probe_tree(tmp_path, PROBE_SRC)
    with pytest.raises(SP.SpecError, match="train_block"):
        TRN.TrainCell(PROBE_MOE, T.TINY_TRAIN, 1, base=base)
    with pytest.raises(SP.SpecError, match="no family file"):
        SP.load_family(dict(PROBE_MOE, family="absent"), base)


def test_round_counters_are_the_registry_change_over_the_round():
    """Each window round carries the change of every engine counter over
    that round; together they are the change over the window, and
    ``packed`` is the packed-decode counter's."""
    cell = SV.ServeCell(T.TINY_CONFIG, T.TINY_CLOSED, 2**31 + 3, 1.0)
    cell.setup()
    start = cell.engine.registry.snapshot()["counters"]
    cell.window()
    end = cell.engine.registry.snapshot()["counters"]
    cell.release()
    total = {}
    for r in cell.rounds:
        assert r.packed == (r.counters.get("decode_packed_launches", 0) > 0)
        assert all(v > 0 for v in r.counters.values())
        for k, v in r.counters.items():
            total[k] = total.get(k, 0) + v
    assert total == SV.counter_change(start, end)
    assert total["decode_rounds"] > 0 and total["prefill_tokens"] > 0
    assert SV.counter_change({"a": 1, "b": 2}, {"a": 1, "b": 5, "c": 1}) \
        == {"b": 3, "c": 1}


@pytest.mark.parametrize("name", ["serve_decode", "tiny_open"])
def test_serving_mix_is_seeded_and_clipped(name):
    traffic = T.TINY_OPEN if name == "tiny_open" else SP.load_traffic(name)
    vocab = 64000
    a = G.serve_items(traffic, 2**31 + 9, 40.0, vocab)
    b = G.serve_items(traffic, 2**31 + 9, 40.0, vocab)
    c = G.serve_items(traffic, 7, 40.0, vocab)
    assert [(x.due, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new, x.prompt.tolist()) for x in b]
    # another seed: the same sizes and gaps in another order, other tokens
    sizes = lambda items: sorted((len(x.prompt), x.max_new) for x in items)
    gaps = lambda items: sorted(np.diff([0.0] + [x.due for x in items]))
    assert sizes(a) == sizes(c)
    assert np.allclose(gaps(a), gaps(c))
    assert [(len(x.prompt), x.max_new) for x in a] != \
        [(len(x.prompt), x.max_new) for x in c]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    for spec, values in ((traffic["prompt_len"], [len(x.prompt) for x in a]),
                         (traffic["output_len"], [x.max_new for x in a])):
        assert spec["min"] <= min(values) and max(values) <= spec["max"]
    assert all(0 <= t < vocab for x in a for t in x.prompt[:50])
    assert max(len(x.prompt) for x in a) + traffic["output_len"]["max"] \
        <= traffic["engine"]["max_len"]


def test_runner_refuses_a_machine_without_a_tpu():
    """Off a TPU the command exits non-zero and prints no result line."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "granite-34b.serve.decode", "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_length_draw_hits_both_clip_bounds():
    spec = {"median": 100, "sigma": 2.0, "min": 50, "max": 400}
    v = G.draw_lengths(spec, 2000, np.random.default_rng(0))
    assert v.min() == 50 and v.max() == 400
    assert 80 <= np.median(v) <= 125
    q = G.draw_lengths(dict(spec, min=48, max=400, quantum=16), 2000,
                       np.random.default_rng(0))
    assert (q % 16 == 0).all() and q.min() == 48 and q.max() == 400


def test_training_tokens_are_seeded():
    traffic = {"doc_lens": [5, 64, 3]}
    a = G.train_tokens(traffic, 1000, 2**40, 3)
    b = G.train_tokens(traffic, 1000, 2**40, 3)
    c = G.train_tokens(traffic, 1000, 2**40, 4)
    assert [x.tolist() for x in a] == [x.tolist() for x in b]
    assert [x.tolist() for x in a] != [x.tolist() for x in c]
    assert [len(x) for x in a] == [6, 65, 4]
