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
from bench.harness import spec as SP

BENCH = pathlib.Path(__file__).resolve().parents[1]


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
