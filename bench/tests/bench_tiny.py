"""A tiny benchmark tree for CPU tests: one configuration at toy widths
and serving/training mixes sized to run in seconds, written under a
temporary directory in the layout of bench/ and BENCHMARK.json.

A new cell may add, each as a file of its own: a configuration
(configs/<config>.json), a model family (families/<family>.py, named by
the configuration's ``"family"``), a traffic mix (traffic/<traffic>.json),
its limits (limits/<workload>.json), per-layer metrics
(metrics/<metric>.py) and kernel families (kernels/<kernel>.json)."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_CONFIG = {
    "name": "tiny", "source": "toy widths for tests",
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "mlp": "swiglu", "hidden_act": "silu",
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "reduced": []}

TINY_OPEN = {
    "kind": "serve", "loop": "open", "rate_per_s": 12.0, "warmup_s": 0.3,
    "shape_seed": 5,
    "prompt_len": {"median": 20, "sigma": 0.6, "min": 8, "max": 48},
    "output_len": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
    "engine": {"slots": 4, "max_len": 64, "prefill_block": 16,
               "prefill_bucket": 32, "decode_block": 16},
    "warm_admits": 2, "trace_s": 0.5,
    "check": {"requests": 4, "pad_to": 64}}

TINY_CLOSED = dict(TINY_OPEN, loop="closed", clients=4, pool_per_client=40,
                   open_after_finished=2)
del TINY_CLOSED["rate_per_s"], TINY_CLOSED["warmup_s"]

GAP_LIMIT = {"served_gap_max": {"limit": 0.05}}

TINY_TRAIN = {
    "kind": "train", "block": 16, "doc_lens": [20, 7, 33, 12, 50],
    "optimizer": {"lr": 3e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 2,
                  "total_steps": 10000, "min_lr_frac": 0.1},
    "check_steps": 2, "batches": 4, "inflight": 2, "trace_s": 0.5}

# toy-size readings on the CPU (sound / scaled-fp8 control / half the batch
# left out / state unchanged): loss 6.6e-4 / 2.3e-3 / 9.0e-3 / -, first
# gradient 1.5e-3 / 2.6e-2 / 0.11 / -, change 3.6e-2 / 8.7e-3 / 0.20 / 1.0
TRAIN_LIMITS = {"grad_norm_gap_max": {"limit": 0.01},
                "change_norm_gap_max": {"limit": 0.07}}


def write_tree(root: pathlib.Path, cells: dict, configs: dict,
               traffics: dict, limits: dict, metrics=("decode_packed_share",
                                                      )) -> pathlib.Path:
    """A checkout-shaped tree: BENCHMARK.json plus bench/ data files and
    the families of bench/families/. The per-layer metric readers named in
    ``metrics`` are copied from bench/ under the cell-neutral names given.
    Returns the tree's bench dir."""
    base = root / "bench"
    for sub in ("configs", "families", "traffic", "limits", "metrics",
                "kernels"):
        (base / sub).mkdir(parents=True, exist_ok=True)
    for name, cfg in configs.items():
        (base / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, tr in traffics.items():
        (base / "traffic" / f"{name}.json").write_text(json.dumps(tr))
    for name, lim in limits.items():
        (base / "limits" / f"{name}.json").write_text(json.dumps(lim))
    shutil.copy(BENCH / "peaks.json", base / "peaks.json")
    for sub, pattern in (("kernels", "*.json"), ("families", "*.py")):
        for p in (BENCH / sub).glob(pattern):
            shutil.copy(p, base / sub / p.name)
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": n, "source": "toy", "file":
                     f"bench/configs/{n}.json", "reduced": [], "why": "test"}
                    for n in configs],
        "workloads": [{"name": w, "config": c, "traffic": t, "chips": 1,
                       "why": "test"} for w, (c, t) in cells.items()],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "itl_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock",
             "workloads": [w for w, (_, t) in cells.items()
                           if traffics[t]["kind"] == "serve"]},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.25, "source": "host_clock",
             "workloads": [w for w, (_, t) in cells.items()
                           if traffics[t]["kind"] == "train"]}],
        "per_layer": []}
    for m in metrics:
        src = next((BENCH / "metrics").glob(f"{m}*.py"))
        shutil.copy(src, base / "metrics" / f"{m}.py")
        bench["per_layer"].append(
            {"name": m, "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return base
