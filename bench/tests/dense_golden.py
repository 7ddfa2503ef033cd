"""Readings of the dense reference and work counts that a refactor of the
harness must leave as they are, at the toy sizes of the reference tests.

  python3 bench/tests/dense_golden.py bench/tests/data/dense_golden.json

records them; ``test_bench_reference.py`` reads them back and compares.
For each toy configuration: the serving reference's gaps and argmax on
fixed sequences (``reference.run``), the training reference's loss and
per-leaf gradient and change norms over two AdamW steps
(``reference.train_reference``), and the operations and bytes the work
functions count from the configuration's shape.
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np

TESTS = pathlib.Path(__file__).resolve().parent
ROOT = TESTS.parents[1]
for _p in (str(TESTS), str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import bench_tiny as T  # noqa: E402

SEED = 2**32 + 17
SEQ_LENS = (30, 17)
ROWS = ((20, 30), (5, 17))
LENS = [20, 7, 33, 12, 50]
KV_LENS = [1, 9, 40, 17]


def configs() -> dict:
    """The toy configurations of test_bench_reference.py."""
    swiglu = dict(T.TINY_CONFIG, torch_dtype="float32")
    mqa_gelu = dict(swiglu, num_attention_heads=6, num_key_value_heads=1,
                    mlp="gelu", hidden_act="gelu_pytorch_tanh")
    return {"gqa_swiglu": swiglu, "mqa_gelu": mqa_gelu}


def readings(model, shape) -> dict:
    """``model``: the reference's model object of a configuration;
    ``shape``: its work shape."""
    from bench.harness import generate as G
    from bench.harness import reference as REF
    from bench.harness import work as WK

    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, model.vocab, size=n).astype(np.int32)
            for n in SEQ_LENS]
    rows = [np.arange(*r) for r in ROWS]
    res = REF.run(model, SEED, seqs, rows,
                  score=[[s[r] for s, r in zip(seqs, rows)]], pad_to=48,
                  batch=3)
    traffic = dict(T.TINY_TRAIN, doc_lens=LENS)
    batches = []
    for step in range(traffic["check_steps"]):
        b = G.train_batch(traffic, model.vocab, SEED, step)
        batches.append({"tokens": b["real_tokens"],
                        "labels": b["real_labels"], "docs": LENS})
    train = REF.train_reference(model, SEED, batches, traffic["optimizer"])
    return {
        "gaps": [g.tolist() for g in res["gaps"][0]],
        "argmax": [a.tolist() for a in res["argmax"]],
        "loss": train["loss"], "grad_norm": train["grad_norm"],
        "grad": train["grad"], "change": train["change"],
        "work": {
            "layer_matmul_params": shape.layer_matmul_params,
            "matmul_params": shape.matmul_params,
            "kv_bytes_per_token": shape.kv_bytes_per_token,
            "packed_fwd": WK.packed_fwd(shape, LENS),
            "packed_bwd": WK.packed_bwd(shape, LENS),
            "decode_attn": WK.decode_attn(shape, KV_LENS),
            "prefill_step": WK.prefill_step(shape, LENS),
            "decode_step": WK.decode_step(shape, KV_LENS),
            "train_step": WK.train_step(shape, LENS)}}


def main(out: str) -> int:
    from bench.harness import spec as SP

    data = {}
    for name, config in configs().items():
        fam = SP.load_family(config)
        data[name] = readings(fam.Reference.from_config(config),
                              fam.Shape.from_config(config))
    pathlib.Path(out).write_text(json.dumps(data, indent=1, sort_keys=True)
                                 + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
