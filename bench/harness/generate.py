"""The one traffic generator: every mix is a data file of its parameters.

A mix's sizes and arrival gaps are drawn once from the file's
``shape_seed``; the run's ``--seed`` draws their order (which prompt goes
with which output length, and the order of the gaps) and the token ids.
So every seed offers the same work and the same set of gaps, in another
order and at other times.

Serving mixes (``"kind": "serve"``):

  "loop": "open"    Poisson arrivals at ``rate_per_s`` (exponential gaps);
                    a request is due at its arrival, whatever the server
                    is doing.
  "loop": "closed"  ``clients`` callers, each sending its next request
                    the moment its previous one completes.

Training mixes (``"kind": "train"``) give the document lengths of one
packed row, fixed in the file, so every step has the same layout; the
seed and the step draw the tokens. ``fill_row`` is how such a file's
lengths were drawn.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    """One request of a serving mix."""
    uid: int
    due: float          # seconds after the window opens (open loop)
    prompt: np.ndarray  # int32 token ids
    max_new: int


def run_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one run (any integer seed)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a lognormal with the given median and sigma,
    rounded to a multiple of ``quantum`` (default 1) and clipped to
    [min, max]."""
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    q = spec.get("quantum", 1)
    return np.clip(np.rint(raw / q) * q, spec["min"], spec["max"]).astype(
        np.int64)


def pool_size(traffic: dict, seconds: float) -> int:
    """Requests the mix offers over warm-up plus a window of ``seconds``."""
    if traffic["loop"] == "open":
        span = traffic.get("warmup_s", 0.0) + seconds
        return int(math.ceil(traffic["rate_per_s"] * span * 1.25)) + 16
    return int(traffic["clients"] * traffic.get("pool_per_client", 4))


def serve_items(traffic: dict, seed: int, seconds: float,
                vocab: int) -> List[Item]:
    """The run's requests in the order they are offered. Open loop: ``due``
    counts from the start of the mix's warm-up."""
    n = pool_size(traffic, seconds)
    shape_rng = np.random.default_rng(traffic["shape_seed"])
    prompts = draw_lengths(traffic["prompt_len"], n, shape_rng)
    outs = draw_lengths(traffic["output_len"], n, shape_rng)
    gaps = shape_rng.exponential(1.0 / traffic["rate_per_s"], size=n) \
        if traffic["loop"] == "open" else np.zeros(n)
    rng = run_rng(seed, 1)
    order = rng.permutation(n)
    due = np.cumsum(rng.permutation(gaps))
    items = []
    for uid, i in enumerate(order):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32)
        items.append(Item(uid, float(due[uid]), toks, int(outs[i])))
    return items


def residual_lengths(items: List[Item], clients: int,
                     seed: int) -> List[Item]:
    """Start a closed loop in its steady state: each client's first
    request keeps a uniform share, drawn from the run's seed, of its output
    length (the residual life of the request that client would be half-way
    through)."""
    rng = run_rng(seed, 2)
    first = []
    for it in items[:clients]:
        keep = max(1, int(math.ceil(it.max_new * rng.uniform())))
        first.append(dataclasses.replace(it, max_new=keep))
    return first + items[clients:]


def distinct_prompt_lens(traffic: dict, seconds: float) -> List[int]:
    """Every prompt length the mix can offer in a run of ``seconds``."""
    n = pool_size(traffic, seconds)
    shape_rng = np.random.default_rng(traffic["shape_seed"])
    return sorted(set(int(x) for x in draw_lengths(traffic["prompt_len"], n,
                                                   shape_rng)))


def train_tokens(traffic: dict, vocab: int, seed: int, step: int
                 ) -> List[np.ndarray]:
    """Token ids of each document of one packed step (length + 1 each: the
    last is the final target)."""
    rng = np.random.default_rng([seed % (1 << 64), 3, step])
    return [rng.integers(0, vocab, size=n + 1, dtype=np.int32)
            for n in traffic["doc_lens"]]


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    """One packed row as the program trains on it: each document's tokens
    at the start of a ``block``-multiple segment, labels shifted within the
    document, mask 1 on real tokens, positions restarting per segment; all
    (1, S). Also the real tokens end to end (``real_tokens``,
    ``real_labels``), the view the plain reference takes."""
    block = traffic["block"]
    docs = train_tokens(traffic, vocab, seed, step)
    pads = [-(-n // block) * block for n in traffic["doc_lens"]]
    s = sum(pads)
    row = {"tokens": np.zeros((1, s), np.int32),
           "labels": np.zeros((1, s), np.int32),
           "mask": np.zeros((1, s), np.float32),
           "positions": np.zeros((1, s), np.int32)}
    st = 0
    for n, pad, doc in zip(traffic["doc_lens"], pads, docs):
        row["tokens"][0, st:st + n] = doc[:-1]
        row["labels"][0, st:st + n] = doc[1:]
        row["mask"][0, st:st + n] = 1.0
        row["positions"][0, st:st + pad] = np.arange(pad)
        st += pad
    row["real_tokens"] = np.concatenate([d[:-1] for d in docs])
    row["real_labels"] = np.concatenate([d[1:] for d in docs])
    return row


def fill_row(spec: dict, row_tokens: int, block: int, seed: int,
             tries: int = 10_000) -> List[int]:
    """Document lengths for one packed row of ``row_tokens`` tokens: draw
    from the lognormal ``spec`` and keep each draw whose padded length
    still fits, until less than a block is left or ``tries`` draws."""
    rng = np.random.default_rng(seed)
    lens, left = [], row_tokens
    for _ in range(tries):
        if left < block:
            break
        n = int(draw_lengths(spec, 1, rng)[0])
        if -(-n // block) * block <= left:
            lens.append(n)
            left -= -(-n // block) * block
    return lens
