"""What decides ``correct``: the served tokens against the plain reference.

Serving: once the window has closed and the program's state is freed, a
sample of the finished requests, drawn from the seed and holding the
longest one, goes through the float32 reference (reference.py) over each
prompt with its served tokens. The number compared is the widest gap by
which a served (greedy) token's reference logit lies below the
reference's best logit at that position: 0 where the program picked the
reference's token, small where rounding flipped a near tie, large where a
token or the state it came from was wrong. Its limit is in
bench/limits/<workload>.json with the readings it was set from.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.harness import generate as G
from bench.harness import reference as REF
from bench.harness import spec as SP


def sample_served(finished, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the
    seed, in a fixed order."""
    if not finished:
        return []
    by_uid = sorted(finished, key=lambda s: s.item.uid)
    longest = max(by_uid, key=lambda s: (len(s.item.prompt)
                                         + len(s.req.out), -s.item.uid))
    rest = [s for s in by_uid if s is not longest]
    rng = G.run_rng(seed, 4)
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gap(config: dict, traffic: dict, seed: int, finished, *,
               base=SP.BENCH_DIR) -> Tuple[float, dict]:
    """(widest gap over the sample, details). A token outside the
    vocabulary reads as an infinite gap."""
    chk = traffic["check"]
    sample = sample_served(finished, seed, chk["requests"])
    if not sample:
        return float("inf"), {"requests": 0, "tokens": 0}
    vocab = config["vocab_size"]
    pairs = [(s.item.prompt, np.asarray(s.req.out, np.int64))
             for s in sample]
    if any(((o < 0) | (o >= vocab)).any() for _, o in pairs):
        return float("inf"), {"requests": len(pairs), "tokens": 0,
                              "out_of_vocab": True}
    model = SP.load_family(config, base).Reference.from_config(config)
    gaps = REF.served_gaps(model, seed, [(p, o.astype(np.int32))
                                         for p, o in pairs],
                           pad_to=chk["pad_to"], batch=chk["requests"])
    flat = np.concatenate(gaps)
    return float(flat.max()), {
        "requests": len(pairs), "tokens": int(flat.size),
        "median_gap": float(np.median(flat)),
        "exact_share": float(np.mean(flat == 0.0)),
        "longest": int(max(len(p) + len(o) for p, o in pairs))}


def control_gap(config: dict, traffic: dict, seed: int, finished, *,
                base=SP.BENCH_DIR) -> Tuple[float, dict]:
    """The control on the same sample: the token the fp8 reference puts
    first at each position, scored like a served token."""
    chk = traffic["check"]
    sample = sample_served(finished, seed, chk["requests"])
    model = SP.load_family(config, base).Reference.from_config(config)
    gaps = REF.control_gaps(model, seed, [(s.item.prompt,
                                           np.asarray(s.req.out, np.int32))
                                          for s in sample],
                            pad_to=chk["pad_to"], batch=chk["requests"])
    flat = np.concatenate(gaps)
    return float(flat.max()), {"tokens": int(flat.size),
                               "median_gap": float(np.median(flat))}


def verdict(values: dict, limits: dict) -> Tuple[bool, dict]:
    """Each compared number beside its limit; correct when every number is
    at or under its limit."""
    checks = {}
    ok = True
    for name, value in values.items():
        limit = limits[name]["limit"]
        checks[name] = {"value": value, "limit": limit}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, checks


def check_lines(checks: dict) -> List[str]:
    return [f"check {n}: {c['value']!r} (limit {c['limit']!r})"
            for n, c in checks.items()]
