"""Crash-safe engine snapshot / restore.

``snapshot(engine)`` captures EVERYTHING the serving loop's determinism
depends on — the slot table (per-slot Request, pos, last token, remaining
budget), the KV cache, the queue and finished lists, the RNG key (via
jax.random.key_data) and the engine clock reading — into host memory.
``restore(snap)`` rebuilds a fresh Engine from the snapshot's recorded
ctor kwargs and overwrites its state, so ``Engine.restore(snap).run()``
resumes TOKEN-IDENTICALLY to the engine that never stopped (greedy
decode; sampled decode resumes on the identical key stream). Request
ages survive the move between clocks: submitted_at is rebased so each
request's elapsed age — what deadlines measure — is preserved even when
a VirtualClock run is restored onto the wall clock or vice versa.

Persistence (``to_dir`` / ``from_dir``) follows train/checkpoint.py's
crash-safety argument: everything is written into ``<dir>.tmp`` and
os.replace'd into place, so a crash mid-save leaves only a .tmp the
loader ignores. Arrays land in one flat .npz (dot-joined tree paths —
params and cache are pure nested dicts, so paths rebuild the tree
exactly); non-numpy-native dtypes (bfloat16) are stored as their exact
float32 widening and cast back on load. No pickle: the format is
inspectable and version-diffable like the training checkpoints.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

SNAPSHOT_VERSION = "repro.resilience.snapshot/v1"

# dtypes np.savez round-trips natively; anything else (bfloat16, fp8) is
# widened to float32 (exact for <=32-bit floats) and cast back on load.
_NATIVE = ("float64", "float32", "float16", "int64", "int32", "int16",
           "int8", "uint64", "uint32", "uint16", "uint8", "bool")


@dataclasses.dataclass
class EngineSnapshot:
    """Host-side image of a serving engine (see module docstring)."""

    cfg: ModelConfig
    params: dict
    cache: dict
    init_kw: dict
    pos: np.ndarray
    last_tok: np.ndarray
    remaining: np.ndarray
    key_data: np.ndarray
    clock_now: float
    admit_round_idx: int
    decode_round_idx: int
    quarantined: Dict[int, int]
    slot_req: List[Optional[dict]]
    queue: List[dict]
    finished: List[dict]
    # -- fused-mode state (PR 9 seam; defaults keep v1 files loadable) --
    # the EFFECTIVE step mode at capture (init_kw carries the REQUESTED
    # one; they differ only when a recurrent arch forced "split").
    step_mode: str = "split"
    # auto_cost_measure's per-mode seconds/tile EMA — without it a
    # restored auto engine re-learns the crossover from scratch.
    mode_cost: Dict[str, Optional[float]] = dataclasses.field(
        default_factory=dict)
    # distinct fused packing templates compiled so far, as JSON-safe
    # [[padded lens...], capacity] pairs (Engine.fused_templates).
    fused_templates: List = dataclasses.field(default_factory=list)


def _req_to_dict(req) -> dict:
    return {"uid": int(req.uid), "prompt": [int(t) for t in req.prompt],
            "max_new": int(req.max_new), "out": list(req.out),
            "done": bool(req.done), "status": req.status,
            "deadline_s": req.deadline_s,
            "submitted_at": float(req.submitted_at),
            "replays": int(req.replays), "error": req.error}


def _req_from_dict(d: dict, shift: float):
    from repro.serve.engine import Request

    return Request(uid=d["uid"], prompt=np.asarray(d["prompt"], np.int32),
                   max_new=d["max_new"], out=list(d["out"]),
                   done=d["done"], status=d["status"],
                   deadline_s=d["deadline_s"],
                   submitted_at=d["submitted_at"] + shift,
                   replays=d["replays"], error=d["error"])


def _host(tree):
    return jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)


def snapshot(engine) -> EngineSnapshot:
    """Capture ``engine`` into host memory (the engine keeps running)."""
    return EngineSnapshot(
        cfg=engine.cfg,
        params=_host(engine.params),
        cache=_host(engine.cache),
        init_kw=dict(engine._init_kw),
        pos=np.array(engine.pos, np.int32),
        last_tok=np.asarray(engine.last_tok),
        remaining=np.asarray(engine.remaining).copy(),
        key_data=np.asarray(jax.random.key_data(engine.key)),
        clock_now=float(engine.clock()),
        admit_round_idx=engine._admit_round_idx,
        decode_round_idx=engine._decode_round_idx,
        quarantined=dict(engine.quarantined),
        slot_req=[None if r is None else _req_to_dict(r)
                  for r in engine.slot_req],
        queue=[_req_to_dict(r) for r in engine.queue],
        finished=[_req_to_dict(r) for r in engine.finished],
        step_mode=engine.step_mode,
        mode_cost=dict(engine._mode_cost),
        fused_templates=sorted(
            [[int(p) for p in tpl], int(cap)]
            for tpl, cap in engine.fused_templates))


def restore(snap: EngineSnapshot, *, params=None, fault_plan=None,
            clock=None, retry=None, escalate_step_errors: bool = False):
    """Rebuild an Engine from ``snap``; run() resumes token-identically.

    ``params`` overrides the snapshot's weights (e.g. to share one
    device copy across engines); fault_plan/clock/retry/
    escalate_step_errors are the runtime harness of the NEW process and
    default to a clean stand-alone engine (a Fleet restores its replicas
    with escalate_step_errors=True)."""
    from repro.serve.engine import Engine

    eng = Engine(snap.params if params is None else params, snap.cfg,
                 fault_plan=fault_plan, clock=clock, retry=retry,
                 escalate_step_errors=escalate_step_errors,
                 **snap.init_kw)
    if snap.step_mode != eng.step_mode:
        raise ValueError(
            f"snapshot captured effective step_mode={snap.step_mode!r} "
            f"but the rebuilt engine resolved {eng.step_mode!r} — the "
            "config drifted between capture and restore")
    eng._mode_cost.update(snap.mode_cost)
    eng.fused_templates = {(tuple(tpl), int(cap))
                           for tpl, cap in snap.fused_templates}
    eng.cache = jax.tree.map(jnp.asarray, snap.cache)
    eng.pos = np.array(snap.pos, np.int32)
    eng.last_tok = jnp.asarray(snap.last_tok)
    eng.remaining = np.asarray(snap.remaining).copy()
    eng.key = jax.random.wrap_key_data(jnp.asarray(snap.key_data))
    eng.quarantined = dict(snap.quarantined)
    eng._admit_round_idx = snap.admit_round_idx
    eng._decode_round_idx = snap.decode_round_idx
    # rebase request ages onto the new clock: elapsed age (what deadlines
    # measure) is preserved across the restore.
    shift = float(eng.clock()) - snap.clock_now
    eng.slot_req = [None if d is None else _req_from_dict(d, shift)
                    for d in snap.slot_req]
    eng.queue = [_req_from_dict(d, shift) for d in snap.queue]
    eng.finished = [_req_from_dict(d, shift) for d in snap.finished]
    return eng


def strip_for_restart(snap: EngineSnapshot) -> EngineSnapshot:
    """A cleaned copy for fleet failover restoration: the victim's
    requests are migrated to a healthy replica, so the restored engine
    starts EMPTY — but keeps its round indices (round-addressed faults it
    already struck never re-fire, making recovery deterministic), RNG
    key, clock base, cost EMA and compile-footprint records."""
    return dataclasses.replace(
        snap,
        slot_req=[None] * len(snap.slot_req),
        queue=[], finished=[], quarantined={},
        remaining=np.zeros_like(snap.remaining))


# ---------------------------------------------------------------------------
# Atomic on-disk persistence
# ---------------------------------------------------------------------------


def _flatten(tree, prefix: str) -> Dict[str, np.ndarray]:
    """Dot-join a pure nested-dict tree (params/cache are exactly that —
    str keys, no dots) into {path: leaf}."""
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            assert "." not in str(k), f"tree key {k!r} would break paths"
            out.update(_flatten(v, f"{prefix}.{k}"))
    else:
        out[prefix] = np.asarray(tree)
    return out


def _unflatten(flat: Dict[str, np.ndarray]) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        parts = path.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def to_dir(snap: EngineSnapshot, path: str) -> str:
    """Atomically persist ``snap`` at ``path`` (a directory): written to
    ``path.tmp`` first, os.replace'd into place — a crash mid-save never
    leaves a half-written snapshot visible."""
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)

    flat = _flatten(snap.params, "params")
    flat.update(_flatten(snap.cache, "cache"))
    flat.update({"pos": snap.pos, "last_tok": snap.last_tok,
                 "remaining": snap.remaining, "key_data": snap.key_data})
    arrays, dtypes = {}, {}
    for key, arr in flat.items():
        dtypes[key] = str(arr.dtype)
        arrays[key] = (arr if arr.dtype.name in _NATIVE
                       else arr.astype(np.float32))
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)

    kw = dict(snap.init_kw)
    kw["cache_dtype"] = str(np.dtype(kw["cache_dtype"]))
    meta = {
        "schema": SNAPSHOT_VERSION,
        "cfg": dataclasses.asdict(snap.cfg),
        "init_kw": kw,
        "dtypes": dtypes,
        "clock_now": snap.clock_now,
        "admit_round_idx": snap.admit_round_idx,
        "decode_round_idx": snap.decode_round_idx,
        "quarantined": {str(k): v for k, v in snap.quarantined.items()},
        "slot_req": snap.slot_req,
        "queue": snap.queue,
        "finished": snap.finished,
        "step_mode": snap.step_mode,
        "mode_cost": snap.mode_cost,
        "fused_templates": snap.fused_templates,
    }
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)

    if os.path.exists(path):
        shutil.rmtree(path)
    os.replace(tmp, path)
    return path


def from_dir(path: str) -> EngineSnapshot:
    """Load a snapshot persisted by to_dir. Ignores any sibling .tmp."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("schema") != SNAPSHOT_VERSION:
        raise ValueError(f"snapshot at {path}: schema "
                         f"{meta.get('schema')!r} != {SNAPSHOT_VERSION}")
    with np.load(os.path.join(path, "arrays.npz")) as z:
        flat = {k: z[k] for k in z.files}
    for key, arr in flat.items():
        want = meta["dtypes"][key]
        if str(arr.dtype) != want:
            flat[key] = np.asarray(jnp.asarray(arr).astype(want))
    cfg_d = meta["cfg"]
    cfg_d["layer_pattern"] = tuple(cfg_d["layer_pattern"])
    kw = dict(meta["init_kw"])
    params = _unflatten({k[len("params."):]: v for k, v in flat.items()
                         if k.startswith("params.")})
    cache = _unflatten({k[len("cache."):]: v for k, v in flat.items()
                        if k.startswith("cache.")})
    return EngineSnapshot(
        cfg=ModelConfig(**cfg_d), params=params, cache=cache, init_kw=kw,
        pos=flat["pos"], last_tok=flat["last_tok"],
        remaining=flat["remaining"], key_data=flat["key_data"],
        clock_now=meta["clock_now"],
        admit_round_idx=meta["admit_round_idx"],
        decode_round_idx=meta["decode_round_idx"],
        quarantined={int(k): v for k, v in meta["quarantined"].items()},
        slot_req=meta["slot_req"], queue=meta["queue"],
        finished=meta["finished"],
        # pre-fused-seam files lack these keys (same v1 schema): default
        # step_mode to the EFFECTIVE mode the engine would resolve from
        # the recorded kwargs (recurrent mixers force "split").
        step_mode=meta.get("step_mode", (
            kw.get("step_mode", "split")
            if all(k == "attn" for k in cfg_d["layer_pattern"])
            else "split")),
        mode_cost=meta.get("mode_cost", {}),
        fused_templates=meta.get("fused_templates", []))
