"""Hand-rolled schema validation for the obs sink formats.

No jsonschema dependency — each validator walks the document and returns
a list of human-readable problems (empty list == valid). Used by
tests/test_obs.py, the ``obs`` lint pass, and the ``repro.obs.validate``
CLI that scripts/check.sh runs after the benchmark smoke tier."""

from __future__ import annotations

from typing import List

from repro.obs.sinks import SCHEMA_VERSION

EVENT_TYPES = ("launch", "span", "degrade", "quarantine",
               "failover", "engine_quarantine", "rebalance")

# Canonical vocabulary of the serving degradation ladder (see
# repro.resilience.faults.LADDERS — the resilience lint pass proves the
# two stay in sync). ``degrade`` events may only move between these.
DEGRADE_STAGES = ("packed", "packed_scan", "sequential", "lockstep",
                  "traced", "host", "fused", "split", "requested",
                  "rebucketed", "active", "quarantined", "restored",
                  "primary", "failover")

# Resilience counters (emitted by serve/engine.py under these exact
# names, globally and in the per-engine registry). Counts of discrete
# events — validate_metrics requires them integral when present.
RESILIENCE_COUNTERS = (
    "requests_retried_total", "deadline_misses_total",
    "launches_degraded_total", "requests_shed_total",
    "slots_quarantined_total", "requests_failed_total",
    "rounds_straggler_total",
)

# Fleet counters (emitted by serve/fleet.py under these exact names, in
# the fleet registry and mirrored globally). Discrete-event counts —
# validate_metrics requires them integral when present. The
# ``engines_quarantined`` GAUGE (current quarantine-set size) rides
# alongside and must be an integral non-negative value.
FLEET_COUNTERS = (
    "fleet_failovers_total", "fleet_requests_migrated_total",
    "fleet_engine_restores_total", "fleet_rounds_straggler_total",
    "fleet_requests_routed_total", "fleet_routed_tiles_total",
    "fleet_requests_shed_total",
)
FLEET_GAUGES = ("engines_quarantined",)

# Required fields per event type (beyond the envelope added by sinks).
_LAUNCH_FIELDS = {
    "name": str, "family": str, "impl": str, "kind": str, "phase": str,
    "grid": list, "cells": int, "block_shape": list,
    "tiles_launched": int, "bytes_moved": int,
}
_LAUNCH_OPTIONAL_INT = ("tiles_domain", "tiles_bb", "tiles_wasted")
_LAUNCH_OPTIONAL_FLOAT = ("utilization", "improvement_vs_bb")
_SPAN_FIELDS = {
    "name": str, "path": str, "depth": int, "duration_ms": (int, float),
}
_DEGRADE_FIELDS = {
    "phase": str, "from": str, "to": str, "round": int, "reason": str,
}
_QUARANTINE_FIELDS = {
    "slot": int, "uid": int, "round": int, "reason": str,
}
_FAILOVER_FIELDS = {
    "engine": int, "target": int, "round": int, "migrated": int,
    "reason": str,
}
_ENGINE_QUARANTINE_FIELDS = {
    "engine": int, "round": int, "consecutive": int,
    "probation_rounds": int, "reason": str,
}
_REBALANCE_FIELDS = {
    "engine": int, "round": int, "reason": str,
}


def _check(errors: List[str], cond: bool, msg: str):
    if not cond:
        errors.append(msg)


def validate_event(ev: dict, *, envelope: bool = True) -> List[str]:
    """Validate one trace event. ``envelope=True`` also requires the sink
    fields (schema/seq/ts_unix) present on persisted JSONL lines."""
    errors: List[str] = []
    if not isinstance(ev, dict):
        return [f"event is not an object: {type(ev).__name__}"]
    if envelope:
        _check(errors, ev.get("schema") == SCHEMA_VERSION,
               f"schema != {SCHEMA_VERSION}: {ev.get('schema')!r}")
        _check(errors, isinstance(ev.get("seq"), int) and ev.get("seq") >= 1,
               f"seq must be int >= 1: {ev.get('seq')!r}")
        _check(errors, isinstance(ev.get("ts_unix"), (int, float)),
               "ts_unix missing or non-numeric")
    etype = ev.get("type")
    _check(errors, etype in EVENT_TYPES,
           f"unknown event type {etype!r} (want one of {EVENT_TYPES})")
    if etype == "launch":
        for field, ftype in _LAUNCH_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"launch.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        for field in _LAUNCH_OPTIONAL_INT:
            v = ev.get(field)
            _check(errors, v is None or isinstance(v, int),
                   f"launch.{field} must be int or null: {v!r}")
        for field in _LAUNCH_OPTIONAL_FLOAT:
            v = ev.get(field)
            _check(errors, v is None or isinstance(v, (int, float)),
                   f"launch.{field} must be numeric or null: {v!r}")
        if not errors:
            # Internal consistency: the paper's identities must hold.
            lau, dom = ev["tiles_launched"], ev.get("tiles_domain")
            if dom is not None:
                _check(errors, ev.get("tiles_wasted") == lau - dom,
                       "tiles_wasted != tiles_launched - tiles_domain")
                if lau > 0 and ev.get("utilization") is not None:
                    _check(errors,
                           abs(ev["utilization"] - dom / lau) < 1e-9,
                           "utilization != tiles_domain/tiles_launched")
            _check(errors, ev["phase"] in ("eager", "trace"),
                   f"launch.phase must be eager|trace: {ev['phase']!r}")
    elif etype == "span":
        for field, ftype in _SPAN_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"span.{field} missing or not {ftype}: {ev.get(field)!r}")
        t0, t1 = ev.get("t0_ns"), ev.get("t1_ns")
        if t0 is not None or t1 is not None:
            _check(errors, isinstance(t0, int) and isinstance(t1, int)
                   and 0 <= t0 <= t1,
                   f"span.t0_ns / t1_ns must be ints, 0 <= t0_ns <= t1_ns: "
                   f"{t0!r}, {t1!r}")
    elif etype == "degrade":
        for field, ftype in _DEGRADE_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"degrade.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        if not errors:
            _check(errors, ev["from"] in DEGRADE_STAGES,
                   f"degrade.from not a registered stage: {ev['from']!r}")
            _check(errors, ev["to"] in DEGRADE_STAGES,
                   f"degrade.to not a registered stage: {ev['to']!r}")
            _check(errors, ev["from"] != ev["to"],
                   "degrade.from == degrade.to (not a transition)")
            _check(errors, ev["round"] >= 0,
                   f"degrade.round must be >= 0: {ev['round']!r}")
    elif etype == "quarantine":
        for field, ftype in _QUARANTINE_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"quarantine.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        if not errors:
            _check(errors, ev["slot"] >= 0,
                   f"quarantine.slot must be >= 0: {ev['slot']!r}")
            _check(errors, ev["round"] >= 0,
                   f"quarantine.round must be >= 0: {ev['round']!r}")
    elif etype == "failover":
        for field, ftype in _FAILOVER_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"failover.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        if not errors:
            for field in ("engine", "target", "round", "migrated"):
                _check(errors, ev[field] >= 0,
                       f"failover.{field} must be >= 0: {ev[field]!r}")
    elif etype == "engine_quarantine":
        for field, ftype in _ENGINE_QUARANTINE_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"engine_quarantine.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        if not errors:
            for field in ("engine", "round"):
                _check(errors, ev[field] >= 0,
                       f"engine_quarantine.{field} must be >= 0: "
                       f"{ev[field]!r}")
            for field in ("consecutive", "probation_rounds"):
                _check(errors, ev[field] >= 1,
                       f"engine_quarantine.{field} must be >= 1: "
                       f"{ev[field]!r}")
    elif etype == "rebalance":
        for field, ftype in _REBALANCE_FIELDS.items():
            _check(errors, isinstance(ev.get(field), ftype),
                   f"rebalance.{field} missing or not {ftype}: "
                   f"{ev.get(field)!r}")
        if not errors:
            for field in ("engine", "round"):
                _check(errors, ev[field] >= 0,
                       f"rebalance.{field} must be >= 0: {ev[field]!r}")
    return errors


def validate_metrics(doc: dict) -> List[str]:
    """Validate an artifacts/metrics.json document."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return [f"metrics doc is not an object: {type(doc).__name__}"]
    _check(errors, doc.get("schema") == SCHEMA_VERSION,
           f"schema != {SCHEMA_VERSION}: {doc.get('schema')!r}")
    _check(errors, doc.get("kind") == "metrics",
           f"kind != 'metrics': {doc.get('kind')!r}")
    _check(errors, isinstance(doc.get("created_unix"), (int, float)),
           "created_unix missing or non-numeric")
    for section in ("counters", "gauges", "histograms"):
        _check(errors, isinstance(doc.get(section), dict),
               f"{section} missing or not an object")
    for name, v in (doc.get("counters") or {}).items():
        _check(errors, isinstance(v, (int, float)) and v >= 0,
               f"counter {name} must be non-negative number: {v!r}")
        base = name.split("{", 1)[0]
        if base in RESILIENCE_COUNTERS or \
                base.removeprefix("engine_") in RESILIENCE_COUNTERS:
            _check(errors, float(v) == int(v),
                   f"resilience counter {name} must be integral "
                   f"(counts discrete events): {v!r}")
        if base in FLEET_COUNTERS:
            _check(errors, float(v) == int(v),
                   f"fleet counter {name} must be integral "
                   f"(counts discrete events): {v!r}")
    for name, v in (doc.get("gauges") or {}).items():
        if name.split("{", 1)[0] in FLEET_GAUGES:
            _check(errors,
                   isinstance(v, (int, float)) and v >= 0
                   and float(v) == int(v),
                   f"fleet gauge {name} must be integral >= 0: {v!r}")
    for name, h in (doc.get("histograms") or {}).items():
        if not isinstance(h, dict):
            errors.append(f"histogram {name} is not an object")
            continue
        for field in ("count", "sum", "min", "max", "mean",
                      "buckets", "bucket_counts"):
            _check(errors, field in h, f"histogram {name} missing {field}")
        if "buckets" in h and "bucket_counts" in h:
            _check(errors,
                   len(h["bucket_counts"]) == len(h["buckets"]) + 1,
                   f"histogram {name}: bucket_counts must have "
                   "len(buckets)+1 entries")
            _check(errors, sum(h["bucket_counts"]) == h.get("count"),
                   f"histogram {name}: bucket_counts do not sum to count")
    return errors


def validate_trajectory(records: list) -> List[str]:
    """Validate BENCH_trajectory.json: a JSON array of run records, each
    with a timestamp, a run id, and per-kernel block-space geometry."""
    errors: List[str] = []
    if not isinstance(records, list):
        return [f"trajectory is not an array: {type(records).__name__}"]
    _check(errors, len(records) >= 1, "trajectory is empty")
    for r_i, rec in enumerate(records):
        where = f"trajectory[{r_i}]"
        if not isinstance(rec, dict):
            errors.append(f"{where} is not an object")
            continue
        _check(errors, rec.get("schema") == SCHEMA_VERSION,
               f"{where}.schema != {SCHEMA_VERSION}")
        _check(errors, isinstance(rec.get("created_unix"), (int, float)),
               f"{where}.created_unix missing")
        kernels = rec.get("kernels")
        if not isinstance(kernels, dict) or not kernels:
            errors.append(f"{where}.kernels missing or empty")
            continue
        for kname, k in kernels.items():
            kw = f"{where}.kernels[{kname}]"
            if not isinstance(k, dict):
                errors.append(f"{kw} is not an object")
                continue
            for field in ("tiles_launched", "tiles_bb", "utilization"):
                _check(errors, field in k, f"{kw} missing {field}")
            lau = k.get("tiles_launched")
            _check(errors, isinstance(lau, int) and lau >= 0,
                   f"{kw}.tiles_launched must be int >= 0: {lau!r}")
            util = k.get("utilization")
            _check(errors,
                   util is None or (isinstance(util, (int, float))
                                    and 0.0 <= util <= 1.0 + 1e-9),
                   f"{kw}.utilization out of [0,1]: {util!r}")
    return errors
