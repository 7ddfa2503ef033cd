"""Instrumented launch wrapper — every kernel launch becomes a measured event.

``instrumented_pallas_call`` is the ONLY place in the repo that invokes
``pl.pallas_call`` (enforced statically by the ``obs_coverage`` lint
pass): kernel families construct a ``LaunchMeta`` from their schedule and
route the launch through here, so each launch emits

  * counters: ``launches_total`` / ``tiles_launched_total`` /
    ``tiles_domain_total`` / ``tiles_bb_total`` / ``tiles_wasted_total``
    / ``launch_bytes_total`` (labels: name, impl),
  * a ``launch`` trace event (obs/sinks.py) carrying the full geometry:
    schedule kind, grid, block shape, tile counts, bytes moved, and the
    paper's waste metrics (utilization = domain/launched, improvement
    I = BB-bound/launched) computed from the schedule contract.

``instrumented_call`` is the same discipline for scan-fallback launches
(one lax.scan over the schedule enumeration == one launch).

Semantics under jit: the wrapper body runs at TRACE time (once per
compile), so events fired from inside a jitted program are tagged
``phase="trace"`` — launch *geometry* is static per compile, which is
exactly the quantity the paper compares. Eager launches (direct op calls,
CPU-interpreted benchmarks) are tagged ``phase="eager"`` and fire per
call. Runtime per-round accounting (decode tiles vs pad-to-max) stays
with the engine's registry-backed counters, which see host-side truth.

Overhead budget: with sinks disabled an emission is a handful of dict
increments (obs/metrics.py, no JAX imports) — and on jitted hot paths it
is removed from the compiled program entirely. ``set_enabled(False)``
kills even that.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from repro.obs import metrics as MET

_ENABLED = True
_LAUNCH_HOOK = None


def set_enabled(flag: bool):
    """Global kill switch for launch telemetry (counters + events)."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def set_launch_hook(hook):
    """Install a pre-launch hook: ``hook(meta)`` runs before every
    instrumented launch (Pallas or scan fallback) and may raise to abort
    it — the injection surface for repro.resilience fault plans. Returns
    the previously installed hook (None when there was none) so callers
    can restore it. Under jit the hook fires at trace time, like the
    telemetry emission itself."""
    global _LAUNCH_HOOK
    prev, _LAUNCH_HOOK = _LAUNCH_HOOK, hook
    return prev


def launch_hook():
    return _LAUNCH_HOOK


@dataclasses.dataclass(frozen=True)
class LaunchMeta:
    """Static description of one launch's block-space geometry.

    ``tiles_launched`` counts the schedule-enumerated lambda-grid steps of
    ONE grid cell (one (batch, head) pair for attention); ``cells`` is the
    product of the prefix grid dims, so total grid steps = cells * tiles.
    ``tiles_domain`` is the useful-tile count from the schedule contract
    (tri(n) for ltm, band_blocks for band, ...); ``tiles_bb`` the
    bounding-box baseline bound the paper compares against (n^2 dense,
    R * n_max^2 pad-to-max for packed). None = unknown at wrap time
    (runtime-table decode rounds)."""

    name: str                     # e.g. "tri_attn.fwd"
    family: str                   # kernel family: tri_attn | tri_edm | ...
    impl: str                     # "pallas" | "scan"
    kind: str                     # schedule kind: ltm | band | packed | ...
    grid: Tuple[int, ...]         # full launch grid (or (steps,) for scans)
    block_shape: Tuple[int, ...]  # tile edge(s)
    tiles_launched: int
    tiles_domain: Optional[int] = None
    tiles_bb: Optional[int] = None
    cells: int = 1
    extra: tuple = ()             # ((key, value), ...) — hashable

    # -- derived paper quantities -------------------------------------------
    @property
    def tiles_wasted(self) -> Optional[int]:
        if self.tiles_domain is None:
            return None
        return self.tiles_launched - self.tiles_domain

    @property
    def utilization(self) -> Optional[float]:
        if self.tiles_domain is None or self.tiles_launched == 0:
            return None
        return self.tiles_domain / self.tiles_launched

    @property
    def improvement_vs_bb(self) -> Optional[float]:
        if self.tiles_bb is None or self.tiles_launched == 0:
            return None
        return self.tiles_bb / self.tiles_launched

    def as_event(self, *, phase: str, bytes_moved: int) -> dict:
        ev = {"type": "launch", "name": self.name, "family": self.family,
              "impl": self.impl, "kind": self.kind, "phase": phase,
              "grid": list(self.grid), "cells": self.cells,
              "block_shape": list(self.block_shape),
              "tiles_launched": self.tiles_launched,
              "tiles_domain": self.tiles_domain,
              "tiles_bb": self.tiles_bb,
              "tiles_wasted": self.tiles_wasted,
              "utilization": self.utilization,
              "improvement_vs_bb": self.improvement_vs_bb,
              "bytes_moved": bytes_moved}
        if self.extra:
            ev["extra"] = {str(k): v for k, v in self.extra}
        return ev


# -- meta constructors (schedule contract -> geometry) -----------------------


def meta_from_trisched(name: str, sched, *, impl: str, cells: int = 1,
                       grid=None) -> LaunchMeta:
    """From a kernel-layer TriSched: launched == domain (exact schedules);
    BB bound is the n x n dense grid the paper's baseline would launch."""
    if grid is None:
        grid = (cells, sched.rm_steps) if cells > 1 else (sched.rm_steps,)
    return LaunchMeta(
        name=name, family="tri_attn", impl=impl, kind=sched.kind,
        grid=tuple(grid), block_shape=(sched.bq, sched.bk),
        tiles_launched=sched.rm_steps, tiles_domain=sched.rm_steps,
        tiles_bb=sched.n * sched.n, cells=cells)


def meta_from_packed(name: str, psched, *, impl: str, cells: int = 1,
                     grid=None) -> LaunchMeta:
    """From a PackedTriSched: BB bound is the pad-to-max batch the packed
    launch replaces — R * n_max^2 dense tiles."""
    r = len(psched.members)
    n_max = max(m.n for m in psched.members)
    if grid is None:
        grid = (cells, psched.steps) if cells > 1 else (psched.steps,)
    return LaunchMeta(
        name=name, family="tri_attn", impl=impl, kind="packed",
        grid=tuple(grid), block_shape=(psched.blk, psched.blk),
        tiles_launched=psched.steps, tiles_domain=psched.steps,
        tiles_bb=r * n_max * n_max, cells=cells,
        extra=(("members", r),))


def meta_dense(name: str, family: str, *, impl: str, grid, block_shape,
               tiles_domain: Optional[int] = None, kind: str = "bb",
               cells: int = 1, extra: tuple = ()) -> LaunchMeta:
    """Dense/bounding-box grids (and recurrent chunk scans): launched is
    the full grid product over the lambda dims; BB bound == launched."""
    launched = 1
    for g in grid:
        launched *= int(g)
    return LaunchMeta(
        name=name, family=family, impl=impl, kind=kind, grid=tuple(grid),
        block_shape=tuple(block_shape), tiles_launched=launched,
        tiles_domain=tiles_domain, tiles_bb=launched, cells=cells,
        extra=extra)


def meta_exact(name: str, family: str, *, impl: str, kind: str, steps: int,
               block_shape, bb_bound: Optional[int], cells: int = 1,
               extra: tuple = ()) -> LaunchMeta:
    """Exact 1-D schedules (ltm/tet EDM & 3-body, decode rounds): launched
    == domain == steps."""
    return LaunchMeta(
        name=name, family=family, impl=impl, kind=kind, grid=(steps,),
        block_shape=tuple(block_shape), tiles_launched=steps,
        tiles_domain=steps, tiles_bb=bb_bound, cells=cells, extra=extra)


# -- emission ----------------------------------------------------------------


def _is_tracer(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.core.Tracer)
    except Exception:
        return False


def _operand_bytes(operands) -> int:
    total = 0
    for x in operands:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            continue
        itemsize = getattr(dtype, "itemsize", None)
        if itemsize is None:
            continue
        total += int(math.prod(shape)) * int(itemsize)
    return total


def record_launch(meta: LaunchMeta, operands=()):
    """Emit one launch's counters + trace event (no-op when disabled).
    An installed launch hook runs FIRST and may raise to abort the launch
    (deterministic fault injection — see repro.resilience.faults)."""
    if _LAUNCH_HOOK is not None:
        _LAUNCH_HOOK(meta)
    if not _ENABLED:
        return
    phase = "trace" if any(_is_tracer(x) for x in operands) else "eager"
    labels = {"name": meta.name, "impl": meta.impl}
    MET.counter_inc("launches_total", 1, labels)
    MET.counter_inc("tiles_launched_total",
                    meta.tiles_launched * meta.cells, labels)
    if meta.tiles_domain is not None:
        MET.counter_inc("tiles_domain_total",
                        meta.tiles_domain * meta.cells, labels)
        MET.counter_inc("tiles_wasted_total",
                        meta.tiles_wasted * meta.cells, labels)
    if meta.tiles_bb is not None:
        MET.counter_inc("tiles_bb_total", meta.tiles_bb * meta.cells,
                        labels)
    bytes_moved = _operand_bytes(operands)
    MET.counter_inc("launch_bytes_total", bytes_moved, labels)

    from repro.obs import sinks as SK

    if SK.trace_enabled():
        SK.emit_event(meta.as_event(phase=phase, bytes_moved=bytes_moved))


_SUMMARY_FIELDS = {
    "launches_total": "launches",
    "tiles_launched_total": "tiles_launched",
    "tiles_domain_total": "tiles_domain",
    "tiles_wasted_total": "tiles_wasted",
    "tiles_bb_total": "tiles_bb",
    "launch_bytes_total": "bytes_moved",
}


def kernel_summary(registry=None) -> dict:
    """Per-kernel aggregate of the launch counters, keyed by launch name:

        {"tri_edm.ltm": {"launches": .., "tiles_launched": ..,
                         "tiles_domain": .., "tiles_wasted": ..,
                         "tiles_bb": .., "bytes_moved": ..,
                         "utilization": .., "improvement_vs_bb": ..,
                         "impls": ["scan", ...]}, ...}

    Sums over impl labels; utilization/improvement recomputed from the
    summed tiles — this is the ``kernels`` body of a BENCH_trajectory.json
    record (obs/schema.py validate_trajectory)."""
    reg = registry or MET.global_registry()
    snap = reg.snapshot()["counters"]
    out: dict = {}
    for key, value in snap.items():
        if "{" not in key:
            continue
        cname, rest = key.split("{", 1)
        if cname not in _SUMMARY_FIELDS:
            continue
        labels = dict(p.split("=", 1) for p in rest.rstrip("}").split(","))
        name = labels.get("name")
        if name is None:
            continue
        d = out.setdefault(name, {f: 0 for f in _SUMMARY_FIELDS.values()})
        d[_SUMMARY_FIELDS[cname]] += int(value)
        if "impl" in labels:
            d.setdefault("impls", [])
            if labels["impl"] not in d["impls"]:
                d["impls"].append(labels["impl"])
    for d in out.values():
        launched = d["tiles_launched"]
        d["utilization"] = (d["tiles_domain"] / launched) if launched else 0.0
        d["improvement_vs_bb"] = \
            (d["tiles_bb"] / launched) if launched else 0.0
        d.setdefault("impls", [])
        d["impls"].sort()
    return out


def instrumented_pallas_call(kernel_fn, *, meta: LaunchMeta, **pallas_kw):
    """The repo's single ``pl.pallas_call`` site. Same signature contract
    as pallas_call (grid/grid_spec/in_specs/out_specs/... forwarded
    verbatim, except ``interpret``, which is decided here); the returned
    callable records the launch before running.

    Compiled or interpreted is resolved from the LOWERING platform by
    ``jax.lax.platform_dependent``: a program lowered for a TPU runs the
    Mosaic kernel, and any other platform (the CPU test suite) runs the
    Pallas interpreter. Only the branch of the lowering platform is
    lowered, so a TPU program can never reach the interpreter and a CPU
    program never asks Mosaic. Deciding from the lowering platform rather
    than ``jax.default_backend()`` also holds when a CPU host compiles for
    a described TPU topology."""
    import jax
    from jax.experimental import pallas as pl

    compiled = pl.pallas_call(kernel_fn, interpret=False, **pallas_kw)
    interpreted = pl.pallas_call(kernel_fn, interpret=True, **pallas_kw)

    def launch(*operands):
        record_launch(meta, operands)
        return jax.lax.platform_dependent(*operands, tpu=compiled,
                                          default=interpreted)

    return launch


def instrumented_call(fn, meta: LaunchMeta):
    """Wrap a scan-fallback (or any single-launch callable) so each call
    emits the same launch telemetry as a Pallas launch."""

    def launch(*args, **kw):
        record_launch(meta, args)
        return fn(*args, **kw)

    return launch
