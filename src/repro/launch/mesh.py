"""Mesh construction. FUNCTIONS only — importing this module must never
touch jax device state (dryrun.py sets XLA_FLAGS before first jax init).
All mesh construction goes through ``make_mesh``."""

from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis in Auto mode: the sharding rules
    (parallel/sharding.py) leave propagation to XLA's partitioner."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: "data" = DP/FSDP, "model" = TP; "pod" composes with "data" for the
    batch dimension (pure DP across the DCI, FSDP inside the pod), and is
    the documented GPipe insertion point past 4k chips (DESIGN.md §5).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """All local devices on a 1-D "data" axis (CPU tests / examples)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
