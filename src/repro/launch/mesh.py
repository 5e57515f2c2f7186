"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Every mesh is built with ``AxisType.Auto`` axes: the sharded code (GSPMD
scatters, ``with_sharding_constraint`` hints, ``shard_map`` islands) is
written for compiler-propagated shardings, not the explicit-sharding type
system ``jax.make_mesh`` defaults to.  Install a mesh as the ambient one
with ``jax.set_mesh(mesh)``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Auto-sharded mesh of the given shape (e.g. (2,4) on 8 devices)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 single pod (16x16) or 2 pods (2x16x16).

    Axes: "pod" is the outer data-parallel axis (gradient all-reduce crosses
    pods once per step over DCN); "data" is FSDP + batch; "model" is tensor/
    expert parallel (stays inside a pod's ICI torus).
    """
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_telemetry_mesh(n_devices: int | None = None, axis: str = "blocks"):
    """1-D mesh for memory-side telemetry: per-block state (collector
    histograms, lane placements) shards over ``axis`` so paper-scale
    (5.24 M page) epoch runs keep the decision loop next to the counters.
    Defaults to all visible devices."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return make_mesh((n,), (axis,))
