"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run driver sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax
use; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the code places arrays
    with ``shard_map`` specs and ``with_sharding_constraint``, which
    refer to Auto axes only (newer jax defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_smoke_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh for CPU tests (requires forced host device count)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def make_solver_mesh(n_rows_axis: int | None = None):
    """1-D mesh for stacked-IPM row megabatches (`lp_rows` axis).

    Default: every visible device.  LP rows are embarrassingly
    data-parallel, so the solver mesh has no model axis — pass the
    result to ``lp.solve_lp_stacked(mesh=)`` /
    ``serving.AllocationServer(mesh=)``.
    """
    n = len(jax.devices()) if n_rows_axis is None else int(n_rows_axis)
    return _make_mesh((n,), ("lp_rows",))
