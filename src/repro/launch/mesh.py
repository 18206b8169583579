"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state: device count is locked on first jax init, and the
smoke tests / benches must keep seeing the single real CPU device while the
dry-run process (which sets XLA_FLAGS *before* any import) sees 512.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    kinds = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=kinds)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return make_mesh((data, model), ("data", "model"))


__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh"]
