"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets the fake-device count before
first jax init; smoke tests see 1 device)."""

from __future__ import annotations

import jax
import numpy as np


def _make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single-pod (256 chips) or (2, 16, 16) two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/benchmarks (host-device or real)."""
    return _make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, axis: str = "x"):
    """1-D mesh over all (host) devices."""
    n = n if n is not None else len(jax.devices())
    return make_mesh((n,), (axis,))


def dp_size(mesh) -> int:
    """Total batch-sharding ways under the default rules (pod x data)."""
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= int(mesh.shape[a])
    return n
