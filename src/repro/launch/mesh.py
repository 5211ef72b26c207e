"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax call, and smoke tests must keep seeing the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(axis_shapes, axis_names) -> Mesh:
    """`jax.make_mesh` with Auto axes (jax's default is Explicit)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 chips per pod ("data", "model"); 2 pods add a leading "pod"."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_rows_mesh(n: int | None = None, axis_name: str = "rows") -> Mesh:
    """1-D mesh for the logdet core (paper's P processors)."""
    n = n or jax.device_count()
    return make_mesh((n,), (axis_name,))


def make_mesh_like(spec: str) -> Mesh:
    """'16x16' / '2x16x16' / '8' -> mesh (for CLI flags)."""
    dims = tuple(int(x) for x in spec.lower().split("x"))
    if len(dims) == 1:
        return make_rows_mesh(dims[0])
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    raise ValueError(spec)
