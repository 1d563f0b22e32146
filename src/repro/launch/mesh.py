"""Production mesh builders.

Single pod: v5e 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2 pods = 512 chips, axes ("pod", "data", "model") — the pod axis
crosses DCN (pure data parallelism; see distributed/sharding.py).

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run must set XLA_FLAGS before *any* jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh(
        (1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
