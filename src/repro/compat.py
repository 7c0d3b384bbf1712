"""Mesh construction for the installed JAX (0.9.0).

``jax.make_mesh`` defaults to ``Explicit`` axis types there. The serving
mesh path places tables with ``NamedSharding`` and lets GSPMD partition the
program (``core/plan.py``), which needs ``Auto`` axes: build meshes here.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))
