"""Device-mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state: a process that forces a host device count must
set ``XLA_FLAGS`` before the first device query.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with explicit Auto axis types where the installed
    jax supports them (``jax.sharding.AxisType`` appeared after 0.4.x; on
    older versions plain axes already behave as Auto)."""
    axis_type = getattr(jax.sharding, "AxisType", None)
    if axis_type is None:
        return jax.make_mesh(shape, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(axis_type.Auto,) * len(axes))
