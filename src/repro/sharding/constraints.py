"""Activation sharding constraints (GSPMD hints inside model code).

``maybe_constrain(x, P(...))`` is a no-op outside a mesh context (smoke
tests, 1-device CPU) and drops axes the current mesh does not have, so model
code can state its preferred layout unconditionally. Uneven dims are allowed
(GSPMD pads), which matters for head counts like 28 on a 16-way model axis.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P


def _auto_axes(mesh) -> tuple:
    return tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                 if t == AxisType.Auto)


def _current_axes():
    """The constrainable (``AxisType.Auto``) axes of the surrounding mesh:
    Explicit axes are sharded by their types, and the Manual axes of a
    shard_map belong to its body."""
    m = jax.sharding.get_abstract_mesh()
    if not m.empty:
        return _auto_axes(m)
    # classic `with mesh:` context
    from jax._src.mesh import thread_resources
    pm = thread_resources.env.physical_mesh
    return () if pm.empty else _auto_axes(pm)


def maybe_constrain(x, spec: P):
    axes = _current_axes()
    if not axes:
        return x
    fixed = []
    changed = False
    want = tuple(spec) + (None,) * (np.ndim(x) - len(tuple(spec)))
    for ax in want[:np.ndim(x)]:
        parts = ax if isinstance(ax, tuple) else ((ax,) if ax else ())
        if parts and all(a in axes for a in parts):
            fixed.append(ax)
            changed = True
        else:
            fixed.append(None)
    if not changed:
        return x
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def data_axes_spec():
    """The batch axis of the current mesh: ("pod","data") / ("data",)."""
    axes = _current_axes()
    if "pod" in axes and "data" in axes:
        return ("pod", "data")
    if "data" in axes:
        return "data"
    return None
