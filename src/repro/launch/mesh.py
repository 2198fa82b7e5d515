"""Host mesh construction.

A FUNCTION, not a module constant, so importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): 1 device.
    Auto (GSPMD-propagated) axes: the model code places activations with
    ``maybe_constrain`` hints, which only Auto axes accept."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
