"""Production mesh construction.

Single pod : (16, 16)      axes ("data", "model")        = 256 chips
Multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

A FUNCTION, not a module constant, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax use).

The "pod" axis doubles as the paper's edge/cloud boundary in the Tier-B
split-inference runtime (DESIGN.md §2): pod 0 = edge tier, pod 1 = cloud
tier, and the split activation crosses pods via collective-permute.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Auto (GSPMD-propagated) axes: the model code places activations
    with ``maybe_constrain`` hints, which only Auto axes accept."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): 1 device."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
