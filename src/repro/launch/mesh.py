"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod axis
composes with data parallelism (hierarchical gradient reduction:
reduce-scatter in-pod over ICI, all-reduce across pods over DCN).

Axes are ``Auto``: the sharding policy places arrays with
``with_sharding_constraint`` and lets the partitioner propagate, which
``jax.make_mesh``'s default of ``Explicit`` axes refuses.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Mesh over whatever devices exist (smoke tests / elastic restarts)."""
    n = len(jax.devices())
    data = n // model_axis
    return auto_mesh((data, model_axis), ("data", "model"))
