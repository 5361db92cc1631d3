"""Mesh construction.

Every mesh in this repo has ``Auto`` axis types: the sharded programs place
data with ``NamedSharding`` and ``shard_map`` and leave propagation to the
compiler (``jax.make_mesh`` would default to ``Explicit`` axes).

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no JAX device state. Single pod: 16×16 = 256
chips (data, model). Multi-pod: 2×16×16 = 512 chips (pod, data, model) —
the ``pod`` axis composes with ``data`` for hierarchical gradient
reduction (reduce-scatter intra-pod, all-reduce across the slow axis).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(
        axis_shapes, axis_names, axis_types=(AxisType.Auto,) * len(axis_names)
    )


def make_mesh_from_spec(spec: str):
    """e.g. "4x2" -> (data, model); "2x4x2" -> (pod, data, model)."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims) :] if len(dims) == 3 else ("data", "model")
    return make_mesh(dims, axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
