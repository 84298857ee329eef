"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods = 512
chips as (pod=2, data=16, model=16) — the "pod" axis carries pure data
parallelism (+ compressed gradient all-reduce, see
training/grad_compress.py) because inter-pod links are an order of
magnitude slower than in-pod ICI.

Every mesh here has Auto axes: the DP/TP rules in
``distributed/sharding.py`` place parameters and leave the rest to
GSPMD propagation, which ``jax.make_mesh``'s default Explicit axes
refuse (an embedding gather over a vocab-sharded table needs an
explicit out sharding there).

FUNCTIONS, not module constants: importing this module must never
touch jax device state (the dry-run needs to set XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis Auto (a mesh
    from ``jax.make_mesh`` defaults to Explicit axes)."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
