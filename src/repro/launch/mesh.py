"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state.

Every mesh on the program's path has ``Auto`` axes. ``jax.make_mesh``
defaults to ``Explicit`` axes, whose sharding-in-types rules reject the
eager scatters and shard_map programs the lowering builds (they assume the
compiler propagates shardings). :func:`make_mesh` is the one place that
builds a mesh; :func:`as_auto` rebuilds a caller's mesh with the same
devices and names.
"""
from __future__ import annotations


def make_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def as_auto(mesh):
    """``mesh`` with every axis ``Auto`` (same devices, same names); None
    and meshes that are already all-Auto pass through unchanged."""
    if mesh is None:
        return None
    import jax
    from jax.sharding import AxisType

    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return jax.sharding.Mesh(mesh.devices, mesh.axis_names,
                             axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """The single-pod 16x16 (data, model) mesh or the 2-pod (pod, data,
    model) = 512-chip mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 4):
    """Small CPU mesh for the distributed test suites."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (§Roofline).
PEAK_BF16_FLOPS = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per chip, one direction)
HBM_BYTES = 16 * 1024**3        # 16 GiB per chip
