"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; callers (dryrun, launchers) decide when devices materialize.

Every mesh the repo builds has Auto axes. ``jax.make_mesh`` defaults to
Explicit axes, under which a ``device_put``/reshard outside a mesh
context, or a reshape of a sharded batch, is refused; the serving plane
and the training step rely on both. ``auto_axes`` normalises a mesh a
caller built itself.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis Auto."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod 'pod' DP axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU lowering tests (run under forced host devices)."""
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(spec: str):
    """Parse a ``DxM`` string (``--mesh 1x8``) into a ('data','model')
    mesh for the paged serving plane. Raises ValueError with the
    available device count when the shape doesn't fit — on a CPU host,
    run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except (TypeError, ValueError):
        raise ValueError(f"--mesh wants DxM (e.g. 1x8), got {spec!r}")
    if d < 1 or m < 1:
        raise ValueError(f"--mesh dims must be >= 1, got {d}x{m}")
    n = len(jax.devices())
    if d * m > n:
        raise ValueError(
            f"mesh {d}x{m} needs {d * m} devices but only {n} present; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{d * m} for a virtual host mesh")
    return make_mesh((d, m), ("data", "model"))


def data_axes(mesh) -> tuple:
    """Every non-'model' axis is a data/batch axis ('pod' included)."""
    return tuple(n for n in mesh.axis_names if n != "model")


def mesh_context(mesh):
    """``jax.set_mesh`` on the Auto-axis form of ``mesh``."""
    return jax.set_mesh(auto_axes(mesh))
