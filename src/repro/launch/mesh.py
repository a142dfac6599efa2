"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.

Axes:
  * ``pod``   — inter-pod data parallelism (DCN-equivalent on real hardware)
  * ``data``  — intra-pod data/FSDP parallelism
  * ``model`` — tensor/expert parallelism
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    # jax.make_mesh defaults to Explicit axes, under which
    # with_sharding_constraint asserts instead of resharding; the model
    # code and the query layer are written for Auto axes
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def make_host_mesh(devices=None):
    """(1, n) ("data", "model") mesh over ``devices`` (default: every
    device that exists)."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh((1, len(devices)), ("data", "model"), _auto(2),
                         devices=devices)


def mesh_axis(mesh, name: str) -> int:
    """Axis size, 1 if the axis does not exist on this mesh."""
    return mesh.shape.get(name, 1)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes present on this mesh (pod first)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
