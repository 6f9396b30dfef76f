"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run's ``__main__``
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the
backend starts; tests and benches see the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..core.substrate import WORKER_AXIS, worker_mesh as _worker_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh with ``Auto`` axes (tests use small ones, e.g. (2,2))."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(tuple(axes)))


def make_worker_mesh(world: int, axis: str = WORKER_AXIS, devices=None):
    """1-D mesh of ``world`` devices for the epoch engine's shard_map
    substrate (raises with the XLA_FLAGS hint when the host has fewer
    devices — see core/substrate.py).  ``devices`` pins the mesh to an
    explicit device list — e.g. a placement-pool lease."""
    return _worker_mesh(world, axis, devices=devices)


def make_device_pool(topology: str = "auto"):
    """A :class:`repro.serve.placement.DevicePool` over the machine
    topology — ``"auto"`` reads the live JAX runtime (grouped by process),
    ``"N"``/``"GxN"`` build abstract pools (see ``DeviceTopology.parse``).
    Lease → mesh binding happens through ``SessionSpec.placement`` (the
    session build calls ``worker_mesh(devices=...)`` itself)."""
    from ..serve.placement import DevicePool, DeviceTopology
    return DevicePool(DeviceTopology.parse(topology))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
