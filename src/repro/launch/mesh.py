"""Production meshes (TPU v5e).

Defined as FUNCTIONS so importing this module never touches jax device
state — critical because the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count BEFORE first jax init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# jax's device_kind of the chip the production meshes are made of (v5e)
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))


def _auto_mesh(shape, axes):
    # Auto axes: sharding.shard() places values with with_sharding_constraint,
    # which jax.make_mesh's default Explicit axes reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_num_chips(mesh) -> int:
    return mesh.devices.size


def data_axis_size(mesh) -> int:
    size = mesh.shape.get("data", 1)
    if "pod" in mesh.axis_names:
        size *= mesh.shape["pod"]
    return size


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)
