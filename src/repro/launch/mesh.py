"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain placeholder devices.

Axes:
* ``pod``   — data parallelism *between* pods (gradient all-reduce
  crosses the inter-pod DCN/optical links);
* ``data``  — FSDP within a pod (params/optimizer 2D-sharded, gathered
  per layer);
* ``model`` — tensor/sequence parallelism within a pod (ICI).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: the model code places arrays
    with ``with_sharding_constraint`` and lets GSPMD propagate, which
    jax's default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_axis: int = 1):
    """An elastic mesh over the first ``n_devices`` available devices
    (used by the elastic runtime after grow/shrink)."""
    data = n_devices // model_axis
    return _auto_mesh((data, model_axis), ("data", "model"),
                      devices=jax.devices()[:n_devices])
