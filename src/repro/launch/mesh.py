"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benchmarks see the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The repo's one mesh constructor: every axis is ``Auto``, which
    ``with_sharding_constraint`` and the sharding rules require (jax 0.9's
    ``jax.make_mesh`` defaults to ``Explicit`` axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_name(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)


def n_chips(mesh) -> int:
    return mesh.devices.size
